"""Seconds of synchronous compilation (tracing, compiling or loading
from the persistent cache) the worker counted before the window."""


def read(obs):
    return obs.setup["compile_s"]
