"""XLA compiles the worker counted inside the window; must read 0."""


def read(obs):
    return obs.counter_delta("device_pipelines", "compiles")
