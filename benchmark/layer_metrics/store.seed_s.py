"""The seed hook inside the worker: generating the tables, installing
them, and ANALYZE (first run of a seed in a checkout) or loading the
replayed statistics."""


def read(obs):
    return obs.setup["seed_s"]
