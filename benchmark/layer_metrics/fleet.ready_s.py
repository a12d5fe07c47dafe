"""Process start to the worker's ready line: boot, taking the device,
data from the seed, ANALYZE (first run of a seed) or log replay."""


def read(obs):
    return obs.setup["fleet_ready_s"]
