"""Share of device busy time in collective operations (all-to-all,
all-gather, all-reduce, permutes), mean over the chips."""


def read(obs):
    return obs.busy_share(lambda x: x["collective_s"])
