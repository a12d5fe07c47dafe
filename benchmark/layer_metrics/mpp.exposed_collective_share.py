"""Share of device busy time in which a collective ran and nothing else
did on that chip."""


def read(obs):
    return obs.busy_share(lambda x: x["exposed_collective_s"])
