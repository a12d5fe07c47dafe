"""Share of device busy time in sort operations (self time by op name)."""


def read(obs):
    return obs.category_share("sort")
