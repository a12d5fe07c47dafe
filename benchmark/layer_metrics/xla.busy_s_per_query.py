"""Device busy seconds (union of the XLA ops' intervals, mean over the
chips) per request of the traced part of the window."""


def read(obs):
    x = obs.xplane
    if not x or not x["requests"]:
        return None
    return x["busy_s"] / len(x["requests"])
