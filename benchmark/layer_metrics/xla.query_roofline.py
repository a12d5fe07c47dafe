"""Share of the memory roofline the traced requests reached: the bytes
their templates must read (each template's ``min_bytes``), over the
chip's peak HBM bytes/s, over the device busy time.  These queries are
integer scans, sorts and gathers; HBM bandwidth bounds them, not the MXU."""


def read(obs):
    x = obs.xplane
    if not x or not x["requests"] or not obs.peaks:
        return None
    need = sum(obs.templates[r.template].min_bytes(obs.rows)
               for r in x["requests"])
    chips = obs.device["count"]
    least_s = need / (obs.peaks["hbm_bytes_per_s"] * chips)
    return 100.0 * least_s / x["busy_s"]
