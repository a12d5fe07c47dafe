"""Share of its memory roofline the residual existence tests reached:
the bytes they must read (the template's ``exists_min_bytes``: lineitem's
order key, supplier and two dates, each once, at the table's row count),
over the chip's peak HBM bytes/s, over ``kernel.join_exists_ms``'s self
time per traced request.  Counted from row counts, so the yardstick does
not move with the program.  None where the time is not read, or no
template of the cell has ``exists_min_bytes``."""

import os

from benchmark.harness.resolve import load_module

_HERE = os.path.dirname(os.path.abspath(__file__))


def read(obs):
    if not obs.peaks:
        return None
    need = [m.exists_min_bytes(obs.rows) for m in obs.templates.values()
            if hasattr(m, "exists_min_bytes")]
    if not need:
        return None
    ms = load_module(os.path.join(_HERE, "kernel.join_exists_ms.py"),
                     "kernel.join_exists_ms").exists_ms(obs)
    if not ms:
        return None
    chips = obs.device["count"]
    least_s = sum(need) / (obs.peaks["hbm_bytes_per_s"] * chips)
    return 100.0 * least_s / (ms / 1e3)
