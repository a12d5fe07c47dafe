"""Device self time under the ``k_join_probe`` scope (join probe, expansion and the lazy per-row gather chain) per traced
request, mean over the chips (``harness/trace_owners.py``)."""

from benchmark.harness import trace_owners


def read(obs):
    return trace_owners.kernel_ms(obs, "k_join_probe")
