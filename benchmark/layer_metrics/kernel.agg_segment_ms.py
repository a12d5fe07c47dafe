"""Device self time under the ``k_agg_segment`` scope (group boundaries, prefix and segment reductions, compaction to the group capacity) per traced
request, mean over the chips (``harness/trace_owners.py``)."""

from benchmark.harness import trace_owners


def read(obs):
    return trace_owners.kernel_ms(obs, "k_agg_segment")
