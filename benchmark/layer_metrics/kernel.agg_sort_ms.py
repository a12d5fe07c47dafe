"""Device self time under the ``k_agg_sort`` scope (group-key expressions, key packing, the grouping argsort(s) and the sorted keys) per traced
request, mean over the chips (``harness/trace_owners.py``)."""

from benchmark.harness import trace_owners


def read(obs):
    return trace_owners.kernel_ms(obs, "k_agg_sort")
