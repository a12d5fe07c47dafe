"""Device self time under the ``k_agg_gather`` scope (aggregate-input expressions and their gather through the sort permutation) per traced
request, mean over the chips (``harness/trace_owners.py``)."""

from benchmark.harness import trace_owners


def read(obs):
    return trace_owners.kernel_ms(obs, "k_agg_gather")
