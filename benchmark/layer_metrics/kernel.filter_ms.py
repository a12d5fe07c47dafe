"""Device self time under the ``k_filter`` scope (scan predicates, live/padding and null masks) per traced
request, mean over the chips (``harness/trace_owners.py``)."""

from benchmark.harness import trace_owners


def read(obs):
    return trace_owners.kernel_ms(obs, "k_filter")
