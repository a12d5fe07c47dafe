"""Device self time under the ``k_exchange`` scope (radix bucketing, all_to_all / all_gather and the merge of received partials) per traced
request, mean over the chips (``harness/trace_owners.py``)."""

from benchmark.harness import trace_owners


def read(obs):
    return trace_owners.kernel_ms(obs, "k_exchange")
