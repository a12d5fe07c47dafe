"""Device self time under the ``k_topk`` scope (order-by/limit over the aggregate's groups) per traced
request, mean over the chips (``harness/trace_owners.py``)."""

from benchmark.harness import trace_owners


def read(obs):
    return trace_owners.kernel_ms(obs, "k_topk")
