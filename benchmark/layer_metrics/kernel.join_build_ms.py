"""Device self time under the ``k_join_build`` scope (the in-program build side of a join: key folding and the build sort) per traced
request, mean over the chips (``harness/trace_owners.py``)."""

from benchmark.harness import trace_owners


def read(obs):
    return trace_owners.kernel_ms(obs, "k_join_build")
