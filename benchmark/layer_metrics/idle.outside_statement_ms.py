"""Device idle time per traced request, mean over the chips, while the
innermost program span open on the host was
no statement open on the worker: the wire, the client, parsing, the result's way back
(``harness/trace_owners.py``; the five ``idle.*`` sum to the idle time)."""

from benchmark.harness import trace_owners


def read(obs):
    return trace_owners.idle_ms(obs, "outside_statement")
