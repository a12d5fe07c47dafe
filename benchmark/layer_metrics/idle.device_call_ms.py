"""Device idle time per traced request, mean over the chips, while the
innermost program span open on the host was
``supervisor.call`` or ``mpp.fragment`` themselves: the host between two programs of one request
(``harness/trace_owners.py``; the five ``idle.*`` sum to the idle time)."""

from benchmark.harness import trace_owners


def read(obs):
    return trace_owners.idle_ms(obs, "device_call")
