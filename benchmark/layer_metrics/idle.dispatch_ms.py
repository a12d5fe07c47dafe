"""Device idle time per traced request, mean over the chips, while the
innermost program span open on the host was
``device.dispatch`` itself, ``scheduler.acquire`` or ``compile.obtain``
(``harness/trace_owners.py``; the five ``idle.*`` sum to the idle time)."""

from benchmark.harness import trace_owners


def read(obs):
    return trace_owners.idle_ms(obs, "dispatch")
