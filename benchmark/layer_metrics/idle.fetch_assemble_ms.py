"""Device idle time per traced request, mean over the chips, while the
innermost program span open on the host was
``executor.run`` itself, ``upload.h2d``, ``fetch.d2h`` or ``host.assemble``
(``harness/trace_owners.py``; the five ``idle.*`` sum to the idle time)."""

from benchmark.harness import trace_owners


def read(obs):
    return trace_owners.idle_ms(obs, "fetch_assemble")
