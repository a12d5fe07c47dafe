"""Device self time under the ``k_join_exists`` scope per traced request,
mean over the chips: a residual existence test (the CSR expansion of the
probe's live rows, the pairs' gathers, the residual, the reduction back
to the probe rows), which the program nests inside ``k_join_probe``, so
``kernel.join_probe_ms`` holds it too.

The trace is reduced again by ``harness/trace_owners.py``'s
``reduce_file``, whose ``names`` name an operation by the FIRST part of
its ``op_name`` path that they hold: here every part but the program's
own ``jit(...)`` and the enclosing ``k_join_probe``, so an operation is
``k_join_exists`` where that scope is the first below them and takes
another name (``k_filter``, ``gather``, ...) everywhere else.  (Passed
``("k_join_exists",)`` alone, an operation outside the scope would find
no name of its own and take that of the nearest operation in the scope
it feeds.)  An operation the compiler made without metadata takes the
name of its nearest named neighbour, as in every ``kernel.*`` metric.
None where the program has no such scope (a parent without it) or the
run has no trace."""

import os

from benchmark.harness import trace_owners, trace_reduce
from benchmark.harness.cell import CACHE_DIR

SCOPE = "k_join_exists"


class _Below:
    """`names` for reduce_file: the parts of an op_name path that name
    it, which is all of them but the program's and k_join_probe."""

    def __contains__(self, part):
        return (bool(part) and part != "k_join_probe"
                and not part.startswith("jit("))


def exists_ms(obs):
    """Self ms under SCOPE per traced request, or None."""
    x = obs.xplane
    if not x or not x.get("requests") or not x.get("busy_s"):
        return None
    path = trace_reduce.find_xplane(
        os.path.join(CACHE_DIR, "run", "*", "trace"))
    if path is None:
        return None
    got = trace_owners.reduce_file(path, x.get("window_s"), names=_Below())
    if not got or abs(got["busy_s"] - x["busy_s"]) > 1e-3 * x["busy_s"]:
        return None
    s = got["kernel_s"].get(SCOPE)
    return 1e3 * s / len(x["requests"]) if s else None


def read(obs):
    return exists_ms(obs)
