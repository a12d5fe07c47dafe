"""Milliseconds a request spends building host join indexes in numpy: the
``join.index_build`` spans' total (``executor/join_index.build_join_index``:
a miss of the ONE index a key column caches, the leaf's filter included),
as the MEAN over the window's requests, a request that opens none counting
0: a rebuild that only every other request pays (two templates alternating
two filter tags on one column) is half of itself here, where a median
would read nothing or all of it.  The span lies under ``supervisor.call``'s
self part, so this is the named share of ``idle.device_call_ms``.  A
program without the span (it counts ``join_index_builds`` beside it) gives
nothing to read."""

from benchmark.harness.observe import total_s


def read(obs):
    if "join_index_builds" not in obs.status1.get("device_pipelines", {}):
        return None
    trees = obs.span_trees()
    if not trees:
        return None
    return 1e3 * sum(total_s(root, "join.index_build") or 0.0
                     for root in trees) / len(trees)
