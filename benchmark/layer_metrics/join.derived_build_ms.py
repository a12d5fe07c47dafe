"""Milliseconds a request spends making the build sides of its join
fragments that are another operator's result: the ``join.derived_build``
spans' total (``executor/device_join._derived_leaf``: the build subtree
run through its own executors, Q17's ``lineitem group by l_partkey``
over all 6M lines, its own ``device.dispatch`` nested inside), as the
MEAN over the window's requests, a request that opens none counting 0.
This is what the derived build costs end to end, device and host; its
index and upload come after the span, under ``join.index_build`` and
``upload.h2d``.  A program without the span (it counts
``join_derived`` beside it) gives nothing to read."""

from benchmark.harness.observe import total_s


def read(obs):
    if "join_derived" not in obs.status1.get("device_pipelines", {}):
        return None
    trees = obs.span_trees()
    if not trees:
        return None
    return 1e3 * sum(total_s(root, "join.derived_build") or 0.0
                     for root in trees) / len(trees)
