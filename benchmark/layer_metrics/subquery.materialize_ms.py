"""Median, over the requests that open one, of the ``subquery.materialize``
spans' total (``executor/device_join.collect_tree``): an aggregate
subquery run through its own executors, its values turned into Python
objects and folded into an in-set filter of the join fragment's probe
side (Q18's ``in (select ... having ...)``).  The subquery's own device
fragments nest under the span, so this is the whole price of the fold,
host and device.  A program without the span gives nothing to read."""

from benchmark.harness.observe import total_s


def read(obs):
    return obs.median_span_ms(
        lambda root: total_s(root, "subquery.materialize"))
