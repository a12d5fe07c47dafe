"""Median, over the requests that open one, of the ``derived.aggregate``
spans' total (``executor/exec_select.HashAggExec``): the host executors'
aggregate over another operator's output, neither a scan nor a join
fragment (Q13's ``group by c_count`` over the 150,000 rows of its
derived table).  The span covers the aggregate alone: the derived
table's own fragment runs before it opens.  A program without the span
gives nothing to read."""

from benchmark.harness.observe import total_s


def read(obs):
    return obs.median_span_ms(
        lambda root: total_s(root, "derived.aggregate"))
