"""Sort-arm programs of the window whose group starts come from ONE sort
at input length and not from a binary search per output slot
(``ops/device._group_spans``; ``ops/device.spans_one_pass(capacity, n)``
names the side from the program's static shapes alone: the search costs
capacity x ceil(log2 n) dependent gathers, the sort n sorted rows), a
hundred sort-arm fragments: growth of
``device_pipelines.agg_spans_one_pass`` (one per turn of a fragment's
capacity loop whose program takes that side) over the growth of
``device_pipelines.agg_sorted`` (one per dispatched fragment, whatever
its turns), both from ``DIAG STATUS``.  TPC-H Q18's inner aggregate runs
two programs a request, at 64 and at 2,097,152 slots over 8,388,608
rows: the second counts.  None where no fragment took the sort arm."""


def read(obs):
    try:
        one_pass = obs.counter_delta("device_pipelines",
                                     "agg_spans_one_pass")
        sort = obs.counter_delta("device_pipelines", "agg_sorted")
    except KeyError:       # a program without the counter
        return None
    return 100.0 * one_pass / sort if sort else None
