"""Share of the window's aggregate fragments whose program aggregates by
dense masked reduction (``ops/device._agg_dense_impl``) and not by sort +
segment: growth of ``device_pipelines.agg_dense`` over the growth of all
the ``agg_*`` arm counters (``DIAG STATUS``; one count per dispatched
fragment, by the arm ``ops/device.agg_arm`` named; ``agg_scatter`` only
ever grows on XLA:CPU)."""


def read(obs):
    try:
        dense = obs.counter_delta("device_pipelines", "agg_dense")
        rest = (obs.counter_delta("device_pipelines", "agg_sorted")
                + obs.counter_delta("device_pipelines", "agg_scatter"))
    except KeyError:       # a program without the counters
        return None
    return 100.0 * dense / (dense + rest) if dense + rest else None
