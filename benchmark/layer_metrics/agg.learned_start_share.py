"""Share of the window's sort-arm scan aggregates whose first program ran
at the capacity the fragment learned on its last run, a hundred such
fragments: growth of ``device_pipelines.agg_start_learned`` over the
growth of ``agg_start_learned`` + ``agg_start_estimated`` (``DIAG
STATUS``; ``device_exec.device_agg`` counts one of the two per
dispatched sort-arm scan aggregate: its first capacity came from the
group count its last kept run stored, or from ``_estimate_groups``).  A
fragment past its estimate reruns at the count it found, so a learned
start is one program and one round trip where an estimated one is two:
TPC-H Q17's inner ``lineitem group by l_partkey`` and Q18's ``group by
l_orderkey`` read 100 once warm.  The dense arm starts at its bucket
count and counts in neither.  None on a program without the counters,
and where no sort-arm scan aggregate ran."""


def read(obs):
    try:
        learned = obs.counter_delta("device_pipelines", "agg_start_learned")
        estimated = obs.counter_delta("device_pipelines",
                                      "agg_start_estimated")
    except KeyError:       # a program without the counters
        return None
    started = learned + estimated
    return 100.0 * learned / started if started else None
