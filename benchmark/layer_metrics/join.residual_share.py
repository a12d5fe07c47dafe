"""Share of the window's semi / anti joins whose residual ran in the
program: growth of ``device_pipelines.join_residual`` over the growth of
``join_semi + join_anti`` (``DIAG STATUS``; all count one per
host-indexed join per dispatched join fragment,
``device_exec.note_join_layouts``).  A residual is a correlated conjunct
that is not a key (Q21's ``l2.l_suppkey <> l1.l_suppkey``): the program
tests it on every pair of a CSR expansion of the probe's live rows, and
the pairs reduce back to their probe row.  100 where every existence
test of the window carries one.  None on a program without the counter,
and where no semi / anti join ran."""


def read(obs):
    try:
        tested = obs.counter_delta("device_pipelines", "join_residual")
        kinds = sum(obs.counter_delta("device_pipelines", "join_" + k)
                    for k in ("semi", "anti"))
    except KeyError:       # a program without the counters
        return None
    return 100.0 * tested / kinds if kinds else None
