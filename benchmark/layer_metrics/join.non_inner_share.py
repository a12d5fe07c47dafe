"""Share of the window's host-indexed joins that are not inner joins:
growth of ``device_pipelines.join_left + join_semi + join_anti`` over the
growth of ``join_direct + join_search`` (``DIAG STATUS``; all count one
per host-indexed join per dispatched join fragment,
``device_exec.note_join_layouts``: the kinds by ``_JoinNode.kind``, the
layouts by the index).  A left join null-extends its build side through
the gather chain, a semi / anti join is an existence count at the
fragment's root: none of them takes the inner arm's shortcuts.  None on
a program without the counters, and where no join ran."""


def read(obs):
    try:
        kinds = sum(obs.counter_delta("device_pipelines", "join_" + k)
                    for k in ("left", "semi", "anti"))
        joins = sum(obs.counter_delta("device_pipelines", "join_" + k)
                    for k in ("direct", "search"))
    except KeyError:       # a program without the counters
        return None
    return 100.0 * kinds / joins if joins else None
