"""Share of the window's host-indexed joins whose OUTPUT is CSR-expanded
(an inner or left join over a non-unique build: ``cnt -> cumsum ->
searchsorted`` over a learned static capacity, every column gathered
through the expansion's row maps) and not probe-shaped (a unique build's
one gather; a semi / anti join's existence count): growth of
``device_pipelines.join_expand`` over the growth of ``join_direct +
join_search`` (``DIAG STATUS``, ``device_exec.note_join_layouts``).  None
on a program without the counter, and where no join ran."""


def read(obs):
    try:
        expanded = obs.counter_delta("device_pipelines", "join_expand")
        joins = sum(obs.counter_delta("device_pipelines", "join_" + k)
                    for k in ("direct", "search"))
    except KeyError:       # a program without the counter
        return None
    return 100.0 * expanded / joins if joins else None
