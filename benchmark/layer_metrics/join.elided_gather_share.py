"""Share of the join fragments' column / mask / row-map gathers that
their programs do not emit, because the result is something the program
already holds: a column or mask of a leaf whose row map is still the
identity (read in place), the mask of a column the host knows holds no
NULL (a constant), a row map composed through the identity (the index
itself).  Growth of ``device_pipelines.join_gathers_elided`` over the
growth of it and ``device_pipelines.join_gathers`` (``DIAG STATUS``; one
bump per dispatched join fragment by the counts of its traced program,
one per distinct (source, indices) pair; index lookups and the
aggregate's own gathers count under neither, and so do the mesh's
fragments)."""


def read(obs):
    try:
        emitted = obs.counter_delta("device_pipelines", "join_gathers")
        elided = obs.counter_delta("device_pipelines",
                                   "join_gathers_elided")
    except KeyError:       # a program without the counters
        return None
    return 100.0 * elided / (emitted + elided) if emitted + elided else None
