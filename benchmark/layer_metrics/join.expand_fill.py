"""How full the window's join expansions ran: rows the expansions emitted
over the slots their programs held for them, growth of
``device_pipelines.join_expand_rows`` over the growth of
``join_expand_capacity`` (``DIAG STATUS``,
``device_exec.note_join_expansion``: per expanded join of a dispatched
join fragment, the total its program counted and the static capacity it
ran at, a power of two learned from the first run).  Every slot is
searched, gathered and sorted, filled or not.  None on a program without
the counters, and where nothing expanded."""


def read(obs):
    try:
        rows = obs.counter_delta("device_pipelines", "join_expand_rows")
        slots = obs.counter_delta("device_pipelines",
                                  "join_expand_capacity")
    except KeyError:       # a program without the counters
        return None
    return 100.0 * rows / slots if slots else None
