"""How full the window's residual existence tests ran: the pairs their
expansions held over the slots their programs held for them, growth of
``device_pipelines.join_residual_rows`` over the growth of
``join_residual_capacity`` (``DIAG STATUS``,
``device_exec.note_join_residual``: per semi / anti join of a
dispatched fragment that tested a residual over a CSR expansion, the
pairs its kept program counted and the static capacity it ran at, a
power of two learned from the first run).  Every slot is mapped,
gathered and tested, filled or not.  None on a program without the
counters, and where no such test ran."""


def read(obs):
    try:
        rows = obs.counter_delta("device_pipelines", "join_residual_rows")
        slots = obs.counter_delta("device_pipelines",
                                  "join_residual_capacity")
    except KeyError:       # a program without the counters
        return None
    return 100.0 * rows / slots if slots else None
