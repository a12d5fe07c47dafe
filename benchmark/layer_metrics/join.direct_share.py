"""Share of the window's host-indexed joins whose probe finds its build
row by address (a ``dense`` ``executor/join_index.JoinIndex``: one or two
gathers) and not by binary search over the host-sorted keys (``sorted``:
ceil(log2(n)) dependent gathers a probe row): growth of
``device_pipelines.join_direct`` over the growth of both ``join_*``
counters (``DIAG STATUS``; one count per host-indexed join per dispatched
join fragment, by the layout in the fragment's strategy snapshot; joins
the mesh builds inside its program count under neither)."""


def read(obs):
    try:
        direct = obs.counter_delta("device_pipelines", "join_direct")
        search = obs.counter_delta("device_pipelines", "join_search")
    except KeyError:       # a program without the counters
        return None
    return 100.0 * direct / (direct + search) if direct + search else None
