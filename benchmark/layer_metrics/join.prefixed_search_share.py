"""Share of the window's SEARCHED host-indexed joins (a ``sorted``
``executor/join_index.JoinIndex``: the direct table over the key span did
not fit) whose index carries a prefix table over the key's high bits, so
that a probe row reads its bucket's two ends by address and bisects the
one or few keys between them, and not the whole sorted array
(ceil(log2(n)) dependent gathers): growth of
``device_pipelines.join_search_prefixed`` over the growth of
``device_pipelines.join_search`` (``DIAG STATUS``; both count one per
searched join per dispatched join fragment, by the index in the
fragment's strategy snapshot).  None where nothing searched."""


def read(obs):
    try:
        prefixed = obs.counter_delta("device_pipelines",
                                     "join_search_prefixed")
        search = obs.counter_delta("device_pipelines", "join_search")
    except KeyError:       # a program without the counter
        return None
    return 100.0 * prefixed / search if search else None
