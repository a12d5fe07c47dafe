"""Cuts of a join fragment's probe path to its live rows a request of the
window: growth of ``device_pipelines.join_compactions`` (``DIAG
STATUS``; one per compaction point whose relation the KEPT program of a
dispatched whole-input join fragment cut, ``device_join.compact_to``:
past the probe leaf's filter or a probe-shaped join, where the learned
live rows fill at most a quarter of the relation) over the window, per
request.  0 where no fragment cuts (a star run by pages, a probe whose
rows all stay live); None on a program without the counter."""


def read(obs):
    try:
        cuts = obs.counter_delta("device_pipelines", "join_compactions")
    except KeyError:       # a program without the counter
        return None
    return cuts / len(obs.requests) if obs.requests else None
