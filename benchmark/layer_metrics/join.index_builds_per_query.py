"""Host join indexes built a request of the window: growth of
``device_pipelines.join_index_builds`` (``DIAG STATUS``; one per miss of
the one index a key column caches, whoever asked:
``device_join._leaf_index``, the mesh's indexed path, the hybrid join's
partitions) over the window, per request.  0 where every fragment finds
its indexes cached; 1 or more where templates alternating filter tags on
one key column rebuild it for each other."""


def read(obs):
    try:
        built = obs.counter_delta("device_pipelines", "join_index_builds")
    except KeyError:       # a program without the counter
        return None
    return built / len(obs.requests) if obs.requests else None
