"""Share of the window's mesh fragments whose shards ran the one-chip
join fragment's own body (``device_join.compile_fragment``: host-built
indexes read by address, the probe leaf read in place on its shard) and
not the mesh's in-program joins (a build lexsort and three searches a
join, after an ``all_to_all`` of both sides where the build is shuffled):
growth of ``device_mpp.indexed_fragments`` over the growth of
``device_mpp.fragments`` (``DIAG STATUS``; both count a dispatched
fragment once, whatever its capacity retries).  A scan fragment of the
mesh has no join and counts in the denominator only."""


def read(obs):
    try:
        indexed = obs.counter_delta("device_mpp", "indexed_fragments")
        fragments = obs.counter_delta("device_mpp", "fragments")
    except KeyError:       # a program without the counter
        return None
    return 100.0 * indexed / fragments if fragments else None
