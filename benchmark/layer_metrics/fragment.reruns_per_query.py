"""Programs a request of the window ran AGAIN at another capacity: growth
of ``device_pipelines.capacity_reruns`` (``DIAG STATUS``; one per turn of
a fragment's capacity loop after a program has run: ``device_agg``, the
streamed scan, ``device_join_agg``, the paged join, the fold of pages' or
blocks' states, the mesh) over the
window, per request.  0 for fragments that start at a capacity that
holds; a fragment that forgets what it learned reads 1 on every
execution."""


def read(obs):
    try:
        reruns = obs.counter_delta("device_pipelines", "capacity_reruns")
    except KeyError:       # a program without the counter
        return None
    return reruns / len(obs.requests) if obs.requests else None
