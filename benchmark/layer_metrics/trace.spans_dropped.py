"""Spans and events the worker's traces lost to their bounds inside the
window: growth of ``device_tracing.spans_dropped`` (``MAX_SPANS`` 256 and
``MAX_EVENTS`` 1,024 a trace, tallied when a trace finishes).  Must read
0, or every span metric of the line is short.  Reported by a program
whose ``_fetch`` opens ``device.wait`` and ``fetch.copy`` (two more spans
a round trip; it counts ``capacity_reruns``): an older program's line
leaves it out."""


def read(obs):
    if "capacity_reruns" not in obs.status1.get("device_pipelines", {}):
        return None
    return obs.counter_delta("device_tracing", "spans_dropped")
