"""Fragments of the window that a pinned device engine left to the host
executors: growth of ``device_pipelines.unsupported`` (``DIAG STATUS``;
``device_exec.note_unsupported``, one per fragment whose every device arm
raised ``DeviceUnsupported``; ``EXPLAIN ANALYZE`` prints the reason as
``device_unsupported:``).  A configuration that pins ``engine: tpu``
reads 0.  A program without the counter gives nothing to read."""


def read(obs):
    try:
        return obs.counter_delta("device_pipelines", "unsupported")
    except KeyError:
        return None
