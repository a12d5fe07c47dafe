"""Megabytes a request of the window sent from host to device: growth of
``device_residency.upload_bytes`` (what the residency ledger installed)
plus ``device_pipelines.stream_upload_bytes`` (the streamed path's
blocks, which bypass the ledger) over the window, per request.  0 for a
resident deployment; a table re-sent by every statement, or columns
evicted and uploaded again, read as their bytes."""


def read(obs):
    try:
        sent = (obs.counter_delta("device_residency", "upload_bytes")
                + obs.counter_delta("device_pipelines",
                                    "stream_upload_bytes"))
    except KeyError:       # a program without the counters
        return None
    return sent / 1e6 / len(obs.requests) if obs.requests else None
