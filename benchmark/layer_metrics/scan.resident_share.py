"""Share of the window's scan-aggregate fragments that ran over columns
resident in HBM (``device_exec.device_agg``) and not in blocks re-sent
by every statement (``device_agg_streaming``): growth of
``device_pipelines.scan_resident`` over the growth of it and
``scan_streamed`` (``DIAG STATUS``; one count per dispatched fragment).
A deployment that states "tables resident in HBM" reads 100."""


def read(obs):
    try:
        resident = obs.counter_delta("device_pipelines", "scan_resident")
        streamed = obs.counter_delta("device_pipelines", "scan_streamed")
    except KeyError:       # a program without the counters
        return None
    total = resident + streamed
    return 100.0 * resident / total if total else None
