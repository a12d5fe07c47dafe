"""Rows the derived build leaves of the window's join fragments held, a
request: growth of ``device_pipelines.join_derived_rows`` (``DIAG
STATUS``; per build leaf that is another operator's result, the rows it
held, once per dispatched join fragment whatever its capacity retries,
``device_exec.note_join_derived``) over the window, per request.  Q17's
``lineitem group by l_partkey`` holds one row a part: 200,000 at SF1,
where a semi-join reduction that pushed the outer filter into the
aggregate would hold the ~200 parts it keeps.  None on a program without
the counter."""


def read(obs):
    try:
        rows = obs.counter_delta("device_pipelines", "join_derived_rows")
    except KeyError:       # a program without the counter
        return None
    return rows / len(obs.requests) if obs.requests else None
