"""Median per request of the client's latency minus the worker's
``statement`` span: the wire both ways, packet writes, the parse, and
whatever the session does outside the span."""

from benchmark.harness import stats


def read(obs):
    vals = [r.latency_s - r.trace["root"]["duration_s"]
            for r in obs.requests if r.trace]
    return stats.median(vals) * 1e3 if vals else None
