"""Median client-side latency of Q18 in the window."""

from benchmark.harness import stats


def read(obs):
    lat = obs.latencies("q18")
    return stats.median(lat) if lat else None
