"""Median client-side latency of Q9 in the window."""

from benchmark.harness import stats


def read(obs):
    lat = obs.latencies("q9")
    return stats.median(lat) if lat else None
