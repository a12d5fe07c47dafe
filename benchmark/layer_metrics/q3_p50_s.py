"""Median client-side latency of Q3 in the window."""

from benchmark.harness import stats


def read(obs):
    lat = obs.latencies("q3")
    return stats.median(lat) if lat else None
