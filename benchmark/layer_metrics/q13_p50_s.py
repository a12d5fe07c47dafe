"""Median client-side latency of Q13 in the window."""

from benchmark.harness import stats


def read(obs):
    lat = obs.latencies("q13")
    return stats.median(lat) if lat else None
