"""Median client-side latency of Q4 in the window."""

from benchmark.harness import stats


def read(obs):
    lat = obs.latencies("q4")
    return stats.median(lat) if lat else None
