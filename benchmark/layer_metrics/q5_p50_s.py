"""Median client-side latency of Q5 in the window."""

from benchmark.harness import stats


def read(obs):
    lat = obs.latencies("q5")
    return stats.median(lat) if lat else None
