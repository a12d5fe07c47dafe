"""Median client-side latency of SSB Q2.1 in the window."""

from benchmark.harness import stats


def read(obs):
    lat = obs.latencies("ssb_q2_1")
    return stats.median(lat) if lat else None
