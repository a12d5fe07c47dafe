"""Median client-side latency of SSB Q4.1 in the window."""

from benchmark.harness import stats


def read(obs):
    lat = obs.latencies("ssb_q4_1")
    return stats.median(lat) if lat else None
