"""90th percentile of client-side latency over the window's requests;
only where the window holds at least 100, so that ten lie beyond it.

Not an end-to-end metric: on the chip its spread over six runs of one
tree read 0.10% in one set and 0.73% in the next (PERF.md section 2), so
no bound is both above twice and under eight times what a check reads.
Read in the traced run, so some of its requests ran under the profiler."""

from benchmark.harness import stats

MIN_REQUESTS = 100


def read(obs):
    lat = obs.latencies()
    return stats.percentile(lat, 90.0) if len(lat) >= MIN_REQUESTS else None
