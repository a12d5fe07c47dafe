"""Median per request of the ``scheduler.acquire`` spans (waiting for and
taking an admission ticket)."""

from benchmark.harness.observe import total_s


def read(obs):
    return obs.median_span_ms(lambda root: total_s(root,
                                                   "scheduler.acquire"))
