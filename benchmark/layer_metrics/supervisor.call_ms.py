"""Median per request of the ``supervisor.call`` spans: the supervised
device call as the host sees it (enqueue, run, wait for the result)."""

from benchmark.harness.observe import total_s


def read(obs):
    return obs.median_span_ms(lambda root: total_s(root, "supervisor.call"))
