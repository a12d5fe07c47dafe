"""Median per request of the ``fetch.d2h`` spans' total: the wait for the device plus the copy of the result back."""

from benchmark.harness.observe import total_s


def read(obs):
    return obs.median_span_ms(lambda root: total_s(root, "fetch.d2h"))
