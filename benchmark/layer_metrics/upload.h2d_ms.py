"""Median per request of the ``upload.h2d`` spans' total: host columns placed on the device (nothing to copy once they are resident)."""

from benchmark.harness.observe import total_s


def read(obs):
    return obs.median_span_ms(lambda root: total_s(root, "upload.h2d"))
