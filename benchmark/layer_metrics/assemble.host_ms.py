"""Median per request of the ``host.assemble`` spans' total: the fetched arrays turned into the result chunk."""

from benchmark.harness.observe import total_s


def read(obs):
    return obs.median_span_ms(lambda root: total_s(root, "host.assemble"))
