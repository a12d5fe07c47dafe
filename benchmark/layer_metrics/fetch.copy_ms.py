"""Median per request of the ``fetch.copy`` spans' total: inside every
``fetch.d2h`` (``device_exec._fetch``), the ``device_get`` of a tree that
is ready: the copy back alone, one transfer an array (tags ``arrays``,
``bytes``).  The part of a fetch that one packed result buffer a fragment
would shorten.  A program that does not split its fetch gives nothing to
read."""

from benchmark.harness.observe import total_s


def read(obs):
    return obs.median_span_ms(lambda root: total_s(root, "fetch.copy"))
