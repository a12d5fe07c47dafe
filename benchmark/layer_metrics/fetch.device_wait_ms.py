"""Median per request of the ``device.wait`` spans' total: inside every
``fetch.d2h`` (``device_exec._fetch``), the ``block_until_ready`` on the
tree about to be copied: the run time of the programs the result depends
on, as the host sees it.  With ``fetch.copy_ms`` it is ``fetch.d2h_ms``
less the slices' dispatch.  A program that does not split its fetch gives
nothing to read."""

from benchmark.harness.observe import total_s


def read(obs):
    return obs.median_span_ms(lambda root: total_s(root, "device.wait"))
