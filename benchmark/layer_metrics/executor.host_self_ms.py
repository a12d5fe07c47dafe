"""Median per request of ``executor.run`` minus the ``device.dispatch``
spans inside it: building inputs, fetching the result, assembling and
ordering rows on the host."""

from benchmark.harness.observe import total_s


def read(obs):
    def self_s(root):
        run = total_s(root, "executor.run")
        return None if run is None else \
            run - (total_s(root, "device.dispatch") or 0.0)
    return obs.median_span_ms(self_s)
