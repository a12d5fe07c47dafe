"""Median per request of the dispatch stack's own time: the
``device.dispatch`` spans (admission, breaker, residency attach,
supervisor, bookkeeping) minus the ``supervisor.call`` spans inside
them, which hold the device call."""

from benchmark.harness.observe import total_s


def read(obs):
    def self_s(root):
        outer = total_s(root, "device.dispatch")
        return None if outer is None else \
            outer - (total_s(root, "supervisor.call") or 0.0)
    return obs.median_span_ms(self_s)
