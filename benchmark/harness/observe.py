"""What one run observed, as the metric readers see it.

A reader is a file ``end_to_end/<name>.py`` or ``layer_metrics/<name>.py``
with one function ``read(obs) -> float | None``.  It takes its number
from the fields and helpers below and returns None when there is
nothing to read (the harness then leaves the metric out of the line).
"""

import dataclasses

from . import stats


@dataclasses.dataclass
class Request:
    template: str
    latency_s: float          # client's monotonic clock, send -> last row
    ok: bool                  # answered, and equal to the reference
    traced: bool = False      # ran inside the profiler's window
    trace: "dict | None" = None   # the worker's span tree of it


@dataclasses.dataclass
class Observation:
    requests: list            # started inside the window, in order
    setup: dict               # setup_s, fleet_ready_s, compiles, ...
    status0: dict             # DIAG STATUS before the window
    status1: dict             # ... and after it
    templates: dict           # {name: template module}
    rows: dict                # {table: row count}
    device: dict              # platform, kind, count, memory_peak_bytes
    hbm_bytes: "int | None"   # one chip's HBM
    peaks: "dict | None"      # the peaks-table entry of this device kind
    xplane: "dict | None"     # trace_reduce's summary of the traced part

    # -- client clock -------------------------------------------------------

    def latencies(self, template: "str | None" = None) -> list:
        return [r.latency_s for r in self.requests
                if template is None or r.template == template]

    def template_medians(self) -> dict:
        return {t: stats.median(self.latencies(t)) for t in self.templates
                if self.latencies(t)}

    # -- spans --------------------------------------------------------------

    def span_trees(self) -> list:
        return [r.trace["root"] for r in self.requests if r.trace]

    def median_span_ms(self, reduce_tree) -> "float | None":
        """Median over the traced requests of `reduce_tree(root)` (s)."""
        vals = [v for v in map(reduce_tree, self.span_trees())
                if v is not None]
        return stats.median(vals) * 1e3 if vals else None

    # -- counters -----------------------------------------------------------

    def counter_delta(self, *path) -> float:
        return delta(self.status0, self.status1, *path)

    # -- device trace -------------------------------------------------------

    def busy_share(self, seconds) -> "float | None":
        """`seconds(xplane)` as a percentage of the device busy time."""
        x = self.xplane
        return 100.0 * seconds(x) / x["busy_s"] if x else None

    def category_share(self, *cats) -> "float | None":
        return self.busy_share(
            lambda x: sum(x["category_s"].get(c, 0.0) for c in cats))


def delta(s0: dict, s1: dict, *path) -> float:
    """Growth of one counter between two DIAG STATUS bodies."""
    for k in path:
        s0, s1 = s0[k], s1[k]
    return s1 - s0


def find_spans(node: dict, name: str) -> list:
    """Every span called `name` in a tree (DIAG TRACEJSON's shape)."""
    out = [node] if node.get("name") == name else []
    for child in node.get("children", ()):
        out += find_spans(child, name)
    return out


def total_s(node: dict, name: str) -> "float | None":
    """Summed duration of the spans called `name`; None if there is none."""
    found = find_spans(node, name)
    return sum(s.get("duration_s") or 0.0 for s in found) if found else None
