"""Rows the host turns into result chunks for one query of the mix: the
``rows`` tags of a request's ``host.assemble`` spans
(``device_exec._assemble_agg``) summed, the median of that per template,
the mean of the medians over the mix's templates (so that the number
does not swing with which template the window ended on).  A near-unique
group key shows here: Q18's inner aggregate brings one group an order
back to the host, Q9 175 rows."""

from benchmark.harness import stats
from benchmark.harness.observe import find_spans


def read(obs):
    per_template = {}
    for r in obs.requests:
        if not r.trace:
            continue
        found = find_spans(r.trace["root"], "host.assemble")
        per_template.setdefault(r.template, []).append(
            sum(int(s.get("tags", {}).get("rows") or 0) for s in found))
    if not per_template:
        return None
    medians = [stats.median(v) for v in per_template.values()]
    return sum(medians) / len(medians)
