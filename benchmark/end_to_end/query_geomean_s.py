"""Geometric mean over the cell's templates of each template's median
client-side latency in the window (the shape of TPC-H Power, cl. 5.4.1;
with one template it is that template's median)."""

from benchmark.harness import stats


def read(obs):
    med = obs.template_medians()
    if len(med) < len(obs.templates):
        return None     # a template never completed: no figure
    return stats.geomean(med.values())
