"""``agg.learned_start_share``: the reader over the counter pair, on made
counters; its entry in ``BENCHMARK.json`` (looked up by name) and the
cells that report it."""

import json
import os
import types

import pytest

from benchmark.harness import observe
from benchmark.harness.resolve import BENCH_DIR, ROOT, Cell, load_module

NAME = "agg.learned_start_share"
CELLS = ["tpch-sf1.q9q18", "tpch-sf1.q17"]
READER = load_module(
    os.path.join(BENCH_DIR, "layer_metrics", NAME + ".py"),
    "per_layer metric")


def _obs(status0, status1):
    o = types.SimpleNamespace(status0=status0, status1=status1)
    o.counter_delta = lambda *path: observe.delta(o.status0, o.status1,
                                                  *path)
    return o


def _counters(learned=None, estimated=None):
    c = {"agg_dense": 0, "agg_sorted": 7, "compiles": 5}
    if learned is not None:
        c.update(agg_start_learned=learned, agg_start_estimated=estimated)
    return {"device_pipelines": c}


@pytest.mark.parametrize("before,after,want", [
    (_counters(1, 1), _counters(11, 1), 100.0),     # warm Q17s / Q18s
    (_counters(0, 0), _counters(3, 1), 75.0),       # one first execution
    (_counters(0, 2), _counters(0, 2), None),       # Q9s, Q3 + Q5: joins
    (_counters(), _counters(), None),               # the parent
])
def test_reader(before, after, want):
    got = READER.read(_obs(before, after))
    assert got == want if want is None else got == pytest.approx(want)


def test_the_entry_and_the_cells_that_report_it():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    entry, = [m for m in spec["per_layer"] if m["name"] == NAME]
    assert entry == {"name": NAME, "unit": "%", "better": "higher",
                     "source": "program_counter", "layer": "dispatch stack",
                     "moves": "query_geomean_s", "workloads": CELLS}
    for w in spec["workloads"]:
        names = {m["name"] for m, _mod in Cell(w["name"]).per_layer}
        assert (NAME in names) == (w["name"] in CELLS)
