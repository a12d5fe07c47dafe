"""``agg.dense_share``: the reader over counter pairs, and the number a
rehearsed cell prints."""

import json
import os
import subprocess
import sys
import types

import pytest

from benchmark.harness import observe
from benchmark.harness.resolve import BENCH_DIR, ROOT, Cell, load_module

READER = load_module(
    os.path.join(BENCH_DIR, "layer_metrics", "agg.dense_share.py"),
    "per_layer metric")


def _obs(before, after):
    o = types.SimpleNamespace(status0={"device_pipelines": before},
                              status1={"device_pipelines": after})
    o.counter_delta = lambda *path: observe.delta(o.status0, o.status1,
                                                  *path)
    return o


def _counters(dense, sorted_, scatter=0, **more):
    return {"agg_dense": dense, "agg_sorted": sorted_,
            "agg_scatter": scatter, "compiles": 3, **more}


@pytest.mark.parametrize("before,after,want", [
    (_counters(2, 5), _counters(229, 5), 100.0),          # Q6, Q1
    (_counters(3, 3), _counters(6, 6), 50.0),             # one of each
    (_counters(0, 4), _counters(0, 8), 0.0),              # the mesh's Q3
    (_counters(1, 1, 1), _counters(2, 1, 4), 25.0),       # XLA:CPU scatter
    (_counters(7, 7), _counters(7, 7), None),             # no fragment ran
    ({"compiles": 3}, {"compiles": 3}, None),             # the parent
])
def test_reader(before, after, want):
    assert READER.read(_obs(before, after)) == want


def test_the_metric_is_reported_in_every_cell():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    entry = spec["per_layer"][-1]
    assert entry == {"name": "agg.dense_share", "unit": "%",
                     "better": "higher", "source": "program_counter",
                     "layer": "XLA programs", "moves": "query_geomean_s"}
    for w in spec["workloads"]:
        assert "agg.dense_share" in {
            m["name"] for m, _mod in Cell(w["name"]).per_layer}


@pytest.mark.parametrize("cell_name,want", [
    ("tpch-sf1.q1", 100.0),
    # join fragments keep the arm they had (agg_arm's `gathered`)
    ("tpch-sf1.q3q5", 0.0)])
def test_rehearsed_cells_print_it(cell_name, want):
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", cell_name, "--seed", "3100200301", "--seconds", "3",
         "--trace", "1", "--rehearse"],
        capture_output=True, text=True, cwd=ROOT, timeout=1200)
    assert p.returncode == 0, p.stderr[-2000:]
    lines = [json.loads(ln) for ln in p.stdout.strip().splitlines()]
    assert lines[-1]["correct"] is True and lines[-1]["failed"] == 0
    values = next(ln["values"] for ln in lines
                  if ln.get("metric") == "bench_rehearsal_values")
    assert values["agg.dense_share"] == want
    assert "agg.dense_share" in lines[-1]["metrics"]
