"""``join.direct_share``: the reader over the counter pair, its entry in
``BENCHMARK.json`` (looked up by name), and the number a rehearsed cell
prints."""

import json
import os
import subprocess
import sys
import types

import pytest

from benchmark.harness import observe
from benchmark.harness.resolve import BENCH_DIR, ROOT, Cell, load_module

NAME = "join.direct_share"
READER = load_module(
    os.path.join(BENCH_DIR, "layer_metrics", NAME + ".py"),
    "per_layer metric")


def _obs(before, after):
    o = types.SimpleNamespace(status0={"device_pipelines": before},
                              status1={"device_pipelines": after})
    o.counter_delta = lambda *path: observe.delta(o.status0, o.status1,
                                                  *path)
    return o


def _counters(direct, search, **more):
    return {"join_direct": direct, "join_search": search, "compiles": 5,
            **more}


@pytest.mark.parametrize("before,after,want", [
    (_counters(14, 0), _counters(49, 0), 100.0),       # Q3 + Q5, this PR
    (_counters(4, 3), _counters(12, 9), 400 / 7),      # the parent's layouts
    (_counters(0, 2), _counters(0, 6), 0.0),           # every join searched
    (_counters(7, 1), _counters(7, 1), None),          # no join fragment ran
    ({"compiles": 5}, {"compiles": 5}, None),          # the parent
])
def test_reader(before, after, want):
    got = READER.read(_obs(before, after))
    assert got == want if want is None else got == pytest.approx(want)


def test_the_entry_and_the_cells_that_report_it():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    entry, = [m for m in spec["per_layer"] if m["name"] == NAME]
    assert entry == {"name": NAME, "unit": "%", "better": "higher",
                     "source": "program_counter", "layer": "XLA programs",
                     "moves": "query_geomean_s",
                     "workloads": ["tpch-sf1.q3q5"]}
    for w in spec["workloads"]:
        names = {m["name"] for m, _mod in Cell(w["name"]).per_layer}
        assert (NAME in names) == (w["name"] in entry["workloads"])


def test_the_rehearsed_join_cell_prints_it():
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", "tpch-sf1.q3q5", "--seed", "3100200329",
         "--seconds", "3", "--trace", "1", "--rehearse"],
        capture_output=True, text=True, cwd=ROOT, timeout=1200)
    assert p.returncode == 0, p.stderr[-2000:]
    lines = [json.loads(ln) for ln in p.stdout.strip().splitlines()]
    assert lines[-1]["correct"] is True and lines[-1]["failed"] == 0
    values = next(ln["values"] for ln in lines
                  if ln.get("metric") == "bench_rehearsal_values")
    assert values[NAME] == 100.0
    assert NAME in lines[-1]["metrics"]
