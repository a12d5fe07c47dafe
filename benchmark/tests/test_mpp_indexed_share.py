"""``mpp.indexed_share``: the reader over the counter pair, its entry in
``BENCHMARK.json`` (looked up by name), and the number the rehearsed mesh
cell prints."""

import json
import os
import subprocess
import sys
import types

import pytest

from benchmark.harness import observe
from benchmark.harness.resolve import BENCH_DIR, ROOT, Cell, load_module

NAME = "mpp.indexed_share"
READER = load_module(
    os.path.join(BENCH_DIR, "layer_metrics", NAME + ".py"),
    "per_layer metric")


def _obs(before, after):
    o = types.SimpleNamespace(status0={"device_mpp": before},
                              status1={"device_mpp": after})
    o.counter_delta = lambda *path: observe.delta(o.status0, o.status1,
                                                  *path)
    return o


def _counters(fragments, indexed, **more):
    return {"fragments": fragments, "indexed_fragments": indexed,
            "shuffle_joins": 0, **more}


@pytest.mark.parametrize("before,after,want", [
    (_counters(4, 4), _counters(60, 60), 100.0),       # power_q3, this PR
    (_counters(4, 4), _counters(12, 8), 50.0),         # half took a shuffle
    (_counters(3, 0), _counters(9, 0), 0.0),           # every join in-program
    (_counters(5, 5), _counters(5, 5), None),          # no mesh fragment ran
    ({"fragments": 3, "shuffle_joins": 3},
     {"fragments": 7, "shuffle_joins": 7}, None),      # the parent
])
def test_reader(before, after, want):
    got = READER.read(_obs(before, after))
    assert got == want if want is None else got == pytest.approx(want)


def test_the_entry_and_the_cells_that_report_it():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    entry, = [m for m in spec["per_layer"] if m["name"] == NAME]
    assert entry == {"name": NAME, "unit": "%", "better": "higher",
                     "source": "program_counter", "layer": "mesh",
                     "moves": "query_geomean_s",
                     "workloads": ["tpch-sf1-mpp4.q3"]}
    for w in spec["workloads"]:
        names = {m["name"] for m, _mod in Cell(w["name"]).per_layer}
        assert (NAME in names) == (w["name"] in entry["workloads"])


def test_the_rehearsed_mesh_cell_prints_it():
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", "tpch-sf1-mpp4.q3", "--seed", "3200200411",
         "--seconds", "3", "--trace", "1", "--rehearse"],
        capture_output=True, text=True, cwd=ROOT, timeout=1200)
    assert p.returncode == 0, p.stderr[-2000:]
    lines = [json.loads(ln) for ln in p.stdout.strip().splitlines()]
    assert lines[-1]["correct"] is True and lines[-1]["failed"] == 0
    values = next(ln["values"] for ln in lines
                  if ln.get("metric") == "bench_rehearsal_values")
    assert values[NAME] == 100.0
    assert NAME in lines[-1]["metrics"]
