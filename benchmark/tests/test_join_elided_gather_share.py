"""``join.elided_gather_share``: the reader over the counter pair, its
entry in ``BENCHMARK.json`` (looked up by name), and the number a
rehearsed cell prints."""

import json
import os
import subprocess
import sys
import types

import pytest

from benchmark.harness import observe
from benchmark.harness.resolve import BENCH_DIR, ROOT, Cell, load_module

NAME = "join.elided_gather_share"
READER = load_module(
    os.path.join(BENCH_DIR, "layer_metrics", NAME + ".py"),
    "per_layer metric")


def _obs(before, after):
    o = types.SimpleNamespace(status0={"device_pipelines": before},
                              status1={"device_pipelines": after})
    o.counter_delta = lambda *path: observe.delta(o.status0, o.status1,
                                                  *path)
    return o


def _counters(emitted, elided, **more):
    return {"join_gathers": emitted, "join_gathers_elided": elided,
            "join_direct": 7, **more}


@pytest.mark.parametrize("before,after,want", [
    # one Q3 (3 emitted, 10 elided) and one Q5 (4, 15)
    (_counters(21, 75), _counters(28, 100), 100 * 25 / 32),
    (_counters(0, 0), _counters(13, 0), 0.0),          # nothing to elide
    (_counters(5, 5), _counters(5, 9), 100.0),         # nothing emitted
    (_counters(7, 25), _counters(7, 25), None),        # no join fragment ran
    ({"join_direct": 7}, {"join_direct": 14}, None),   # the parent
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
         "--workload", "tpch-sf1.q3q5", "--seed", "3100200331",
         "--seconds", "3", "--trace", "1", "--rehearse"],
        capture_output=True, text=True, cwd=ROOT, timeout=1200)
    assert p.returncode == 0, p.stderr[-2000:]
    lines = [json.loads(ln) for ln in p.stdout.strip().splitlines()]
    assert lines[-1]["correct"] is True and lines[-1]["failed"] == 0
    values = next(ln["values"] for ln in lines
                  if ln.get("metric") == "bench_rehearsal_values")
    # Q3 elides 10 of 13 and Q5 15 of 19, whatever the mix of the two
    assert 100 * 10 / 13 <= values[NAME] <= 100 * 15 / 19
    assert values["join.direct_share"] == 100.0
    assert NAME in lines[-1]["metrics"]
