"""``join.one_pass_expand_share`` (PR 38): the reader over the counter pair
on made ``DIAG STATUS`` bodies (a program without the counter and a window
in which nothing expanded read nothing), its entry in ``BENCHMARK.json``
looked up BY NAME (a later PR appends), the cells that report it, and what
the rehearsed cell prints of it."""

import json
import os
import subprocess
import sys
import types

import pytest

from benchmark.harness import observe
from benchmark.harness.resolve import BENCH_DIR, ROOT, Cell, load_module

NAME = "join.one_pass_expand_share"
CELL = "tpch-sf1.q13q4"
READER = load_module(
    os.path.join(BENCH_DIR, "layer_metrics", NAME + ".py"),
    "per_layer metric")


def _obs(pipes0, pipes1):
    o = types.SimpleNamespace(status0={"device_pipelines": pipes0},
                              status1={"device_pipelines": pipes1})
    o.counter_delta = lambda *path: observe.delta(o.status0, o.status1,
                                                  *path)
    return o


def _counters(expand, one_pass=None, direct=0):
    c = {"join_direct": direct, "join_search": 0, "join_expand": expand}
    if one_pass is not None:
        c["join_expand_one_pass"] = one_pass
    return c


@pytest.mark.parametrize("before,after,want", [
    # thirty-one Q13 (one expansion each, in one pass) and thirty Q4 (a
    # semi join: none): the cell, this PR
    (_counters(3, 3, 6), _counters(34, 34, 67), 100.0),
    # of four expansions one kept a program that searches
    (_counters(2, 2, 2), _counters(6, 5, 6), 75.0),
    (_counters(2, 0, 2), _counters(6, 0, 6), 0.0),       # all search
    (_counters(0, 0, 8), _counters(0, 0, 80), None),     # nothing expanded
    (_counters(3, 3, 6), _counters(3, 3, 6), None),      # no join ran
    (_counters(3, None, 6), _counters(34, None, 67), None),   # the parent
    ({}, {}, None),
])
def test_reader(before, after, want):
    assert READER.read(_obs(before, after)) == want


def test_the_entry_and_the_cells_that_report_it():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    entry, = [m for m in spec["per_layer"] if m["name"] == NAME]
    assert {k: v for k, v in entry.items() if k != "workloads"} == {
        "name": NAME, "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "XLA programs",
        "moves": "query_geomean_s"}
    assert CELL in entry["workloads"]
    for w in spec["workloads"]:
        names = {m["name"] for m, _mod in Cell(w["name"]).per_layer}
        assert (NAME in names) == (w["name"] in entry["workloads"])


def test_the_rehearsed_cell_prints_it():
    """SF0.01 on XLA:CPU: Q13's learned 16,384 slots over the 2,048-row
    customer bucket take the pass, as 2,097,152 over 185,364 do."""
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", CELL, "--seed", "3800200101", "--seconds", "4",
         "--trace", "1", "--rehearse"],
        capture_output=True, text=True, cwd=ROOT, timeout=900)
    assert p.returncode == 0, p.stderr[-2000:]
    lines = [json.loads(ln) for ln in p.stdout.strip().splitlines()]
    last = lines[-1]
    assert last["correct"] is True and last["failed"] == 0
    values = next(ln["values"] for ln in lines
                  if ln.get("metric") == "bench_rehearsal_values")
    assert values[NAME] == 100.0 and NAME in last["metrics"]
    assert 50.0 <= values["join.expanded_share"] < 52.0
