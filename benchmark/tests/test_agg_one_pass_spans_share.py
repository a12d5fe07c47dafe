"""``agg.one_pass_spans_share``: the reader over the counter pair, on made
counters and on a ``DIAG STATUS`` pair taken around the cell's two
templates at SF0.02, where Q18's inner aggregate at its learned capacity
(32,768 slots over 131,072 rows) sorts as it does at SF1; its entry in
``BENCHMARK.json`` (looked up by name); and what the rehearsed cell
prints of it."""

import json
import os
import subprocess
import sys
import types

import pytest

from benchmark.harness import observe
from benchmark.harness.resolve import BENCH_DIR, ROOT, Cell, load_module

NAME = "agg.one_pass_spans_share"
CELLS = ["tpch-sf1.q3q5", "tpch-sf1-mpp4.q3", "ssb-sf10.flights",
         "tpch-sf1.q9q18"]
READER = load_module(
    os.path.join(BENCH_DIR, "layer_metrics", NAME + ".py"),
    "per_layer metric")


def _obs(status0, status1):
    o = types.SimpleNamespace(status0=status0, status1=status1)
    o.counter_delta = lambda *path: observe.delta(o.status0, o.status1,
                                                  *path)
    return o


def _counters(dense, sort, one_pass=None):
    c = {"agg_dense": dense, "agg_sorted": sort, "compiles": 5}
    if one_pass is not None:
        c["agg_spans_one_pass"] = one_pass
    return {"device_pipelines": c}


@pytest.mark.parametrize("before,after,want", [
    # ten pairs of Q9 (one sort-arm fragment) and Q18 (two, and the inner
    # one's second program sorts): the cell, this PR
    (_counters(0, 9, 3), _counters(0, 39, 13), 100.0 / 3),
    (_counters(0, 9, 3), _counters(0, 40, 13), 100.0 * 10 / 31),
    (_counters(0, 14, 0), _counters(0, 42, 0), 0.0),     # Q3 + Q5 search
    (_counters(7, 0, 0), _counters(900, 0, 0), None),    # a dense cell
    (_counters(0, 9), _counters(0, 39), None),           # the parent
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
                     "moves": "query_geomean_s", "workloads": CELLS}
    assert spec["per_layer"][-1] is entry
    for w in spec["workloads"]:
        names = {m["name"] for m, _mod in Cell(w["name"]).per_layer}
        assert (NAME in names) == (w["name"] in CELLS)


def test_a_status_pair_around_the_cells_templates():
    """`DIAG STATUS` before and after settled Q9s and Q18s over the
    generator's data: of a pair's three sort-arm fragments one runs a
    program that sorts its flagged positions."""
    from benchmark.datasets import tpch
    from tidb_tpu.testkit import TestKit
    cell = Cell("tpch-sf1.q9q18")
    want = {t: list(cols) for t, cols in tpch.SCHEMA.items()}
    tk = TestKit()
    tpch.load(tk, tpch.generate(3600200101, 0.02, want), want, False,
              "test_agg_one_pass_spans_share")
    for stmt in ("set tidb_device_dispatch_rows = 1",
                 "set tidb_result_cache = 'OFF'",
                 "set tidb_executor_engine = 'tpu'"):
        tk.must_exec(stmt)

    def status():
        return json.loads(tk.must_query("DIAG STATUS").rows[0][0])

    for mod in cell.templates.values():        # learn the capacities
        tk.must_query(mod.SQL)
    status0 = status()
    for _ in range(2):
        for mod in cell.templates.values():
            tk.must_query(mod.SQL)
    obs = _obs(status0, status())
    # one a Q18: its inner aggregate at the learned capacity (at this
    # scale no order passes the HAVING and the outer fragment has
    # nothing to probe for; at SF1 a pair is three fragments)
    assert obs.counter_delta("device_pipelines", "agg_spans_one_pass") == 2
    sort = obs.counter_delta("device_pipelines", "agg_sorted")
    assert sort in (4, 6)
    assert READER.read(obs) == pytest.approx(100 * 2 / sort)
    status0 = status()
    assert tk.must_query(cell.templates["q9"].SQL).rows
    assert READER.read(_obs(status0, status())) == 0.0


def test_the_rehearsed_cell_searches():
    """At the rehearsal's SF0.01 Q18's inner aggregate reads a 65,536-row
    bucket, under the rule's floor: every program searches and the line
    says 0 (the chip's SF1 reads 33.3 or 32.3: PERF.md)."""
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", "tpch-sf1.q9q18", "--seed", "3600200103",
         "--seconds", "4", "--trace", "1", "--rehearse"],
        capture_output=True, text=True, cwd=ROOT, timeout=900)
    assert p.returncode == 0, p.stderr[-2000:]
    lines = [json.loads(ln) for ln in p.stdout.strip().splitlines()]
    assert lines[-1]["correct"] is True and lines[-1]["failed"] == 0
    values = next(ln["values"] for ln in lines
                  if ln.get("metric") == "bench_rehearsal_values")
    assert values[NAME] == 0.0 and NAME in lines[-1]["metrics"]
