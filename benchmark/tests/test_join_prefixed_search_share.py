"""``join.prefixed_search_share``: the reader over the counter pair, on made
counters and on a ``DIAG STATUS`` pair taken around the cell's two
templates with partsupp searched; its entry in ``BENCHMARK.json`` (looked
up by name); and what the rehearsed cell prints of it."""

import json
import os
import subprocess
import sys
import types

import pytest

from benchmark.harness import observe
from benchmark.harness.resolve import BENCH_DIR, ROOT, Cell, load_module

NAME = "join.prefixed_search_share"
CELL = "tpch-sf1.q9q18"
READER = load_module(
    os.path.join(BENCH_DIR, "layer_metrics", NAME + ".py"),
    "per_layer metric")


def _obs(status0, status1):
    o = types.SimpleNamespace(status0=status0, status1=status1)
    o.counter_delta = lambda *path: observe.delta(o.status0, o.status1,
                                                  *path)
    return o


def _counters(direct, search, prefixed=None):
    c = {"join_direct": direct, "join_search": search, "compiles": 5}
    if prefixed is not None:
        c["join_search_prefixed"] = prefixed
    return {"device_pipelines": c}


@pytest.mark.parametrize("before,after,want", [
    (_counters(18, 3, 3), _counters(52, 9, 9), 100.0),   # the cell, this PR
    (_counters(0, 4, 1), _counters(0, 12, 3), 25.0),     # one of four
    (_counters(0, 2, 0), _counters(0, 6, 0), 0.0),       # hot keys only
    (_counters(14, 0, 0), _counters(49, 0, 0), None),    # nothing searched
    (_counters(18, 3), _counters(52, 9), None),          # the parent
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
                     "moves": "query_geomean_s", "workloads": [CELL]}
    for w in spec["workloads"]:
        names = {m["name"] for m, _mod in Cell(w["name"]).per_layer}
        assert (NAME in names) == (w["name"] == CELL)


def test_a_status_pair_around_the_cells_templates(monkeypatch):
    """`DIAG STATUS` before and after Q9 and Q18 over the generator's data
    with partsupp's slot table refused, as at SF1: Q9's one searched join
    starts from a bucket, `join.direct_share` reads what it read."""
    from benchmark.datasets import tpch
    from tidb_tpu.executor import join_index
    from tidb_tpu.testkit import TestKit
    cell = Cell(CELL)
    # SF0.02: partsupp's table 3.4 MB, orders' 0.48 MB
    monkeypatch.setattr(join_index, "_DIRECT_MAX_BYTES", 1 << 20)
    want = {t: list(cols) for t, cols in tpch.SCHEMA.items()}
    tk = TestKit()
    tpch.load(tk, tpch.generate(3400200101, 0.02, want), want, False,
              "test_join_prefixed_search_share")
    for stmt in ("set tidb_device_dispatch_rows = 1",
                 "set tidb_result_cache = 'OFF'",
                 "set tidb_executor_engine = 'tpu'"):
        tk.must_exec(stmt)

    def status():
        return json.loads(tk.must_query("DIAG STATUS").rows[0][0])

    status0 = status()
    for _ in range(2):
        for mod in cell.templates.values():
            assert tk.must_query(mod.SQL).rows
    obs = _obs(status0, status())
    assert READER.read(obs) == 100.0
    direct = load_module(os.path.join(
        BENCH_DIR, "layer_metrics", "join.direct_share.py"), "per_layer")
    # (4 + 2) of 7 a pair; the chip's window ends on a Q9: 17 of 20, 85.0
    assert direct.read(obs) == pytest.approx(100 * 6 / 7)
    # the same statements over the parent's index: the counter stays
    monkeypatch.setattr(join_index, "_bucket_prefix", lambda *a: None)
    tk.must_exec("insert into partsupp (ps_partkey, ps_suppkey) "
                 "values (null, null)")
    status0 = status()
    assert tk.must_query(cell.templates["q9"].SQL).rows
    assert READER.read(_obs(status0, status())) == 0.0


def test_the_rehearsed_cell_addresses_every_join():
    """At the rehearsal's SF0.01 partsupp's slot table is 0.9 MB and fits:
    nothing is searched, the reader finds nothing to divide by and the
    line leaves the metric out (the chip's SF1 reads 100: PERF.md)."""
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", CELL, "--seed", "3400200103", "--seconds", "4",
         "--trace", "1", "--rehearse"],
        capture_output=True, text=True, cwd=ROOT, timeout=900)
    assert p.returncode == 0, p.stderr[-2000:]
    lines = [json.loads(ln) for ln in p.stdout.strip().splitlines()]
    assert lines[-1]["correct"] is True and lines[-1]["failed"] == 0
    values = next(ln["values"] for ln in lines
                  if ln.get("metric") == "bench_rehearsal_values")
    assert values["join.direct_share"] == 100.0
    assert NAME not in values and NAME not in lines[-1]["metrics"]
