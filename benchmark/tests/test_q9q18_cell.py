"""The configuration ``tpch-sf1-joinclass-1chip``, its cell
``tpch-sf1.q9q18`` and the five readers the cell brought (PR 33).  Every
entry of ``BENCHMARK.json`` is looked up BY NAME: a later PR appends."""

import json
import os
import subprocess
import sys
import types

import pytest

from benchmark.harness import observe
from benchmark.harness.observe import Request
from benchmark.harness.resolve import BENCH_DIR, ROOT, Cell, load_module

CELL = "tpch-sf1.q9q18"
CONFIG = "tpch-sf1-joinclass-1chip"
NEW_METRICS = ("q9_p50_s", "q18_p50_s", "subquery.materialize_ms",
               "assemble.rows_per_query", "device.unsupported")
APPENDED_TO = ("kernel.join_build_ms", "kernel.join_probe_ms",
               "kernel.topk_ms", "join.direct_share",
               "join.elided_gather_share", "join.probe_resident_share",
               "upload.h2d_ms", "scan.resident_share")


def _reader(name):
    return load_module(os.path.join(BENCH_DIR, "layer_metrics", name + ".py"),
                       "per_layer metric")


def _config(name):
    with open(os.path.join(BENCH_DIR, "configs", name + ".json")) as f:
        return json.load(f)


def _spec():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _named(entries, name):
    (found,) = [e for e in entries if e["name"] == name]
    return found


# -- the configuration and the cell -------------------------------------------

def test_the_cell_resolves_and_its_reads_are_installed():
    cell = Cell(CELL)
    assert cell.config_name == CONFIG and cell.chips == 1
    assert cell.traffic_name == "power_q9q18"
    assert list(cell.templates) == ["q9", "q18"] == cell.traffic["order"]
    t = cell.traffic
    assert (t["loop"], t["clients"], t["rate_per_s"], t["parameters"],
            t["writers"]) == ("closed", 1, None, "validation", 0)
    cfg = cell.config
    assert cfg["tables"] == {t: list(cols)
                             for t, cols in cell.dataset.SCHEMA.items()}
    for mod in cell.templates.values():
        for table, cols in mod.READS.items():
            assert set(cols) <= set(cfg["tables"][table])


def test_it_is_the_sf1_deployment_with_statements_of_its_own():
    new, old = _config(CONFIG), _config("tpch-sf1-1chip")
    for key in ("dataset", "scale_factor", "chips", "engine", "layout",
                "session", "guarantees", "tables"):
        assert new[key] == old[key], key
    assert new["engine"] == "tpu" and new["session"]["tidb_result_cache"] \
        == "OFF"
    assert "tidb_wal_fsync=commit" in new["guarantees"]["durability"]
    assert len(new["source"]) <= 200
    for word in ("TPC-H spec v3", "cl.1.4", "cl.4.2.3", "SF1", "cl.2.4.9",
                 "cl.2.4.18", "green", "300", "one v5e chip", "MySQL wire"):
        assert word in new["source"], word
    assert new["source"] != old["source"]
    assert set(new["reduced"]) == {"scale_factor", "tables"}
    assert "under 1 s" in new["reduced"]["scale_factor"]
    assert "ps_partkey, ps_suppkey" in new["assumed"]["primary_keys"]
    assert "from memory" in new["assumed"]["clause_numbers"]


def test_benchmark_json_names_them():
    spec = _spec()
    entry = _named(spec["configs"], CONFIG)
    assert entry["source"] == _config(CONFIG)["source"]
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    assert entry["reduced"] == ["scale_factor", "tables"]
    cell = _named(spec["workloads"], CELL)
    assert cell == {"name": CELL, "config": CONFIG, "traffic": "power_q9q18",
                    "chips": 1, "why": cell["why"]}
    assert len(cell["why"]) <= 200 and len(entry["why"]) <= 200
    for name in NEW_METRICS:
        m = _named(spec["per_layer"], name)
        assert m["workloads"] == [CELL] and m["moves"] == "query_geomean_s"
    for name in APPENDED_TO:
        assert CELL in _named(spec["per_layer"], name)["workloads"], name
    assert CELL not in _named(spec["per_layer"],
                              "mpp.indexed_share")["workloads"]
    names = {m["name"] for m, _mod in Cell(CELL).per_layer}
    assert set(NEW_METRICS) | set(APPENDED_TO) <= names
    assert "xla.query_roofline" in names
    assert {m["name"] for m, _mod in Cell(CELL).end_to_end} == {
        "query_geomean_s", "setup_s"}


def test_min_bytes_count_q18s_lineitem_twice():
    cell = Cell(CELL)
    rows = {"lineitem": 6_000_000, "orders": 1_500_000, "customer": 150_000,
            "part": 200_000, "partsupp": 800_000, "supplier": 10_000,
            "nation": 25}
    assert cell.templates["q18"].min_bytes(rows) == (
        150_000 * (8 + 4) + 1_500_000 * (8 + 8 + 4 + 8)
        + 2 * 6_000_000 * (8 + 8))
    assert cell.templates["q9"].min_bytes(rows) == (
        200_000 * (8 + 4) + 10_000 * 16 + 6_000_000 * 6 * 8
        + 800_000 * 24 + 1_500_000 * (8 + 4) + 25 * (8 + 4))


# -- the readers, on made observations ----------------------------------------

def _span(name, dur=None, tags=None, children=()):
    return {"name": name, "duration_s": dur, "tags": tags or {},
            "children": list(children)}


def _q18_tree(inner_rows, materialize_s):
    inner = _span("host.assemble", 0.2, {"rows": inner_rows})
    sub = _span("subquery.materialize", materialize_s,
                {"rows": 9, "kept": 9}, [_span("device.dispatch", 0.5,
                                               children=[inner])])
    return {"root": _span("statement", 2.0, children=[
        _span("supervisor.call", 1.9, children=[
            sub, _span("host.assemble", 0.001, {"rows": 9})])])}


def _q9_tree():
    return {"root": _span("statement", 4.0, children=[
        _span("host.assemble", 0.001, {"rows": 175})])}


def _observation(requests, pipes0=None, pipes1=None):
    return observe.Observation(
        requests=requests, setup={},
        status0={"device_pipelines": pipes0 or {}},
        status1={"device_pipelines": pipes1 or {}},
        templates={"q9": None, "q18": None}, rows={}, device={},
        hbm_bytes=None, peaks=None, xplane=None)


def test_span_readers_read_the_subquery_and_the_rows():
    reqs = [Request("q9", 4.0, True, trace=_q9_tree()),
            Request("q18", 2.0, True, trace=_q18_tree(1_500_000, 0.9)),
            Request("q9", 4.2, True, trace=_q9_tree()),
            Request("q18", 2.2, True, trace=_q18_tree(1_500_000, 1.1)),
            Request("q9", 4.1, True, trace=_q9_tree())]
    obs = _observation(reqs)
    assert _reader("subquery.materialize_ms").read(obs) == \
        pytest.approx(1000.0)
    # per template the median of a request's summed rows, then the mean of
    # the templates: an odd request more of Q9 does not move it
    assert _reader("assemble.rows_per_query").read(obs) == \
        (175 + 1_500_009) / 2
    assert _reader("q9_p50_s").read(obs) == 4.1
    assert _reader("q18_p50_s").read(obs) == pytest.approx(2.1)


def test_span_readers_find_nothing_in_a_program_without_the_span():
    """The parent: no ``subquery.materialize``; and an untraced run."""
    bare = {"root": _span("statement", 2.0, children=[
        _span("host.assemble", 0.2, {"rows": 1_500_000})])}
    obs = _observation([Request("q18", 2.0, True, trace=bare)])
    assert _reader("subquery.materialize_ms").read(obs) is None
    assert _reader("assemble.rows_per_query").read(obs) == 1_500_000
    obs = _observation([Request("q18", 2.0, True)])
    assert _reader("subquery.materialize_ms").read(obs) is None
    assert _reader("assemble.rows_per_query").read(obs) is None
    assert _reader("q9_p50_s").read(obs) is None


@pytest.mark.parametrize("before,after,want", [
    ({"unsupported": 3}, {"unsupported": 3}, 0),     # the cell
    ({"unsupported": 0}, {"unsupported": 14}, 14),   # every Q9 on the host
    ({"compiles": 4}, {"compiles": 4}, None),        # the parent: no counter
])
def test_device_unsupported(before, after, want):
    o = types.SimpleNamespace(status0={"device_pipelines": before},
                              status1={"device_pipelines": after})
    o.counter_delta = lambda *path: observe.delta(o.status0, o.status1,
                                                  *path)
    assert _reader("device.unsupported").read(o) == want


# -- the cell, rehearsed ------------------------------------------------------

def test_rehearsal_ends_with_a_last_line():
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", CELL, "--seed", "3300200101", "--seconds", "4",
         "--trace", "1", "--rehearse"],
        capture_output=True, text=True, cwd=ROOT, timeout=900)
    assert p.returncode == 0, p.stderr[-2000:]
    lines = [json.loads(ln) for ln in p.stdout.strip().splitlines()]
    last = lines[-1]
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] >= 2
    window = next(ln for ln in lines if ln.get("metric") == "bench_window")
    assert window["problems"] == [] and window["window_compiles"] == 0
    values = next(ln["values"] for ln in lines
                  if ln.get("metric") == "bench_rehearsal_values")
    assert values["device.unsupported"] == 0
    assert values["compile.window_compiles"] == 0
    assert values["join.direct_share"] == 100.0     # SF0.01: all addressed
    assert values["join.probe_resident_share"] == 100.0
    assert values["scan.resident_share"] == 100.0   # Q18's inner fragment
    assert values["subquery.materialize_ms"] > 0
    # one group an order (15,000 at SF0.01), a handful of rows, Q9's 175
    assert values["assemble.rows_per_query"] > 7_500
    for name in (*NEW_METRICS, *APPENDED_TO, "fetch.d2h_ms",
                 "assemble.host_ms", "supervisor.call_ms"):
        assert values[name] is not None and name in last["metrics"], name
