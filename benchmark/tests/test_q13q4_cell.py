"""The configuration ``tpch-sf1-joinkinds-1chip``, its cell
``tpch-sf1.q13q4``, the data set ``tpch_text`` behind it and the six
readers the cell brought (PR 37).  Every entry of ``BENCHMARK.json`` is
looked up BY NAME: a later PR appends."""

import json
import os
import subprocess
import sys

import pytest

from benchmark.harness import observe
from benchmark.harness.observe import Request
from benchmark.harness.resolve import BENCH_DIR, ROOT, Cell, load_module

CELL = "tpch-sf1.q13q4"
CONFIG = "tpch-sf1-joinkinds-1chip"
NEW_METRICS = ("q13_p50_s", "q4_p50_s", "join.non_inner_share",
               "join.expanded_share", "join.expand_fill",
               "derived.aggregate_ms")
APPENDED_TO = ("kernel.join_build_ms", "kernel.join_probe_ms",
               "kernel.topk_ms", "join.direct_share",
               "join.elided_gather_share", "join.probe_resident_share",
               "join.index_build_ms", "join.index_builds_per_query",
               "agg.one_pass_spans_share", "upload.h2d_ms",
               "assemble.rows_per_query", "device.unsupported")
NOT_APPENDED_TO = ("scan.resident_share", "join.prefixed_search_share",
                   "mpp.indexed_share", "subquery.materialize_ms")


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
    assert cell.traffic_name == "power_q13q4"
    assert list(cell.templates) == ["q13", "q4"] == cell.traffic["order"]
    t = cell.traffic
    assert (t["loop"], t["clients"], t["rate_per_s"], t["parameters"],
            t["writers"]) == ("closed", 1, None, "validation", 0)
    cfg = cell.config
    assert cfg["dataset"] == "tpch_text"
    assert cell.dataset.__name__ == "benchmark.datasets.tpch_text"
    assert cfg["tables"] == {t: list(cols)
                             for t, cols in cell.dataset.SCHEMA.items()}
    assert sum(map(len, cfg["tables"].values())) == 50
    assert "o_comment" in cfg["tables"]["orders"]
    for mod in cell.templates.values():
        for table, cols in mod.READS.items():
            assert set(cols) <= set(cfg["tables"][table])
        assert callable(mod.reference) and callable(mod.min_bytes)


def test_it_is_the_joinclass_deployment_on_the_text_data_set():
    new, old = _config(CONFIG), _config("tpch-sf1-joinclass-1chip")
    for key in ("scale_factor", "chips", "engine", "layout", "session",
                "guarantees"):
        assert new[key] == old[key], key
    assert new["dataset"] == "tpch_text" and old["dataset"] == "tpch"
    assert new["engine"] == "tpu" and new["session"]["tidb_result_cache"] \
        == "OFF"
    assert "tidb_wal_fsync=commit" in new["guarantees"]["durability"]
    assert "exact" in new["guarantees"]["answers"]
    assert len(new["source"]) <= 200
    for word in ("TPC-H spec v3", "cl.1.4", "o_comment", "cl.4.2.2.10",
                 "cl.4.2.3", "SF1", "cl.2.4.13", "special, requests",
                 "cl.2.4.4", "1993-07-01", "one v5e chip", "MySQL wire"):
        assert word in new["source"], word
    assert new["source"] not in [c["source"] for c in _spec()["configs"]
                                 if c["name"] != CONFIG]
    assert set(new["reduced"]) == {"scale_factor", "tables"}
    cut = new["reduced"]["scale_factor"]
    for word in ("15M", "_compile_str_pattern", "16,777,216", "M4",
                 "tpch-sf10.q13q4"):
        assert word in cut, word
    assert "11 of" in new["reduced"]["tables"]
    for key in ("clause_numbers", "text_grammar", "matched_share", "keys",
                "answers", "generator"):
        assert key in new["assumed"], key
    assert "from memory" in new["assumed"]["clause_numbers"]
    assert "from memory" in new["assumed"]["text_grammar"]
    assert "NOT declared" in new["assumed"]["keys"]
    assert new["stands_for"]


def test_benchmark_json_names_them():
    spec = _spec()
    entry = _named(spec["configs"], CONFIG)
    assert entry["source"] == _config(CONFIG)["source"]
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    assert entry["reduced"] == ["scale_factor", "tables"]
    cell = _named(spec["workloads"], CELL)
    assert cell == {"name": CELL, "config": CONFIG, "traffic": "power_q13q4",
                    "chips": 1, "why": cell["why"]}
    assert len(cell["why"]) <= 200 and len(entry["why"]) <= 200
    layers = {"derived.aggregate_ms": "fetch + host assembly"}
    for name in NEW_METRICS:
        m = _named(spec["per_layer"], name)
        assert m["workloads"] == [CELL] and m["moves"] == "query_geomean_s"
        assert m["layer"] == layers.get(name, "XLA programs")
    for name in APPENDED_TO:
        assert CELL in _named(spec["per_layer"], name)["workloads"], name
    for name in NOT_APPENDED_TO:
        assert CELL not in _named(spec["per_layer"], name)["workloads"], name
    names = {m["name"] for m, _mod in Cell(CELL).per_layer}
    assert set(NEW_METRICS) | set(APPENDED_TO) <= names
    assert "xla.query_roofline" in names
    assert not names & set(NOT_APPENDED_TO)
    assert {m["name"] for m, _mod in Cell(CELL).end_to_end} == {
        "query_geomean_s", "setup_s"}


def test_min_bytes_count_every_read_column_once():
    cell = Cell(CELL)
    rows = {"lineitem": 6_000_000, "orders": 1_500_000, "customer": 150_000}
    # o_comment as its 4-byte codes
    assert cell.templates["q13"].min_bytes(rows) == (
        150_000 * 8 + 1_500_000 * (8 + 8 + 4))
    assert cell.templates["q4"].min_bytes(rows) == (
        1_500_000 * (8 + 4 + 4) + 6_000_000 * (8 + 4 + 4))


# -- the readers, on made observations ----------------------------------------

def _span(name, dur=None, tags=None, children=()):
    return {"name": name, "duration_s": dur, "tags": tags or {},
            "children": list(children)}


def _q13_tree(aggregate_s):
    return {"root": _span("statement", 1.5, children=[
        _span("supervisor.call", 1.4, children=[
            _span("host.assemble", 0.02, {"rows": 150_000})]),
        _span("derived.aggregate", aggregate_s,
              {"rows_in": 150_000, "groups": 42})])}


def _q4_tree():
    return {"root": _span("statement", 0.2, children=[
        _span("host.assemble", 0.001, {"rows": 5})])}


def _observation(requests, pipes0=None, pipes1=None):
    return observe.Observation(
        requests=requests, setup={},
        status0={"device_pipelines": pipes0 or {}},
        status1={"device_pipelines": pipes1 or {}},
        templates={"q13": None, "q4": None}, rows={}, device={},
        hbm_bytes=None, peaks=None, xplane=None)


def test_span_and_clock_readers():
    reqs = [Request("q13", 1.5, True, trace=_q13_tree(0.010)),
            Request("q4", 0.2, True, trace=_q4_tree()),
            Request("q13", 1.7, True, trace=_q13_tree(0.014)),
            Request("q4", 0.4, True, trace=_q4_tree()),
            Request("q13", 1.6, True, trace=_q13_tree(0.012))]
    obs = _observation(reqs)
    # the median over the requests that open one: Q4 opens none
    assert _reader("derived.aggregate_ms").read(obs) == pytest.approx(12.0)
    assert _reader("q13_p50_s").read(obs) == 1.6
    assert _reader("q4_p50_s").read(obs) == pytest.approx(0.3)
    assert _reader("assemble.rows_per_query").read(obs) == (150_000 + 5) / 2


def test_span_readers_find_nothing_in_a_program_without_the_span():
    """The parent (it cannot run Q13, but any cell may be asked), and an
    untraced run."""
    obs = _observation([Request("q4", 0.2, True, trace=_q4_tree())])
    assert _reader("derived.aggregate_ms").read(obs) is None
    assert _reader("q13_p50_s").read(obs) is None
    obs = _observation([Request("q13", 1.5, True)])
    assert _reader("derived.aggregate_ms").read(obs) is None
    assert _reader("q13_p50_s").read(obs) == 1.5
    assert _reader("q4_p50_s").read(obs) is None


#: growth over a window of 20 Q13 and 20 Q4
_CELL_BEFORE = {"join_direct": 4, "join_search": 0, "join_left": 2,
                "join_semi": 2, "join_anti": 0, "join_expand": 2,
                "join_expand_rows": 3_060_000,
                "join_expand_capacity": 4_194_304}
_CELL_AFTER = {"join_direct": 44, "join_search": 0, "join_left": 22,
               "join_semi": 22, "join_anti": 0, "join_expand": 22,
               "join_expand_rows": 3_060_000 + 20 * 1_530_000,
               "join_expand_capacity": 4_194_304 + 20 * 2_097_152}
#: Q3 and Q5: eight inner joins over unique builds a pair
_INNER_BEFORE = dict.fromkeys(_CELL_BEFORE, 0)
_INNER_AFTER = {**_INNER_BEFORE, "join_direct": 70, "join_search": 10}
#: the parent: the layouts' counters alone
_PARENT = {"join_direct": 40, "join_search": 0}


@pytest.mark.parametrize("before,after,non_inner,expanded,fill", [
    (_CELL_BEFORE, _CELL_AFTER, 100.0, 50.0,
     100.0 * 1_530_000 / 2_097_152),
    (_INNER_BEFORE, _INNER_AFTER, 0.0, 0.0, None),       # nothing expanded
    (_CELL_AFTER, _CELL_AFTER, None, None, None),        # no join ran
    ({"join_direct": 0, "join_search": 0}, _PARENT, None, None, None),
    ({}, {}, None, None, None),
])
def test_counter_readers(before, after, non_inner, expanded, fill):
    obs = _observation([], before, after)
    assert _reader("join.non_inner_share").read(obs) == non_inner
    assert _reader("join.expanded_share").read(obs) == expanded
    got = _reader("join.expand_fill").read(obs)
    assert got == (pytest.approx(fill) if fill is not None else None)


def test_an_anti_join_counts_as_not_inner_and_not_expanded():
    after = {**_INNER_BEFORE, "join_direct": 4, "join_anti": 1,
             "join_left": 1, "join_expand": 1, "join_expand_rows": 10,
             "join_expand_capacity": 16}
    obs = _observation([], _INNER_BEFORE, after)
    assert _reader("join.non_inner_share").read(obs) == 50.0
    assert _reader("join.expanded_share").read(obs) == 25.0
    assert _reader("join.expand_fill").read(obs) == 62.5


# -- the cell, rehearsed ------------------------------------------------------

def test_rehearsal_ends_with_a_last_line():
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", CELL, "--seed", "3700200101", "--seconds", "4",
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
    assert values["fragment.reruns_per_query"] == 0
    assert values["join.direct_share"] == 100.0     # SF0.01: all addressed
    assert values["join.probe_resident_share"] == 100.0
    assert values["join.non_inner_share"] == 100.0
    # one of every pair; a window that ends after a Q13 holds one more
    assert 50.0 <= values["join.expanded_share"] < 52.0
    # 15,000 orders less the matched, 500 customers and more without
    # one, in the 16,384 slots of the learned capacity
    assert 90.0 < values["join.expand_fill"] <= 100.0
    assert values["derived.aggregate_ms"] > 0
    # one group a customer (1,500 at SF0.01) and Q4's five
    assert values["assemble.rows_per_query"] == (1500 + 5) / 2
    assert last["attempted"] >= 20
    for name in (*NEW_METRICS, *APPENDED_TO, "fetch.d2h_ms",
                 "assemble.host_ms", "supervisor.call_ms"):
        assert values[name] is not None and name in last["metrics"], name
    for name in NOT_APPENDED_TO:
        assert name not in last["metrics"], name
