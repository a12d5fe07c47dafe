"""The configuration ``tpch-sf1-joinderived-1chip``, its cell
``tpch-sf1.q17`` and the two readers the cell brought.  Every entry of
``BENCHMARK.json`` is looked up BY NAME and a list is asked for what it
holds: a later change appends."""

import json
import os
import subprocess
import sys
from decimal import ROUND_HALF_UP, Decimal

import numpy as np
import pytest

from benchmark.harness import observe
from benchmark.harness.observe import Request
from benchmark.harness.resolve import BENCH_DIR, ROOT, Cell, load_module

CELL = "tpch-sf1.q17"
CONFIG = "tpch-sf1-joinderived-1chip"
TWIN = "tpch-sf1-joinresid-1chip"
NEW_METRICS = ("join.derived_build_ms", "join.derived_rows_per_query")
APPENDED_TO = ("device.unsupported", "kernel.join_probe_ms",
               "join.index_build_ms", "join.index_builds_per_query",
               "join.compactions_per_query")
NOT_APPENDED_TO = ("join.residual_share", "join.residual_fill",
                   "kernel.join_exists_ms", "kernel.join_exists_roofline",
                   "subquery.materialize_ms", "derived.aggregate_ms",
                   "q13_p50_s", "q18_p50_s")

q17 = load_module(os.path.join(BENCH_DIR, "queries", "q17.py"),
                  "query template")


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
    assert cell.traffic_name == "power_q17"
    assert list(cell.templates) == ["q17"] == cell.traffic["order"]
    t = cell.traffic
    assert (t["loop"], t["clients"], t["rate_per_s"], t["parameters"],
            t["writers"]) == ("closed", 1, None, "validation", 0)
    assert cell.config["dataset"] == "tpch"
    mod = cell.templates["q17"]
    for table, cols in mod.READS.items():
        assert set(cols) <= set(cell.config["tables"][table])
    assert callable(mod.reference) and callable(mod.counts)


def test_it_is_the_joinresid_deployment_key_by_key():
    new, old = _config(CONFIG), _config(TWIN)
    own = {"source", "stands_for", "reduced", "assumed"}
    assert set(new) == set(old)
    for key in set(new) - own:
        assert new[key] == old[key], key
    assert len(new["source"]) <= 200
    for word in ("TPC-H spec v3", "cl.1.4", "cl.4.2.3", "SF1", "cl.2.4.17",
                 "Brand#23", "MED BOX", "one v5e chip", "MySQL wire"):
        assert word in new["source"], word
    assert new["source"] not in [c["source"] for c in _spec()["configs"]
                                 if c["name"] != CONFIG]
    assert set(new["reduced"]) == {"scale_factor", "tables"}
    assert new["reduced"]["tables"] == old["reduced"]["tables"]
    cut = new["reduced"]["scale_factor"]
    for word in ("SF1", "SF10", "16,777,216", "M4", "tpch-sf10.q17"):
        assert word in cut, word
    assert new["assumed"]["clause_numbers"] \
        == old["assumed"]["clause_numbers"]
    assert "l_partkey is NOT declared" in new["assumed"]["keys"]
    assert "half up" in new["assumed"]["decimals"]
    assert new["stands_for"] != old["stands_for"]


def test_benchmark_json_names_them():
    spec = _spec()
    entry = _named(spec["configs"], CONFIG)
    assert entry["source"] == _config(CONFIG)["source"]
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    assert entry["reduced"] == ["scale_factor", "tables"]
    cell = _named(spec["workloads"], CELL)
    assert cell == {"name": CELL, "config": CONFIG, "traffic": "power_q17",
                    "chips": 1, "why": cell["why"]}
    assert len(cell["why"]) <= 200 and len(entry["why"]) <= 200
    build = _named(spec["per_layer"], "join.derived_build_ms")
    rows = _named(spec["per_layer"], "join.derived_rows_per_query")
    assert (build["source"], build["layer"], build["unit"]) == (
        "program_span", "dispatch stack", "ms")
    assert (rows["source"], rows["layer"], rows["better"]) == (
        "program_counter", "XLA programs", "lower")
    for m in (build, rows):
        assert m["workloads"] == [CELL] and m["moves"] == "query_geomean_s"
    for name in APPENDED_TO:
        assert CELL in _named(spec["per_layer"], name)["workloads"], name
    for name in NOT_APPENDED_TO:
        assert CELL not in _named(spec["per_layer"], name)["workloads"], name
    names = {m["name"] for m, _mod in Cell(CELL).per_layer}
    assert set(NEW_METRICS) | set(APPENDED_TO) <= names
    assert not names & set(NOT_APPENDED_TO)
    assert "xla.query_roofline" in names
    assert {m["name"] for m, _mod in Cell(CELL).end_to_end} == {
        "query_geomean_s", "setup_s"}


def test_min_bytes_count_the_subquerys_columns_twice():
    mod = Cell(CELL).templates["q17"]
    rows = {"lineitem": 6_000_000, "part": 200_000}
    # l_partkey, l_quantity twice, l_extendedprice once (8 B each); part's
    # key (8 B) and two dictionary codes (4 B each)
    assert mod.min_bytes(rows) == (6_000_000 * 8 * 5
                                   + 200_000 * (8 + 4 + 4))


def test_a_program_without_the_derived_build_refuses_the_cell(monkeypatch):
    """The parent answers Q17 with the host's joins under a pinned `tpu`
    engine: the template says so at once, before the worker starts."""
    from tidb_tpu.executor import device_join
    monkeypatch.delattr(device_join, "_derived_leaf")
    with pytest.raises(NotImplementedError, match="derived build"):
        load_module(os.path.join(BENCH_DIR, "queries", "q17.py"),
                    "query template")


# -- the reference, by hand ---------------------------------------------------

def _tables(parts, lines):
    """Generator-shaped tables: `parts` [(key, brand, container)], `lines`
    [(partkey, quantity, extendedprice)] in the columns' integer units."""
    brands, containers = [b"Brand#23", b"Brand#12"], [b"MED BOX", b"LG BOX"]
    return {
        "part": {
            "p_partkey": np.array([p[0] for p in parts], dtype=np.int64),
            "p_brand": (np.array([brands.index(p[1]) for p in parts],
                                 dtype=np.int32), brands),
            "p_container": (np.array([containers.index(p[2])
                                      for p in parts], dtype=np.int32),
                            containers)},
        "lineitem": {
            "l_partkey": np.array([ln[0] for ln in lines], dtype=np.int64),
            "l_quantity": np.array([ln[1] for ln in lines], dtype=np.int64),
            "l_extendedprice": np.array([ln[2] for ln in lines],
                                        dtype=np.int64)}}


_PARTS = [(1, b"Brand#23", b"MED BOX"), (2, b"Brand#23", b"MED BOX"),
          (3, b"Brand#23", b"MED BOX"), (4, b"Brand#23", b"LG BOX"),
          (5, b"Brand#12", b"MED BOX")]


def test_reference_by_hand():
    # quantities in hundredths: part 1 avg 5.5, threshold 1.1, its 1.00 is
    # under it; part 2 avg 1.333333 (rounded half up at scale 6),
    # threshold 0.2666666: none; part 3 avg 5, threshold 1.0, its 1.00
    # EQUALS it and is not under it; parts 4 and 5 are not asked for
    lines = [(1, 100, 10_050), (1, 1_000, 99_900),
             (2, 100, 500), (2, 100, 500), (2, 200, 900),
             (3, 100, 7_777), (3, 900, 80_000),
             (4, 100, 1), (4, 5_000, 1), (5, 100, 1), (5, 5_000, 1)]
    t = _tables(_PARTS, lines)
    assert q17.counts(t) == {"parts": 3, "live_lines": 7, "lines_under": 1,
                             "groups": 5}
    # 100.50 / 7 = 14.357142857...: 14.357143
    assert q17.reference(t) == [("14.357143",)]
    # 5.00 / 7 = 0.714285714...: rounded up at the sixth decimal
    t = _tables(_PARTS[:1], [(1, 100, 500), (1, 1_000, 1)])
    assert q17.reference(t) == [("0.714286",)]
    # the average is rounded before it is compared: 25,000 lines whose
    # average is 5.0000004 give 5.000000 and a threshold of 1.0000000,
    # which a line of 1.00 is not under (unrounded it would be)
    qty = [100] + [500] * 24_998 + [901]
    t = _tables(_PARTS[:1], [(1, q, 3) for q in qty])
    assert sum(qty) == 12_500_001
    assert q17.counts(t)["lines_under"] == 0
    assert q17.reference(t) == [(None,)]
    # nothing under its threshold: the sum over no row is NULL
    t = _tables(_PARTS, [(1, 100, 5), (1, 100, 5), (4, 1, 1), (4, 900, 1)])
    assert q17.reference(t) == [(None,)]


def _by_decimal(t):
    """Q17 over generated tables with Python's decimal module, a part at a
    time: the rules the engine follows, not the reference's integers."""
    li, p = t["lineitem"], t["part"]
    brand, container = p["p_brand"], p["p_container"]
    keep = ((brand[0] == brand[1].index(b"Brand#23"))
            & (container[0] == container[1].index(b"MED BOX")))
    revenue, found = Decimal(0), False
    for key in p["p_partkey"][keep]:
        mine = li["l_partkey"] == key
        qty = [Decimal(int(q)) / 100 for q in li["l_quantity"][mine]]
        if not qty:
            continue
        avg = (sum(qty) / len(qty)).quantize(Decimal("0.000001"),
                                             ROUND_HALF_UP)
        for q, e in zip(qty, li["l_extendedprice"][mine]):
            if q < Decimal("0.2") * avg:
                revenue += Decimal(int(e)) / 100
                found = True
    if not found:
        return [(None,)]
    return [(str((revenue / Decimal("7.0")).quantize(Decimal("0.000001"),
                                                     ROUND_HALF_UP)),)]


@pytest.mark.parametrize("seed", (2100000001, 4300200013))
def test_reference_at_sf001_is_the_decimal_rules(seed):
    from benchmark.datasets import tpch
    t = tpch.generate(seed, 0.01, q17.READS)
    assert q17.counts(t)["groups"] == len(
        np.unique(t["lineitem"]["l_partkey"]))
    assert q17.reference(t) == _by_decimal(t)


# -- the readers, on made observations ----------------------------------------

def _span(name, dur=None, tags=None, children=()):
    return {"name": name, "duration_s": dur, "tags": tags or {},
            "children": list(children)}


def _q17_tree(build_s):
    build = _span("join.derived_build", build_s,
                  {"rows": 200_000, "cols": 2, "bytes": 3_400_000},
                  [_span("device.dispatch", build_s - 0.01)])
    return {"root": _span("statement", 0.6, children=[
        _span("supervisor.call", 0.5, children=[
            build, _span("join.index_build", 0.004)])])}


def _observation(requests, pipes0=None, pipes1=None):
    return observe.Observation(
        requests=requests, setup={},
        status0={"device_pipelines": pipes0 or {}},
        status1={"device_pipelines": pipes1 or {}},
        templates={"q17": None}, rows={}, device={}, hbm_bytes=None,
        peaks=None, xplane=None)


def test_the_span_reader_is_a_mean_a_request():
    reqs = [Request("q17", 0.6, True, trace=_q17_tree(0.25)),
            Request("q17", 0.6, True, trace=_q17_tree(0.35)),
            Request("q17", 0.6, True,
                    trace={"root": _span("statement", 0.1)})]
    obs = _observation(reqs, {"join_derived": 0}, {"join_derived": 2})
    assert _reader("join.derived_build_ms").read(obs) == \
        pytest.approx(200.0)
    # untraced: nothing to read
    obs = _observation([Request("q17", 0.6, True)], {"join_derived": 0},
                       {"join_derived": 1})
    assert _reader("join.derived_build_ms").read(obs) is None


@pytest.mark.parametrize("before,after,n,want", [
    # 40 requests of one 200,000-row derived leaf each
    ({"join_derived": 3, "join_derived_rows": 600_000},
     {"join_derived": 43, "join_derived_rows": 8_600_000}, 40, 200_000.0),
    # a window whose fragments held no derived leaf
    ({"join_derived": 3, "join_derived_rows": 600_000},
     {"join_derived": 3, "join_derived_rows": 600_000}, 10, 0.0),
])
def test_the_counter_reader(before, after, n, want):
    obs = _observation([Request("q17", 0.6, True)] * n, before, after)
    assert _reader("join.derived_rows_per_query").read(obs) == want


def test_the_readers_find_nothing_in_a_parent():
    """A program without the counters and the span: the in-set fold's
    span alone, the kinds' counters alone."""
    tree = {"root": _span("statement", 2.0, children=[
        _span("subquery.materialize", 0.7)])}
    obs = _observation([Request("q17", 2.0, True, trace=tree)],
                       {"join_semi": 0, "unsupported": 0},
                       {"join_semi": 0, "unsupported": 1})
    assert _reader("join.derived_build_ms").read(obs) is None
    assert _reader("join.derived_rows_per_query").read(obs) is None


# -- the cell, rehearsed ------------------------------------------------------

def test_rehearsal_ends_with_a_last_line():
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", CELL, "--seed", "2100000001", "--seconds", "4",
         "--trace", "1", "--rehearse"],
        capture_output=True, text=True, cwd=ROOT, timeout=900)
    assert p.returncode == 0, p.stderr[-2000:]
    lines = [json.loads(ln) for ln in p.stdout.strip().splitlines()]
    last = lines[-1]
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] >= 5
    window = next(ln for ln in lines if ln.get("metric") == "bench_window")
    assert window["problems"] == [] and window["window_compiles"] == 0
    assert window["hbm_evictions"] == 0
    values = next(ln["values"] for ln in lines
                  if ln.get("metric") == "bench_rehearsal_values")
    assert values["device.unsupported"] == 0
    assert values["compile.window_compiles"] == 0
    # one group a part: 2,000 at SF0.01
    assert values["join.derived_rows_per_query"] == 2000.0
    assert values["join.derived_build_ms"] > 0
    # the derived leaf's index is the statement's: built every request
    assert values["join.index_builds_per_query"] == 1.0
    # the inner aggregate forgets its capacity (device_agg): one rerun a
    # request
    assert values["fragment.reruns_per_query"] == 1.0
    assert values["join.compactions_per_query"] == 1.0
    for name in (*NEW_METRICS, *APPENDED_TO):
        assert name in last["metrics"], name
    for name in NOT_APPENDED_TO:
        assert name not in last["metrics"], name

