"""The configuration ``tpch-sf1-joinresid-1chip``, its cell
``tpch-sf1.q21`` and the four readers the cell brought (PR 39).  Every
entry of ``BENCHMARK.json`` is looked up BY NAME and a list is asked for
what it holds: a later PR appends."""

import json
import os
import subprocess
import sys

import pytest

from benchmark.harness import observe, trace_owners, trace_reduce
from benchmark.harness.observe import Request
from benchmark.harness.resolve import BENCH_DIR, ROOT, Cell, load_module

CELL = "tpch-sf1.q21"
CONFIG = "tpch-sf1-joinresid-1chip"
TWIN = "tpch-sf1-joinclass-1chip"
NEW_METRICS = ("join.residual_share", "join.residual_fill",
               "kernel.join_exists_ms", "kernel.join_exists_roofline")
APPENDED_TO = ("device.unsupported", "kernel.join_probe_ms",
               "join.index_build_ms", "join.index_builds_per_query")
NOT_APPENDED_TO = ("join.non_inner_share", "join.expanded_share",
                   "join.expand_fill", "join.one_pass_expand_share",
                   "q13_p50_s", "q4_p50_s", "derived.aggregate_ms",
                   "subquery.materialize_ms", "scan.resident_share")


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
    assert cell.traffic_name == "power_q21"
    assert list(cell.templates) == ["q21"] == cell.traffic["order"]
    t = cell.traffic
    assert (t["loop"], t["clients"], t["rate_per_s"], t["parameters"],
            t["writers"]) == ("closed", 1, None, "validation", 0)
    assert cell.config["dataset"] == "tpch"
    mod = cell.templates["q21"]
    for table, cols in mod.READS.items():
        assert set(cols) <= set(cell.config["tables"][table])
    assert callable(mod.reference) and callable(mod.exists_min_bytes)


def test_it_is_the_joinclass_deployment_key_by_key():
    new, old = _config(CONFIG), _config(TWIN)
    own = {"source", "stands_for", "reduced", "assumed"}
    assert set(new) == set(old)
    for key in set(new) - own:
        assert new[key] == old[key], key
    assert len(new["source"]) <= 200
    for word in ("TPC-H spec v3", "cl.1.4", "cl.4.2.3", "SF1",
                 "cl.2.4.21", "SAUDI ARABIA", "one v5e chip", "MySQL wire"):
        assert word in new["source"], word
    assert new["source"] not in [c["source"] for c in _spec()["configs"]
                                 if c["name"] != CONFIG]
    assert set(new["reduced"]) == {"scale_factor", "tables"}
    assert new["reduced"]["tables"] == old["reduced"]["tables"]
    cut = new["reduced"]["scale_factor"]
    for word in ("SF1", "SF10", "16,777,216", "M4", "unique",
                 "tpch-sf10.q21"):
        assert word in cut, word
    assert new["assumed"]["clause_numbers"] \
        == old["assumed"]["clause_numbers"]
    assert "l_orderkey is NOT declared" in new["assumed"]["keys"]
    assert new["stands_for"] != old["stands_for"]


def test_benchmark_json_names_them():
    spec = _spec()
    entry = _named(spec["configs"], CONFIG)
    assert entry["source"] == _config(CONFIG)["source"]
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    assert entry["reduced"] == ["scale_factor", "tables"]
    cell = _named(spec["workloads"], CELL)
    assert cell == {"name": CELL, "config": CONFIG, "traffic": "power_q21",
                    "chips": 1, "why": cell["why"]}
    assert len(cell["why"]) <= 200 and len(entry["why"]) <= 200
    for name in NEW_METRICS:
        m = _named(spec["per_layer"], name)
        assert m["workloads"] == [CELL] and m["moves"] == "query_geomean_s"
        assert m["layer"] == "XLA programs"
    assert _named(spec["per_layer"], "kernel.join_exists_roofline")[
        "unit"] == "%"
    for name in APPENDED_TO:
        assert CELL in _named(spec["per_layer"], name)["workloads"], name
    for name in NOT_APPENDED_TO:
        assert CELL not in _named(spec["per_layer"], name)["workloads"], name
    names = {m["name"] for m, _mod in Cell(CELL).per_layer}
    assert set(NEW_METRICS) | set(APPENDED_TO) <= names
    assert not names & set(NOT_APPENDED_TO)
    assert {m["name"] for m, _mod in Cell(CELL).end_to_end} == {
        "query_geomean_s", "setup_s"}


def test_exists_min_bytes_by_hand_at_sf001():
    cell = Cell(CELL)
    mod = cell.templates["q21"]
    # SF0.01: 60,175 lines (seed 7's count: what the generator gives)
    rows = {"lineitem": 60_175, "orders": 15_000, "supplier": 100,
            "nation": 25}
    # l_orderkey, l_suppkey (8 B each), l_commitdate, l_receiptdate (4 B)
    assert mod.exists_min_bytes(rows) == 60_175 * (8 + 8 + 4 + 4)
    assert mod.min_bytes(rows) == (60_175 * 24 + 15_000 * (8 + 4)
                                   + 100 * (8 + 4 + 8) + 25 * (8 + 4))


def test_a_planner_without_the_residual_refuses_the_cell(monkeypatch):
    """The parent leaves the `<>` to the per-row Apply: the template says
    so at once instead of at the harness's wire timeout."""
    from tidb_tpu.planner import builder
    monkeypatch.delattr(builder, "bind_outer_refs")
    with pytest.raises(NotImplementedError, match="Apply"):
        load_module(os.path.join(BENCH_DIR, "queries", "q21.py"),
                    "query template")


# -- the readers, on made observations ----------------------------------------

def _observation(pipes0=None, pipes1=None, xplane=None, peaks=None,
                 rows=None):
    return observe.Observation(
        requests=[Request("q21", 1.0, True)], setup={},
        status0={"device_pipelines": pipes0 or {}},
        status1={"device_pipelines": pipes1 or {}},
        templates={"q21": Cell(CELL).templates["q21"]}, rows=rows or {},
        device={"count": 1}, hbm_bytes=None, peaks=peaks, xplane=xplane)


#: growth over a window of 40 Q21: a semi and an anti join each, both
#: with a residual, 361,229 + 303,000 pairs in 524,288 + 524,288 slots
_BEFORE = {"join_semi": 2, "join_anti": 2, "join_residual": 4,
           "join_residual_rows": 1_328_458,
           "join_residual_capacity": 2_097_152}
_AFTER = {"join_semi": 42, "join_anti": 42, "join_residual": 84,
          "join_residual_rows": 1_328_458 + 40 * 664_229,
          "join_residual_capacity": 2_097_152 + 40 * 1_048_576}
#: Q4: a semi join with no residual
_Q4_BEFORE = dict.fromkeys(_BEFORE, 0)
_Q4_AFTER = {**_Q4_BEFORE, "join_semi": 20}
#: the parent: the kinds' counters alone
_PARENT = {"join_semi": 20, "join_anti": 0}


@pytest.mark.parametrize("before,after,share,fill", [
    (_BEFORE, _AFTER, 100.0, 100.0 * 664_229 / 1_048_576),
    (_Q4_BEFORE, _Q4_AFTER, 0.0, None),            # no residual
    (_AFTER, _AFTER, None, None),                  # no existence test ran
    ({"join_semi": 0, "join_anti": 0}, _PARENT, None, None),
    ({}, {}, None, None),
])
def test_counter_readers(before, after, share, fill):
    obs = _observation(before, after)
    assert _reader("join.residual_share").read(obs) == share
    got = _reader("join.residual_fill").read(obs)
    assert got == (pytest.approx(fill) if fill is not None else None)


def test_the_exists_scope_is_named_below_the_probe():
    names = _reader("kernel.join_exists_ms")._Below()
    of = trace_owners.kernel_of
    assert of("jit(pipeline_ks1)/k_join_probe/k_join_exists/jit(_where)/"
              "select_n", names) == "k_join_exists"
    # the probe's own work, and every other kernel, take other names
    assert of("jit(pipeline_ks1)/k_join_probe/gather", names) == "gather"
    assert of("jit(pipeline_ks1)/k_agg_sort/jit(argsort)/sort",
              names) == "k_agg_sort"
    assert of("", names) is None
    # the harness's own vocabulary still names it the probe's
    assert of("jit(pipeline_ks1)/k_join_probe/k_join_exists/gather") \
        == "k_join_probe"


def _traced(monkeypatch, exists_s, busy_s=2.0):
    monkeypatch.setattr(trace_reduce, "find_xplane", lambda _p: "t.pb")
    got = {"busy_s": busy_s, "kernel_s": {"gather": 0.1}}
    if exists_s is not None:
        got["kernel_s"]["k_join_exists"] = exists_s
    monkeypatch.setattr(trace_owners, "reduce_file",
                        lambda _p, _w, names: got)
    return {"busy_s": busy_s, "window_s": 10.0,
            "requests": [Request("q21", 1.0, True)] * 10}


def test_the_trace_readers(monkeypatch):
    x = _traced(monkeypatch, 0.5)
    peaks = {"hbm_bytes_per_s": 819e9}
    rows = {"lineitem": 6_000_000}
    obs = _observation(xplane=x, peaks=peaks, rows=rows)
    assert _reader("kernel.join_exists_ms").read(obs) == pytest.approx(50.0)
    # 144 MB a request at 819 GB/s: 0.1758 ms of the 50
    assert _reader("kernel.join_exists_roofline").read(obs) == \
        pytest.approx(100.0 * 6_000_000 * 24 / 819e9 / 0.05)
    # no peaks (the CPU), no trace
    assert _reader("kernel.join_exists_roofline").read(
        _observation(xplane=x, rows=rows)) is None
    assert _reader("kernel.join_exists_ms").read(_observation()) is None


def test_the_trace_readers_find_nothing_in_a_parent(monkeypatch):
    """A program without the scope (the parent), and a trace that is not
    the run's."""
    x = _traced(monkeypatch, None)
    obs = _observation(xplane=x, peaks={"hbm_bytes_per_s": 819e9},
                       rows={"lineitem": 6_000_000})
    assert _reader("kernel.join_exists_ms").read(obs) is None
    assert _reader("kernel.join_exists_roofline").read(obs) is None
    x = _traced(monkeypatch, 0.5, busy_s=3.0)
    obs = _observation(xplane={**x, "busy_s": 2.0})
    assert _reader("kernel.join_exists_ms").read(obs) is None


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
    assert last["attempted"] >= 10
    window = next(ln for ln in lines if ln.get("metric") == "bench_window")
    assert window["problems"] == [] and window["window_compiles"] == 0
    values = next(ln["values"] for ln in lines
                  if ln.get("metric") == "bench_rehearsal_values")
    assert values["device.unsupported"] == 0
    assert values["compile.window_compiles"] == 0
    assert values["fragment.reruns_per_query"] == 0
    assert values["join.index_builds_per_query"] == 0
    assert values["join.residual_share"] == 100.0
    assert 50.0 < values["join.residual_fill"] <= 100.0
    assert 0 < values["kernel.join_exists_ms"] \
        <= values["kernel.join_probe_ms"]
    # a share of a peak needs the chip's peaks: none on the CPU
    assert "kernel.join_exists_roofline" not in values
    for name in (*NEW_METRICS[:3], *APPENDED_TO):
        assert name in last["metrics"], name
    for name in NOT_APPENDED_TO:
        assert name not in last["metrics"], name
