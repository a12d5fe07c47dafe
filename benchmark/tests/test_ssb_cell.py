"""The configuration ``ssb-sf10-1chip``, its cell ``ssb-sf10.flights`` and
the five readers the cell brought (PR 31)."""

import json
import os
import subprocess
import sys
import types

import pytest

from benchmark.harness import observe
from benchmark.harness.observe import Request
from benchmark.harness.resolve import BENCH_DIR, ROOT, Cell, load_module

CELL = "ssb-sf10.flights"
FLIGHTS = ["ssb_q1_1", "ssb_q2_1", "ssb_q3_1", "ssb_q4_1"]


def _reader(name):
    return load_module(os.path.join(BENCH_DIR, "layer_metrics", name + ".py"),
                       "per_layer metric")


def _config(name):
    with open(os.path.join(BENCH_DIR, "configs", name + ".json")) as f:
        return json.load(f)


# -- the configuration --------------------------------------------------------

def test_configuration_installs_the_data_sets_whole_schema():
    cell = Cell(CELL)
    cfg = cell.config
    assert cfg["dataset"] == "ssb" and cell.dataset.DB == "ssb"
    assert cfg["scale_factor"] == 10 and cfg["chips"] == 1
    assert cfg["tables"] == {t: list(cols)
                             for t, cols in cell.dataset.SCHEMA.items()}
    assert {t: len(c) for t, c in cfg["tables"].items()} == {
        "customer": 5, "supplier": 4, "part": 8, "date": 17,
        "lineorder": 17}
    assert cell.chips == 1 and cell.traffic_name == "power_ssb_flights"
    assert list(cell.templates) == FLIGHTS == cell.traffic["order"]
    for mod in cell.templates.values():
        for table, cols in mod.READS.items():
            assert set(cols) <= set(cfg["tables"][table])


def test_it_gives_what_the_sf10_deployment_gives():
    """Engine, session, layout and guarantees of tpch-sf10-1chip; nothing
    weakened."""
    ssb, tpch = _config("ssb-sf10-1chip"), _config("tpch-sf10-1chip")
    for key in ("scale_factor", "chips", "engine", "layout", "session"):
        assert ssb[key] == tpch[key], key
    assert set(ssb["guarantees"]) == set(tpch["guarantees"])
    for key in ("answers", "isolation"):
        assert ssb["guarantees"][key] == tpch["guarantees"][key]
    assert "tidb_wal_fsync=commit" in ssb["guarantees"]["durability"]
    assert set(ssb["reduced"]) == {"scale_factor", "tables"}
    assert "resident in HBM" in ssb["stands_for"]
    assert "never re-sent per statement" in ssb["stands_for"]
    assert "from memory" in ssb["assumed"]["paper"]
    assert len(ssb["source"]) <= 200
    for word in ("Star Schema Benchmark", "revision 3", "section 2", "SF10",
                 "Q1.1", "Q2.1", "Q3.1", "Q4.1"):
        assert word in ssb["source"], word


def test_benchmark_json_gained_exactly_these():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    assert [c["name"] for c in spec["configs"]][-1] == "ssb-sf10-1chip"
    assert spec["configs"][-1]["source"] == _config("ssb-sf10-1chip")["source"]
    assert spec["workloads"][-1] == {
        "name": CELL, "config": "ssb-sf10-1chip",
        "traffic": "power_ssb_flights", "chips": 1,
        "why": spec["workloads"][-1]["why"]}
    assert len(spec["workloads"][-1]["why"]) <= 200
    new = spec["per_layer"][-5:]
    assert [m["name"] for m in new] == ["join.probe_resident_share"] + [
        t + "_p50_s" for t in FLIGHTS]
    assert new[0] == {"name": "join.probe_resident_share", "unit": "%",
                      "better": "higher", "source": "program_counter",
                      "layer": "residency", "moves": "query_geomean_s",
                      "workloads": ["tpch-sf1.q3q5", CELL]}
    for m in new[1:]:
        assert m == {"name": m["name"], "unit": "s", "better": "lower",
                     "source": "host_clock", "layer": "XLA programs",
                     "moves": "query_geomean_s", "workloads": [CELL]}
    listed = {m["name"] for m in spec["per_layer"]
              if CELL in m.get("workloads", ())}
    assert listed == {"kernel.join_build_ms", "kernel.join_probe_ms",
                      "kernel.topk_ms", "join.direct_share",
                      "join.elided_gather_share"} | {m["name"] for m in new}
    names = {m["name"] for m, _mod in Cell(CELL).per_layer}
    assert listed <= names and "xla.query_roofline" in names
    assert "scan.resident_share" not in names
    # ``upload.h2d_ms`` got the list of the cells it had: the parent's paged
    # join opens no ``upload.h2d`` span, so nothing is there to read in CELL
    upload = next(m for m in spec["per_layer"] if m["name"] == "upload.h2d_ms")
    assert upload["workloads"] == [w["name"] for w in spec["workloads"][:-1]]
    assert "upload.h2d_ms" not in names


# -- the readers, on made observations ----------------------------------------

def _obs(pipes0, pipes1):
    o = types.SimpleNamespace(status0={"device_pipelines": pipes0},
                              status1={"device_pipelines": pipes1})
    o.counter_delta = lambda *path: observe.delta(o.status0, o.status1,
                                                  *path)
    return o


def _probes(resident, sent):
    return {"join_probe_resident": resident, "join_probe_sent": sent,
            "compiles": 4}


@pytest.mark.parametrize("before,after,want", [
    (_probes(12, 0), _probes(24, 0), 100.0),     # the cell, this PR
    (_probes(0, 12), _probes(0, 24), 0.0),       # every probe sent
    (_probes(3, 1), _probes(6, 2), 75.0),
    (_probes(5, 5), _probes(5, 5), None),        # no join fragment ran
    ({"compiles": 4}, {"compiles": 4}, None),    # the parent: no counters
])
def test_probe_resident_share(before, after, want):
    got = _reader("join.probe_resident_share").read(_obs(before, after))
    assert got == want


def test_the_flights_medians():
    lat = {"ssb_q1_1": [2.0, 2.2, 2.4], "ssb_q2_1": [4.0, 4.5, 4.1],
           "ssb_q3_1": [5.0, 4.0], "ssb_q4_1": [4.75]}
    requests = [Request(t, s, True) for t, vals in lat.items() for s in vals]
    obs = observe.Observation(
        requests=requests, setup={}, status0={}, status1={},
        templates=dict.fromkeys(lat), rows={}, device={}, hbm_bytes=None,
        peaks=None, xplane=None)
    got = {t: _reader(t + "_p50_s").read(obs) for t in FLIGHTS}
    assert got == {"ssb_q1_1": 2.2, "ssb_q2_1": 4.1, "ssb_q3_1": 4.5,
                   "ssb_q4_1": 4.75}
    obs.requests = [r for r in requests if r.template != "ssb_q3_1"]
    assert _reader("ssb_q3_1_p50_s").read(obs) is None


def test_min_bytes_count_every_read_column_once():
    cell = Cell(CELL)
    rows = {"lineorder": 60_000_000, "date": 2556, "part": 800_000,
            "supplier": 20_000, "customer": 300_000}
    # Q1.1: four lineorder columns and two of date, all 8 bytes wide
    assert cell.templates["ssb_q1_1"].min_bytes(rows) == \
        4 * 8 * 60_000_000 + 2 * 8 * 2556
    # Q2.1: four fact columns; a dictionary code is 4 bytes
    assert cell.templates["ssb_q2_1"].min_bytes(rows) == \
        4 * 8 * 60_000_000 + 2 * 8 * 2556 + 800_000 * (8 + 4 + 4) \
        + 20_000 * (8 + 4)


# -- the cell, rehearsed ------------------------------------------------------

def test_rehearsal_ends_with_a_last_line():
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", CELL, "--seed", "3100200341", "--seconds", "4",
         "--trace", "1", "--rehearse"],
        capture_output=True, text=True, cwd=ROOT, timeout=900)
    assert p.returncode == 0, p.stderr[-2000:]
    lines = [json.loads(ln) for ln in p.stdout.strip().splitlines()]
    last = lines[-1]
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] >= 4
    values = next(ln["values"] for ln in lines
                  if ln.get("metric") == "bench_rehearsal_values")
    assert values["join.probe_resident_share"] == 100.0
    assert values["join.direct_share"] == 100.0
    assert values["agg.dense_share"] == 0.0
    assert values["compile.window_compiles"] == 0
    for name in ("fetch.d2h_ms", "assemble.host_ms",
                 "supervisor.call_ms", *(t + "_p50_s" for t in FLIGHTS)):
        assert values[name] is not None and name in last["metrics"]
    # the parent's paged join opens no ``upload.h2d`` span, so the accepted
    # metric lists the cells it had and is not read here
    assert "upload.h2d_ms" not in last["metrics"]
