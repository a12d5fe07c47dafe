"""The configuration ``tpch-sf10-1chip``, its cell ``tpch-sf10.q1`` and the
three residency readers (PR 27)."""

import json
import os
import subprocess
import sys
import types

import pytest

from benchmark.harness import observe
from benchmark.harness.resolve import BENCH_DIR, ROOT, Cell, load_module


def _reader(name):
    return load_module(os.path.join(BENCH_DIR, "layer_metrics", name + ".py"),
                       "per_layer metric")


def _config(name):
    with open(os.path.join(BENCH_DIR, "configs", name + ".json")) as f:
        return json.load(f)


# -- the configuration --------------------------------------------------------

def test_configuration_lists_the_data_sets_whole_schema():
    cell = Cell("tpch-sf10.q1")
    cfg = cell.config
    assert cfg["scale_factor"] == 10 and cfg["chips"] == 1
    assert cfg["tables"] == {t: list(cols)
                             for t, cols in cell.dataset.SCHEMA.items()}
    assert sum(len(c) for c in cfg["tables"].values()) == 49
    assert cell.chips == 1 and cell.traffic_name == "power_q1"
    assert list(cell.templates) == ["q1"]


def test_it_is_the_sf1_deployment_at_another_scale():
    """Same data set, engine, session and guarantees; nothing weakened."""
    sf1, sf10 = _config("tpch-sf1-1chip"), _config("tpch-sf10-1chip")
    for key in ("dataset", "chips", "engine", "layout", "session", "tables",
                "guarantees"):
        assert sf10[key] == sf1[key], key
    assert set(sf10["reduced"]) == {"scale_factor", "tables"}
    assert sf10["reduced"]["tables"] == sf1["reduced"]["tables"]
    assert {k: v for k, v in sf10["assumed"].items()
            if k not in ("residency", "generator")} == \
        {k: v for k, v in sf1["assumed"].items() if k != "generator"}
    assert "resident" in sf10["assumed"]["residency"]
    assert "resident in HBM" in sf10["stands_for"]
    assert len(sf10["source"]) <= 200


def test_benchmark_json_gained_exactly_these():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    assert [c["name"] for c in spec["configs"]][-1] == "tpch-sf10-1chip"
    assert spec["workloads"][-1] == {
        "name": "tpch-sf10.q1", "config": "tpch-sf10-1chip",
        "traffic": "power_q1", "chips": 1,
        "why": spec["workloads"][-1]["why"]}
    assert [m["name"] for m in spec["per_layer"]][-3:] == [
        "scan.resident_share", "residency.window_upload_mb",
        "residency.hbm_peak_share"]
    scan_cells = ["tpch-sf1.q6", "tpch-sf1.q1", "tpch-sf10.q1"]
    for m in spec["per_layer"][-3:]:
        assert m["layer"] == "residency" and m["moves"] == "query_geomean_s"
        assert m["source"] == "program_counter"
        assert m.get("workloads") == (
            scan_cells if m["name"] == "scan.resident_share" else None)
    names = {m["name"] for m, _mod in Cell("tpch-sf10.q1").per_layer}
    assert {"scan.resident_share", "residency.window_upload_mb",
            "residency.hbm_peak_share", "xla.query_roofline",
            "agg.dense_share"} <= names


# -- the readers, on recorded observations ------------------------------------

def _obs(pipes0, pipes1, res0=None, res1=None, requests=100, peak=None,
         hbm=None):
    status0 = {"device_pipelines": pipes0,
               "device_residency": res0 or {"upload_bytes": 10}}
    status1 = {"device_pipelines": pipes1,
               "device_residency": res1 or {"upload_bytes": 10}}
    o = types.SimpleNamespace(
        status0=status0, status1=status1, requests=[None] * requests,
        device={"memory_peak_bytes": peak}, hbm_bytes=hbm)
    o.counter_delta = lambda *path: observe.delta(status0, status1, *path)
    return o


def _pipes(resident, streamed, stream_bytes=0):
    return {"scan_resident": resident, "scan_streamed": streamed,
            "stream_upload_bytes": stream_bytes, "compiles": 1}


#: a resident window (tpch-sf10.q1 as the change runs it), a streamed one
#: (15 blocks of 4,194,304 rows x 51 B a request), one with neither
#: counter moving (a join cell), and a program without the counters
RESIDENT = _obs(_pipes(2, 0), _pipes(602, 0), requests=600)
STREAMED = _obs(_pipes(0, 2, 2 * 3_208_642_560),
                _pipes(0, 32, 32 * 3_208_642_560), requests=30)
NEITHER = _obs(_pipes(4, 0), _pipes(4, 0), requests=5)
PARENT = _obs({"compiles": 1}, {"compiles": 1}, requests=30)
EVICTED = _obs(_pipes(2, 0), _pipes(12, 0), {"upload_bytes": 10},
               {"upload_bytes": 10 + 10 * 3_422_552_064}, requests=10)


@pytest.mark.parametrize("obs,want", [
    (RESIDENT, 100.0), (STREAMED, 0.0), (NEITHER, None), (PARENT, None),
    (_obs(_pipes(0, 0), _pipes(3, 1)), 75.0)])
def test_scan_resident_share(obs, want):
    assert _reader("scan.resident_share").read(obs) == want


@pytest.mark.parametrize("obs,want", [
    (RESIDENT, 0.0), (STREAMED, 3208.64256), (NEITHER, 0.0), (PARENT, None),
    # an eviction storm the engine annotation cannot see
    (EVICTED, 3422.552064),
    (_obs(_pipes(1, 0), _pipes(1, 0), requests=0), None)])
def test_window_upload_mb(obs, want):
    got = _reader("residency.window_upload_mb").read(obs)
    assert got == (pytest.approx(want) if want is not None else None)


@pytest.mark.parametrize("peak,hbm,want", [
    (7_340_032_000, 16_911_433_728, 43.4027), (None, 16_911_433_728, None),
    (123, None, None), (0, 16_911_433_728, None)])
def test_hbm_peak_share(peak, hbm, want):
    got = _reader("residency.hbm_peak_share").read(
        _obs(_pipes(0, 0), _pipes(0, 0), peak=peak, hbm=hbm))
    assert got == (pytest.approx(want, rel=1e-4) if want is not None
                   else None)


# -- the cell, rehearsed ------------------------------------------------------

def test_rehearsal_ends_with_a_last_line():
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", "tpch-sf10.q1", "--seed", "3000000007", "--seconds",
         "2", "--trace", "1", "--rehearse"],
        capture_output=True, text=True, cwd=ROOT, timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    lines = [json.loads(ln) for ln in p.stdout.strip().splitlines()]
    last = lines[-1]
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] > 0
    values = next(ln["values"] for ln in lines
                  if ln.get("metric") == "bench_rehearsal_values")
    assert values["scan.resident_share"] == 100.0
    assert values["residency.window_upload_mb"] == 0.0
    assert values["agg.dense_share"] == 100.0
    assert values["compile.window_compiles"] == 0
