"""The whole harness on XLA:CPU at SF0.01 (``--rehearse``): a cell end to
end, and the verdict when the answers come from the wrong engine."""

import json
import os
import subprocess
import sys
import time

from benchmark.harness.resolve import ROOT, Cell


def _run(*args):
    return subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"), *args],
        capture_output=True, text=True, cwd=ROOT, timeout=600)


def test_off_the_chip_without_rehearse_fails_before_loading_data():
    p = _run("--workload", "tpch-sf1.q6", "--seed", "11", "--seconds", "1",
             "--trace", "0")
    assert p.returncode != 0
    assert "not 'tpu'" in p.stderr and "refusing to load data" in p.stderr
    assert '"correct"' not in p.stdout


def test_rehearsal_prints_the_contract_line_with_nulls():
    p = _run("--workload", "tpch-sf1.q6", "--seed", "11", "--seconds", "2",
             "--trace", "1", "--rehearse")
    assert p.returncode == 0, p.stderr[-2000:]
    lines = p.stdout.strip().splitlines()
    last = json.loads(lines[-1])
    assert set(last) == {"correct", "attempted", "failed", "metrics",
                         "device"}
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] > 0
    assert last["device"]["platform"] == "cpu"
    assert all(m["value"] is None for m in last["metrics"].values())
    assert "xla.busy_s_per_query" in last["metrics"]
    for ln in lines[:-1]:
        assert json.loads(ln).get("platform") == "cpu"


def test_answers_from_the_host_engine_are_not_correct():
    """The configuration pins engine:tpu; a session that runs on the host
    engine answers exactly and must still be refused."""
    sys.path.insert(0, ROOT)
    os.chdir(ROOT)
    from benchmark.harness.cell import run_cell
    cell = Cell("tpch-sf1.q6")
    cell.config["session"]["tidb_executor_engine"] = "host"
    result = run_cell(cell, seed=12, seconds=1.0, trace=False,
                      rehearse=True, t_start=time.monotonic())
    assert result["correct"] is False
    assert result["failed"] == 0 and result["attempted"] > 0
