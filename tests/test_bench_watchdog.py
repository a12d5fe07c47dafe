"""Per-query bench watchdog: one dead backend (or injected failure) skips
that query with an error JSON line and the run CONTINUES — a failure in
one query must cost that query, not every query after it.
Also checks the measured compile_s split: warm runs re-dispatch cached
compiled fragments, so warm_compile_s ~ 0 while the cold run pays the
compiles."""

import os
import pathlib
import sys

import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

import bench  # noqa: E402
from tidb_tpu.testkit import TestKit  # noqa: E402


@pytest.fixture(scope="module")
def tpch_tk():
    tk = TestKit()
    n = bench.gen_all(tk, 0.001)
    return tk, n


def _run(tk, n, qnames, monkeypatch, fail="", budget_s=0):
    emitted = []
    monkeypatch.setattr(bench, "_emit", lambda obj: emitted.append(obj))
    monkeypatch.setattr(bench, "_COMPLETED", [0])
    if fail:
        monkeypatch.setenv("BENCH_FAIL_QUERY", fail)
    else:
        monkeypatch.delenv("BENCH_FAIL_QUERY", raising=False)
    failures = bench._bench_loop(
        tk, qnames, 0.001, n, {"platform": "cpu", "sf": 0.001},
        query_budget_s=budget_s)
    return failures, emitted


def test_injected_failure_skips_query_and_run_continues(tpch_tk,
                                                        monkeypatch):
    tk, n = tpch_tk
    failures, emitted = _run(tk, n, ["q1", "q3"], monkeypatch, fail="q1")
    assert failures == 1
    q1 = [e for e in emitted if e["metric"].startswith("tpch_q1")]
    assert len(q1) == 1 and "injected backend failure" in q1[0]["error"]
    # the run CONTINUED: q3 completed with a real result line
    q3 = [e for e in emitted if e["metric"].startswith("tpch_q3")]
    assert q3 and q3[-1]["value"] > 0 and "error" not in q3[-1]
    assert q3[-1]["vs_baseline"] > 0  # host reference ran too


def test_warm_compile_s_amortized(tpch_tk, monkeypatch):
    """Acceptance: warm-run compile_s < 10% of cold-run compile_s (the
    compiled-fragment cache + shape buckets make the timed runs
    dispatch-only). Holds on any backend; tier-1 checks it on XLA:CPU."""
    tk, n = tpch_tk
    failures, emitted = _run(tk, n, ["q1", "q18"], monkeypatch)
    assert failures == 0
    for qname in ("q1", "q18"):
        line = [e for e in emitted
                if e["metric"] == f"tpch_{qname}_sf0.001_device_rows_per_sec"]
        assert line, f"no result line for {qname}: {emitted}"
        rec = line[0]
        # cold run pays real compiles; warm runs re-dispatch cached
        # programs
        assert rec["compile_s"] > 0, rec
        assert rec["warm_compile_s"] < 0.1 * rec["compile_s"], rec


def test_supervisor_skips_hung_query_and_run_continues(tpch_tk,
                                                       monkeypatch):
    """Layer 1 of the watchdog stack: a backend HANG (GIL-blocked in the
    real failure; an injected sleep here) inside one benchmarked query is
    abandoned by the device-runtime supervisor at the per-query budget —
    error JSON line, fresh session, and the NEXT query completes."""
    import time

    from tidb_tpu.executor import supervisor
    from tidb_tpu.utils import failpoint

    tk, n = tpch_tk
    # hang only q1's first device dispatch (past the budget); q3 must run
    # clean after — its post-fence COLD compile (~3s on XLA-CPU) must fit
    # the budget, hence 8s/12s rather than something snappier
    failpoint.enable("device-agg-exec", "1*sleep(12)")
    try:
        failures, emitted = _run(tk, n, ["q1", "q3"], monkeypatch,
                                 budget_s=8)
    finally:
        failpoint.disable("device-agg-exec")
    assert failures == 1
    q1 = [e for e in emitted if e["metric"].startswith("tpch_q1")]
    assert len(q1) == 1 and q1[0].get("watchdog") == "supervisor", q1
    assert "DeviceHangError" in q1[0]["error"]
    q3 = [e for e in emitted if e["metric"].startswith("tpch_q3")]
    assert q3 and q3[-1]["value"] > 0 and "error" not in q3[-1]
    # the abandoned worker drains once its sleep ends
    deadline = time.monotonic() + 10.0
    while supervisor.abandoned_calls() and time.monotonic() < deadline:
        time.sleep(0.05)
    assert supervisor.abandoned_calls() == 0


def test_query_timeout_exception_is_skippable():
    # _QueryTimeout must flow through the generic error path (a skip),
    # not kill the loop
    assert issubclass(bench._QueryTimeout, Exception)


def test_arm_is_noop_without_handler():
    # a test/caller that never installed the SIGALRM handler must not arm
    # the default (process-killing) action
    assert not bench._ALARM_READY[0]
    bench._arm_query_alarm(5)  # no handler installed: must be a no-op
    import signal
    assert signal.alarm(0) == 0  # nothing pending
