"""Query-lifecycle span tracing (tidb_tpu/session/tracing.py, ISSUE 10):

- OVERHEAD: sampling off ⇒ one branch per chokepoint — span() returns
  the shared no-op singleton, no Trace is ever allocated (the tier-1
  micro-check the acceptance criteria name).
- SPAN TREE: a forced-tpu aggregate under TRACE shows the full layer
  stack — admission → compile (with mode) → supervised call → device
  dispatch — with durations that sum sanely against the statement.
- THREAD HOPS: supervisor worker threads adopt the dispatching
  statement's trace; background compiles run under a LINKED CHILD trace
  whose parent_id is the submitting statement's.
- SURFACES: TRACE FORMAT='row'/'json', information_schema.trace_records,
  slow-log items carrying the rendered tree, the tidb_slow_query_file
  appender, /metrics latency histograms (monotone cumulative buckets),
  /status device_tracing.
- BOUNDS + DRAIN: per-trace span cap counts dropped instead of growing;
  every begun trace is finished even on failing statements.
"""

import json
import re
import threading

import pytest

from tidb_tpu.session import Session, tracing
from tidb_tpu.session.observe import HIST_BUCKETS, Observability
from tidb_tpu.testkit import TestKit

#: distinct filter constants per test AND per run: the compiled-pipeline
#: cache is process-wide and the persistent signature index survives
#: across pytest runs, so a cold compile (the span under test) needs a
#: constant no previous run ever signed.  Clock-derived, NOT the global
#: `random` module — an earlier test file seeds it (random.seed(7) in
#: test_device_stream.py), which made "random" constants identical
#: across full-suite runs and the persist index served them warm.
import itertools as _it
import time as _time

_UNIQ = _it.count(_time.time_ns() % 10**12)


def _fresh_q():
    return (f"select b, sum(a) from t where a > -{next(_UNIQ)} "
            "group by b order by b")


@pytest.fixture()
def tk():
    t = TestKit()
    t.must_exec("create table t (a int primary key, b int, c varchar(16))")
    t.must_exec("insert into t values " + ",".join(
        f"({i}, {i % 3}, 'v{i % 5}')" for i in range(16)))
    return t


def _span_names(tr):
    return [sp.name for sp in tr.spans]


def _events(tr):
    return [(n, tg) for sp in tr.spans for (_t, n, tg) in sp.events]


# -- overhead: the micro-check ------------------------------------------------

class TestOverheadWhenOff:
    def test_span_returns_shared_noop(self):
        assert tracing.active() is None
        assert tracing.span("anything", tag=1) is tracing._NOOP
        assert tracing.span("other") is tracing._NOOP

    def test_event_and_capture_are_single_branch_noops(self):
        assert tracing.capture() is None
        tracing.event("nothing", x=1)  # must not raise nor allocate

    def test_propagation_helpers_are_single_branch_noops(self):
        """The cross-process hop helpers keep the same off-path contract
        as span/event: no active trace (or None in) ⇒ one branch out,
        nothing allocated, no STATS movement."""
        assert tracing.active() is None
        s0 = dict(tracing.STATS)
        assert tracing.wire_ctx() is None
        assert tracing.begin_remote(None, "rpc.op") is None
        assert tracing.finish_remote(None) is None
        tracing.attach_remote(None)  # must not raise
        tracing.attach_remote({"gid": "dead-1", "name": "orphan"})
        assert dict(tracing.STATS) == s0, \
            "off-path propagation must never touch the tracer"

    def test_statement_allocates_no_trace_when_unsampled(self, tk):
        s0 = dict(tracing.STATS)
        tk.must_query("select count(*) from t")
        tk.must_exec("insert into t values (900001, 1, 'x')")
        assert dict(tracing.STATS) == s0, \
            "unsampled statements must never touch the tracer"


# -- the TRACE statement ------------------------------------------------------

class TestTraceStatement:
    def test_forced_tpu_span_tree(self, tk):
        """The acceptance criterion: admission, compile (with mode),
        supervised-call and device-dispatch spans present, durations
        consistent with the statement latency."""
        tk.must_exec("set tidb_executor_engine = 'tpu'")
        r = tk.must_query(f"trace format='row' {_fresh_q()}")
        ops = [row[0] for row in r.rows]
        assert ops[0].startswith("statement")
        for needed in ("device.dispatch", "scheduler.acquire",
                       "supervisor.call", "compile.obtain"):
            assert any(needed in o for o in ops), (needed, ops)
        # durations: every span fits inside the statement, and the
        # statement's direct children sum to no more than the total
        tr = tracing.recent_traces()[-1]
        total = tr.dur_s
        assert total is not None and total > 0
        kids = tr.children_of()
        for sp in tr.spans:
            assert sp.dur_s is not None
            assert sp.dur_s <= total * 1.05 + 0.01, (sp.name, sp.dur_s,
                                                     total)
        child_sum = sum(c.dur_s for c in kids.get(0, ()))
        assert child_sum <= total * 1.05 + 0.01
        # the compile span carries its resolution mode
        csp = next(sp for sp in tr.spans if sp.name == "compile.obtain")
        assert csp.tags.get("mode") in ("sync", "cached")

    def test_trace_golden_shape(self, tk):
        """Golden output shape: (operation, startTS, duration) columns,
        two-space indentation per depth, events prefixed '@'."""
        tk.must_exec("set tidb_executor_engine = 'tpu'")
        r = tk.must_query(f"trace {_fresh_q()}")
        assert r.result.names == ["operation", "startTS", "duration"]
        ops = [row[0] for row in r.rows]
        assert ops[0] == "statement"
        assert "  statement.dispatch" in ops
        assert any(o.startswith("    ") and "plan_query" in o for o in ops)
        assert any("@operator." in o for o in ops)
        # durations column parses as a unit-suffixed number or '-'
        for row in r.rows:
            assert row[2] == "-" or re.match(r"^\d+(\.\d+)?(s|ms|µs)$",
                                             row[2]), row

    def test_trace_json(self, tk):
        r = tk.must_query("trace format='json' select sum(b) from t")
        doc = json.loads(r.rows[0][0])
        assert doc["root"]["name"] == "statement"
        assert doc["origin"] == "trace_stmt"
        assert doc["spans"] >= 2
        dispatch = doc["root"]["children"][0]
        assert dispatch["name"] == "statement.dispatch"

    def test_trace_while_sampled_renders_finished_tree(self, tk):
        """Review regression: a TRACE statement that the sampler ALSO
        traced must still render a finished tree (root duration set,
        succ meaningful) — not the live, unfinished trace."""
        tk.must_exec("set tidb_trace_sampling_rate = 1")
        r = tk.must_query("trace format='json' select sum(b) from t")
        doc = json.loads(r.rows[0][0])
        assert doc["duration_s"] is not None
        assert doc["origin"] == "sampled"  # the sampler's trace, reused
        r2 = tk.must_query("trace select count(*) from t")
        assert r2.rows[0][2] != "-"  # root duration rendered
        tk.must_exec("set tidb_trace_sampling_rate = 0")
        assert tracing.verify_drained()["ok"]

    def test_failed_dispatch_still_observed_in_histogram(self, tk):
        """Review regression: a fragment that FAILS after admission
        (injected fault → classified degrade) still contributes to
        device_dispatch_seconds — incident latencies must not vanish
        from the scraped p99."""
        from tidb_tpu.utils import failpoint
        tk.must_exec("set tidb_executor_engine = 'tpu'")
        obs = tk.session.domain.observe
        snap0 = obs.hist_snapshot().get("device_dispatch_seconds")
        n0 = snap0[3] if snap0 else 0
        with failpoint.enabled("device-agg-exec", "panic"):
            tk.must_query(_fresh_q())  # degrades to host, still succeeds
        snap1 = obs.hist_snapshot()["device_dispatch_seconds"]
        assert snap1[3] > n0

    def test_trace_non_select(self, tk):
        r = tk.must_query("trace insert into t values (900100, 2, 'y')")
        assert r.rows[0][0] == "statement"
        assert tk.must_query(
            "select count(*) from t where a = 900100").rows[0][0] == "1"

    def test_trace_failing_statement_still_drains(self, tk):
        s0 = tracing.STATS["started"]
        with pytest.raises(Exception):
            tk.must_query("trace select * from no_such_table_xyz")
        assert tracing.STATS["started"] > s0
        assert tracing.verify_drained()["ok"], tracing.verify_drained()

    def test_opt_format_unchanged(self, tk):
        r = tk.must_query("trace format='opt' select b from t where a = 3")
        assert r.result.names == ["step", "rule", "plan"]


# -- sampling + ring ----------------------------------------------------------

class TestSampling:
    def test_rate_one_records_every_statement(self, tk):
        tk.must_exec("set tidb_trace_sampling_rate = 1")
        f0 = tracing.STATS["finished"]
        tk.must_query("select count(*) from t")
        tk.must_query("select max(a) from t")
        assert tracing.STATS["finished"] >= f0 + 2
        tr = tracing.recent_traces()[-1]
        assert tr.origin == "sampled"
        assert tracing.verify_drained()["ok"]

    def test_rate_zero_records_nothing(self, tk):
        tk.must_exec("set tidb_trace_sampling_rate = 0")
        s0 = dict(tracing.STATS)
        tk.must_query("select count(*) from t")
        assert dict(tracing.STATS) == s0

    def test_ring_bounded(self):
        for _ in range(tracing.RING_CAP + 10):
            tr = tracing.begin("x")
            tracing.finish(tr)
        assert len(tracing.recent_traces()) == tracing.RING_CAP

    def test_span_bound_counts_dropped(self):
        tr = tracing.begin("bounded")
        for i in range(tracing.MAX_SPANS + 5):
            with tracing.span(f"s{i}"):
                pass
        tracing.finish(tr)
        assert len(tr.spans) == tracing.MAX_SPANS
        assert tr.dropped >= 5
        assert tracing.snapshot()["spans_dropped"] >= 5

    def test_finished_trace_is_frozen(self):
        """Review regression: an abandoned worker unsticking after the
        statement finished must not mutate the ring-published trace."""
        tr = tracing.begin("frozen")
        with tracing.span("child"):
            pass
        tracing.finish(tr)
        n_spans, n_events = len(tr.spans), tr.n_events
        assert tr._start_span("late", 0, {}) is None
        tr.add_event(None, "late_event", {})
        assert len(tr.spans) == n_spans and tr.n_events == n_events
        assert tr.dropped == 0  # post-finish drops don't drift STATS
        # a span left open at finish (abandoned worker) stays frozen
        # open-ended: the late _end_span must not rewrite the published
        # tree (review round 3)
        sp = tr.spans[-1]
        sp.dur_s = None
        tr._end_span(sp, error="LateError")
        assert sp.dur_s is None and "error" not in sp.tags

    def test_last_trace_skips_bg_children(self, tk):
        """Review regression: a compile.bg child finishing after the
        failed statement must not shadow it in the bench post-mortem."""
        tr = tracing.begin("stmt-x", conn_id=12345)
        tracing.finish(tr)
        child = tracing.Trace("compile.bg", "child", 12345, tr.trace_id)
        with tracing._RING_LOCK:
            tracing.STATS["started"] += 1
        tracing.finish(child)
        got = tracing.last_trace(12345)
        assert got is tr
        assert tracing.last_trace(12345, include_children=True) is child
        assert "stmt-x" in tracing.last_trace_text(12345)

    def test_last_trace_text_prefers_live_trace(self):
        """A watchdog firing mid-statement renders the HUNG query's live
        timeline, not the previous statement's finished one."""
        done = tracing.begin("previous")
        tracing.finish(done)
        live = tracing.begin("hung-now", conn_id=7)
        try:
            assert "hung-now" in tracing.last_trace_text()
            assert "hung-now" in tracing.last_trace_text(7)
            # another session's live trace never serves a foreign conn's
            # post-mortem (multiplexed-thread guard, review round 3)
            assert "hung-now" not in tracing.last_trace_text(8)
        finally:
            tracing.finish(live)

    def test_trace_records_memtable(self, tk):
        tk.must_exec("set tidb_trace_sampling_rate = 1")
        tk.must_query("select count(*) from t where a > -881999")
        tk.must_exec("set tidb_trace_sampling_rate = 0")
        rows = tk.must_query(
            "select trace_id, origin, spans, succ, tree from "
            "information_schema.trace_records").rows
        assert rows
        assert any("statement" in r[4] for r in rows)
        assert all(int(r[2]) >= 1 for r in rows)


# -- thread hops --------------------------------------------------------------

class TestThreadPropagation:
    def test_supervised_worker_adopts_trace(self):
        from tidb_tpu.executor import supervisor

        def body():
            tracing.event("inside_worker", mark=42)
            return 7

        tr = tracing.begin("sup-test")
        try:
            out = supervisor.call_supervised(body, (), deadline_s=5.0)
        finally:
            tracing.finish(tr)
        assert out == 7
        assert "supervisor.call" in _span_names(tr)
        evs = _events(tr)
        assert ("inside_worker", {"mark": 42}) in evs
        # the worker-side event nests under the supervisor.call span
        sup = next(sp for sp in tr.spans if sp.name == "supervisor.call")
        assert any(n == "inside_worker" for (_t, n, _g) in sup.events)

    def test_bg_compile_links_child_trace(self, tk):
        from tidb_tpu.executor import compile_service
        tk.must_exec("set tidb_executor_engine = 'tpu'")
        tk.must_exec("set tidb_compile_async = 'ON'")
        tk.must_exec("set tidb_trace_sampling_rate = 1")
        tk.must_query(_fresh_q())
        assert compile_service.wait_idle(30)
        # identify THIS test's traces by connection — the full suite may
        # have straggler children from other files' abandoned workers
        parent = tracing.last_trace(tk.session.conn_id)
        assert parent is not None and parent.origin == "sampled"
        links = [tg for n, tg in _events(parent)
                 if n == "linked_child_trace"]
        assert links, (
            "statement never recorded a bg-compile link\n"
            f"compile: {compile_service.snapshot()}\n"
            f"tree:\n{tracing.render_tree(parent)}")
        ch = next(t for t in tracing.recent_traces()
                  if t.trace_id == links[0]["trace_id"])
        assert ch.origin == "child" and ch.parent_id == parent.trace_id
        assert ch.name == "compile.bg"
        assert "supervisor.call" in _span_names(ch)
        assert tracing.verify_drained()["ok"], tracing.verify_drained()

    def test_backoff_sleep_event(self):
        from tidb_tpu.utils.backoff import Backoffer
        tr = tracing.begin("backoff-test")
        try:
            bo = Backoffer(budget_ms=100.0, seed=1, sleep=False)
            bo.backoff("txnLock", ValueError("x"))
        finally:
            tracing.finish(tr)
        evs = [(n, tg) for n, tg in _events(tr) if n == "backoff.sleep"]
        assert evs, _events(tr)
        name, tags = evs[0]
        assert tags["kind"] == "txnLock" and tags["attempt"] == 1
        assert "cls" in tags and "ms" in tags


# -- slow log + slow-query file ----------------------------------------------

class TestSlowLogTrace:
    def test_slow_item_carries_tree(self, tk):
        tk.must_exec("set tidb_trace_sampling_rate = 1")
        tk.must_exec("set tidb_slow_log_threshold = 0")
        tk.must_query("select sum(b) from t where a > -777001")
        rows = tk.must_query(
            "select trace from information_schema.slow_query "
            "where query like '%777001%'").rows
        assert rows and "statement" in rows[-1][0], rows

    def test_unsampled_slow_item_has_empty_trace(self, tk):
        tk.must_exec("set tidb_slow_log_threshold = 0")
        tk.must_query("select sum(b) from t where a > -777002")
        rows = tk.must_query(
            "select trace from information_schema.slow_query "
            "where query like '%777002%'").rows
        assert rows and rows[-1][0] == ""

    def test_slow_query_file_appends(self, tk, tmp_path):
        path = tmp_path / "slow.log"
        tk.must_exec(f"set tidb_slow_query_file = '{path}'")
        tk.must_exec("set tidb_slow_log_threshold = 0")
        tk.must_exec("set tidb_trace_sampling_rate = 1")
        tk.must_query("select min(a) from t where a > -777003")
        text = path.read_text()
        assert "# Time: " in text
        assert "# Query_time: " in text
        assert "# Digest: " in text
        assert "777003" in text
        assert "# Trace: " in text  # the sampled tree rides along

    def test_slow_query_file_write_failure_logged_not_raised(
            self, tk, caplog):
        # a DIRECTORY as target: open(...,'a') fails — the statement
        # must succeed and the failure must be logged classified
        tk.must_exec("set tidb_slow_query_file = '/'")
        tk.must_exec("set tidb_slow_log_threshold = 0")
        import logging
        with caplog.at_level(logging.WARNING, "tidb_tpu.observe"):
            r = tk.must_query("select count(*) from t")
        assert r.rows
        assert any("slow-query-file append failed" in m
                   for m in caplog.messages), caplog.messages


# -- observe_stmt contention (satellite: lock-scope fix) ----------------------

class TestObserveContention:
    def test_threaded_observe_exact_totals(self):
        obs = Observability(slow_log_cap=100000)
        n_threads, n_ops = 8, 200
        errs = []

        def worker(tid):
            try:
                for i in range(n_ops):
                    obs.observe_stmt(
                        user="u", db="d", sql=f"q{tid}",
                        digest=f"dig{tid % 3}", latency_s=0.001,
                        rows=1, succ=(i % 2 == 0), slow_threshold_s=0.0)
                    obs.inc("side_counter")
            except Exception as e:  # noqa: BLE001 — surfaced below
                errs.append(e)

        threads = [threading.Thread(target=worker, args=(t,))
                   for t in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
        assert not errs, errs
        total = n_threads * n_ops
        assert obs.counters["executor_statement_total"] == total
        assert obs.counters["executor_statement_error_total"] == total // 2
        assert obs.counters["side_counter"] == total
        assert len(obs.slow_queries) == total  # no lost slow items
        assert sum(st.exec_count
                   for st in obs.stmt_summary.values()) == total


# -- histograms ---------------------------------------------------------------

class TestHistograms:
    def test_metrics_buckets_monotone(self, tk):
        from tidb_tpu.server.http_status import StatusServer
        tk.must_exec("set tidb_executor_engine = 'tpu'")
        tk.must_query(_fresh_q())
        srv = StatusServer(tk.session.domain, port=0)
        try:
            txt = srv._metrics()
            status = srv._status()
        finally:
            srv._server.server_close()
        for name in ("statement_duration_seconds",
                     "device_dispatch_seconds"):
            vals = [int(m) for m in re.findall(
                rf'{name}_bucket{{le="[^"]+"}} (\d+)', txt)]
            assert vals, f"{name} not rendered:\n{txt[:1000]}"
            assert vals == sorted(vals), (name, vals)
            assert f'{name}_bucket{{le="+Inf"}}' in txt
            cnt = int(re.search(rf"{name}_count (\d+)", txt).group(1))
            assert cnt == vals[-1]
            assert re.search(rf"{name}_sum \d", txt)
        assert "device_tracing" in status
        assert status["device_tracing"]["ring_cap"] == tracing.RING_CAP

    def test_trace_ring_dropped_counter(self, tk):
        """/metrics pins trace_ring_dropped_total: a proper counter
        series that moves exactly when finished traces age out of the
        bounded ring unread."""
        from tidb_tpu.server.http_status import StatusServer
        srv = StatusServer(tk.session.domain, port=0)
        try:
            txt = srv._metrics()
            assert "# TYPE trace_ring_dropped_total counter" in txt
            base = int(re.search(
                r"trace_ring_dropped_total (\d+)", txt).group(1))
            for i in range(tracing.RING_CAP + 3):
                tracing.finish(tracing.begin(f"overflow{i}",
                                             origin="test"))
            txt2 = srv._metrics()
            cur = int(re.search(
                r"trace_ring_dropped_total (\d+)", txt2).group(1))
        finally:
            srv._server.server_close()
        assert cur >= base + 3, (base, cur)
        assert tracing.snapshot()["ring_dropped"] == cur

    def test_sync_compile_histogram_observed(self, tk):
        tk.must_exec("set tidb_executor_engine = 'tpu'")
        tk.must_query(_fresh_q())  # cold key → sync XLA compile
        snap = tk.session.domain.observe.hist_snapshot()
        assert "sync_compile_seconds" in snap, sorted(snap)
        _bounds, _counts, hsum, cnt = snap["sync_compile_seconds"]
        assert cnt >= 1 and hsum > 0

    def test_admission_wait_histogram_on_queued_path(self, tk):
        """Force the queued path: a held ticket saturates the per-tenant
        running cap, so the next admit waits for the scheduler thread."""
        from tidb_tpu.executor import scheduler
        scheduler.attach(tk.session)  # run_device does this before admit
        tk.must_exec("set global tidb_device_tenant_running_cap = 1")
        try:
            t1 = scheduler.admit(tk.session, shape="agg")
            done = threading.Event()

            def second():
                t2 = scheduler.admit(tk.session, shape="agg")
                scheduler.release(t2)
                done.set()

            th = threading.Thread(target=second, daemon=True)
            th.start()
            import time
            time.sleep(0.05)
            scheduler.release(t1)
            assert done.wait(10)
            th.join(10)
        finally:
            tk.must_exec("set global tidb_device_tenant_running_cap "
                         "= default")
        snap = tk.session.domain.observe.hist_snapshot()
        assert "admission_wait_seconds" in snap, sorted(snap)

    def test_registry_matches_lint_inventory(self):
        # the four per-layer names the README documents are registered
        for name in ("statement_duration_seconds", "admission_wait_seconds",
                     "sync_compile_seconds", "device_dispatch_seconds"):
            assert name in HIST_BUCKETS
            b = HIST_BUCKETS[name]
            assert list(b) == sorted(b)


# -- MPP ----------------------------------------------------------------------

@pytest.mark.multichip
class TestMppFragmentSpan:
    def test_mpp_fragment_span_present(self):
        tk = TestKit()
        tk.must_exec("set tidb_mpp_devices = 8")
        tk.must_exec("set tidb_executor_engine = 'tpu-mpp'")
        tk.must_exec("create table dim (k bigint primary key, g varchar(8))")
        tk.must_exec("insert into dim values " + ",".join(
            f"({i}, 'g{i % 4}')" for i in range(1, 33)))
        tk.must_exec("create table fact (a bigint primary key, k bigint, "
                     "v bigint)")
        tk.must_exec("insert into fact values " + ",".join(
            f"({i}, {(i % 32) + 1}, {i * 7})" for i in range(1, 321)))
        r = tk.must_query(
            "trace select dim.g, sum(fact.v) from fact, dim "
            "where fact.k = dim.k group by dim.g order by dim.g")
        ops = [row[0] for row in r.rows]
        assert any("mpp.fragment" in o for o in ops), ops
        tr = tracing.recent_traces()[-1]
        sp = next(s for s in tr.spans if s.name == "mpp.fragment")
        assert sp.tags.get("shards") == 8
        assert tracing.verify_drained()["ok"]


# -- drain after failures -----------------------------------------------------

class TestDrain:
    def test_sampled_error_statement_drains(self, tk):
        tk.must_exec("set tidb_trace_sampling_rate = 1")
        with pytest.raises(Exception):
            tk.must_query("select * from missing_table_zzz")
        tk.must_exec("set tidb_trace_sampling_rate = 0")
        d = tracing.verify_drained()
        assert d["ok"], d

    def test_session_api_never_binds_foreign_thread(self, tk):
        # a second session on the SAME thread must not inherit a trace
        tk.must_exec("set tidb_trace_sampling_rate = 1")
        tk.must_query("select 1")
        assert tracing.active() is None
        s2 = Session(tk.session.domain)
        try:
            s2.execute("select 1")
            assert tracing.active() is None
        finally:
            s2.close()


class TestToDictSnapshot:
    """Regression (ISSUE 11 guarded-state): Trace.to_dict read spans /
    dropped bare while supervisor workers appended — it now takes one
    locked snapshot, so a mid-flight render (the bench watchdog path) is
    internally consistent."""

    def test_render_while_spans_append(self):
        tr = tracing.Trace("hammer", origin="trace_stmt")
        stop = threading.Event()
        errs = []

        def appender():
            try:
                while not stop.is_set():
                    sp = tr._start_span("s", 0, {})
                    if sp is not None:
                        tr._end_span(sp)
            except Exception as e:  # pragma: no cover - fail loudly
                errs.append(e)

        threads = [threading.Thread(target=appender) for _ in range(3)]
        for t in threads:
            t.start()
        try:
            for _ in range(60):
                d = tr.to_dict()
                # snapshot consistency: the reported span count is the
                # rendered snapshot's, never a later value
                assert d["spans"] <= tracing.MAX_SPANS
                assert d["dropped"] >= 0
                tracing.render_tree(tr)
        finally:
            stop.set()
            for t in threads:
                t.join()
        assert errs == []
        # _finish directly: finish() would append to the process ring
        # and skew the drain invariant other tests assert on
        tr._finish(True)
        done = tr.to_dict()
        assert done["spans"] == len(tr.spans)


# -- one trace on one clock (ISSUE 24) ----------------------------------------
#
# Spans are also jax.profiler.TraceAnnotation events, so that a profiler
# session shows them on the clock of the device's operations; the three
# host<->device boundaries have spans of their own; parsing and uploaded
# bytes have counters.

_BOUNDARY_SPANS = ("statement", "executor.run", "device.dispatch",
                   "supervisor.call", "upload.h2d", "fetch.d2h",
                   "host.assemble")

_PROFILED_SQL = {
    "agg": "select b, sum(a), count(*) from t where a >= 0 group by b",
    "join": ("select t.b, sum(u.v) from t join u on t.a = u.k "
             "where u.v >= 0 group by t.b"),
}


def _tree_spans(node, depth=0, out=None):
    """[(name, depth)] of a DIAG TRACEJSON tree, in start order."""
    out = [] if out is None else out
    out.append((node["name"], depth))
    for child in sorted(node.get("children", ()),
                        key=lambda c: c["start_s"]):
        _tree_spans(child, depth + 1, out)
    return out


@pytest.fixture(scope="module")
def profiled(tmp_path_factory):
    """{kind: (DIAG TRACEJSON tree of the statement, [(name, start_ns,
    end_ns)] of the host events the profiler recorded meanwhile)}: one
    warm statement of each kind, sampled, under jax.profiler."""
    import glob
    import jax
    from jax.profiler import ProfileData
    tk = TestKit()
    tk.must_exec("create table t (a int primary key, b int)")
    tk.must_exec("create table u (k int primary key, v int)")
    tk.must_exec("insert into t values " + ",".join(
        f"({i}, {i % 3})" for i in range(64)))
    tk.must_exec("insert into u values " + ",".join(
        f"({i}, {i * 2})" for i in range(64)))
    tk.must_exec("set tidb_executor_engine = 'tpu'")
    tk.must_exec("set tidb_result_cache = 'OFF'")
    out = {}
    for kind, sql in _PROFILED_SQL.items():
        want = tk.must_query(sql).rows       # compiles; learns capacities
        tk.must_query(sql)
        plan = tk.must_query("explain analyze " + sql).rows
        assert any("engine:tpu" in r[2] for r in plan), plan
        d = str(tmp_path_factory.mktemp(f"prof_{kind}"))
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        tk.must_exec("set tidb_trace_sampling_rate = 1")
        tracing.reset_for_tests()
        jax.profiler.start_trace(d, profiler_options=opts)
        try:
            got = tk.must_query(sql).rows
        finally:
            jax.profiler.stop_trace()
            tk.must_exec("set tidb_trace_sampling_rate = 0")
        assert got == want
        trees = [tr for tr in json.loads(tk.must_query(
            "DIAG TRACEJSON").rows[0][0])["rows"]
            if tr["root"].get("tags", {}).get("stmt") == "SelectStmt"]
        assert len(trees) == 1
        path, = glob.glob(f"{d}/plugins/profile/*/*.xplane.pb")
        names = {n for n, _d in _tree_spans(trees[0]["root"])}
        events = sorted(
            (ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
            for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events
            if ev.name in names)
        out[kind] = (trees[0]["root"], sorted(events, key=lambda e: e[1]))
    return out


class TestSpansOnTheProfilersClock:
    @pytest.mark.parametrize("span", _BOUNDARY_SPANS)
    @pytest.mark.parametrize("kind", sorted(_PROFILED_SQL))
    def test_span_is_a_host_event_of_the_profile(self, profiled, kind, span):
        tree, events = profiled[kind]
        assert span in {n for n, _d in _tree_spans(tree)}, \
            "the statement opened no such span"
        assert span in {n for n, _s, _e in events}

    @pytest.mark.parametrize("kind", sorted(_PROFILED_SQL))
    def test_order_and_nesting_match_the_span_tree(self, profiled, kind):
        tree, events = profiled[kind]
        want = _tree_spans(tree)
        # same names in the same start order ...
        assert [n for n, _s, _e in events] == [n for n, _d in want]
        # ... and the same nesting: an event's depth is the number of
        # earlier events still open when it starts
        stack, got = [], []
        for name, s, e in events:
            while stack and stack[-1] <= s:
                stack.pop()
            assert not stack or e <= stack[-1], f"{name} outlives its parent"
            got.append((name, len(stack)))
            stack.append(e)
        assert got == want

    @pytest.mark.parametrize("kind", sorted(_PROFILED_SQL))
    def test_boundary_spans_carry_their_tags(self, profiled, kind):
        from benchmark.harness.observe import find_spans
        tree, _events = profiled[kind]
        up, = find_spans(tree, "upload.h2d")
        assert up["tags"]["cols"] >= 2 and up["tags"]["bytes"] == 0  # warm
        assert all(f["tags"]["bytes"] > 0
                   for f in find_spans(tree, "fetch.d2h"))
        asm, = find_spans(tree, "host.assemble")
        assert asm["tags"]["rows"] == 3


class TestNothingNewWhenSamplingIsOff:
    def test_no_annotation_is_constructed(self, tk, monkeypatch):
        import jax
        made = []
        real = jax.profiler.TraceAnnotation

        class Counting(real):
            def __init__(self, name, **kw):
                made.append(name)
                super().__init__(name, **kw)
        monkeypatch.setattr(jax.profiler, "TraceAnnotation", Counting)
        tk.must_exec("set tidb_executor_engine = 'tpu'")
        tk.must_exec("set tidb_trace_sampling_rate = 0")
        q = _fresh_q()
        tk.must_query(q)
        assert made == []
        assert tracing.span("upload.h2d") is tracing._NOOP
        tk.must_exec("set tidb_trace_sampling_rate = 1")
        tk.must_query(q)
        assert made[0] == "statement" and "fetch.d2h" in made

    def test_tracing_module_never_imports_jax(self):
        """Loaded by path: the package's own __init__ imports jax, which
        is the package's business; a process that has not imported jax
        gets spans without annotations and stays JAX-free."""
        import subprocess
        import sys
        code = (
            "import importlib.util, sys\n"
            f"spec = importlib.util.spec_from_file_location('tr', "
            f"{tracing.__file__!r})\n"
            "tr = importlib.util.module_from_spec(spec)\n"
            "spec.loader.exec_module(tr)\n"
            "t = tr.begin('statement')\n"
            "with tr.span('upload.h2d') as sp:\n"
            "    assert sp is not None\n"
            "tr.finish(t)\n"
            "assert t._ann is None and len(t.spans) == 2\n"
            "assert not [m for m in sys.modules if m.split('.')[0] "
            "in ('jax', 'jaxlib')], 'jax was imported'\n")
        p = subprocess.run([sys.executable, "-c", code],
                           capture_output=True, text=True, timeout=60)
        assert p.returncode == 0, p.stderr


class TestBoundaryCounters:
    def _status(self, tk):
        from tidb_tpu.server.http_status import status_payload
        return status_payload(tk.domain)

    def test_statements_and_parse_seconds_count_client_texts(self, tk):
        s0 = self._status(tk)["server"]
        tk.must_query("select 1")
        tk.must_exec("select 2; select 3")          # one text, two statements
        tk.must_query("DIAG STATUS")                # not SQL: never parsed
        tk.session._internal += 1                   # the engine's own SQL
        try:
            tk.must_query("select 4")
        finally:
            tk.session._internal -= 1
        assert tk.exec_error("selec nonsense") is not None   # parse error
        s1 = self._status(tk)["server"]
        assert s1["statements"] - s0["statements"] == 3
        assert s1["parse_s"] > s0["parse_s"]
        tk.must_query("DIAG STATUS")
        assert self._status(tk)["server"] == s1

    def test_upload_bytes_count_what_reaches_the_device_once(self, tk):
        tk.must_exec("set tidb_executor_engine = 'tpu'")
        tk.must_exec("set tidb_result_cache = 'OFF'")
        tk.must_exec("set tidb_trace_sampling_rate = 1")
        r0 = self._status(tk)["device_residency"]
        q = _fresh_q()
        tk.must_query(q)
        r1 = self._status(tk)["device_residency"]
        # two int columns (a, b) and their null masks, at one row bucket
        assert r1["uploads"] - r0["uploads"] == 2
        from tidb_tpu.ops import device as dev
        nb = dev.bucket_rows(16, dev.shape_buckets(tk.session))
        grew = r1["upload_bytes"] - r0["upload_bytes"]
        assert grew == 2 * nb * (8 + 1)      # int64 data + bool nulls
        up = [sp for sp in tracing.last_trace().spans
              if sp.name == "upload.h2d"]
        assert [sp.tags for sp in up] == [{"cols": 2, "bytes": grew}]
        tk.must_query(q)                            # resident: no copy
        r2 = self._status(tk)["device_residency"]
        assert r2["upload_bytes"] == r1["upload_bytes"]
        up = [sp for sp in tracing.last_trace().spans
              if sp.name == "upload.h2d"]
        assert [sp.tags for sp in up] == [{"cols": 2, "bytes": 0}]


def test_benchmark_reads_the_programs_vocabulary():
    """benchmark/harness/trace_owners.py keeps its own copy of the kernel
    names and of the span -> owner table (the yardstick may not import
    what it measures); both must name what the program has."""
    import pathlib
    import sys
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))
    from benchmark.harness import trace_owners
    from tidb_tpu.ops.device import KERNEL_SCOPES
    assert trace_owners.KERNELS == KERNEL_SCOPES
    pkg = pathlib.Path(tracing.__file__).resolve().parents[1]
    opened = set()
    for f in pkg.rglob("*.py"):
        opened |= set(re.findall(r'tracing\.(?:span|begin)\(\s*"([^"]+)"',
                                 f.read_text()))
    assert set(trace_owners.SPAN_OWNER) <= opened
