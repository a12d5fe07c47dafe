"""Observability state shared by all sessions of a Domain: slow-query log,
statement summary and a metrics registry.

Reference roles: slow log (`executor/slow_query.go` + SlowLogFormat in
sessionctx/variable/session.go), statement summary
(`util/stmtsummary/statement_summary.go`), Prometheus metrics
(`metrics/metrics.go:169`). All three are fed from one hook in the
session statement loop and read back through information_schema memtables,
keeping the reference's "observability is SQL-queryable" property."""

from __future__ import annotations

import collections
import logging
import threading
import time

log = logging.getLogger("tidb_tpu.observe")

#: serializes slow-query-file appends ACROSS Observability instances: a
#: multi-line SlowLogFormat entry bigger than the I/O buffer would
#: otherwise interleave with a concurrent session's entry and corrupt
#: both records for the parser (process-level because the file is)
_SLOW_FILE_LOCK = threading.Lock()

#: rendered-trace cap inside a slow-file entry (the memtable keeps the
#: full tree; the text file favors parseability over completeness)
_SLOW_FILE_TRACE_CAP = 8000

#: The per-layer latency histogram inventory (name -> bucket upper bounds
#: in SECONDS).  This literal dict is the registry the `gauge-consistency`
#: lint audits: every `observe_hist` call in the package must name a key
#: here, and every key must have a caller — the histogram analog of the
#: gauge inventory (README "Tracing").  /metrics renders each as proper
#: Prometheus `_bucket`/`_sum`/`_count` series so p99s are scrapeable
#: without bench.py.
HIST_BUCKETS = {
    # whole-statement wall clock (session/session.py statement loop)
    "statement_duration_seconds": (
        0.001, 0.005, 0.02, 0.1, 0.5, 2.5, 10.0, 60.0),
    # device admission queue wait (executor/scheduler.py queued path)
    "admission_wait_seconds": (
        0.0005, 0.002, 0.01, 0.05, 0.25, 1.0, 5.0),
    # sync XLA compiles paid on the query path (executor/device_exec.py
    # observed_jit meter; background compiles deliberately excluded)
    "sync_compile_seconds": (
        0.01, 0.05, 0.25, 1.0, 5.0, 30.0, 180.0),
    # one admitted device fragment end-to-end (executor/device_exec.py
    # run_device: supervisor + breaker + upload + dispatch)
    "device_dispatch_seconds": (
        0.0005, 0.002, 0.01, 0.05, 0.25, 1.0, 5.0, 30.0),
    # hybrid hash join probe halves (executor/hybrid_join.py): the
    # device partitions' pipelined pass vs the supervisor worker's
    # concurrent numpy pass over the spilled partitions — the measured
    # inputs of the cost-based device/host split point
    "hj_probe_device_seconds": (
        0.001, 0.005, 0.02, 0.1, 0.5, 2.5, 10.0, 60.0),
    "hj_probe_host_seconds": (
        0.001, 0.005, 0.02, 0.1, 0.5, 2.5, 10.0, 60.0),
    # fleet-frontier freshness wait at ts acquisition (session/session.py
    # Domain hookup of kv/shared_store.fresh_read_ts): 0 on the fast
    # path, up to the FRESHNESS_BUDGET_MS refusal ceiling when blocked
    # behind a lagging origin's durable commit frontier
    "freshness_wait_seconds": (
        0.0005, 0.002, 0.01, 0.05, 0.25, 1.0, 5.0),
}


class Histogram:
    """Fixed-bucket latency histogram (reference: the prometheus client's
    cumulative-bucket model, rendered by server/http_status.py)."""

    __slots__ = ("bounds", "counts", "sum", "count")

    def __init__(self, bounds):
        self.bounds = tuple(bounds)
        self.counts = [0] * (len(self.bounds) + 1)  # +1: the +Inf bucket
        self.sum = 0.0
        self.count = 0

    def observe(self, value: float):
        v = float(value)
        self.sum += v
        self.count += 1
        for i, b in enumerate(self.bounds):
            if v <= b:
                self.counts[i] += 1
                return
        self.counts[-1] += 1


class SlowQueryItem:
    __slots__ = ("ts", "user", "db", "duration_s", "digest", "sql",
                 "rows", "succ", "plan", "trace")

    def __init__(self, ts, user, db, duration_s, digest, sql, rows, succ,
                 plan="", trace=""):
        self.ts = ts
        self.user = user
        self.db = db
        self.duration_s = duration_s
        self.digest = digest
        self.sql = sql
        self.rows = rows
        self.succ = succ
        self.plan = plan
        # the statement's rendered span tree when it was traced
        # (session/tracing.py) — the causal timeline right next to the
        # slow entry, readable back through information_schema.slow_query
        self.trace = trace


class StmtSummary:
    """Per-digest aggregate (reference: stmtSummaryByDigest)."""

    __slots__ = ("digest", "sample_sql", "db", "exec_count", "sum_latency",
                 "max_latency", "min_latency", "sum_rows", "first_seen",
                 "last_seen", "err_count")

    def __init__(self, digest, sample_sql, db, now=None):
        self.digest = digest
        self.sample_sql = sample_sql
        self.db = db
        self.exec_count = 0
        self.sum_latency = 0.0
        self.max_latency = 0.0
        self.min_latency = float("inf")
        self.sum_rows = 0
        self.first_seen = now if now is not None else time.time()
        self.last_seen = self.first_seen
        self.err_count = 0

    def add(self, latency_s, rows, succ, now=None):
        self.exec_count += 1
        self.sum_latency += latency_s
        self.max_latency = max(self.max_latency, latency_s)
        self.min_latency = min(self.min_latency, latency_s)
        self.sum_rows += rows
        self.last_seen = now if now is not None else time.time()
        if not succ:
            self.err_count += 1


class Observability:
    def __init__(self, slow_log_cap=1024, summary_cap=512):
        self._lock = threading.Lock()
        self.slow_queries = collections.deque(maxlen=slow_log_cap)
        self.stmt_summary: "collections.OrderedDict[str, StmtSummary]" = \
            collections.OrderedDict()
        self._summary_cap = summary_cap
        # metrics: flat counter/gauge registry (reference: metrics/metrics.go)
        self.counters = collections.Counter()
        # gauges are SET, not incremented: point-in-time values like the
        # supervisor's "abandoned device calls outstanding"
        # (executor/supervisor.py publishes into every registered sink)
        self.gauges: dict = {}
        # per-layer latency histograms (HIST_BUCKETS registry above)
        self.histograms: dict[str, Histogram] = {}
        # client SQL texts parsed and the seconds the parser took
        # (Session.execute): parsing happens before the statement's
        # trace begins, so it has a counter where the rest has spans
        self.statements = 0
        self.parse_s = 0.0

    def inc(self, name, n=1):
        with self._lock:
            self.counters[name] += n

    def note_parse(self, seconds: float, statements: int):
        with self._lock:
            self.statements += statements
            self.parse_s += seconds

    def server_snapshot(self) -> dict:
        """The ``/status`` ``server`` section."""
        with self._lock:
            return {"statements": self.statements, "parse_s": self.parse_s}

    def set_gauge(self, name, value):
        with self._lock:
            self.gauges[name] = value

    def gauge_snapshot(self) -> dict:
        with self._lock:
            return dict(self.gauges)

    def observe_hist(self, name, value):
        """Record one latency sample into a registered histogram.  Names
        must come from HIST_BUCKETS (lint-pinned); an unregistered name
        still records (with a default ladder) rather than failing the
        caller's statement."""
        with self._lock:
            h = self.histograms.get(name)
            if h is None:
                h = self.histograms[name] = Histogram(
                    HIST_BUCKETS.get(
                        name, (0.001, 0.01, 0.1, 1.0, 10.0)))
            h.observe(value)

    def hist_snapshot(self) -> dict:
        """name -> (bounds, per-bucket counts, sum, count) — consumed by
        the /metrics renderer (server/http_status.py)."""
        with self._lock:
            return {name: (h.bounds, list(h.counts), h.sum, h.count)
                    for name, h in self.histograms.items()}

    def observe_stmt(self, *, user, db, sql, digest, latency_s, rows, succ,
                     slow_threshold_s, plan="", trace="",
                     slow_query_file=""):
        # item construction (and the wall-clock reads) happen OUTSIDE the
        # lock: N concurrent sessions funnel through this hook per
        # statement, and the critical section must stay counter/append
        # sized — not time.time()-twice-plus-allocation sized
        now = time.time()
        slow_item = None
        if latency_s >= slow_threshold_s:
            slow_item = SlowQueryItem(now, user, db, latency_s, digest,
                                      sql, rows, succ, plan, trace)
        with self._lock:
            st = self.stmt_summary.get(digest)
            if st is None:
                while len(self.stmt_summary) >= self._summary_cap:
                    self.stmt_summary.popitem(last=False)
                st = self.stmt_summary[digest] = StmtSummary(digest, sql,
                                                             db, now=now)
            st.add(latency_s, rows, succ, now=now)
            self.counters["executor_statement_total"] += 1
            if not succ:
                self.counters["executor_statement_error_total"] += 1
            if slow_item is not None:
                self.slow_queries.append(slow_item)
        if slow_item is not None and slow_query_file:
            self._append_slow_file(slow_query_file, slow_item)

    def _append_slow_file(self, path: str, it: SlowQueryItem):
        """SlowLogFormat-style text append (reference: the slow-log file
        executor/slow_query.go parses back;
        sessionctx/variable/session.go SlowLogFormat).  A write failure
        is LOGGED CLASSIFIED, never swallowed and never allowed to fail
        the statement."""
        try:
            ts = time.strftime("%Y-%m-%dT%H:%M:%S", time.localtime(it.ts))
            lines = [
                f"# Time: {ts}.{int((it.ts % 1) * 1e6):06d}",
                f"# User@Host: {it.user}",
                f"# Db: {it.db}",
                f"# Query_time: {it.duration_s:.6f}",
                f"# Digest: {it.digest}",
                f"# Result_rows: {it.rows}",
                f"# Succ: {'true' if it.succ else 'false'}",
            ]
            if it.trace:
                lines += ["# Trace: " + ln for ln in
                          it.trace[:_SLOW_FILE_TRACE_CAP].splitlines()]
            sql = it.sql.rstrip(";")
            lines.append(sql + ";")
            payload = "\n".join(lines) + "\n"
            with _SLOW_FILE_LOCK:
                with open(path, "a") as f:
                    f.write(payload)
        except Exception as e:
            from ..utils.backoff import classify
            log.warning("slow-query-file append failed (%s, path=%s): %s",
                        classify(e), path, e)
