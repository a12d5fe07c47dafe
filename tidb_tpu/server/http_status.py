"""HTTP status API (reference: server/http_status.go:194-240 routes +
http_handler.go introspection): /status, /schema, /ddl/history, /metrics
(Prometheus text format), /settings, /regions."""

from __future__ import annotations

import json
import threading
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

from ..meta import Meta
from ..model import JobState, SchemaState


def _mpp_snapshot() -> dict:
    """MPP mesh-path gauges for /status and /metrics (process-wide, like
    the supervisor/residency gauges)."""
    from ..executor import mpp_exec
    return mpp_exec.snapshot()


def _compiler_snapshot() -> dict:
    """Compile-service gauges for /status and /metrics (process-wide)."""
    from ..executor import compile_service
    return compile_service.snapshot()


def _hybrid_join_snapshot() -> dict:
    """Lazy import: the hybrid join pulls the executor stack in."""
    from ..executor import hybrid_join
    return hybrid_join.snapshot()


def _tracing_snapshot() -> dict:
    """Span-tracer ring stats for /status (process-wide)."""
    from ..session import tracing
    return tracing.snapshot()


def _fabric_snapshot() -> dict:
    """Serving-fabric gauges (tidb_tpu/fabric/state.py): this worker's
    slot + dedup/remote-compile counters, and the fleet-global view
    (live workers, respawns) when a coordination segment is attached."""
    from ..fabric import state
    return state.snapshot()


def _wal_snapshot(domain) -> dict:
    """Durable-store gauges (kv/wal.py + kv/shared_store.py): append /
    fsync / group-commit / recovery / torn-truncation counters, plus
    this replica's applied-vs-end LSN when the store is durable — WAL
    lag and recovery history diagnosable from the status port."""
    from ..kv import wal as wal_mod
    out = wal_mod.snapshot()
    status = getattr(domain.store.mvcc, "wal_status", None)
    if status is not None:
        out.update(status())
    return out


def status_payload(domain) -> dict:
    """The ``/status`` body — also served as ``DIAG STATUS`` on a fleet
    worker's direct port (session/diag.py), where no HTTP listener
    runs."""
    from ..executor import device_exec, scheduler, supervisor
    from ..ops import residency
    return {
        "version": "8.0.11-tpu-htap",
        "connections": len(domain.sessions),
        # client statements parsed and the parser's seconds for them
        "server": domain.observe.server_snapshot(),
        "kv_engine": domain.store.backend,
        # the backend this process holds (platform, device_kind, count)
        # and each device's allocator bytes — which chip answered, and
        # whether every mesh device holds data
        "device_backend": residency.backend_report(),
        # compiled-pipeline cache: hits/misses, dispatches that traced +
        # compiled and their wall seconds (sync and background)
        "device_pipelines": device_exec.pipe_cache_stats(),
        # device-runtime supervision (executor/supervisor.py): the
        # abandoned-calls gauge plus hang/fence counters, so a hung
        # backend is diagnosable from the status port alone
        "device_abandoned_calls": supervisor.abandoned_calls(),
        "device_supervisor": supervisor.snapshot(),
        # HBM residency (ops/residency.py): cached-bytes ledger,
        # budget, epoch and the eviction / OOM-recovery counters —
        # device memory pressure diagnosable from the status port
        "device_residency": residency.snapshot(),
        # serving scheduler (executor/scheduler.py): admission queue
        # depth, per-tenant running counts / degradations, WFQ state
        "device_scheduler": scheduler.snapshot(),
        # MPP mesh path (executor/mpp_exec.py): fragments, retries
        # (capacity growth / transport / radix-exchange overflow),
        # placement-cache entries + residency-ledgered bytes
        "device_mpp": _mpp_snapshot(),
        # compile service (executor/compile_service.py): background
        # queue depth, worker pool, sync/bg compile counters, the
        # persistent-index hits and the last classified compile error
        # — a refused compile endpoint is diagnosable from the status
        # port alone
        "device_compiler": _compiler_snapshot(),
        # breaker stat lines keyed by (shape, resource group)
        "device_breakers": {
            shape: br.snapshot() for shape, br in
            getattr(domain, "_device_breakers", {}).items()},
        # span tracing (session/tracing.py): finished-trace ring
        # occupancy, started/finished/outstanding trace counts and
        # the per-trace span-bound drop counter — whether the
        # recorder is keeping up is diagnosable from the status port
        "device_tracing": _tracing_snapshot(),
        # hybrid hash join (executor/hybrid_join.py): partition
        # fanout, spilled partitions/bytes, co-processed host rows
        # and the open-spill-set drain gauge — whether a build side
        # is spilling (and leaking) is diagnosable from the port
        "device_hybrid_join": _hybrid_join_snapshot(),
        # serving fabric (tidb_tpu/fabric): worker slot, live fleet
        # size, respawns, fragment-dedup hits/waits, compile-server
        # RTT + remote errors — which worker this is and whether the
        # fleet is whole, diagnosable from any worker's status port
        "device_fabric": _fabric_snapshot(),
        # durable shared store (kv/wal.py): appends, fsync policy +
        # counts, group commits, recoveries, torn-tail truncations,
        # and this replica's applied WAL frontier
        "storage_wal": _wal_snapshot(domain),
    }


class StatusServer:
    def __init__(self, domain, sql_server=None, host="127.0.0.1", port=10080):
        self.domain = domain
        self.sql_server = sql_server
        outer = self

        class Handler(BaseHTTPRequestHandler):
            def log_message(self, *a):
                pass

            def do_GET(self):
                try:
                    outer._route(self)
                except Exception as e:  # introspection must not kill the server
                    self.send_response(500)
                    self.end_headers()
                    self.wfile.write(str(e).encode())

        self._server = ThreadingHTTPServer((host, port), Handler)
        self._server.daemon_threads = True
        self.port = self._server.server_address[1]
        self._thread = None

    def start(self):
        self._thread = threading.Thread(
            target=self._server.serve_forever, daemon=True)
        self._thread.start()
        return self

    def shutdown(self):
        self._server.shutdown()
        self._server.server_close()

    # -- routing -------------------------------------------------------------

    def _route(self, req):
        path = req.path.split("?", 1)[0].rstrip("/") or "/"
        if path == "/status":
            return self._json(req, self._status())
        if path == "/metrics":
            return self._text(req, self._metrics())
        if path == "/schema":
            return self._json(req, list(self.domain.infoschema().schema_names()))
        if path.startswith("/schema/"):
            return self._schema(req, path[len("/schema/"):])
        if path == "/ddl/history":
            return self._json(req, self._ddl_history())
        if path == "/settings":
            return self._json(req, dict(self.domain.global_vars))
        if path == "/regions":
            return self._json(req, [
                {"id": r.id, "start": r.start.hex(), "end": r.end.hex()}
                for r in self.domain.store.mvcc.regions])
        req.send_response(404)
        req.end_headers()

    def _json(self, req, obj):
        body = json.dumps(obj, indent=1, default=str).encode()
        req.send_response(200)
        req.send_header("Content-Type", "application/json")
        req.send_header("Content-Length", str(len(body)))
        req.end_headers()
        req.wfile.write(body)

    def _text(self, req, s: str):
        body = s.encode()
        req.send_response(200)
        req.send_header("Content-Type", "text/plain; version=0.0.4")
        req.send_header("Content-Length", str(len(body)))
        req.end_headers()
        req.wfile.write(body)

    # -- payloads ------------------------------------------------------------

    def _status(self):
        return status_payload(self.domain)

    def _metrics(self):
        """Prometheus text exposition of the domain counters (reference:
        metrics/metrics.go registry served on the status port)."""
        from ..executor import supervisor
        lines = []
        for name, val in sorted(self.domain.observe.counters.items()):
            lines.append(f"# TYPE {name} counter")
            lines.append(f"{name} {val}")
        # span-ring eviction pressure: finished traces aged out of the
        # bounded ring before a reader pulled them — when this moves, the
        # cluster memtables / TRACE post-mortems are losing history and
        # RING_CAP (session/tracing.py) needs a look
        ts = _tracing_snapshot()
        lines.append("# TYPE trace_ring_dropped_total counter")
        lines.append(
            f"trace_ring_dropped_total {ts.get('ring_dropped', 0)}")
        gauges = dict(self.domain.observe.gauge_snapshot())
        # the supervisor/residency gauges are process-wide; surface them
        # even when no device dispatch has registered this domain's sink
        from ..ops import residency
        rs = residency.snapshot()
        gauges.setdefault("device_abandoned_calls",
                          supervisor.abandoned_calls())
        gauges.setdefault("hbm_bytes_cached", rs["hbm_bytes_cached"])
        gauges.setdefault("hbm_evictions", rs["hbm_evictions"])
        gauges.setdefault("hbm_oom_recoveries", rs["hbm_oom_recoveries"])
        from ..executor import scheduler
        ss = scheduler.snapshot()
        gauges.setdefault("sched_queue_depth", ss["sched_queue_depth"])
        gauges.setdefault("sched_admission_waits_ms",
                          ss["sched_admission_waits_ms"])
        gauges.setdefault("sched_batched_fragments",
                          ss["sched_batched_fragments"])
        ms = _mpp_snapshot()
        gauges.setdefault("mpp_place_bytes", ms["mpp_place_bytes"])
        gauges.setdefault("mpp_fragments", ms["fragments"])
        gauges.setdefault("mpp_retries", ms["retries"])
        gauges.setdefault("mpp_exchange_overflow_retries",
                          ms["exchange_overflow_retries"])
        cs = _compiler_snapshot()
        gauges.setdefault("compile_queue_depth", cs["compile_queue_depth"])
        gauges.setdefault("compile_pending_fragments",
                          cs["compile_pending_fragments"])
        gauges.setdefault("compile_bg_seconds", cs["compile_bg_seconds"])
        gauges.setdefault("compile_persist_hits",
                          cs["compile_persist_hits"])
        hs = _hybrid_join_snapshot()
        gauges.setdefault("hj_partitions", hs["hj_partitions"])
        gauges.setdefault("hj_spilled_partitions",
                          hs["hj_spilled_partitions"])
        gauges.setdefault("hj_spill_bytes", hs["hj_spill_bytes"])
        gauges.setdefault("hj_coproc_host_rows", hs["hj_coproc_host_rows"])
        fs = _fabric_snapshot()
        gauges.setdefault("fabric_workers", fs.get("fabric_workers", 0))
        gauges.setdefault("fabric_respawns", fs.get("fabric_respawns", 0))
        gauges.setdefault("fabric_dedup_hits", fs["fabric_dedup_hits"])
        gauges.setdefault("fabric_compile_rtt_ms",
                          fs["fabric_compile_rtt_ms"])
        # versioned result cache (executor/agg_cache.py): this worker's
        # share + the fleet-global segment counters when attached
        gauges.setdefault("cache_hits", fs.get("cache_hits", 0))
        gauges.setdefault("cache_invalidations",
                          fs.get("cache_invalidations", 0))
        gauges.setdefault("cache_delta_folds",
                          fs.get("cache_delta_folds", 0))
        gauges.setdefault("cache_stale_reads",
                          fs.get("cache_stale_reads", 0))
        gauges.setdefault("fleet_cache_hits",
                          fs.get("fleet_cache_hits", 0))
        # fleet-frontier freshness (kv/shared_store.fresh_read_ts):
        # waits that blocked, budget blowups (9011 refusals) and
        # explicit stale_ok downgrades — the zero-silent-staleness
        # contract's scrapeable evidence
        gauges.setdefault("freshness_waits",
                          fs.get("freshness_waits", 0))
        gauges.setdefault("freshness_timeouts",
                          fs.get("freshness_timeouts", 0))
        gauges.setdefault("freshness_stale_ok",
                          fs.get("freshness_stale_ok", 0))
        # shared fragment-perf store (fabric/perf.py + the segment's
        # TPUFAB4 PERF section): fleet row/sample totals when attached,
        # this process's feed counters always
        gauges.setdefault("fabric_perf_rows",
                          fs.get("fabric_perf_rows", 0))
        gauges.setdefault("fabric_perf_samples",
                          fs.get("fabric_perf_samples", 0))
        ps = fs.get("perf_store", {})
        gauges.setdefault("perf_notes", ps.get("perf_notes", 0))
        gauges.setdefault("perf_merged", ps.get("perf_merged", 0))
        ws = _wal_snapshot(self.domain)
        gauges.setdefault("wal_appends", ws["wal_appends"])
        gauges.setdefault("wal_fsyncs", ws["wal_fsyncs"])
        gauges.setdefault("wal_group_commits", ws["wal_group_commits"])
        gauges.setdefault("wal_replayed_records",
                          ws["wal_replayed_records"])
        gauges.setdefault("wal_truncated_records",
                          ws["wal_truncated_records"])
        gauges.setdefault("wal_tail_records", ws["wal_tail_records"])
        # per-tenant degradations as ONE labeled series (a single TYPE
        # header — duplicate TYPE lines are invalid text exposition and
        # fail the whole scrape); the observe-sink mirror keys them
        # "sched_degradations:<group>", folded in here
        per_group = dict(ss["degradations_by_group"])
        for name in [k for k in gauges if
                     k.startswith("sched_degradations:")]:
            per_group.setdefault(name.split(":", 1)[1], gauges[name])
            del gauges[name]
        if per_group:
            lines.append("# TYPE sched_degradations gauge")
            for g, n in sorted(per_group.items()):
                # label escaping per the exposition format: the group
                # name is a free-form session sysvar, and one raw quote
                # or newline would invalidate the WHOLE scrape
                esc = (str(g).replace("\\", r"\\").replace('"', r'\"')
                       .replace("\n", r"\n"))
                lines.append(
                    f'sched_degradations{{resource_group="{esc}"}} {n}')
        for name, val in sorted(gauges.items()):
            lines.append(f"# TYPE {name} gauge")
            lines.append(f"{name} {val}")
        # per-layer latency histograms (session/observe.py HIST_BUCKETS)
        # as proper Prometheus cumulative `_bucket`/`_sum`/`_count`
        # series — statement / admission-wait / sync-compile / dispatch
        # p99s are scrapeable without bench.py
        for name, (bounds, counts, hsum, _cnt) in sorted(
                self.domain.observe.hist_snapshot().items()):
            lines.append(f"# TYPE {name} histogram")
            cum = 0
            for b, c in zip(bounds, counts):
                cum += c
                lines.append(f'{name}_bucket{{le="{b:g}"}} {cum}')
            cum += counts[-1]
            lines.append(f'{name}_bucket{{le="+Inf"}} {cum}')
            lines.append(f"{name}_sum {hsum:g}")
            lines.append(f"{name}_count {cum}")
        lines.append("# TYPE server_connections gauge")
        lines.append(f"server_connections {len(self.domain.sessions)}")
        return "\n".join(lines) + "\n"

    def _schema(self, req, rest: str):
        infos = self.domain.infoschema()
        parts = rest.split("/")
        if len(parts) == 1:
            if infos.schema_by_name(parts[0]) is None:
                req.send_response(404)
                req.end_headers()
                return
            tables = [t.name for t in infos.tables_in_schema(parts[0])]
            return self._json(req, tables)
        tbl = infos.table_by_name(parts[0], parts[1])
        if tbl is None:
            req.send_response(404)
            req.end_headers()
            return
        payload = tbl.to_json()
        if isinstance(payload, str):
            payload = json.loads(payload)
        return self._json(req, payload)

    def _ddl_history(self):
        txn = self.domain.store.begin()
        try:
            jobs = Meta(txn).history_jobs()[-50:]
        finally:
            txn.rollback()
        return [{
            "id": j.id, "type": j.type,
            "state": JobState.NAMES.get(j.state, "?"),
            "schema_state": SchemaState.NAMES.get(j.schema_state, "?"),
            "table_id": j.table_id, "row_count": j.row_count,
            "err": j.error,
        } for j in jobs]
