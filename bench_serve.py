"""Serving benchmark: N concurrent client threads multiplexing ONE
Domain's device through the admission scheduler (ISSUE 6 / ROADMAP open
item 2).

Where bench.py measures one query at a time as fast as the hardware
allows, THIS bench measures the serving story: mixed TPC-H reads
(analytical tenant, forced device engine) + transfer-DML and point reads
(OLTP tenant, auto engine) from N client threads, with per-tenant
p50/p99 latency, queries/s, admission waits, batched fragments and
degradations on the report — optionally under the threaded chaos
catalog (seeded failpoints: backend hangs beneath a small
`tidb_device_call_timeout`, synthetic HBM OOM, admission refusals and
stalls), so SLO behavior under faults is pinned, not hoped for.

Invariants enforced (exit code 1 on violation):
  * every operation succeeds or fails with a CLEAN classified error —
    never an unclassified exception;
  * zero incorrect results: analytical reads match a fault-free host
    golden bit-for-bit; the transfer ledger sums to its seed total in
    every snapshot and at the end;
  * the admission queue drains to zero (no leaked tickets) and the
    residency ledger shows no drift.

Output: one JSON line per metric (same convention as bench.py):
  {"metric": "serve_latency_ms", "group": "olap", "p50": ..., "p99": ...}
  {"metric": "serve_qps", "value": ..., "threads": N, ...}
  {"metric": "serve_sched", "sched_queue_depth": 0, ...}

The fleet modes (--procs N >= 2, --hosts N) are CPU benches: a chip
belongs to ONE process, and a fleet is N device-using workers plus a
compile server whose warm-up executes programs on its own backend.
Until ROADMAP R2 gives the fleet a chip-ownership model, every process
these modes spawn is pinned to JAX_PLATFORMS=cpu and this parent never
initialises a backend before spawning; their timings are host-platform
numbers, not device metrics.  One worker on the chip is chip_smoke.py.

Usage:
  python bench_serve.py                  # 8 threads, default mix
  python bench_serve.py --smoke          # small fixed-seed tier-1 run
  python bench_serve.py --threads 16 --ops 40 --sf 0.01 --chaos
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import random
import sys
import threading
import time

import tidb_tpu  # noqa: F401  (x64 on)

from tidb_tpu.errors import TiDBError
from tidb_tpu.testkit import TestKit
from tidb_tpu.utils import failpoint
from tidb_tpu.utils.failpoint import FailpointError

import bench  # repo-root sibling: TPC-H datagen + the north-star queries

#: transfer-ledger seed state (the write-atomicity invariant)
N_ACCTS = 8
SEED_BAL = 1000
LEDGER_TOTAL = N_ACCTS * SEED_BAL

#: analytical corpus: the north-star shapes that fit a serving mix
#: (Q1 scan-agg, Q3 join-agg — bench.py's exact SQL, so the serving and
#: single-query benches measure the same fragments)
OLAP_QUERIES = ("q1", "q3")

#: chaos catalog for --chaos runs: the threaded-chaos failure families
#: (hang + OOM + admission) at serving-friendly rates
CHAOS_FAULTS = {
    "device-agg-exec": ["1*panic", "sleep(0.05)"],
    "device-join-exec": ["1*panic", "sleep(0.05)"],
    "device-upload-oom": ["1*oom", "2*oom", "oom"],
    "device-admission": ["admission-queue-full", "1*admission-wait(0.05)",
                         "2*admission-wait(0.02)"],
    "txn-before-commit": ["1*panic"],
    "txn-before-prewrite": ["1*panic"],
}

_EMIT_LOCK = threading.Lock()


def _emit(obj) -> None:
    with _EMIT_LOCK:
        print(json.dumps(obj), flush=True)


def _is_clean(err: Exception) -> bool:
    return isinstance(err, (TiDBError, FailpointError))


def _pctl(sorted_vals, q: float):
    if not sorted_vals:
        return None
    i = min(int(q * len(sorted_vals)), len(sorted_vals) - 1)
    return round(sorted_vals[i], 2)


#: span-ring stat keys surfaced on every per-phase line (a bench
#: regression names its phase AND whether the tracer was dropping)
_RING_KEYS = ("ring_traces", "started", "finished", "spans_dropped",
              "ring_dropped", "remote_hops", "remote_traces")


def _phase(emit, phase: str, t0: float, ring: "dict | None" = None,
           **extra) -> None:
    """One ``serve_phase`` JSON line: the phase's wall clock plus
    span-ring stats (this process's ring for thread-mode runs; pass a
    worker's DIAG-fetched snapshot for fleet phases)."""
    if ring is None:
        from tidb_tpu.session import tracing
        ring = tracing.snapshot()
    emit({"metric": "serve_phase", "phase": phase,
          "wall_s": round(time.monotonic() - t0, 3),
          **{k: ring.get(k, 0) for k in _RING_KEYS}, **extra})


def _fleet_ring(port: int) -> dict:
    """One worker's span-ring stats over its DIAG endpoint (zeros when
    the peer is unreachable — phase lines must never fail a run)."""
    try:
        from tidb_tpu.fabric.client import FleetClient
        c = FleetClient(port, timeout=5.0)
        try:
            _cols, rows = c.must_query("DIAG metrics")
            return json.loads(rows[0][0]).get("tracing", {})
        finally:
            c.close()
    except Exception:  # noqa: BLE001 — diagnostics-only feed
        return {}


def _setup(sf: float) -> tuple:
    """One Domain: TPC-H tables at `sf` (tpch db) + the transfer ledger
    (test db).  Returns (tk, goldens) — goldens are the fault-free HOST
    engine results for the analytical corpus."""
    tk = TestKit()
    failpoint.disable_all()
    bench.gen_all(tk, sf)
    tk.must_exec("use test")
    tk.must_exec("create table ledger (acct int primary key, bal int)")
    tk.must_exec("insert into ledger values " + ",".join(
        f"({i}, {SEED_BAL})" for i in range(1, N_ACCTS + 1)))
    tk.must_exec("use tpch")
    tk.must_exec("set tidb_executor_engine = 'host'")
    goldens = {q: tuple(map(tuple, tk.must_query(bench.QUERIES[q]).rows))
               for q in OLAP_QUERIES}
    tk.must_exec("set tidb_executor_engine = 'auto'")
    return tk, goldens


def run_serve(n_threads: int = 8, n_ops: int = 20, sf: float = 0.01,
              seed: int = 0, chaos: bool = False, emit=_emit) -> dict:
    """Drive the serving workload; returns the summary dict (also
    emitted as JSON lines).  Raises AssertionError on any invariant
    violation — tests call this in-process, the CLI exits 1."""
    from tidb_tpu.executor import scheduler, supervisor
    from tidb_tpu.ops import residency

    tk, goldens = _setup(sf)
    t_start = time.monotonic()

    mu = threading.Lock()
    lat = {}          # group -> [latency_ms]
    counts = {"ok": 0, "clean_errors": 0, "writes_ok": 0,
              "writes_failed": 0}
    violations: list = []
    start = threading.Barrier(n_threads)

    def record(group, ms):
        with mu:
            lat.setdefault(group, []).append(ms)

    def bump(key):
        with mu:
            counts[key] += 1

    def violate(tid, what, exc=None, conn_id=None):
        # a violation's post-mortem: the OFFENDING session's most recent
        # finished span trace (conn_id-filtered — with N concurrent
        # workers, a healthy thread's timeline must never be
        # misattributed to the failure), when the run samples
        from tidb_tpu.session import tracing
        trace = tracing.last_trace_text(conn_id, cap=2000)
        with mu:
            violations.append(
                f"thread {tid}: {what}"
                + (f" ({type(exc).__name__}: {exc})" if exc else "")
                + (("\n" + trace) if trace else ""))

    def _olap_op(wtk, rng, tid):
        qname = OLAP_QUERIES[rng.randrange(len(OLAP_QUERIES))]
        t0 = time.monotonic()
        try:
            rows = tuple(map(tuple,
                             wtk.must_query(bench.QUERIES[qname]).rows))
        except Exception as e:  # noqa: BLE001 — classification IS the check
            if _is_clean(e):
                bump("clean_errors")
            else:
                violate(tid, f"unclassified analytical failure on "
                        f"{qname}", e, conn_id=wtk.session.conn_id)
            return
        record("olap", (time.monotonic() - t0) * 1000.0)
        bump("ok")
        if rows != goldens[qname]:
            violate(tid, f"WRONG RESULT for {qname} (device path diverged"
                    " from host golden)", conn_id=wtk.session.conn_id)

    def _oltp_op(wtk, rng, tid):
        kind = rng.random()
        t0 = time.monotonic()
        try:
            if kind < 0.45:  # point read
                acct = rng.randrange(1, N_ACCTS + 1)
                wtk.must_query(
                    f"select bal from ledger where acct = {acct}")
            elif kind < 0.65:  # ledger-sum snapshot (atomicity check)
                total = wtk.must_query(
                    "select sum(bal) from ledger").rows[0][0]
                if str(total) != str(LEDGER_TOTAL):
                    violate(tid, f"ATOMICITY VIOLATION: ledger sum "
                            f"{total} != {LEDGER_TOTAL}")
            else:  # transfer write (acct order: no deadlock cycles)
                a, b = sorted(rng.sample(range(1, N_ACCTS + 1), 2))
                amt = rng.randrange(1, 40)
                wtk.must_exec("begin")
                wtk.must_exec(
                    f"update ledger set bal = bal - {amt} where acct={a}")
                wtk.must_exec(
                    f"update ledger set bal = bal + {amt} where acct={b}")
                wtk.must_exec("commit")
                bump("writes_ok")
        except Exception as e:  # noqa: BLE001
            if _is_clean(e):
                bump("clean_errors")
                if kind >= 0.65:
                    with mu:
                        counts["writes_failed"] += 1
                        counts["clean_errors"] -= 1
                try:
                    wtk.session.rollback()
                except Exception:
                    pass
            else:
                violate(tid, "unclassified OLTP failure", e,
                        conn_id=wtk.session.conn_id)
            return
        record("oltp", (time.monotonic() - t0) * 1000.0)
        bump("ok")

    def worker(tid):
        try:
            _worker_body(tid)
        except Exception as e:  # noqa: BLE001 — a dead worker IS a finding
            violate(tid, "worker thread died", e)

    def _worker_body(tid):
        rng = random.Random((seed << 8) ^ tid)
        olap = tid % 2 == 0  # even threads analytical, odd threads OLTP
        wtk = tk.new_session()
        group = "olap" if olap else "oltp"
        wtk.must_exec(f"set tidb_resource_group = '{group}'")
        if os.environ.get("BENCH_TRACE", "") == "1":
            # opt-in, same BENCH_TRACE=1 gate as bench.py: the serving
            # bench measures contended p99s, and N threads × sampling
            # every op would skew exactly the latencies under test
            wtk.must_exec("set tidb_trace_sampling_rate = 1")
        wtk.must_exec("set innodb_lock_wait_timeout = 2")
        if olap:
            wtk.must_exec("use tpch")
            # analytical tenants force the device engine: they are the
            # traffic the admission queue exists to schedule
            wtk.must_exec("set tidb_executor_engine = 'tpu'")
        else:
            wtk.must_exec("use test")
        start.wait(timeout=60)
        for _op in range(n_ops):
            with contextlib.ExitStack() as st:
                if chaos:
                    # half the ops run supervised with a deadline smaller
                    # than the injected sleeps: the hang path fires live
                    wtk.must_exec("set tidb_device_call_timeout = "
                                  + ("0.02" if rng.random() < 0.5 else "0"))
                    if rng.random() < 0.5:
                        for name in rng.sample(sorted(CHAOS_FAULTS),
                                               k=rng.choice([1, 1, 2])):
                            st.enter_context(failpoint.enabled(
                                name, rng.choice(CHAOS_FAULTS[name])))
                if olap:
                    _olap_op(wtk, rng, tid)
                else:
                    _oltp_op(wtk, rng, tid)

    threads = [threading.Thread(target=worker, args=(tid,), daemon=True,
                                name=f"serve-{tid}")
               for tid in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(300.0)
    stuck = [t.name for t in threads if t.is_alive()]
    failpoint.disable_all()
    wall_s = time.monotonic() - t_start

    # -- invariants ----------------------------------------------------------
    assert not stuck, f"STUCK CLIENT THREADS: {stuck}"
    assert not violations, "\n".join(violations)
    tk.must_exec("use test")
    tk.must_exec("set tidb_executor_engine = 'host'")
    total = tk.must_query("select sum(bal) from ledger").rows[0][0]
    assert str(total) == str(LEDGER_TOTAL), (
        f"final ledger sum {total} != {LEDGER_TOTAL}")
    # abandoned supervised calls drain (chaos hangs are short sleeps),
    # then the admission queue must show zero leaked tickets
    deadline = time.monotonic() + 15.0
    while ((supervisor.abandoned_calls() > 0
            or not scheduler.verify_drained()["ok"])
           and time.monotonic() < deadline):
        time.sleep(0.01)
    drained = scheduler.verify_drained()
    assert drained["ok"], f"LEAKED ADMISSION TICKETS: {drained}"
    led = residency.verify_ledger()
    assert led["ok"], f"HBM LEDGER DRIFT: {led}"

    # -- report --------------------------------------------------------------
    n_queries = counts["ok"]
    sched = scheduler.snapshot()
    summary = {
        "threads": n_threads, "ops_per_thread": n_ops, "sf": sf,
        "seed": seed, "chaos": chaos, "wall_s": round(wall_s, 2),
        "qps": round(n_queries / wall_s, 2) if wall_s > 0 else 0.0,
        **counts,
        "violations": 0,
    }
    emit({"metric": "serve_clients", "value": n_threads,
          "unit": "threads", "chaos": chaos, "sf": sf, "seed": seed})
    for group, vals in sorted(lat.items()):
        vals.sort()
        emit({"metric": "serve_latency_ms", "group": group,
              "p50": _pctl(vals, 0.50), "p99": _pctl(vals, 0.99),
              "n": len(vals)})
        summary[f"p50_{group}"] = _pctl(vals, 0.50)
        summary[f"p99_{group}"] = _pctl(vals, 0.99)
    emit({"metric": "serve_qps", "value": summary["qps"],
          "unit": "queries/s", "threads": n_threads,
          "wall_s": summary["wall_s"], "ok": counts["ok"],
          "clean_errors": counts["clean_errors"],
          "writes_ok": counts["writes_ok"],
          "writes_failed": counts["writes_failed"]})
    emit({"metric": "serve_sched",
          "sched_queue_depth": sched["sched_queue_depth"],
          "sched_admission_waits_ms": sched["sched_admission_waits_ms"],
          "sched_batched_fragments": sched["sched_batched_fragments"],
          "sched_degradations": sched["degradations_by_group"],
          "admitted": sched["admitted"], "queued": sched["queued"],
          "rejected_full": sched["rejected_full"],
          "rejected_timeout": sched["rejected_timeout"],
          "rejected_injected": sched["rejected_injected"],
          "hbm_bytes_cached": residency.resident_bytes(),
          "supervisor_hangs": supervisor.snapshot()["hangs"]})
    # compile-service attribution (executor/compile_service.py): how much
    # compile the serving run paid on the query path vs in the background
    # pool, plus the pending/persist/prewarm counters — a chaos run with
    # injected compile faults also reports bg_failed here
    from tidb_tpu.executor import compile_service
    from tidb_tpu.executor.device_exec import pipe_cache_stats
    ps = pipe_cache_stats()
    emit({"metric": "serve_compile",
          "sync_compile_s": round(ps["compile_s"], 4),
          "bg_compile_s": round(ps["bg_compile_s"], 4),
          **compile_service.report_gauges()})
    summary.update({k: sched[k] for k in
                    ("admitted", "queued", "sched_batched_fragments",
                     "rejected_full", "rejected_timeout",
                     "rejected_injected")})
    summary["degradations_by_group"] = sched["degradations_by_group"]
    summary["sync_compile_s"] = round(ps["compile_s"], 4)
    summary["bg_compile_s"] = round(ps["bg_compile_s"], 4)
    _phase(emit, "serve", t_start)
    return summary


# -- durability phase (ISSUE 15): WAL cost + kill-recover round trip ---------

#: the kill-recover child: ack K committed rows, then die by SIGKILL at
#: the widest 2PC crash window.  The parent times reopen+recovery and
#: requires every acked row back.
_DUR_CHILD = r"""
import json, sys
from tidb_tpu.utils import failpoint
from tidb_tpu.kv import new_store
st = new_store(wal_dir=sys.argv[1])
n = int(sys.argv[2])
for i in range(n):
    t = st.begin(); t.put(b"dur%06d" % i, b"v"); t.commit()
    print(json.dumps({"acked": i}), flush=True)
failpoint.enable("txn-before-commit", "1*return(kill)")
t = st.begin(); t.put(b"doomed", b"x"); t.commit()
"""


def run_durability(n_txns: int = 150, emit=_emit) -> dict:
    """The durability phase of the smoke: transfer-DML-shaped KV txn
    qps with WAL off / ``fsync=never`` / ``fsync=commit`` (the
    group-commit overhead, measured not guessed), plus one SIGKILL-mid-
    commit → reopen → recovery round trip timed end to end with
    committed-visible / uncommitted-gone asserted.  One JSON line:
    ``{"metric": "serve_durability", ...}``."""
    import shutil
    import subprocess
    import tempfile
    from tidb_tpu.kv import new_store

    def dml_qps(wal_dir, policy):
        if wal_dir:
            st = new_store(wal_dir=wal_dir)
            st.mvcc.wal.policy_source = lambda: policy
        else:
            # the WAL-OFF baseline must be genuinely in-memory: plain
            # Storage, NOT new_store(None) — that falls through to the
            # TIDB_TPU_WAL_DIR env fallback and would both skew the
            # comparison and write bench keys into a real WAL dir
            from tidb_tpu.kv.store import Storage
            st = Storage()
        t0 = time.monotonic()
        for i in range(n_txns):
            t = st.begin()
            t.put(b"q%06d" % i, b"a")
            t.put(b"r%06d" % i, b"b")
            t.commit()
        dt = max(time.monotonic() - t0, 1e-9)
        st.close()
        return round(n_txns / dt, 1)

    tmp = tempfile.mkdtemp(prefix="serve-dur-")
    t_dur = time.monotonic()
    out = {"metric": "serve_durability", "n_txns": n_txns}
    try:
        out["qps_wal_off"] = dml_qps(None, None)
        out["qps_fsync_never"] = dml_qps(os.path.join(tmp, "nv"), "never")
        out["qps_fsync_commit"] = dml_qps(os.path.join(tmp, "cm"),
                                          "commit")
        out["group_commit_overhead_pct"] = round(
            100.0 * (1.0 - out["qps_fsync_commit"]
                     / max(out["qps_wal_off"], 1e-9)), 1)
        # kill-recover round trip
        kdir = os.path.join(tmp, "kill")
        acked = 8
        r = subprocess.run(
            [sys.executable, "-c", _DUR_CHILD, kdir, str(acked)],
            env={**os.environ, "JAX_PLATFORMS": "cpu",
                 "PYTHONPATH": os.pathsep.join(
                     [p for p in sys.path if p]
                     + [os.environ.get("PYTHONPATH", "")])},
            capture_output=True, text=True, timeout=240)
        assert r.returncode == -9, (
            f"kill child exited {r.returncode}: {r.stderr[-500:]}")
        t0 = time.monotonic()
        st = new_store(wal_dir=kdir)  # reopen = recover
        out["kill_recover_s"] = round(time.monotonic() - t0, 4)
        snap = st.get_snapshot()
        recovered = sum(1 for i in range(acked)
                        if snap.get(b"dur%06d" % i) == b"v")
        assert recovered == acked, (
            f"LOST COMMITTED ROWS: {recovered}/{acked} after recovery")
        assert snap.get(b"doomed") is None, (
            "un-acked mid-kill txn visible after recovery")
        st.close()
        out["acked"] = acked
        out["recovered"] = recovered
    finally:
        with contextlib.suppress(OSError):
            shutil.rmtree(tmp)
    emit(out)
    _phase(emit, "durability", t_dur)
    return out


# -- multi-host failover (--hosts N, ISSUE 16): region failover --------------
#
# Where run_fleet kills ONE worker process (its siblings keep the same
# shared WAL), run_failover kills a whole simulated HOST — its private
# process group and every region it owned — and requires the REGION
# layer (tidb_tpu/fabric/region.py) to turn that into a failover, not
# data loss: surviving hosts claim the dead host's expired region
# leases, restore checkpoint+tail from the blob store, replay, resume.
# Coordination rides the NETWORK coordinator (fabric/coord_net.py) so
# the failover path is exercised over real TCP frames, not the
# same-machine segment shortcut.

#: one simulated host: claims its share of the region grid over the
#: network coordinator, serves 2PC writes with replicate-on-ack (a row
#: is "acked" only after its region's checkpoint+tail landed in the
#: blob store), and — if doomed — dies by the fabric-kill-host
#: failpoint mid-commit: prewrite replicated, commit never written, the
#: whole host process group SIGKILLed (same contract as
#: tidb_tpu/fabric/worker.py: TIDB_TPU_FABRIC_HOST set means my
#: process group IS my host).
_FAILOVER_CHILD = r"""
import json, os, signal, sys, threading, time
root, addr, host_id, hosts, n_ack, doomed = (
    sys.argv[1], sys.argv[2], int(sys.argv[3]), int(sys.argv[4]),
    int(sys.argv[5]), int(sys.argv[6]))
from tidb_tpu.fabric.blob import LocalDirBlobStore
from tidb_tpu.fabric.coord_net import NetCoordinator
from tidb_tpu.fabric.region import RegionStore
from tidb_tpu.kv.store import OP_PUT, Storage
from tidb_tpu.utils import failpoint

def say(**kw):
    print(json.dumps(kw), flush=True)

net = NetCoordinator(addr)
net.claim_slot(host_id)
blob = LocalDirBlobStore(os.path.join(root, "blob"))
rs = RegionStore(os.path.join(root, "h%d" % host_id), net, host_id,
                 blob=blob)
mine = [r for r in range(rs.region_map.n) if r % hosts == host_id]
got = rs.open_regions(mine)
st = Storage(mvcc=rs)
say(phase="up", host=host_id, regions=got)

stop_path = os.path.join(root, "stop")

def beat():
    n = 0
    while not os.path.exists(stop_path):
        try:
            net.heartbeat(host_id)
            rs.heartbeat()
            n += 1
            if n % 3 == 0:
                rs.failover_expired()
        except Exception:
            pass
        time.sleep(0.25)

threading.Thread(target=beat, daemon=True).start()

def rkey(rid, i):
    lo = (rid << 64) // rs.region_map.n
    return lo.to_bytes(8, "big") + (b"h%d-%06d" % (host_id, i))

for i in range(n_ack):
    rid = got[i % len(got)]
    k, v = rkey(rid, i), b"val-%d-%d" % (host_id, i)
    t = st.begin(); t.put(k, v); t.commit()
    rs.replicate([rid])   # the ack point: durable in the blob store
    say(phase="ack", k=k.hex(), v=v.hex())
say(phase="acked_all", host=host_id)

if host_id == doomed:
    # die mid-commit at the widest 2PC crash window: prewrite lands in
    # the replicated log, the commit never does — failover must roll
    # the orphan back (un-acked rows gone)
    failpoint.enable("fabric-kill-host", "1*return(1)")
    t = st.begin()
    kd = rkey(got[0], 999999)
    rs.prewrite([(kd, OP_PUT, b"doomed")], kd, t.start_ts)
    rs.replicate()
    say(phase="doomed_prewrite", k=kd.hex())
    if failpoint.inject("fabric-kill-host"):
        if os.environ.get("TIDB_TPU_FABRIC_HOST") is not None:
            os.killpg(os.getpgid(0), signal.SIGKILL)
        os.kill(os.getpid(), signal.SIGKILL)

while not os.path.exists(stop_path):
    time.sleep(0.1)
ts = rs.tso.next_ts()
pairs = []
for rid in sorted(rs.stores):
    s, e = rs.region_map.bounds(rid)
    pairs += [[k.hex(), v.hex()] for k, v in rs.scan(s, e, ts)]
owned = sorted(rs.stores)
rs.close()
net.release_slot(host_id)
say(phase="final", host=host_id, owned=owned, pairs=pairs)
"""

#: host failover must land within this budget (region lease 2s +
#: heartbeat period + restore/replay — generous for a loaded CI box)
FAILOVER_BUDGET_S = 30.0


def run_failover(hosts: int = 3, n_ack: int = 4, nregions: int = 6,
                 seed: int = 0, emit=_emit) -> dict:
    """SIGKILL one simulated host mid-commit; assert region failover
    within the lease budget, every acked row readable fleet-wide,
    un-acked rows gone, and a cold restart from the blob store ALONE
    bit-equal.  Emits one ``serve_failover`` JSON line."""
    import shutil
    import signal
    import subprocess
    import tempfile
    from tidb_tpu.fabric.blob import LocalDirBlobStore
    from tidb_tpu.fabric.coord import Coordinator
    from tidb_tpu.fabric.coord_net import CoordServer
    from tidb_tpu.fabric.region import RegionStore, \
        verify_region_invariants

    assert hosts >= 3, "failover mode needs >= 3 hosts (2 survivors)"
    t_fo = time.monotonic()
    rng = random.Random(seed)
    doomed = rng.randrange(hosts)
    root = tempfile.mkdtemp(prefix="serve-failover-")
    coord = Coordinator.create(os.path.join(root, "coord"),
                               nregions=nregions)
    srv = CoordServer(coord)
    addr = srv.start()
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": os.pathsep.join(
               [p for p in sys.path if p]
               + [os.environ.get("PYTHONPATH", "")])}
    lines = {h: [] for h in range(hosts)}
    errs = {h: [] for h in range(hosts)}
    procs = {}
    readers = []
    out = {"metric": "serve_failover", "hosts": hosts,
           "nregions": nregions, "doomed_host": doomed, "seed": seed}

    def read_json(h, pipe):
        for ln in pipe:
            with contextlib.suppress(ValueError):
                lines[h].append(json.loads(ln))

    def read_err(h, pipe):
        for ln in pipe:
            errs[h].append(ln)

    def wait_phase(h, phase, budget=FAILOVER_BUDGET_S):
        t0 = time.monotonic()
        while time.monotonic() - t0 < budget:
            for obj in list(lines[h]):
                if obj.get("phase") == phase:
                    return obj
            time.sleep(0.02)
        raise AssertionError(
            f"host {h} never reached phase {phase!r} (rc="
            f"{procs[h].poll()}, saw="
            f"{[o.get('phase') for o in lines[h]]}, stderr="
            f"{''.join(errs[h])[-500:]!r})")

    try:
        for h in range(hosts):
            p = subprocess.Popen(
                [sys.executable, "-c", _FAILOVER_CHILD, root, addr,
                 str(h), str(hosts), str(n_ack), str(doomed)],
                stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                text=True, env=dict(env, TIDB_TPU_FABRIC_HOST=str(h)),
                preexec_fn=os.setpgrp)
            procs[h] = p
            for target, pipe in ((read_json, p.stdout),
                                 (read_err, p.stderr)):
                t = threading.Thread(target=target, args=(h, pipe),
                                     daemon=True)
                t.start()
                readers.append(t)
        # every host acks its rows (ack = replicated to the blob store)
        for h in range(hosts):
            wait_phase(h, "acked_all", budget=240.0)
        acked = {o["k"]: o["v"] for h in range(hosts)
                 for o in lines[h] if o.get("phase") == "ack"}
        assert len(acked) == hosts * n_ack, (
            f"expected {hosts * n_ack} acked rows, saw {len(acked)}")
        # the doomed host dies mid-commit, whole process group at once
        dk = wait_phase(doomed, "doomed_prewrite")["k"]
        rc = procs[doomed].wait(timeout=FAILOVER_BUDGET_S)
        t_dead = time.monotonic()
        assert rc == -signal.SIGKILL, (
            f"doomed host exited {rc}, not SIGKILL — the "
            f"fabric-kill-host failpoint did not fire")
        # surviving hosts must claim every region within the budget
        failover_s = None
        while time.monotonic() - t_dead < FAILOVER_BUDGET_S:
            owners = coord.region_owners()
            if len(owners) == nregions and doomed not in owners.values():
                failover_s = time.monotonic() - t_dead
                break
            time.sleep(0.05)
        assert failover_s is not None, (
            f"regions not failed over within {FAILOVER_BUDGET_S}s: "
            f"owners={coord.region_owners()}")
        # quiesce: survivors report their full served state and drain
        with open(os.path.join(root, "stop"), "w"):
            pass
        for h in range(hosts):
            if h != doomed:
                rc = procs[h].wait(timeout=FAILOVER_BUDGET_S)
                assert rc == 0, (
                    f"survivor {h} exited {rc}: "
                    f"{''.join(errs[h])[-500:]!r}")
        for t in readers:
            t.join(5.0)
        finals = {o["host"]: o for h in range(hosts) if h != doomed
                  for o in lines[h] if o.get("phase") == "final"}
        assert len(finals) == hosts - 1, (
            f"missing survivor final reports: got {sorted(finals)}")
        merged = {k: v for f in finals.values() for k, v in f["pairs"]}
        missing = [k for k in acked if merged.get(k) != acked[k]]
        assert not missing, (
            f"ACKED ROWS LOST after host failover: {len(missing)} of "
            f"{len(acked)} ({missing[:4]})")
        assert dk not in merged, (
            "un-acked mid-kill row visible fleet-wide after failover")
        covered = sorted(set().union(
            *(set(f["owned"]) for f in finals.values())))
        assert covered == list(range(nregions)), (
            f"survivors cover regions {covered}, want 0..{nregions - 1}")
        # reap the dead host's slot lease + its shared 2PC lock claims
        # (what fleet.Fleet does on child death), then the segment must
        # drain clean and the blob manifests must be honest
        coord.reclaim_expired(0.0)
        blob = LocalDirBlobStore(os.path.join(root, "blob"))
        inv = verify_region_invariants(coord, blob)
        assert inv["ok"], f"REGION INVARIANT VIOLATION: {inv}"
        drained = coord.verify_drained()
        assert drained["ok"], f"coordinator not drained: {drained}"
        # cold restart from the blob store ALONE: fresh segment, fresh
        # WAL dirs — must serve bit-equal data
        coord2 = Coordinator.create(os.path.join(root, "coord2"),
                                    nregions=nregions)
        try:
            coord2.claim_slot(0)
            cold = RegionStore(os.path.join(root, "cold"), coord2, 0,
                               blob=blob)
            cold.open_regions(restore=True)
            ts = cold.tso.next_ts()
            cold_pairs = {k.hex(): v.hex()
                          for k, v in cold.scan(b"", b"", ts)}
            cold.close(replicate=False)
        finally:
            with contextlib.suppress(Exception):
                coord2.unlink()
        assert cold_pairs == merged, (
            f"COLD RESTORE DIVERGENCE: {len(cold_pairs)} rows from "
            f"blobs vs {len(merged)} served by the survivors")
        out.update({"failover_s": round(failover_s, 3),
                    "acked": len(acked), "recovered": len(acked),
                    "survivor_rows": len(merged),
                    "cold_restore_rows": len(cold_pairs),
                    "unacked_gone": True, "cold_restore_ok": True})
        emit(out)
        _phase(emit, "failover", t_fo)
        return out
    finally:
        import signal as _sig
        for p in procs.values():
            if p.poll() is None:
                with contextlib.suppress(OSError):
                    os.killpg(p.pid, _sig.SIGKILL)
        srv.stop()
        with contextlib.suppress(Exception):
            coord.unlink()
        with contextlib.suppress(OSError):
            shutil.rmtree(root)


# -- fleet mode (--procs N): the cross-process serving fabric ----------------
#
# Where run_serve drives N THREADS against one Domain, run_fleet drives
# N PROCESSES (tidb_tpu/fabric): a parent-supervised worker fleet behind
# one SO_REUSEPORT port, coordinated through the shared-memory segment
# (fleet-wide WFQ + per-tenant caps + fragment dedup), with the
# separated compile server owning the XLA compiles.  The parent is a
# pure wire CLIENT — every measured operation crosses the real MySQL
# protocol, and per-process latency attribution comes from the
# fleet-unique conn-id slot prefix, no side channel.
#
# Phases (each emits JSON lines; --smoke pins all three as regressions):
#   mix        shared-port mixed OLAP/OLTP load, per-process AND
#              fleet-aggregate p50/p99/qps
#   wfq        the CROSS-PROCESS starved-tenant regression: a heavy
#              tenant floods worker A (+ one pinned heavy client on B,
#              so the fleet-wide cap actually crosses processes) while a
#              light tenant runs on worker B — light p99 must stay
#              below heavy p50, and the segment's peak_running for the
#              heavy tenant must never exceed the fleet cap
#   dedup      barrier-synchronized identical OLAP fragments on TWO
#              different workers — the fleet fragment-dedup counter
#              must move (one device call served both)
#   cache      a pure repeat loop of one Q1-shape fragment serves from
#              the version-stamped result cache with ZERO admissions;
#              a committed INSERT invalidates the page and the next
#              read delta-folds, bit-equal to a from-scratch compute
#   kill       (--chaos) the seeded FLEET_FAULTS catalog SIGKILLs one
#              worker mid-query: clean classified client error, parent
#              respawn within the backoff budget, segment lease
#              reclaimed, survivors serving, zero leaked leases/tickets
#              at drain

#: queries for the fleet phases (bench.QUERIES keys)
FLEET_OLAP = ("q1", "q3")

#: the WFQ phase's heavy corpus: q1-shaped scans with PER-CLIENT filter
#: constants.  Distinct constants give each client a distinct compiled
#: pipeline identity, so the fabric's fragment dedup cannot collapse the
#: flood into one device call — the phase must measure device-TIME
#: fairness, and a flood the dedup serves from one page is (correctly!)
#: not a flood.  The dedicated dedup phase uses identical queries on
#: purpose; this one must not.
FLEET_WFQ_DATES = ("1998-09-02", "1998-06-02", "1998-03-02",
                   "1997-12-02", "1997-09-02", "1997-06-02")


def _wfq_heavy_q(i: int) -> str:
    return bench.QUERIES["q1"].replace(
        "'1998-09-02'", f"'{FLEET_WFQ_DATES[i % len(FLEET_WFQ_DATES)]}'")
#: respawn must land within this budget (fleet backoff base 0.2s,
#: worker boot ~a second — generous for a loaded CI machine)
RESPAWN_BUDGET_S = 30.0


def _fabric_seed(domain, seeded: bool = False):
    """Worker-side data init (TIDB_TPU_FABRIC_INIT hook): TPC-H at
    BENCH_FABRIC_SF + the transfer ledger.  Deterministic (bench.gen_all
    is fixed-seeded), so every worker holds IDENTICAL data — the
    property the content-hashed fragment dedup keys rely on.  Under the
    durable shared store the KV half (schema, ledger, stats) replicates
    through the log and only the FIRST worker writes it (`seeded` is
    True for the rest); the bulk-installed columnar caches are
    process-local and rebuild in every worker (gen_all detects the
    replayed schema and skips its DDL/KV writes)."""
    from tidb_tpu.testkit import TestKit
    sf = float(os.environ.get("BENCH_FABRIC_SF", "0.002"))
    tk = TestKit(domain)
    bench.gen_all(tk, sf)
    if not seeded:
        tk.must_exec("use test")
        tk.must_exec("create table ledger (acct int primary key, bal int)")
        tk.must_exec("insert into ledger values " + ",".join(
            f"({i}, {SEED_BAL})" for i in range(1, N_ACCTS + 1)))


def _fleet_conn(port, db="tpch", group=None, engine=None):
    from tidb_tpu.fabric.client import FleetClient
    c = FleetClient(port)
    c.must_exec(f"use {db}")
    if group:
        c.must_exec(f"set tidb_resource_group = '{group}'")
    if engine:
        c.must_exec(f"set tidb_executor_engine = '{engine}'")
    return c


def run_fleet(procs: int = 4, n_threads: int = 8, n_ops: int = 6,
              sf: float = 0.002, seed: int = 0, chaos: bool = False,
              emit=_emit) -> dict:
    """Drive the fleet serving workload; returns the summary dict.
    Raises AssertionError on any invariant violation (tests call this
    in-process; the CLI exits 1)."""
    from tests.chaos_harness import FLEET_FAULTS
    from tidb_tpu.fabric.fleet import Fleet

    assert procs >= 2, "fleet mode needs at least 2 workers"
    assert not chaos or procs >= 3, (
        "fleet chaos needs >= 3 workers: the WFQ/dedup phases require "
        "two DISTINCT surviving processes")
    rng = random.Random(seed)
    doomed = rng.randrange(procs) if chaos else -1
    slot_env = {}
    if chaos:
        action = rng.choice(FLEET_FAULTS["fabric-kill-worker"])
        slot_env[doomed] = {
            "TIDB_TPU_FABRIC_FAILPOINTS": f"fabric-kill-worker={action}"}
    fleet = Fleet(
        procs, init="bench_serve:_fabric_seed",
        sysvars={"tidb_device_tenant_running_cap": "1"},
        # N workers + a compile server cannot share one chip (see the
        # module docstring): the fleet bench runs on XLA:CPU
        env_extra={"BENCH_FABRIC_SF": str(sf), "JAX_PLATFORMS": "cpu"},
        slot_env=slot_env,
        # workers coordinate over TCP: every segment op becomes a
        # traced hop into the parent, the topology the trace phase's
        # >=3-process stitching assertion rides on
        net_coord=True)
    t_start = time.monotonic()
    fleet.start(timeout_s=300.0)
    emit({"metric": "fleet_up", "procs": procs, "port": fleet.port,
          "boot_s": round(time.monotonic() - t_start, 2), "sf": sf,
          "seed": seed, "chaos": chaos,
          "compile_server": bool(fleet.compile_server_addr)})
    try:
        return _run_fleet_phases(fleet, procs, n_threads, n_ops, seed,
                                 chaos, doomed, emit)
    finally:
        drained = fleet.shutdown()
        emit({"metric": "fleet_drained", **(drained or {"ok": False})})
        for s in fleet.slots:
            if s.summary is not None:
                emit(s.summary)
        assert drained and drained["ok"], (
            f"FLEET DRAIN LEAK (leases/running/dedup): {drained}")


def _run_fleet_phases(fleet, procs, n_threads, n_ops, seed, chaos,
                      doomed, emit) -> dict:
    from tidb_tpu.fabric.client import FleetClient, WireError

    survivors = [s for s in range(procs) if s != doomed]
    golden_slot = survivors[0]
    # the ORIGINAL pids: the kill-chaos respawn check must compare
    # against the first incarnation even when the doomed worker dies
    # early (a shared-port mix client may trip its failpoint first)
    first_pids = {s: fleet.worker_pid(s) for s in range(procs)}

    # goldens over the wire (host engine) from ONE worker: the seeding
    # is deterministic, so one worker's host answer is the fleet's
    gc = _fleet_conn(fleet.direct_port(golden_slot), engine="host")
    goldens = {q: gc.must_query(bench.QUERIES[q])[1] for q in FLEET_OLAP}
    gc.close()

    mu = threading.Lock()
    lat = {}          # (phase, group, slot) -> [ms]
    counts = {"ok": 0, "clean_errors": 0, "writes_ok": 0,
              "writes_failed": 0, "wire_drops": 0}
    violations: list = []

    def record(phase, group, slot, ms):
        with mu:
            lat.setdefault((phase, group, slot), []).append(ms)

    def bump(key, n=1):
        with mu:
            counts[key] += n

    def violate(what):
        with mu:
            violations.append(what)

    # -- phase: mixed load over the shared port ------------------------------

    def mix_worker(tid):
        wrng = random.Random((seed << 8) ^ tid)
        olap = tid % 2 == 0
        try:
            c = _fleet_conn(fleet.port,
                            db="tpch" if olap else "test",
                            group="olap" if olap else "oltp",
                            engine="tpu" if olap else None)
        except WireError:
            # with chaos a shared-port connection may land on the doomed
            # worker and trip its kill failpoint during setup — a CLEAN
            # classified drop; without chaos it is a finding
            if chaos:
                bump("wire_drops")
            else:
                violate(f"thread {tid}: wire failure without chaos")
            return
        slot = c.slot
        try:
            for _op in range(n_ops):
                t0 = time.monotonic()
                try:
                    if olap:
                        q = FLEET_OLAP[wrng.randrange(len(FLEET_OLAP))]
                        rows = c.must_query(bench.QUERIES[q])[1]
                        if rows != goldens[q]:
                            violate(f"WRONG RESULT {q} on slot {slot}")
                    elif wrng.random() < 0.5:
                        # STRICT single read: ts acquisition waits on
                        # the fleet committed frontier (fresh_read_ts),
                        # so the snapshot covers every acked transfer —
                        # no re-read deflake, any mismatch is a real
                        # atomicity/consistency break
                        total = c.must_query(
                            "select sum(bal) from ledger")[1][0][0]
                        if str(total) != str(LEDGER_TOTAL):
                            violate(f"ATOMICITY: ledger {total} on "
                                    f"slot {slot}")
                    else:
                        a, b = sorted(wrng.sample(
                            range(1, N_ACCTS + 1), 2))
                        amt = wrng.randrange(1, 40)
                        c.must_exec("begin")
                        c.must_exec(f"update ledger set bal = bal - "
                                    f"{amt} where acct = {a}")
                        c.must_exec(f"update ledger set bal = bal + "
                                    f"{amt} where acct = {b}")
                        c.must_exec("commit")
                        bump("writes_ok")
                except WireError as e:
                    # a dropped connection is CLEAN only when chaos is
                    # killing workers; otherwise it is a finding
                    if chaos:
                        bump("wire_drops")
                        return
                    violate(f"wire failure without chaos: {e}")
                    return
                record("mix", "olap" if olap else "oltp", slot,
                       (time.monotonic() - t0) * 1000.0)
                bump("ok")
        finally:
            c.close()

    t_mix = time.monotonic()
    threads = [threading.Thread(target=mix_worker, args=(t,),
                                daemon=True) for t in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(600.0)
    assert not any(t.is_alive() for t in threads), "STUCK mix clients"
    mix_wall = time.monotonic() - t_mix
    # per-phase line: wall clock + the golden worker's span-ring stats,
    # so a bench regression is attributable to its phase without rerun
    ring_port = fleet.direct_port(golden_slot)
    _phase(emit, "fleet_mix", t_mix, _fleet_ring(ring_port))

    # -- phase: cross-process starved-tenant WFQ regression ------------------
    t_wfq = time.monotonic()
    slot_a, slot_b = survivors[0], survivors[1 % len(survivors)]
    wfq_lat = {"heavy": [], "light": []}
    wfq_mu = threading.Lock()
    n_flood = 5
    wfq_start = threading.Barrier(n_flood + 2)
    wfq_errs = []

    def wfq_client(group, port, query, n):
        try:
            c = _fleet_conn(port, group=group, engine="tpu")
            # the phase measures DEVICE-TIME fairness: the versioned
            # result cache would (correctly!) serve the repeats without
            # dispatching, and an un-dispatched flood is not a flood —
            # same reasoning as the per-client filter constants vs dedup
            c.must_exec("set tidb_result_cache = 'OFF'")
            c.must_query(query)  # absorb cold compile outside the clock
            wfq_start.wait(timeout=300)
            for _ in range(n):
                t0 = time.monotonic()
                c.must_query(query)
                with wfq_mu:
                    wfq_lat[group].append(time.monotonic() - t0)
            c.close()
        except Exception as e:  # noqa: BLE001
            wfq_errs.append(e)

    light_q = ("select r_regionkey, count(*) from region "
               "group by r_regionkey order by r_regionkey")
    wfq_threads = (
        # the flood on process A (distinct per-client heavy variants —
        # see FLEET_WFQ_DATES: dedup must not collapse the flood)...
        [threading.Thread(target=wfq_client, daemon=True,
                          args=("heavy", fleet.direct_port(slot_a),
                                _wfq_heavy_q(i), 5)) for i in range(n_flood)]
        # ...plus ONE heavy client on process B: the fleet-wide cap=1
        # must serialize it behind A's flood THROUGH THE SEGMENT —
        # without cross-process coordination B would run it in parallel
        + [threading.Thread(target=wfq_client, daemon=True,
                            args=("heavy", fleet.direct_port(slot_b),
                                  _wfq_heavy_q(n_flood), 3))]
        # the light tenant on process B must not starve
        + [threading.Thread(target=wfq_client, daemon=True,
                            args=("light", fleet.direct_port(slot_b),
                                  light_q, 8))])
    # barrier is sized for heavy+light = 6 clients
    for t in wfq_threads:
        t.start()
    for t in wfq_threads:
        t.join(600.0)
    assert not wfq_errs, f"WFQ phase errors: {wfq_errs}"
    heavy = sorted(wfq_lat["heavy"])
    light = sorted(wfq_lat["light"])
    p99_light = light[-1]
    p50_heavy = heavy[len(heavy) // 2]
    peak_heavy = fleet.coord.peak_running("heavy")
    emit({"metric": "fleet_wfq", "p99_light_s": round(p99_light, 4),
          "p50_heavy_s": round(p50_heavy, 4),
          "peak_running_heavy": peak_heavy,
          "slot_heavy": slot_a, "slot_light": slot_b})
    _phase(emit, "fleet_wfq", t_wfq, _fleet_ring(ring_port))

    # -- phase: fleet fragment dedup -----------------------------------------
    t_ded = time.monotonic()
    ded_start = threading.Barrier(2)
    ded_errs = []

    def dedup_client(port):
        try:
            c = _fleet_conn(port, group="olap", engine="tpu")
            # cache off: this phase pins IN-FLIGHT coalescing (claim /
            # wait / page-serve between two racing workers), which a
            # versioned cache hit would short-circuit before the claim
            c.must_exec("set tidb_result_cache = 'OFF'")
            c.must_query(bench.QUERIES["q1"])  # warm the compiled path
            for _ in range(4):
                ded_start.wait(timeout=300)
                rows = c.must_query(bench.QUERIES["q1"])[1]
                if rows != goldens["q1"]:
                    ded_errs.append("dedup WRONG RESULT")
            c.close()
        except Exception as e:  # noqa: BLE001
            ded_errs.append(e)

    dt = [threading.Thread(target=dedup_client, daemon=True,
                           args=(fleet.direct_port(slot_a),)),
          threading.Thread(target=dedup_client, daemon=True,
                           args=(fleet.direct_port(slot_b),))]
    for t in dt:
        t.start()
    for t in dt:
        t.join(600.0)
    assert not ded_errs, f"dedup phase errors: {ded_errs}"
    ctrs = fleet.coord.counters()
    emit({"metric": "fleet_dedup",
          **{k: v for k, v in ctrs.items() if k.startswith("fabric_")}})
    _phase(emit, "fleet_dedup", t_ded, _fleet_ring(ring_port))

    # -- phase: version-stamped fragment result cache ------------------------
    # a pure repeat loop of one Q1-shape fragment must serve from the
    # versioned page with ZERO admissions (no WFQ ticket, no HBM charge,
    # no device dispatch — the probe runs before the scheduler);
    # committed INSERTs then invalidate the page and the final read
    # folds only the WAL delta through the cached partials, bit-equal
    # to a from-scratch compute.  The INSERTs run on the SAME worker that
    # serves the cached reads: the version advance still travels through
    # the fleet coordinator (the invalidation under test), while the
    # worker's columnar delta-tree stays maintained (bulk-installed TPC-H
    # columns are process-local; a remote worker rebuilding them from KV
    # is a separate, pre-existing limitation).
    t_cache = time.monotonic()
    cq = bench.QUERIES["q1"]
    cc = _fleet_conn(fleet.direct_port(slot_a), group="olap",
                     engine="tpu")
    cc.must_query(cq)  # lead/publish (or already paged by the dedup phase)
    base = fleet.coord.counters()
    n_repeat = 6
    for _ in range(n_repeat):
        if cc.must_query(cq)[1] != goldens["q1"]:
            violate("CACHE WRONG RESULT: cached q1 != golden")
    mid = fleet.coord.counters()
    rep_hits = (mid.get("fabric_cache_hits", 0)
                - base.get("fabric_cache_hits", 0))
    rep_adm = (mid.get("fabric_admissions", 0)
               - base.get("fabric_admissions", 0))
    # two committed INSERTs inside q1's shipdate window.  The FIRST
    # gives the (bulk-installed, so far version-0) table its first real
    # fleet version: the cached page invalidates, and the fold window
    # (0, T1] is unprovable by design — a full recompute republishes at
    # T1.  The SECOND advances T1 -> T2 with a ring-provable pure-insert
    # delta: the next read must DELTA-FOLD instead of recomputing.
    wc = _fleet_conn(fleet.direct_port(slot_a), db="tpch")
    wc.must_exec("insert into lineitem values "
                 "(999999001, 1, 1, 7.00, 1000.00, 0.04, 0.02, "
                 "'N', 'O', '1997-01-01')")
    r1 = cc.must_query(cq)[1]  # invalidated -> recompute + republish
    if r1 == goldens["q1"]:
        violate("CACHE STALE SERVE: q1 unchanged after a committed "
                "INSERT into its shipdate window")
    wc.must_exec("insert into lineitem values "
                 "(999999002, 2, 2, 3.00, 500.00, 0.10, 0.01, "
                 "'R', 'F', '1996-06-15')")
    wc.close()
    folded = cc.must_query(cq)[1]  # delta-fold through the partials
    cc.close()
    if folded == r1:
        violate("CACHE STALE SERVE: q1 unchanged after the second "
                "committed INSERT")
    post = fleet.coord.counters()
    # the bit-equality oracle: same worker, cache OFF, from scratch
    oc = _fleet_conn(fleet.direct_port(slot_a), group="olap",
                     engine="tpu")
    oc.must_exec("set tidb_result_cache = 'OFF'")
    fresh = oc.must_query(cq)[1]
    oc.close()
    if folded != fresh:
        violate(f"CACHE DELTA-FOLD MISMATCH: folded q1 != from-scratch "
                f"(folded {folded} vs fresh {fresh})")
    cache_stats = {
        "repeat_n": n_repeat, "hits": rep_hits,
        "hit_rate": round(rep_hits / n_repeat, 3),
        "admissions_during_repeat": rep_adm,
        "invalidations": (post.get("fabric_cache_invalidations", 0)
                          - mid.get("fabric_cache_invalidations", 0)),
        "delta_folds": (post.get("fabric_cache_delta_folds", 0)
                        - mid.get("fabric_cache_delta_folds", 0)),
        "stale_reads": post.get("fabric_cache_stale_reads", 0),
    }
    emit({"metric": "serve_cache", **cache_stats})
    _phase(emit, "fleet_cache", t_cache, _fleet_ring(ring_port))

    # -- phase: process-kill chaos -------------------------------------------
    respawn_s = None
    if chaos:
        t0 = time.monotonic()
        if fleet.respawns == 0:
            # nothing tripped the failpoint yet: aim a query at the
            # doomed worker's direct port — it dies MID-QUERY and the
            # client must see a clean classified drop, never a hang
            try:
                dc = FleetClient(fleet.direct_port(doomed))
                dc.must_exec("use tpch")
                dc.must_query("select count(*) from region")  # boom
                violations.append("fabric-kill-worker armed but the "
                                  "doomed worker survived its query")
            except WireError:
                counts["wire_drops"] += 1  # the CLEAN classified outcome
        assert fleet.wait_respawn(doomed, first_pids[doomed],
                                  RESPAWN_BUDGET_S), (
            f"worker {doomed} not respawned within {RESPAWN_BUDGET_S}s")
        respawn_s = time.monotonic() - t0
        # survivors kept serving while the corpse was reclaimed
        sc = _fleet_conn(fleet.direct_port(slot_a))
        assert sc.must_query("select count(*) from region")[1]
        sc.close()
        ctrs = fleet.coord.counters()
        assert ctrs["fabric_lease_reclaims"] >= 1, ctrs
        assert fleet.respawns >= 1
        emit({"metric": "fleet_kill_chaos", "slot": doomed,
              "respawn_s": round(respawn_s, 2),
              "lease_reclaims": ctrs["fabric_lease_reclaims"]})
        _phase(emit, "fleet_kill", t0, _fleet_ring(ring_port))

    # -- phase: distributed trace stitching + fleet observability ------------
    # runs LAST so every worker is live again (the kill phase ends with
    # the doomed worker respawned).  Three regressions in one pass:
    #   * one statement's stitched trace must carry spans from >= 3
    #     distinct PROCESSES (worker + compile server + the parent's
    #     network coordinator);
    #   * cluster_statements_summary must return ok rows from EVERY
    #     live worker (the DIAG fan-out path);
    #   * the shared fragment-perf store must hold strictly more
    #     samples than any single worker contributed, and EXPLAIN
    #     ANALYZE must render the fleet perf line from it.
    t_trace = time.monotonic()
    for s in range(procs):
        # every worker needs statement history before the cluster
        # summary fan-out is asserted on row coverage
        pc = _fleet_conn(fleet.direct_port(s))
        pc.must_query("select count(*) from region")
        pc.close()
    tc = _fleet_conn(fleet.direct_port(slot_a), group="olap",
                     engine="tpu")
    tc.must_exec("set tidb_result_cache = 'OFF'")
    # a filter constant no run has ever compiled: the persistent
    # signature index survives across bench invocations, and a warm
    # pipeline would skip the compile-server hop under test
    uniq = time.time_ns() % 10**9
    tq = bench.QUERIES["q1"].replace(
        "'1998-09-02'", f"'1998-09-02' and l_tax > -{uniq}")
    tree = json.loads(
        tc.must_query("trace format='json' " + tq)[1][0][0])

    def _trace_pids(node, acc):
        # every span subtree (local or hop-grafted) carries its
        # process's pid in the gid prefix
        if isinstance(node, dict):
            gid = node.get("gid")
            if isinstance(gid, str) and "-" in gid:
                acc.add(int(gid.split("-")[0], 16))
            for v in node.values():
                _trace_pids(v, acc)
        elif isinstance(node, list):
            for v in node:
                _trace_pids(v, acc)
        return acc

    trace_pids = _trace_pids(tree, set())
    scols, srows = tc.must_query(
        "select * from information_schema.cluster_statements_summary")
    i_inst, i_err = scols.index("instance"), scols.index("error")
    sum_ok = {r[i_inst] for r in srows if not r[i_err]}

    def _perf_totals(port):
        c = FleetClient(port)
        try:
            c.must_exec("use tpch")
            pn, pr = c.must_query(
                "select * from information_schema.tidb_fragment_perf")
        finally:
            c.close()
        ic, il = pn.index("count"), pn.index("local_count")
        return (sum(int(r[ic]) for r in pr),
                sum(int(r[il]) for r in pr))

    perf_fleet_a, perf_local_a = _perf_totals(fleet.direct_port(slot_a))
    perf_fleet_b, perf_local_b = _perf_totals(fleet.direct_port(slot_b))
    _ecols, erows = tc.must_query("explain analyze " + tq)
    ea_text = "\n".join(" ".join(str(cell) for cell in row)
                        for row in erows)
    tc.close()
    emit({"metric": "fleet_trace", "procs_in_trace": len(trace_pids),
          "summary_instances_ok": len(sum_ok),
          "summary_rows": len(srows),
          "perf_fleet_samples": max(perf_fleet_a, perf_fleet_b),
          "perf_local_samples": [perf_local_a, perf_local_b],
          "explain_fleet_line": "fleet:" in ea_text})
    _phase(emit, "fleet_trace", t_trace, _fleet_ring(ring_port))

    # -- report --------------------------------------------------------------
    assert not violations, "\n".join(str(v) for v in violations)
    by_slot = {}
    fleet_all = {}
    for (phase, group, slot), vals in lat.items():
        if phase != "mix":
            continue
        by_slot.setdefault((group, slot), []).extend(vals)
        fleet_all.setdefault(group, []).extend(vals)
    for (group, slot), vals in sorted(by_slot.items()):
        vals.sort()
        emit({"metric": "fleet_latency_ms", "group": group,
              "slot": slot, "p50": _pctl(vals, 0.50),
              "p99": _pctl(vals, 0.99), "n": len(vals)})
    summary = {"procs": procs, "threads": n_threads, "seed": seed,
               "chaos": chaos, "violations": 0, **counts,
               "p99_light_s": p99_light, "p50_heavy_s": p50_heavy,
               "peak_running_heavy": peak_heavy,
               "dedup_hits": ctrs["fabric_dedup_hits"],
               "cache_hits": rep_hits,
               "cache_hit_rate": cache_stats["hit_rate"],
               "cache_delta_folds": cache_stats["delta_folds"],
               "respawn_s": respawn_s}
    for group, vals in sorted(fleet_all.items()):
        vals.sort()
        emit({"metric": "fleet_latency_ms", "group": group,
              "slot": "all", "p50": _pctl(vals, 0.50),
              "p99": _pctl(vals, 0.99), "n": len(vals)})
        summary[f"p50_{group}"] = _pctl(vals, 0.50)
        summary[f"p99_{group}"] = _pctl(vals, 0.99)
    qps = round(counts["ok"] / mix_wall, 2) if mix_wall > 0 else 0.0
    summary["qps"] = qps
    emit({"metric": "fleet_qps", "value": qps, "ok": counts["ok"],
          "wall_s": round(mix_wall, 2),
          "clean_errors": counts["clean_errors"],
          "wire_drops": counts["wire_drops"],
          "writes_ok": counts["writes_ok"]})

    # the acceptance regressions, asserted LAST so the report above is
    # emitted even when one trips
    assert p99_light < max(p50_heavy, 0.05), (
        f"CROSS-PROCESS WFQ REGRESSION: light p99 {p99_light:.3f}s on "
        f"slot {slot_b} >= heavy p50 {p50_heavy:.3f}s flooding slot "
        f"{slot_a} — light tenant starved across the process boundary")
    assert peak_heavy <= 1, (
        f"FLEET CAP VIOLATION: heavy tenant peaked at {peak_heavy} "
        "concurrent fragments fleet-wide (cap 1)")
    assert ctrs["fabric_dedup_hits"] > 0, (
        "FLEET DEDUP INERT: identical concurrent OLAP fragments on two "
        f"workers produced zero dedup hits ({ctrs})")
    assert rep_hits >= n_repeat and rep_adm == 0, (
        f"CACHE BYPASS REGRESSION: {rep_hits}/{n_repeat} versioned hits "
        f"with {rep_adm} admissions across a pure repeat loop — a hit "
        "must serve with no WFQ ticket and no device dispatch")
    assert cache_stats["invalidations"] >= 1, (
        "CACHE INVALIDATION INERT: the post-INSERT read claimed no "
        f"invalidated entry ({cache_stats})")
    assert cache_stats["delta_folds"] >= 1, (
        "DELTA FOLD INERT: the invalidated read recomputed from scratch "
        f"instead of folding the WAL delta ({cache_stats})")
    assert len(trace_pids) >= 3, (
        f"TRACE STITCHING REGRESSION: one statement's stitched trace "
        f"crossed only {len(trace_pids)} process(es) ({sorted(trace_pids)})"
        " — want worker + compile server + coordinator")
    assert len(sum_ok) == procs, (
        f"CLUSTER SUMMARY GAP: ok rows from {len(sum_ok)}/{procs} live "
        f"workers (instances {sorted(sum_ok)})")
    assert (perf_fleet_a > max(perf_local_a, perf_local_b)
            and perf_fleet_b > max(perf_local_a, perf_local_b)), (
        f"FLEET PERF STORE INERT: fleet sample totals "
        f"{perf_fleet_a}/{perf_fleet_b} not strictly above every single "
        f"worker's local share ({perf_local_a}/{perf_local_b})")
    assert "fleet:" in ea_text, (
        "EXPLAIN ANALYZE missing the fleet perf line (fabric/perf.py "
        "lookup produced nothing for a just-dispatched fragment)")
    return summary


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--threads", type=int, default=8)
    ap.add_argument("--ops", type=int, default=20,
                    help="operations per client thread")
    ap.add_argument("--sf", type=float, default=0.01)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--procs", type=int, default=1,
                    help="worker PROCESSES (>1 = fleet mode over the "
                         "serving fabric; tidb_tpu/fabric)")
    ap.add_argument("--hosts", type=int, default=1,
                    help="simulated HOSTS (>1 = region-failover mode: "
                         "SIGKILL one whole host mid-commit, surviving "
                         "hosts fail its regions over from the blob "
                         "store; tidb_tpu/fabric/region.py)")
    ap.add_argument("--chaos", action="store_true",
                    help="run under the seeded chaos catalog "
                         "(threads: hang + OOM + admission failpoints; "
                         "fleet: + process-kill)")
    ap.add_argument("--smoke", action="store_true",
                    help="small fixed-seed run for CI (tiny SF, chaos "
                         "on; with --procs N the fleet smoke preset)")
    args = ap.parse_args(argv)
    if args.smoke:
        args.threads, args.ops, args.sf, args.chaos = 8, 4, 0.002, True
        if args.procs > 1:
            args.ops = 3
    try:
        if args.hosts > 1:
            run_failover(hosts=args.hosts, seed=args.seed)
        elif args.procs > 1:
            run_fleet(procs=args.procs, n_threads=args.threads,
                      n_ops=args.ops, sf=args.sf, seed=args.seed,
                      chaos=args.chaos)
        else:
            run_serve(n_threads=args.threads, n_ops=args.ops, sf=args.sf,
                      seed=args.seed, chaos=args.chaos)
        if args.smoke and args.hosts <= 1:
            # durability phase (ISSUE 15): WAL-off/never/commit DML qps
            # + the SIGKILL-mid-commit recover round trip (the --hosts
            # mode is its own durability story: replicate-on-ack +
            # region failover + cold blob restore)
            run_durability()
    except AssertionError as e:
        _emit({"metric": "serve_violation", "error": str(e)[:2000]})
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
