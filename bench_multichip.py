"""Multi-chip MPP bench: the carry-over acceptance record (MULTICHIP_rNN).

A CPU bench by construction: it pins JAX_PLATFORMS=cpu and forces an
8-device VIRTUAL mesh (the same --xla_force_host_platform_device_count
harness the dryrun and tests/conftest.py use), so its counts are exact
and its timings are host-platform numbers, never device metrics.  The
mesh path's check on real chips is `chip_smoke.py --engine tpu-mpp`.
Measures:

  1. Q3-class MPP join+agg WARM ROUNDS: per-round XLA trace/compile
     counts and wall time through the mesh-keyed compiled-fragment
     cache. The zero-recompile acceptance: round 2 and the
     post-within-bucket-INSERT round perform ZERO new traces, with
     bit-exact host parity. (r05 had no MPP-layer cache at all — every
     round re-traced the full SPMD pipeline; the warm trajectory here
     must be strictly below that.)
  2. RADIX-EXCHANGE hot-key convergence: a dominant probe key overflows
     the initial per-sub-bucket capacity and converges via the exact
     next_pow2(need) jump — retries counted, zero dropped rows (parity).
  3. THREADED CHAOS + MESH FENCE: the tests/chaos_harness.py threaded
     catalog (hang/OOM/exchange faults over mixed engines incl.
     tpu-mpp) with an explicit supervisor.fence() injected mid-schedule;
     afterwards residency.verify_ledger() must hold (placement-cache
     bytes accounted, zero drift) and a post-fence MPP query must be
     exact — a fenced mesh never serves stale shards.

Watchdog: a global SIGALRM (BENCH_TIMEOUT_S, default 900) guarantees the
JSON record is written even on a hang — phases already completed keep
their numbers, the record carries ok=false. Emits one JSON line per
phase on stdout (bench.py convention) and writes MULTICHIP_r06.json
(override with MULTICHIP_OUT).
"""

import json
import os
import signal
import sys
import threading
import time

N_DEVICES = 8
OUT_PATH = os.environ.get("MULTICHIP_OUT", "MULTICHIP_r06.json")
TIMEOUT_S = int(os.environ.get("BENCH_TIMEOUT_S", "900"))

# the virtual mesh must exist BEFORE jax initializes a backend
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + f" --xla_force_host_platform_device_count={N_DEVICES}"
    ).strip()

import tidb_tpu  # noqa: F401,E402  (x64 + the persistent compile cache)
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

from tidb_tpu.testkit import TestKit  # noqa: E402

RECORD = {"n_devices": N_DEVICES, "rc": 0, "ok": False, "skipped": False,
          "phases": {}}


def _emit(obj):
    print(json.dumps(obj), flush=True)


def _last_trace_text(cap=4000) -> str:
    """Most recent finished span trace, rendered (the failed phase's
    post-mortem timeline; see bench.py); "" when tracing never ran."""
    from tidb_tpu.session import tracing
    return tracing.last_trace_text(cap=cap)


def _compile_gauges() -> dict:
    """Compile-service gauges for the record (executor/compile_service):
    pending fragments / persistent-index hits / prewarm counts — a round
    whose first execution was host-served says so."""
    from tidb_tpu.executor import compile_service
    return compile_service.report_gauges()


def _write_record():
    with open(OUT_PATH, "w") as f:
        json.dump(RECORD, f, indent=1)
        f.write("\n")


def _watchdog(signum, frame):
    RECORD["rc"] = 1
    RECORD["error"] = f"global watchdog fired after {TIMEOUT_S}s"
    RECORD["trace"] = _last_trace_text()
    _emit({"metric": "multichip_watchdog", "value": 0, **RECORD})
    _write_record()
    os._exit(1)


def _pipe_stats():
    from tidb_tpu.executor.device_exec import pipe_cache_stats
    return pipe_cache_stats()


def _mk_q3_tk(n_cust=64, n_ord=256, n_line=1000):
    # n_line=1000: 125 rows/shard → bucket 128 with headroom, so the
    # phase-1 within-bucket INSERT stays inside (1024 would sit exactly
    # ON the boundary and the delta would legitimately recompile)
    tk = TestKit()
    tk.must_exec("create database mc")
    tk.must_exec("use mc")
    tk.must_exec("set tidb_mpp_devices = 8")
    if os.environ.get("BENCH_TRACE", "") == "1":
        # opt-in (same comparability rule as bench.py): a failed phase's
        # error line then carries the query's span trace
        tk.must_exec("set tidb_trace_sampling_rate = 1")
    tk.must_exec("""create table customer (
        c_custkey bigint primary key, c_mktsegment varchar(10))""")
    tk.must_exec("""create table orders (
        o_orderkey bigint primary key, o_custkey bigint,
        o_orderdate date, o_shippriority bigint)""")
    tk.must_exec("""create table lineitem (
        l_orderkey bigint, l_extendedprice decimal(15,2),
        l_discount decimal(15,2), l_shipdate date)""")
    segs = ["BUILDING", "MACHINERY", "AUTOMOBILE"]
    tk.must_exec("insert into customer values " + ",".join(
        f"({i}, '{segs[i % 3]}')" for i in range(1, n_cust + 1)))
    tk.must_exec("insert into orders values " + ",".join(
        f"({i}, {(i % n_cust) + 1}, '199{4 + i % 3}-0{1 + i % 9}-15', 0)"
        for i in range(1, n_ord + 1)))
    tk.must_exec("insert into lineitem values " + ",".join(
        f"({(i % n_ord) + 1}, {100 + i}.25, 0.0{i % 8},"
        f" '199{4 + i % 4}-0{1 + i % 9}-02')" for i in range(n_line)))
    return tk


Q3 = """
    select l_orderkey, sum(l_extendedprice * (1 - l_discount)) as rev,
           o_orderdate, o_shippriority
    from customer, orders, lineitem
    where c_mktsegment = 'BUILDING' and c_custkey = o_custkey
      and l_orderkey = o_orderkey and o_orderdate < '1996-03-15'
    group by l_orderkey, o_orderdate, o_shippriority
    order by rev desc, o_orderdate limit 10"""


def _round(tk, q, engine="tpu-mpp"):
    tk.must_exec(f"set tidb_executor_engine = '{engine}'")
    s0 = _pipe_stats()
    t0 = time.perf_counter()
    rows = tk.must_query(q).rows
    wall = time.perf_counter() - t0
    s1 = _pipe_stats()
    return rows, {"wall_s": round(wall, 4),
                  "traces": s1["traces"] - s0["traces"],
                  "compiles": s1["compiles"] - s0["compiles"],
                  # query-path (sync) vs compile-service background split
                  # (executor/compile_service.py): mesh rounds compile
                  # sync today, so bg stays 0 unless prewarm/async ran
                  "sync_compile_s": round(
                      s1["compile_s"] - s0["compile_s"], 4),
                  "compile_s": round(s1["compile_s"] - s0["compile_s"], 4),
                  "bg_compile_s": round(
                      s1["bg_compile_s"] - s0["bg_compile_s"], 4),
                  "pipe_misses": s1["misses"] - s0["misses"],
                  "pipe_hits": s1["hits"] - s0["hits"]}


def phase_warm_rounds():
    from tidb_tpu.executor import mpp_exec
    tk = _mk_q3_tk()
    host, _ = _round(tk, Q3, engine="host")
    frags0 = mpp_exec.MPP_STATS["fragments"]
    r1rows, r1 = _round(tk, Q3)
    r2rows, r2 = _round(tk, Q3)
    assert mpp_exec.MPP_STATS["fragments"] > frags0, "never reached mesh"
    assert r1rows == host and r2rows == host, "mpp/host divergence"
    # within-bucket INSERT: the zero-recompile acceptance round
    tk.must_exec("insert into lineitem values "
                 "(1, 999.25, 0.02, '1994-02-02'),"
                 "(2, 998.25, 0.03, '1995-03-02')")
    host2, _ = _round(tk, Q3, engine="host")
    r3rows, r3 = _round(tk, Q3)
    assert r3rows == host2, "post-INSERT mpp/host divergence"
    ok = (r2["traces"] == 0 and r2["pipe_misses"] == 0
          and r3["traces"] == 0 and r3["pipe_misses"] == 0)
    out = {
        "query": "q3_class_mpp_join_agg",
        "round1_cold": r1, "round2_warm": r2,
        "round3_post_insert_within_bucket": r3,
        "zero_recompile_ok": ok,
        "mpp_gauges": mpp_exec.report_gauges(),
        "compile_gauges": _compile_gauges(),
        # r05 ran the mesh path with EXACT shard shapes and no MPP-layer
        # pipeline cache: every round re-traced the SPMD program (warm
        # trace count == cold trace count). The carry-over's warm
        # trajectory must be strictly below that.
        "r05_trajectory": {"warm_traces_per_round": r1["traces"],
                           "note": "r05: exact shapes, no mesh cache — "
                                   "every round re-traced"},
    }
    assert ok, f"zero-recompile regression failed: {out}"
    assert r2["traces"] < max(r1["traces"], 1), "warm not below r05 line"
    return out


def phase_skew_exchange():
    from tidb_tpu.executor import mpp_exec
    tk = TestKit()
    tk.must_exec("create database skew")
    tk.must_exec("use skew")
    tk.must_exec("set tidb_mpp_devices = 8")
    tk.must_exec("create table dim (k bigint primary key, w bigint)")
    tk.must_exec("insert into dim values " + ",".join(
        f"({i}, {i})" for i in range(1, 65)))
    tk.must_exec("create table fact (a bigint primary key, k bigint, "
                 "v bigint)")
    tk.must_exec("insert into fact values " + ",".join(
        f"({i}, {7 if i <= 224 else (i % 64) + 1}, {i})"
        for i in range(1, 321)))
    tk.must_exec("set tidb_broadcast_join_threshold_count = 30")
    # dim's key is unique: only a build over the size threshold too is
    # shuffled, anything else takes the indexed broadcast path
    tk.must_exec("set tidb_broadcast_join_threshold_size = 1")
    q = ("select count(1), sum(fact.v + dim.w) from fact, dim "
         "where fact.k = dim.k")
    host, _ = _round(tk, q, engine="host")
    ovf0 = mpp_exec.MPP_STATS["exchange_overflow_retries"]
    sh0 = mpp_exec.MPP_STATS["shuffle_joins"]
    rows, r1 = _round(tk, q)
    assert rows == host, "skew round dropped rows (parity failed)"
    retries = mpp_exec.MPP_STATS["exchange_overflow_retries"] - ovf0
    assert mpp_exec.MPP_STATS["shuffle_joins"] > sh0, "no shuffle path"
    assert retries >= 1, "hot key never overflowed the initial capacity"
    rows2, r2 = _round(tk, q)  # learned caps: no rediscovery
    assert rows2 == host and r2["traces"] == 0
    return {"hot_key_rows": 224, "overflow_retries": retries,
            "dropped": 0, "cold": r1, "warm": r2}


def phase_chaos_fence(n_seeds=2):
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "tests"))
    import chaos_harness
    from tidb_tpu.executor import mpp_exec, supervisor
    from tidb_tpu.ops import residency

    fences = []

    def fence_injector(stop):
        # one explicit mesh fence mid-schedule, on top of whatever the
        # catalog's hang injections trigger
        time.sleep(0.5)
        if not stop.is_set():
            supervisor.fence("bench_multichip: injected mesh fence")
            fences.append(1)

    results = []
    for seed in range(n_seeds):
        stop = threading.Event()
        inj = threading.Thread(target=fence_injector, args=(stop,),
                               daemon=True)
        inj.start()
        try:
            stats = chaos_harness.run_threaded_seed(seed, n_threads=4,
                                                    n_ops=6)
        finally:
            stop.set()
            inj.join(timeout=5)
        results.append(stats)
    led = residency.verify_ledger()
    assert led["ok"], f"ledger drift after chaos+fence: {led}"
    # a fenced mesh must serve fresh shards, exactly
    tk = _mk_q3_tk(n_cust=16, n_ord=64, n_line=256)
    host, _ = _round(tk, Q3, engine="host")
    rows, _ = _round(tk, Q3)
    assert rows == host, "post-fence MPP divergence"
    return {"seeds": n_seeds, "fences_injected": sum(fences),
            "ledger": led, "post_fence_parity": True,
            "mpp_place_bytes": mpp_exec.place_cache_bytes(),
            "chaos": [{k: v for k, v in r.items()} for r in results]}


def main():
    signal.signal(signal.SIGALRM, _watchdog)
    signal.alarm(TIMEOUT_S)
    failures = 0
    for name, fn in (("warm_rounds", phase_warm_rounds),
                     ("skew_exchange", phase_skew_exchange),
                     ("chaos_fence", phase_chaos_fence)):
        t0 = time.perf_counter()
        try:
            res = fn()
            res["phase_s"] = round(time.perf_counter() - t0, 2)
            RECORD["phases"][name] = res
            _emit({"metric": f"multichip_{name}", "value": 1, **res})
        except Exception as e:  # noqa: BLE001 — record and continue
            failures += 1
            trace = _last_trace_text()
            RECORD["phases"][name] = {"error": f"{type(e).__name__}: {e}",
                                      "trace": trace}
            _emit({"metric": f"multichip_{name}", "value": 0,
                   "error": str(e), "trace": trace})
    RECORD["ok"] = failures == 0
    RECORD["rc"] = 0 if failures == 0 else 1
    _write_record()
    _emit({"metric": "multichip_record", "value": int(RECORD["ok"]),
           "out": OUT_PATH})
    return RECORD["rc"]


if __name__ == "__main__":
    sys.exit(main())
