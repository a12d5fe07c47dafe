"""chip_smoke.py — the quickest proof that tpu-htap still serves on the chip.

    python chip_smoke.py [--sf 1] [--engine tpu|tpu-mpp] [--allow-cpu]

One serving process owns the device: ``Fleet(1, compile_server=False)``
spawns ``python -m tidb_tpu.fabric.worker``, whose durable store (WAL,
``tidb_wal_fsync`` as shipped) is seeded with TPC-H at ``--sf`` by
``bench.gen_all`` through the seed hook below.  This process never
initialises a JAX backend: it starts the worker, speaks the MySQL wire
protocol to it, and reads its lines.  Over the wire it runs TPC-H
Q1/Q3/Q5/Q9/Q18 (Q3/Q5 under ``--engine tpu-mpp``) with the device
engine pinned, then the same queries on the host engine, and requires

* exact row equality between every device run and the host run;
* ``engine:<--engine>`` on every fused fragment of ``EXPLAIN ANALYZE``;
* at least one compile in the cold pass and none afterwards;
* from ``DIAG STATUS`` at the end: no breaker failure or degradation, no
  admission or compile-service degradation, no supervisor fence or hang,
  no respawn, ``hbm_bytes_cached > 0`` (and, on a mesh, ``mpp_fragments >
  0`` with every device holding bytes).

The worker refuses to load data unless its platform is ``tpu``;
``--allow-cpu`` (the tier-1 test) is the only way around that.  Exit
code 0 and a last stdout line ``{"ok": true, "device": {...}}`` mean
every phase passed; any failure exits non-zero and prints no such line.
The per-query lines are smoke observations for CHANGES.md, not
benchmark results.
"""

import argparse
import json
import os
import shutil
import statistics
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))

#: the queries each engine mode must serve (bench.QUERIES names)
QUERY_SETS = {"tpu": ("q1", "q3", "q5", "q9", "q18"),
              "tpu-mpp": ("q3", "q5")}
WARM_RUNS = 3
#: the whole run must end inside the driver's 1200 s
DEADLINE_S = 1140.0

#: the worker-side seed hook, as Fleet(init=...) names it
SEED_HOOK = "chip_smoke:seed"
_SF_ENV = "CHIP_SMOKE_SF"
_ALLOW_CPU_ENV = "CHIP_SMOKE_ALLOW_CPU"


def seed(domain, seeded: bool = False):
    """Worker-side seed hook (SEED_HOOK).  Runs
    after the worker took its device and before any data exists: on the
    wrong platform it raises, the worker exits before ready, and
    ``Fleet.start`` fails with this message."""
    import jax
    platform = jax.devices()[0].platform
    if platform != "tpu" and os.environ.get(_ALLOW_CPU_ENV) != "1":
        pinned = os.environ.get("JAX_PLATFORMS")
        raise RuntimeError(
            f"chip_smoke: the worker's JAX platform is {platform!r}, not "
            "'tpu'" + (f" (the environment exports JAX_PLATFORMS={pinned!r},"
                       " which keeps JAX off the chip)" if pinned else "")
            + "; refusing to load data. Pass --allow-cpu to run off-chip.")
    import bench
    from tidb_tpu.testkit import TestKit
    sf = float(os.environ[_SF_ENV])
    t0 = time.monotonic()
    rows = bench.gen_all(TestKit(domain), sf)
    print(json.dumps({"metric": "chip_smoke_seed", "sf": sf,
                      "lineitem_rows": rows, "seeded_before": seeded,
                      "seed_s": round(time.monotonic() - t0, 2)}),
          flush=True)


class _Failed(Exception):
    """One phase failed; the message says which and why."""


def _cache_entries(cache_dir: str) -> int:
    try:
        return sum(1 for f in os.listdir(cache_dir) if f.endswith("-cache"))
    except OSError:
        return 0


def _status(diag) -> dict:
    _cols, rows = diag.must_query("DIAG STATUS")
    return json.loads(rows[0][0])


def _engines(plan_rows) -> list:
    """Every ``engine:<name>`` annotation in an EXPLAIN ANALYZE result."""
    out = []
    for row in plan_rows:
        for part in (row[2] or "").split(", "):
            if part.startswith("engine:"):
                out.append(part[len("engine:"):])
    return out


def _timed(cli, sql):
    t0 = time.monotonic()
    _cols, rows = cli.must_query(sql)
    return time.monotonic() - t0, rows


def _run_query(cli, diag, qname, sql, engine):
    """Cold + settle + EXPLAIN ANALYZE + warm runs on `engine`, then the
    host reference; prints the query's line.  Raises _Failed on any
    broken requirement."""
    cli.must_exec(f"set tidb_executor_engine = '{engine}'")
    p0 = _status(diag)["device_pipelines"]
    cold_s, cold_rows = _timed(cli, sql)
    # the second run absorbs the learned-capacity shrink-to-fit
    # recompile (device_join _CAP_STORE); it belongs to the cold pass
    settle_s, settle_rows = _timed(cli, sql)
    p1 = _status(diag)["device_pipelines"]
    _cols, plan = cli.must_query("explain analyze " + sql)
    warm = [_timed(cli, sql) for _ in range(WARM_RUNS)]
    p2 = _status(diag)["device_pipelines"]
    cli.must_exec("set tidb_executor_engine = 'host'")
    host_s, host_rows = _timed(cli, sql)

    engines = _engines(plan)
    rec = {
        "query": qname, "engine": sorted(set(engines)),
        "cold_s": round(cold_s, 3), "settle_s": round(settle_s, 3),
        "warm_s": round(statistics.median(t for t, _r in warm), 4),
        "warm_runs": [round(t, 4) for t, _r in warm],
        "host_s": round(host_s, 3),
        "cold_compiles": p1["compiles"] - p0["compiles"],
        "cold_sync_compile_s": round(p1["compile_s"] - p0["compile_s"], 3),
        "warm_compiles": p2["compiles"] - p1["compiles"],
        "rows": len(host_rows),
        "parity": all(r == host_rows for r in
                      [cold_rows, settle_rows] + [r for _t, r in warm]),
    }
    print(json.dumps({"metric": "chip_smoke_query", **rec}), flush=True)
    problems = []
    if not engines or any(e != engine for e in engines):
        problems.append(f"fused fragments ran as {engines or 'none'}, "
                        f"wanted every one engine:{engine}")
    if not rec["parity"]:
        problems.append("device rows differ from the host engine's")
    if not host_rows:
        problems.append("the host engine returned no rows")
    if rec["cold_compiles"] < 1:
        problems.append("no compile counted in the cold pass")
    if rec["warm_compiles"]:
        problems.append(f"{rec['warm_compiles']} compiles after the "
                        "cold pass")
    if problems:
        st = _status(diag)
        raise _Failed(
            f"{qname}: " + "; ".join(problems) + "\n  breakers: "
            + json.dumps({k: {f: v[f] for f in ("state", "failures",
                                                "degraded", "last_error")}
                          for k, v in st["device_breakers"].items()})
            + "\n  compiler last_error: "
            + repr(st["device_compiler"]["last_error"])
            + "\n  plan:\n    "
            + "\n    ".join(" | ".join(map(str, r[:3])) for r in plan))


def _final_checks(st, engine, fleet, pid0) -> list:
    """The end-of-run requirements on the worker's own counters."""
    bad = []
    for shape, br in st["device_breakers"].items():
        for k in ("failures", "degraded", "opened"):
            if br[k]:
                bad.append(f"breaker[{shape}].{k} = {br[k]} "
                           f"(last_error {br['last_error']!r})")
    sched = st["device_scheduler"]
    for k in ("rejected_full", "rejected_timeout"):
        if sched[k]:
            bad.append(f"scheduler.{k} = {sched[k]}")
    if sum(sched["degradations_by_group"].values()):
        bad.append("admission degradations: "
                   f"{sched['degradations_by_group']}")
    comp = st["device_compiler"]
    for k in ("compile_pending_fragments", "breaker_degrades", "bg_failed"):
        if comp[k]:
            bad.append(f"compiler.{k} = {comp[k]} "
                       f"(last_error {comp['last_error']!r})")
    sup = st["device_supervisor"]
    for k in ("fences", "hangs"):
        if sup[k]:
            bad.append(f"supervisor.{k} = {sup[k]}")
    if st["device_residency"]["hbm_bytes_cached"] <= 0:
        bad.append("hbm_bytes_cached = 0: nothing is resident on the device")
    if fleet.respawns or fleet.worker_pid(0) != pid0 \
            or fleet.slots[0].proc.poll() is not None:
        bad.append(f"the worker died or was respawned "
                   f"(respawns {fleet.respawns}, pid {pid0} -> "
                   f"{fleet.worker_pid(0)})")
    if engine == "tpu-mpp":
        if st["device_mpp"]["fragments"] <= 0:
            bad.append("mpp_fragments = 0: nothing ran through the mesh")
        per_dev = st["device_backend"]["bytes_in_use"]
        if len(per_dev) < 2 or any(not b for b in per_dev
                                   if b is not None):
            bad.append(f"not every mesh device holds data: {per_dev}")
    return bad


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--sf", type=float, default=1.0,
                    help="TPC-H scale factor (default 1)")
    ap.add_argument("--engine", choices=sorted(QUERY_SETS), default="tpu")
    ap.add_argument("--allow-cpu", action="store_true",
                    help="run although the worker's platform is not tpu")
    args = ap.parse_args(argv)

    os.chdir(HERE)  # the worker imports bench and this module from here
    sys.path.insert(0, HERE)
    try:
        import bench
        from tidb_tpu.fabric.client import FleetClient, WireError
        from tidb_tpu.fabric.fleet import Fleet
    except ImportError as e:
        print(f"chip_smoke: the tpu-htap checkout is not beside this "
              f"script ({e})", file=sys.stderr)
        return 2

    t_start = time.monotonic()

    def left() -> float:
        return max(DEADLINE_S - (time.monotonic() - t_start), 1.0)

    run_dir = tempfile.mkdtemp(prefix="chip-smoke-")
    fleet = Fleet(
        1, compile_server=False, init=SEED_HOOK, run_dir=run_dir,
        env_extra={_SF_ENV: repr(args.sf),
                   _ALLOW_CPU_ENV: "1" if args.allow_cpu else ""})
    failures = []
    device = None
    try:
        try:
            fleet.start(timeout_s=left())
        except RuntimeError as e:
            print(f"chip_smoke: FAILED to start the worker: {e}",
                  file=sys.stderr)
            return 1
        boot_s = time.monotonic() - t_start
        pid0 = fleet.worker_pid(0)
        be = fleet.slots[0].backend
        device = {"platform": be["platform"], "kind": be["device_kind"],
                  "count": be["device_count"]}
        seed_line = next(json.loads(ln) for ln in fleet.lines
                         if '"chip_smoke_seed"' in ln)

        cli = FleetClient(fleet.port, db="tpch", timeout=left())
        diag = FleetClient(fleet.direct_port(0), timeout=left())
        # the host reference at SF>=1 must not trip the per-statement
        # quota's cancel action (bench.py lifts it the same way)
        cli.must_exec("set tidb_mem_quota_query = 0")
        # every run must execute: a repeat served from the versioned
        # result cache would time (and prove) nothing on the device
        cli.must_exec("set tidb_result_cache = 'OFF'")
        st = _status(diag)
        cache_dir = st["device_backend"]["compile_cache_dir"]
        cache_before = _cache_entries(cache_dir)
        print(json.dumps({
            "metric": "chip_smoke_setup", **device, "jax": be["jax"],
            "engine": args.engine, "sf": args.sf,
            "lineitem_rows": seed_line["lineitem_rows"],
            "seed_s": seed_line["seed_s"], "boot_s": round(boot_s, 2),
            "kv_engine": st["kv_engine"],
            "compile_cache_dir": cache_dir,
            "cache_entries_at_start": cache_before}), flush=True)
        cli.must_exec("set tidb_executor_engine = 'host'")
        _cols, cnt = cli.must_query("select count(*) from lineitem")
        if int(cnt[0][0]) != seed_line["lineitem_rows"] or \
                seed_line["lineitem_rows"] != int(6_001_215 * args.sf):
            raise _Failed(f"lineitem holds {cnt[0][0]} rows, the seed "
                          f"reported {seed_line['lineitem_rows']}")

        for qname in QUERY_SETS[args.engine]:
            cli.sock.settimeout(left())
            try:
                _run_query(cli, diag, qname, bench.QUERIES[qname],
                           args.engine)
            except _Failed as e:
                failures.append(str(e))
            if time.monotonic() - t_start > DEADLINE_S:
                raise _Failed(f"out of time after {qname} "
                              f"({DEADLINE_S:.0f}s budget)")

        st = _status(diag)
        cache_after = _cache_entries(cache_dir)
        print(json.dumps({
            "metric": "chip_smoke_totals",
            "compiles": st["device_pipelines"]["compiles"],
            "sync_compile_s": round(st["device_pipelines"]["compile_s"], 2),
            "compile_persist_hits":
                st["device_compiler"]["compile_persist_hits"],
            "cache_entries_written": cache_after - cache_before,
            # every program the cold passes needed was already on disk
            "cold_pass_hit_cache": cache_before > 0
                                   and cache_after == cache_before,
            "hbm_bytes_cached": st["device_residency"]["hbm_bytes_cached"],
            "hbm_evictions": st["device_residency"]["hbm_evictions"],
            "hbm_oom_recoveries":
                st["device_residency"]["hbm_oom_recoveries"],
            "device_bytes_in_use": st["device_backend"]["bytes_in_use"],
            "mpp_fragments": st["device_mpp"]["fragments"],
            "wall_s": round(time.monotonic() - t_start, 1)}), flush=True)
        failures += _final_checks(st, args.engine, fleet, pid0)
        cli.close()
        diag.close()
    except (_Failed, WireError, OSError) as e:
        failures.append(f"{type(e).__name__}: {e}")
    finally:
        drained = fleet.shutdown()
        shutil.rmtree(run_dir, ignore_errors=True)
    try:
        fleet.check()
    except RuntimeError as e:
        failures.append(str(e))
    if not failures and not (drained and drained["ok"]):
        failures.append(f"the fleet did not drain clean: {drained}")
    from jax._src import xla_bridge
    if xla_bridge.backends_are_initialized():
        failures.append("the smoke's parent process initialised a JAX "
                        "backend; only the worker may hold the device")
    if failures:
        for f in failures:
            print(f"chip_smoke: FAILED: {f}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
