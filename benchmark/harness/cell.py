"""Run one cell: one worker that owns the chip(s), driven over the MySQL
wire by this process, which never initialises JAX.

    set-up   worker boot -> data from the seed -> reference answers
             (here, in a thread, while the worker loads) -> warm-up
             passes of EXPLAIN ANALYZE until one counts no compile
             (every fused fragment must name the pinned engine)
    window   the traffic file's loop for --seconds; each request timed on
             this process's monotonic clock from send to last row, and
             compared with the reference after its time is taken
    after    DIAG STATUS deltas, spans, the device trace, the worker's
             peak bytes; the worker is stopped and waited for
"""

import itertools
import json
import os
import shutil
import threading
import time

from . import checks, trace_reduce
from .observe import Observation, Request, delta
from .resolve import BENCH_DIR, Cell
from .worker_hook import ENV_SPEC, write_json

HOOK = "benchmark.harness.worker_hook:seed"
CACHE_DIR = os.path.join(BENCH_DIR, ".cache")
#: scale factor of --rehearse (the chip command never passes it)
REHEARSE_SF = 0.01
MAX_WARM_PASSES = 4
#: the worker's span ring holds 64 traces: collect at least this often
SPAN_POLL_EVERY = 32
#: the profiler traces at least this long, and at least one whole pass
TRACE_MIN_S = 10.0
WIRE_TIMEOUT_S = 600.0


class Failed(Exception):
    """The run cannot give a result; the message says why."""


def log(obj: dict) -> None:
    print(json.dumps(obj), flush=True)


def _union_reads(templates: dict) -> dict:
    want = {}
    for mod in templates.values():
        for table, cols in mod.READS.items():
            have = want.setdefault(table, [])
            have += [c for c in cols if c not in have]
    return want


def _check_traffic(cell: Cell) -> None:
    t = cell.traffic
    if t.get("loop") != "closed" or t.get("clients") != 1:
        raise NotImplementedError(
            f"traffic {cell.traffic_name!r}: loop {t.get('loop')!r} with "
            f"{t.get('clients')} client(s) is not implemented; this "
            "harness drives one closed-loop client (open loops and "
            "concurrent streams are listed in PERF.md)")
    if t.get("parameters") != "validation":
        raise NotImplementedError(
            f"traffic {cell.traffic_name!r}: parameter policy "
            f"{t.get('parameters')!r} is not implemented; templates carry "
            "the validation parameters only (see PERF.md, adhoc_seeded)")
    if t.get("writers"):
        raise NotImplementedError(
            f"traffic {cell.traffic_name!r}: writers are not implemented "
            "(see PERF.md, refresh_rf1)")
    installed = cell.config["tables"]
    for name, mod in cell.templates.items():
        for table, cols in mod.READS.items():
            missing = [c for c in cols if c not in installed.get(table, ())]
            if missing:
                raise Failed(f"template {name!r} reads {table}.{missing}, "
                             f"which config {cell.config_name!r} does not "
                             "install")


class _Reference(threading.Thread):
    """Reference answers from the seed, computed beside the worker's
    boot; cached with the store (same seed, same data, same answers)."""

    def __init__(self, cell: Cell, seed: int, sf: float, cache: str):
        super().__init__(name="benchmark-reference", daemon=True)
        self.cell, self.seed, self.sf, self.cache = cell, seed, sf, cache
        self.answers = {}
        self.error = None
        self.seconds = 0.0
        self.cached = []

    def _path(self, template: str) -> str:
        return os.path.join(self.cache, f"reference-{template}.json")

    def run(self):
        t0 = time.monotonic()
        try:
            todo = {}
            for name, mod in self.cell.templates.items():
                try:
                    with open(self._path(name)) as f:
                        self.answers[name] = [tuple(r) for r in json.load(f)]
                    self.cached.append(name)
                except (OSError, ValueError):
                    todo[name] = mod
            if todo:
                tables = self.cell.dataset.generate(
                    self.seed, self.sf, _union_reads(todo))
                for name, mod in todo.items():
                    self.answers[name] = [tuple(r)
                                          for r in mod.reference(tables)]
        except Exception as e:  # noqa: BLE001 -- re-raised by the caller
            self.error = e
        self.seconds = time.monotonic() - t0

    def save(self):
        os.makedirs(self.cache, exist_ok=True)
        for name, rows in self.answers.items():
            if name not in self.cached:
                write_json(self._path(name), rows)


def _store_cache(cell: Cell, seed: int, sf: float) -> str:
    return os.path.join(CACHE_DIR, cell.config_name,
                        f"seed{seed}-sf{sf:g}-gen{cell.dataset.GEN_VERSION}")


def _store_meta(cell: Cell, seed: int, sf: float) -> dict:
    return {"dataset": cell.config["dataset"],
            "gen_version": cell.dataset.GEN_VERSION, "seed": seed, "sf": sf,
            "tables": cell.config["tables"]}


def _restore_store(cell, seed, sf, run_dir) -> bool:
    """Put the cached store (schema, stats, nation, region: what the WAL
    holds) under `run_dir`, as a restarted deployment would find it.  A
    cache of another seed, scale, column set or generator is rebuilt."""
    cache = _store_cache(cell, seed, sf)
    try:
        with open(os.path.join(cache, "meta.json")) as f:
            if json.load(f) != _store_meta(cell, seed, sf):
                raise ValueError("stale")
        shutil.copytree(os.path.join(cache, "wal"),
                        os.path.join(run_dir, "wal"))
        return True
    except (OSError, ValueError):
        shutil.rmtree(cache, ignore_errors=True)
        shutil.rmtree(os.path.join(run_dir, "wal"), ignore_errors=True)
        return False


def _save_store(cell, seed, sf, run_dir) -> None:
    cache = _store_cache(cell, seed, sf)
    os.makedirs(cache, exist_ok=True)
    tmp = os.path.join(cache, "wal.tmp")
    shutil.rmtree(tmp, ignore_errors=True)
    shutil.copytree(os.path.join(run_dir, "wal"), tmp)
    shutil.rmtree(os.path.join(cache, "wal"), ignore_errors=True)
    os.replace(tmp, os.path.join(cache, "wal"))
    write_json(os.path.join(cache, "meta.json"),
                _store_meta(cell, seed, sf))      # last: marks it whole


def _status(diag) -> dict:
    _cols, rows = diag.must_query("DIAG STATUS")
    return json.loads(rows[0][0])


def _ask_worker(run_dir: str, ask: str, answer: str, timeout_s=120.0):
    """Touch `ask`, wait for the hook's thread to write `answer`."""
    path = os.path.join(run_dir, answer)
    if os.path.exists(path):
        os.remove(path)
    open(os.path.join(run_dir, ask), "w").close()
    deadline = time.monotonic() + timeout_s
    while not os.path.exists(path):
        if time.monotonic() > deadline:
            raise Failed(f"the worker did not answer {ask} within "
                         f"{timeout_s:.0f}s")
        time.sleep(0.01)
    with open(path) as f:
        return json.load(f)


def _collect_spans(diag, conn_id: int, seen: dict) -> None:
    _cols, rows = diag.must_query("DIAG TRACEJSON")
    for tr in json.loads(rows[0][0])["rows"]:
        if tr.get("conn_id") == conn_id and \
                tr["root"].get("tags", {}).get("stmt") == "SelectStmt":
            seen[tr["trace_id"]] = tr


def _peaks(kind: str) -> dict:
    with open(os.path.join(BENCH_DIR, "harness", "peaks.json")) as f:
        table = json.load(f)["device_kinds"]
    if kind not in table:
        raise Failed(f"device kind {kind!r} is not in benchmark/harness/"
                     f"peaks.json (it knows {sorted(table)}); add its "
                     "published peaks with their source")
    return table[kind]


def _warm_up(cli, diag, sql: dict, order: list) -> "tuple[dict, int]":
    """EXPLAIN ANALYZE executes the statement and names the engine of
    every fused fragment, so one statement per template and pass both
    warms and checks.  The pass that counts no compile ends the warm-up
    (the second pass absorbs the learned-capacity recompile).
    -> ({template: plan rows} of the last pass, passes made)"""
    for passes in range(1, MAX_WARM_PASSES + 1):
        before = _status(diag)["device_pipelines"]["compiles"]
        plans = {t: cli.must_query("explain analyze " + sql[t])[1]
                 for t in order}
        if _status(diag)["device_pipelines"]["compiles"] == before:
            return plans, passes
    raise Failed(f"pass {MAX_WARM_PASSES} of the warm-up still compiled")


def _window(ask, order, seconds, trace, run_dir, diag, conn_id):
    """The closed loop for `seconds`; with `trace`, the profiler runs over
    whole passes of the mix for at least TRACE_MIN_S and the worker's span
    ring is collected as it fills.
    -> (requests, the worker's trace.done answer or None, span trees)"""
    requests, spans = [], {}
    tracing = "wanted" if trace else "off"   # -> "on" -> "done"
    trace_info = None
    t_win = time.monotonic()
    for i, template in enumerate(itertools.cycle(order)):
        if i % len(order) == 0:       # between whole passes of the mix
            if tracing == "wanted":
                _ask_worker(run_dir, "trace.start", "trace.on")
                tracing, t_trace = "on", time.monotonic()
            elif tracing == "on" and \
                    time.monotonic() - t_trace >= TRACE_MIN_S:
                trace_info = _ask_worker(run_dir, "trace.stop",
                                         "trace.done")
                tracing = "done"
        if time.monotonic() - t_win >= seconds:
            break
        req = ask(template)
        req.traced = tracing == "on"
        requests.append(req)
        if trace and len(requests) % SPAN_POLL_EVERY == 0:
            _collect_spans(diag, conn_id, spans)
    if tracing == "on":
        trace_info = _ask_worker(run_dir, "trace.stop", "trace.done")
    if trace:
        _collect_spans(diag, conn_id, spans)
    return requests, trace_info, [spans[k] for k in sorted(spans)]


def _reduce_trace(run_dir, trace_info, requests) -> dict:
    path = trace_reduce.find_xplane(os.path.join(run_dir, "trace"))
    if path is None:
        raise Failed("the worker wrote no .xplane.pb")
    xplane = trace_reduce.reduce_file(path, trace_info["window_s"])
    if not xplane or xplane["busy_s"] <= 0:
        raise Failed("the trace shows no operation on the device")
    xplane["requests"] = [r for r in requests if r.traced]
    return xplane


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             rehearse: bool, t_start: float) -> dict:
    """-> the result line.  Raises Failed / WireError /
    NotImplementedError when there is no result to print."""
    _check_traffic(cell)
    from tidb_tpu.fabric.client import FleetClient
    from tidb_tpu.fabric.fleet import Fleet

    cfg = cell.config
    engine = cfg["engine"]
    sf = REHEARSE_SF if rehearse else float(cfg["scale_factor"])
    run_dir = os.path.join(CACHE_DIR, "run", cell.name)
    shutil.rmtree(run_dir, ignore_errors=True)
    os.makedirs(run_dir)
    seeded = _restore_store(cell, seed, sf, run_dir)
    spec = {"seed": seed, "sf": sf, "dataset": cfg["dataset"],
            "tables": cfg["tables"],
            "chips": cell.chips, "rehearse": rehearse, "run_dir": run_dir}
    env = {ENV_SPEC: json.dumps(spec)}
    if rehearse:
        # XLA:CPU stands in for the chip(s); at SF0.01 the inputs fit the
        # in-flight fragment coalescer, whose 0.2 s result page would
        # answer every second request of a closed loop without a device
        # call -- at SF1 its own size gate keeps it out of the way
        env.update({
            "JAX_PLATFORMS": "cpu", "TF_CPP_MIN_LOG_LEVEL": "3",
            "XLA_FLAGS": (os.environ.get("XLA_FLAGS", "") + " --xla_force_"
                          f"host_platform_device_count={cell.chips}").strip(),
            "TIDB_TPU_FABRIC_DEDUP_TTL": "1e-9"})
    ref = _Reference(cell, seed, sf, _store_cache(cell, seed, sf))
    ref.start()
    fleet = Fleet(1, compile_server=False, init=HOOK, run_dir=run_dir,
                  env_extra=env)
    try:
        try:
            fleet.start(timeout_s=1100.0)
        except RuntimeError as e:
            raise Failed(f"the worker did not start: {e}") from e
        fleet_ready_s = time.monotonic() - t_start
        pid0 = fleet.worker_pid(0)
        be = fleet.slots[0].backend
        device = {"platform": be["platform"], "kind": be["device_kind"],
                  "count": be["device_count"]}
        if device["count"] != cell.chips:
            raise Failed(f"the worker holds {device['count']} device(s); "
                         f"the cell is defined on {cell.chips}")
        seed_line = next(json.loads(ln) for ln in fleet.lines
                         if '"bench_seed"' in ln)
        peaks = None if rehearse else _peaks(device["kind"])
        ref.join()
        if ref.error is not None:
            raise Failed(f"reference answers: {ref.error!r}") from ref.error

        cli = FleetClient(fleet.port, db=cell.dataset.DB,
                          timeout=WIRE_TIMEOUT_S)
        diag = FleetClient(fleet.direct_port(0), timeout=WIRE_TIMEOUT_S)
        for var, val in cfg["session"].items():
            cli.must_exec(f"set {var} = {val!r}" if isinstance(val, str)
                          else f"set {var} = {val}")
        log({"metric": "bench_setup", "cell": cell.name, **device,
             "rehearsal": rehearse, "seed": seed, "sf": sf,
             "rows": seed_line["rows"], "store_replayed": seeded,
             "gen_s": seed_line["gen_s"], "load_s": seed_line["load_s"],
             "fleet_ready_s": round(fleet_ready_s, 3),
             "reference_s": round(ref.seconds, 3),
             "reference_cached": ref.cached,
             "compile_cache_dir": _status(diag)["device_backend"]
             ["compile_cache_dir"]})

        order = cell.traffic["order"]
        sql = {t: mod.SQL for t, mod in cell.templates.items()}
        problems = []

        def ask(template: str) -> Request:
            t0 = time.monotonic()
            kind, payload = cli.query(sql[template])
            dt = time.monotonic() - t0
            ok = kind == "rows" and payload[1] == ref.answers[template]
            if not ok and len(problems) < 3:
                problems.append(
                    f"{template}: " + (f"error {payload}" if kind != "rows"
                                       else f"rows differ: got "
                                       f"{payload[1][:2]}, reference "
                                       f"{ref.answers[template][:2]}"))
            return Request(template, dt, ok)

        plans, passes = _warm_up(cli, diag, sql, order)
        correct = True
        for t, plan in plans.items():
            found = checks.engines(plan)
            if not found or any(e != engine for e in found):
                correct = False
                problems.append(f"{t}: fused fragments ran as "
                                f"{found or 'none'}, the configuration "
                                f"pins engine:{engine}")
        if trace:
            cli.must_exec("set tidb_trace_sampling_rate = 1")
        status0 = _status(diag)
        setup = {
            "setup_s": time.monotonic() - t_start,
            "fleet_ready_s": fleet_ready_s,
            "seed_s": seed_line["gen_s"] + seed_line["load_s"],
            "compiles": status0["device_pipelines"]["compiles"],
            "compile_s": status0["device_pipelines"]["compile_s"]}

        t_win = time.monotonic()
        requests, trace_info, trees = _window(
            ask, order, seconds, trace, run_dir, diag, cli.conn_id)
        window_s = time.monotonic() - t_win
        status1 = _status(diag)
        if len(trees) == len(requests):
            for req, tr in zip(requests, trees):
                req.trace = tr
        elif trace:
            log({"metric": "bench_note", "note": "span trees "
                 f"({len(trees)}) do not pair with the window's requests "
                 f"({len(requests)}); span metrics left out"})
        mem = [m for m in _ask_worker(run_dir, "mem.req", "mem.json") if m]
        device["memory_peak_bytes"] = max(
            (m["peak_bytes_in_use"] for m in mem), default=None)
        cli.close()
        diag.close()

        # -- verdicts --------------------------------------------------------
        bad = checks.degraded(status0, status1, engine == "tpu-mpp",
                              len(requests))
        if fleet.respawns or fleet.worker_pid(0) != pid0 \
                or fleet.slots[0].proc.poll() is not None:
            bad.append((1, f"the worker died or was respawned (respawns "
                           f"{fleet.respawns})"))
        wrong = sum(1 for r in requests if not r.ok)
        xplane = _reduce_trace(run_dir, trace_info, requests) \
            if trace_info else None
        obs = Observation(
            requests=requests, setup=setup, status0=status0,
            status1=status1, templates=cell.templates,
            rows=seed_line["rows"], device=device,
            hbm_bytes=max((m["bytes_limit"] for m in mem), default=None),
            peaks=peaks, xplane=xplane)
        metrics = {}
        for entry, mod in (cell.per_layer if trace else cell.end_to_end):
            value = mod.read(obs)
            if value is not None:
                metrics[entry["name"]] = {"value": value,
                                          "unit": entry["unit"]}
        medians = obs.template_medians()
        log({"metric": "bench_window", "cell": cell.name, **device,
             "rehearsal": rehearse, "window_s": round(window_s, 3),
             "requests": len(requests),
             "per_template": {t: {"n": len(obs.latencies(t)),
                                  "median_s": medians.get(t)}
                              for t in cell.templates},
             "warm_passes": passes, "setup_compiles": setup["compiles"],
             "setup_compile_s": round(setup["compile_s"], 3),
             "persist_hits":
                 status0["device_compiler"]["compile_persist_hits"],
             "window_compiles": delta(status0, status1, "device_pipelines",
                                      "compiles"),
             "dedup_hits": delta(status0, status1, "device_fabric",
                                 "fabric_dedup_hits"),
             "hbm_bytes_cached":
                 status1["device_residency"]["hbm_bytes_cached"],
             "hbm_evictions": delta(status0, status1, "device_residency",
                                    "hbm_evictions"),
             "hbm_peak_share": (100.0 * device["memory_peak_bytes"]
                                / obs.hbm_bytes) if obs.hbm_bytes else None,
             "degraded": [w for _n, w in bad], "problems": problems})
        result = {"correct": correct and wrong == 0,
                  "attempted": len(requests),
                  "failed": wrong + sum(n for n, _w in bad),
                  "metrics": metrics, "device": device}
        if xplane:
            device["busy_s"] = xplane["busy_s"]
            device["window_s"] = xplane["window_s"]
            result["breakdown"] = {"device_ops": xplane["device_ops"],
                                   "idle_gaps": xplane["idle_gaps"]}
        if rehearse:
            # a CPU timing is never written under a device metric's name:
            # the values go on a line of their own, the result gets nulls
            log({"metric": "bench_rehearsal_values", "platform": "cpu",
                 "values": {k: v["value"] for k, v in metrics.items()},
                 "breakdown": result.pop("breakdown", None)})
            for m in metrics.values():
                m["value"] = None
            device.update({k: None for k in ("busy_s", "window_s")
                           if k in device})
    finally:
        drained = fleet.shutdown()
    fleet.check()
    if not (drained and drained["ok"]):
        raise Failed(f"the fleet did not drain clean: {drained}")
    from jax._src import xla_bridge
    if xla_bridge.backends_are_initialized():
        raise Failed("this process initialised a JAX backend; only the "
                     "worker may hold the device")
    if not seeded:
        _save_store(cell, seed, sf, run_dir)
    ref.save()
    return result
