"""Compile service: async background compilation, persistent executable
index, prewarmed bucket ladders, resilient remote compile.

Why this exists: the July 2026 TPC-H SF1 run on a v5e showed the
production enemy is COMPILATION, not execution — Q1 ran 22.7x faster
than host but paid 147–379s of XLA compile per query shape, and one
refused compile request at Q5 zeroed the rest of the run.  PRs 1–7 made retries, hangs, HBM and admission owned resources;
this module does the same for the compile pipeline, applying the
co-processing principle ("Revisiting Co-Processing for Hash Joins on the
Coupled CPU-GPU Architecture", PAPERS.md) to compilation itself: while
the device's program compiles in the background, the HOST serves the
query — host and device do different useful work concurrently instead of
the query blocking on XLA.

The five layers a device fragment now passes (run_device order):

    1. ADMISSION            may this fragment occupy the device now?
    2. COMPILE SERVICE      is its executable ready?  (this module)
    3. SUPERVISOR deadline  is the backend still responsive?
    4. CIRCUIT BREAKER      is this fragment shape healthy?
    5. RESIDENCY            do its uploads fit the HBM budget?

Model — every compiled-pipeline build routes through :func:`obtain`
(device_exec.acquire_pipeline is the sole caller; the AST lint in
tests/test_compile_service.py confines direct ``jax.jit`` of query
pipelines to this module + ops/device.py):

* **Async compile, host-first serving** (``tidb_compile_async``): a cold
  ``_PIPE_CACHE`` miss SUBMITS the (plan sig, pack sig, bucket shape)
  signature to a bounded compile worker pool and immediately raises
  ``DeviceUnsupported`` — the fragment runs on the host engine (counted
  ``compile_pending_fragments``, NO breaker charge: a pending compile is
  not ill-health).  The worker builds the pipeline and warms it against
  zero-filled arrays of the recorded shapes, so the trace + XLA compile
  happen off the query path; when the executable lands in the shared
  ``_PIPE_CACHE`` the next same-shaped query flips to the device with
  ZERO new traces.  First-query latency is bounded by host speed, never
  by XLA.

* **Persistent executable index**: jax's AOT compilation cache (enabled
  process-wide in tidb_tpu/__init__.py, host-fingerprint-scoped — PR 7)
  persists the serialized executables themselves, for the CPU AND PJRT
  backends; this module adds a SIGNATURE INDEX next to it
  (``<jax-cache-dir>/pipe-index/``, override
  ``TIDB_TPU_COMPILE_INDEX``).  A cold obtain whose signature is in the
  index compiles INLINE even under async — the XLA artifact comes off
  disk, so the "compile" is a deserialize — and counts
  ``compile_persist_hits``: a process restart or a second serving
  process starts warm.

* **Prewarm ladder** (``tidb_compile_prewarm`` at Domain start, the
  ``ADMIN COMPILE`` statement, or :func:`prewarm`): every build registers
  a RECIPE (builder + arg shapes); prewarm background-compiles each hot
  recipe's geometric bucket ladder (the next ``ladder_up`` row buckets
  above the seen shape), so the shapes growing traffic will hit are
  traced before traffic arrives — a delta that crosses a bucket boundary
  re-dispatches a prewarmed program instead of paying a sync compile.
  Fragment signatures with learned capacities (device_join.learned_sigs)
  are prewarm-priority: they are the shapes real traffic converged on.

* **Resilient remote compile**: a compile worker runs under the PR 3
  supervisor deadline (``tidb_compile_timeout`` — a hung remote compile
  is abandoned and fenced like any other device hang), classified
  compile/transport failures retry on the shared Backoffer's
  ``compileRetry`` curve, and terminal failures charge a COMPILE-SCOPED
  circuit breaker (shape="compile"): a flaky compile service degrades
  fragments to host and recovers via half-open probe instead of killing
  the run (the Q5 failure mode).  Chaos hook: failpoint
  ``device-compile`` with ``compile-fail`` / ``[N*]compile-slow(s)``
  actions, asserted drained by ``verify_drained`` in both chaos modes.

Gauges — ``compile_queue_depth``, ``compile_pending_fragments``,
``compile_bg_seconds``, ``compile_persist_hits`` — surface in EXPLAIN
ANALYZE annotations (plus a per-fragment ``compile_mode``: ``cached`` /
``prewarmed`` / ``async_pending`` / ``sync``), observe gauges,
``/status`` (``device_compiler``), ``/metrics`` and the bench JSON lines
(``sync_compile_s`` vs ``bg_compile_s``).
"""

from __future__ import annotations

import collections
import hashlib
import itertools
import json
import logging
import os
import queue
import threading
import time
import weakref

log = logging.getLogger("tidb_tpu.compile_service")

_LOCK = threading.Lock()

#: in-flight background jobs keyed by job key (pipeline cache key, or
#: (pipeline key, ("ladder", bucket)) for prewarm shape warms)
_JOBS: dict = {}
#: async backlog bound (the bg pool's admission, mirroring the
#: scheduler's bounded queue): every queued join/MPP job pins its builder
#: closure — the host table chunk and its device columns — until the
#: build runs, so an unbounded burst of distinct signatures would bypass
#: the residency ledger and grow host RAM without limit
_BACKLOG_MAX = 32
_JOB_Q: "queue.SimpleQueue" = queue.SimpleQueue()
_WORKERS: list = []
_WORKER_SEQ = itertools.count()

#: how a cached pipeline entry came to exist: "bg" (async background
#: compile) or "prewarm" (ladder warm) — anything absent was built sync.
#: Drives the per-fragment compile_mode annotation on later cache hits.
_ORIGIN: dict = {}
_ORIGIN_MAX = 512

#: prewarm recipes: every first build records its builder + arg shapes so
#: the ladder can re-trace the signature at neighboring bucket shapes
#: (and rebuild it after an off-CPU fence dropped the pipe cache)
_RECIPES: "collections.OrderedDict" = collections.OrderedDict()
_RECIPES_MAX = 128

STATS = {
    "bg_submitted": 0,        # background jobs enqueued
    "bg_completed": 0,        # jobs whose executable landed in the cache
    "bg_failed": 0,           # jobs that failed classified (breaker fed)
    "bg_discarded": 0,        # jobs dropped (stale after an off-CPU fence)
    "sync_compiles": 0,       # builds done inline on the query path
    "compile_pending_fragments": 0,  # dispatches degraded to host because
    #                                  their compile was pending/in flight
    "compile_prewarmed": 0,   # ladder shape warms completed
    "compile_persist_hits": 0,  # cold obtains served warm by the index
    "compile_bg_seconds": 0.0,  # wall seconds spent in background builds
    "breaker_degrades": 0,    # obtains refused by an OPEN compile breaker
    "bg_backlog_rejects": 0,  # submits refused by the _BACKLOG_MAX bound
}
_LAST_ERROR = [""]

#: resolved config (GLOBAL-vars discipline, same as scheduler._refresh_cfg:
#: the worker pool is process-wide, so a session SET must not resize it)
_CFG = {"workers": 2, "timeout_s": 0.0}

#: observe sinks mirroring the gauges (pattern of scheduler/residency)
_SINKS: "weakref.WeakSet" = weakref.WeakSet()


class _Recipe:
    __slots__ = ("key", "build", "spec", "dict_refs", "shape", "sig",
                 "uses", "bucket", "pd")

    def __init__(self, key, build, spec, dict_refs, shape, sig,
                 ladder=True, per_double=2):
        self.key = key
        self.build = build
        self.spec = spec
        self.dict_refs = dict_refs
        self.shape = shape
        self.sig = sig
        self.uses = 1
        # bucket None = no ladder: streamed fragments always dispatch at
        # the FIXED tidb_device_stream_rows block shape, so a
        # bigger-bucket warm could never serve traffic (only the
        # post-eviction rebuild applies to them)
        self.bucket = _base_bucket(spec) if ladder else None
        # the registering session's bucket granularity
        # (tidb_device_shape_buckets): the ladder must climb the SAME
        # curve the dispatch sites bucket on, or every warm is a shape
        # traffic never hits
        self.pd = per_double


class _Job:
    __slots__ = ("jkey", "cache_key", "build", "spec", "dict_refs",
                 "shape", "sig", "br", "sid", "origin", "done", "error",
                 "fence_gen", "tchild")

    def __init__(self, jkey, cache_key, build, spec, dict_refs, shape,
                 sig, br, sid, origin):
        self.jkey = jkey
        self.cache_key = cache_key
        self.build = build          # None: warm an already-cached fn
        self.spec = spec
        self.dict_refs = dict_refs
        self.shape = shape
        self.sig = sig
        self.br = br                # compile-scoped breaker (may be None)
        self.sid = sid              # probe-owner token for the breaker
        self.origin = origin        # "bg" | "prewarm"
        self.done = threading.Event()
        self.error = None
        self.fence_gen = _fence_gen()
        # linked child trace (session/tracing.py link_child): a bg job
        # submitted by a TRACED statement runs under its own trace whose
        # parent_id is the statement's — the async compile's lifetime
        # stays attributable to the query that triggered it
        self.tchild = None


# -- config / small helpers --------------------------------------------------

def _refresh_cfg(ctx):
    src = None
    dom = getattr(ctx, "domain", None)
    if dom is not None:
        gv = dom.global_vars
        src = lambda name, d: gv.get(name, d)  # noqa: E731
    elif ctx is not None:
        src = lambda name, d: ctx.get_sysvar(name)  # noqa: E731
    if src is None:
        return
    # resolve outside _LOCK (sysvar reads do arbitrary session work),
    # publish under it (the pool size and deadline are read under _LOCK
    # by _ensure_workers and the worker loop)
    vals = {}
    try:
        vals["workers"] = max(int(src("tidb_compile_workers", 2)), 1)
    except Exception:
        pass
    try:
        vals["timeout_s"] = max(float(src("tidb_compile_timeout", 0.0)),
                                0.0)
    except Exception:
        pass
    with _LOCK:
        _CFG.update(vals)


def _async_on(ctx) -> bool:
    if ctx is None:
        return False
    try:
        return str(ctx.get_sysvar("tidb_compile_async")).upper() in (
            "ON", "1", "TRUE")
    except Exception:
        return False


def _fence_gen() -> int:
    try:
        from . import supervisor
        return supervisor.fence_generation()
    except Exception:
        return 0


def _remote_serve(key, build, spec, shape, sig, _tsp=None) -> tuple:
    """Resolve a cold pipeline through the separated compile server
    (tidb_tpu/fabric/compile_client.py) when one is configured:
    ``(fn, None)`` on success (artifact deserialize or remote compile),
    ``(None, classified_error)`` when the remote path failed — the
    caller builds inline and charges the compile breaker — and
    ``(None, None)`` when there is no server / nothing exportable."""
    try:
        from ..fabric.compile_client import get_client
        cli = get_client()
    except Exception as e:  # noqa: BLE001 — remote is an optimization
        log.warning("fabric compile client unavailable (building "
                    "locally): %s", e)
        return None, None
    if cli is None:
        return None, None
    fn, err = cli.serve(key, build, spec, shape, sig)
    if _tsp is not None and fn is not None:
        _tsp.tags["remote"] = True
    return fn, err


def _spec_of(args):
    """args pytree (concrete arrays / scalars) → ShapeDtypeStruct pytree.
    Derived at submit time so the job never pins the query's real data.
    Python scalars stay literal zeros of their type: jit traces them
    WEAK-typed, and a strong-typed stand-in would give the warm call a
    different aval than the real dispatch (forcing the very retrace the
    warm exists to avoid)."""
    import jax
    import numpy as np

    def leaf(a):
        if isinstance(a, bool):
            return False
        if isinstance(a, int):
            return 0
        if isinstance(a, float):
            return 0.0
        a = np.asarray(a) if not hasattr(a, "dtype") else a
        return jax.ShapeDtypeStruct(tuple(a.shape), a.dtype)
    return jax.tree_util.tree_map(leaf, args)


def _zeros_of(spec):
    """Zero-filled concrete arrays matching a spec — the warm call's
    arguments.  Zeros are safe: the pipelines are pure static-shape
    numeric programs (division is where-guarded, padding is masked), and
    the warm result is discarded."""
    import jax
    import numpy as np

    def leaf(s):
        if isinstance(s, jax.ShapeDtypeStruct):
            return np.zeros(s.shape, s.dtype)
        return s  # literal python scalar placeholder (weak-typed arg)
    return jax.tree_util.tree_map(leaf, spec)


def _base_bucket(spec):
    """The single leading dimension shared by every array leaf of the
    spec (the fragment's row bucket), or None when leaves disagree —
    only single-bucket pipelines get a prewarm ladder."""
    import jax
    dims = {s.shape[0] for s in jax.tree_util.tree_leaves(spec)
            if getattr(s, "shape", ()) and len(s.shape) >= 1}
    if len(dims) == 1:
        return next(iter(dims))
    return None


def next_buckets(base: int, count: int, per_double: int = 2) -> list:
    """The `count` geometric row buckets strictly above `base` (the
    prewarm ladder: shapes growing traffic will hit next)."""
    from ..ops.device import bucket_rows
    if per_double <= 0:
        return []  # exact shapes: there is no bucket curve to climb
    out = []
    b = int(base)
    for _ in range(count):
        nb = bucket_rows(b + 1, per_double)
        if nb <= b:
            break
        out.append(nb)
        b = nb
    return out


def _scale_spec(spec, base: int, bucket: int):
    """The recipe's spec with every `base`-length leading dim scaled to
    `bucket` — the ladder shape one step up."""
    import jax

    def leaf(s):
        if getattr(s, "shape", ()) and len(s.shape) >= 1 \
                and s.shape[0] == base:
            return jax.ShapeDtypeStruct((bucket,) + tuple(s.shape[1:]),
                                        s.dtype)
        return s
    return jax.tree_util.tree_map(leaf, spec)


# -- persistent signature index ----------------------------------------------

def _persist_dir():
    """The signature-index directory, or None when persistence is off.
    Lives INSIDE the jax compilation cache dir (tidb_tpu/__init__.py):
    the index only vouches for executables that directory holds."""
    d = os.environ.get("TIDB_TPU_COMPILE_INDEX", "")
    if d == "off":
        return None
    if d:
        return d
    import jax
    base = getattr(jax.config, "jax_compilation_cache_dir", None)
    if not base:
        return None
    return os.path.join(base, "pipe-index")


def _persist_hash(key) -> str:
    """Stable hash of a pipeline cache key (sig strings / ints / tuples —
    repr-stable by construction) + backend identity: the same signature
    on a different backend or mesh width is a different executable."""
    import jax
    from ..ops.device import KERNEL_SCOPES_TAG
    # the scopes tag: the programs' names carry it, so an index entry
    # written under another tag vouches for executables jax will not find
    ident = repr((key, jax.default_backend(), jax.device_count(),
                  KERNEL_SCOPES_TAG))
    return hashlib.sha1(ident.encode()).hexdigest()


def _persist_lookup(key) -> bool:
    d = _persist_dir()
    if d is None:
        return False
    try:
        return os.path.exists(os.path.join(d, _persist_hash(key) + ".json"))
    except Exception:
        return False


def _persist_record(key, shape: str, sig: str, origin: str):
    """Record that this signature has compiled on this host: the jax AOT
    cache underneath holds the executable bytes, so a later process's
    obtain of the same key is served warm (compile_persist_hits)."""
    d = _persist_dir()
    if d is None:
        return
    try:
        os.makedirs(d, exist_ok=True)
        path = os.path.join(d, _persist_hash(key) + ".json")
        if os.path.exists(path):
            return
        tmp = path + f".{os.getpid()}.tmp"
        with open(tmp, "w") as f:
            # sig may be a structured tuple (join fragment_sig, window
            # sig) — repr it: the index entry is diagnostic, the HASH in
            # the filename is the lookup key
            json.dump({"shape": shape, "sig": repr(sig)[:512],
                       "origin": origin, "ts": time.time()}, f)
        os.replace(tmp, path)
    except Exception:
        pass  # the index is an optimization; never fail a compile on it


# -- stats plumbing -----------------------------------------------------------

def _mode(mode: str):
    """Record the per-fragment compile mode into the pipe-cache stats
    (process totals + thread-local), riding the supervisor's existing
    TLS bridging so EXPLAIN ANALYZE sees it through worker threads."""
    from .device_exec import _bump
    _bump("mode_" + mode)


def note_hit(key):
    """acquire_pipeline reports a pipe-cache HIT: compile_mode is
    `prewarmed` when the prewarm ladder produced/touched this entry,
    plain `cached` otherwise.  Deliberately LOCK-FREE: this runs on
    every warm fragment dispatch, and serializing all sessions on the
    compile-service lock would contend the steady-state path that does
    zero compile work — plain dict gets are GIL-atomic, and the uses
    bump is a prewarm-ranking heuristic where a lost increment under a
    race only nudges the ordering."""
    origin = _ORIGIN.get(key)
    rec = _RECIPES.get(key)
    if rec is not None:
        rec.uses += 1
    _mode("prewarmed" if origin == "prewarm" else "cached")


def _set_origin(key, origin: str):
    with _LOCK:
        _ORIGIN[key] = origin
        while len(_ORIGIN) > _ORIGIN_MAX:
            _ORIGIN.pop(next(iter(_ORIGIN)))


def _register_recipe(key, build, spec, dict_refs, shape, sig, ladder=True,
                     per_double=2):
    with _LOCK:
        rec = _RECIPES.get(key)
        if rec is not None:
            rec.uses += 1
            _RECIPES.move_to_end(key)
            return
        _RECIPES[key] = _Recipe(key, build, spec, dict_refs, shape, sig,
                                ladder, per_double)
        while len(_RECIPES) > _RECIPES_MAX:
            _RECIPES.popitem(last=False)


# -- the obtain chokepoint ----------------------------------------------------

def obtain(key, build, dict_refs, *, ctx=None, args=None, spec=None,
           shape="agg", sig="", ladder=True):
    """Resolve a compiled pipeline for a ``_PIPE_CACHE`` MISS (the sole
    caller is device_exec.acquire_pipeline, which already tried the
    cache).  Returns the built fn (sync path), or raises
    ``DeviceUnsupported`` when the fragment should run on the host
    engine instead: compile pending in the background, compile breaker
    open, or the build itself failed classified."""
    from ..session import tracing
    # the statement's span tracer: the compile span carries the MODE the
    # service resolved this fragment with (sync / async_pending /
    # persist_hit / breaker_open) — one branch when sampling is off
    with tracing.span("compile.obtain", shape=shape) as _tsp:
        return _obtain_impl(key, build, dict_refs, ctx, args, spec, shape,
                            sig, ladder, _tsp)


def _obtain_impl(key, build, dict_refs, ctx, args, spec, shape, sig,
                 ladder, _tsp):
    from ..ops.device import DeviceUnsupported
    from ..session import tracing
    from ..utils import failpoint
    from ..utils.backoff import classify, CLASS_COMPILE, CLASS_TRANSPORT
    from .circuit import get_breaker
    attach(ctx)
    _refresh_cfg(ctx)
    # a concurrent resolver may have LANDED this key between the caller's
    # cache miss and here (its bg job completed, or another session built
    # it sync): serve the fresh entry instead of rebuilding — on a real
    # TPU a redundant rebuild is minutes of XLA
    fn = _cached_fn(key)
    if fn is not None:
        note_hit(key)
        if _tsp is not None:
            _tsp.tags["mode"] = "cached"
        return fn
    if spec is None and args is not None:
        spec = _spec_of(args)
    if spec is not None:
        # join/MPP builders close over the execution's LEAVES — the full
        # host table chunk and its device-resident columns.  A recipe
        # lives for the process, so retaining such a builder would pin
        # whole tables in RAM and make residency eviction a lie (the
        # ledger drops the entry, the closure keeps the buffer).  Those
        # shapes register builder-less: they still dedup in-flight jobs
        # and count uses; only the post-eviction REBUILD (which needs a
        # builder) is skipped for them.  Agg/window builders close over
        # compiled expression fns only — safe to retain.
        keep = build if shape not in ("join", "mpp") else None
        from ..ops.device import shape_buckets
        _register_recipe(key, keep, spec,
                         dict_refs if keep is not None else (), shape, sig,
                         ladder, shape_buckets(ctx))

    br = get_breaker(ctx, shape="compile")
    sid = getattr(ctx, "conn_id", None)
    group = None
    try:
        from .scheduler import resource_group
        group = resource_group(ctx)
    except Exception:
        pass

    with _LOCK:
        in_flight = key in _JOBS
    if in_flight:
        # the executable is being built right now: this execution (and
        # any concurrent ones) serve host-side until it lands
        with _LOCK:
            STATS["compile_pending_fragments"] += 1
        _mode("async_pending")
        _publish_gauges()
        if _tsp is not None:
            _tsp.tags["mode"] = "async_pending"
        tracing.event("host_degraded", reason="compile_pending",
                      shape=shape)
        raise DeviceUnsupported(
            f"device executable for this {shape} fragment is compiling "
            "in the background (fragment served by the host engine)")

    if not br.allow(session=sid, group=group):
        # compile path unhealthy (a compile endpoint refusing every
        # request): don't even queue — degrade instantly, recover via
        # the half-open probe
        with _LOCK:
            STATS["breaker_degrades"] += 1
        if _tsp is not None:
            _tsp.tags["mode"] = "breaker_open"
        tracing.event("host_degraded", reason="compile_breaker_open",
                      shape=shape)
        raise DeviceUnsupported(
            f"compile circuit open for device executables (cooling "
            f"down; {shape} fragment degraded to host engine)")

    persist_warm = _persist_lookup(key)
    if persist_warm:
        with _LOCK:
            STATS["compile_persist_hits"] += 1
        if _tsp is not None:
            _tsp.tags["persist_hit"] = True

    if _async_on(ctx) and spec is not None and not persist_warm:
        # async path: submit and serve this execution host-side.  The
        # probe slot (if this allow() won one) transfers to the job —
        # its verdict is the background compile's outcome.
        job = _Job(key, key, build, spec, dict_refs, shape, sig, br, sid,
                   "bg")
        with _LOCK:
            # re-check ATOMICALLY with the insert: a concurrent miss on
            # the same key between the fast-path check above and here
            # must not double-submit (the overwrite would let the first
            # job's finish pop the second's live entry — leaked-job
            # false positives in verify_drained, and a duplicate
            # minutes-long compile on a real TPU)
            if key in _JOBS:
                job = None
                STATS["compile_pending_fragments"] += 1
            elif len(_JOBS) >= _BACKLOG_MAX:
                # backlog full: degrade to host WITHOUT submitting — the
                # signature re-submits on a later miss once the queue
                # drains (see the _BACKLOG_MAX comment for why the bound
                # exists at all)
                job = None
                STATS["bg_backlog_rejects"] += 1
                STATS["compile_pending_fragments"] += 1
            else:
                _JOBS[key] = job
                STATS["bg_submitted"] += 1
                STATS["compile_pending_fragments"] += 1
        if job is None:
            br.release_probe(session=sid)
            _mode("async_pending")
            _publish_gauges()
            if _tsp is not None:
                _tsp.tags["mode"] = "async_pending"
            tracing.event("host_degraded", reason="compile_pending",
                          shape=shape)
            raise DeviceUnsupported(
                f"device executable for this {shape} fragment is "
                "compiling in the background (fragment served by the "
                "host engine)")
        # linked child trace: the background build's own timeline, tied
        # back to this statement's trace by parent_id (the async
        # compile's lifetime is attributable to the query it serves)
        job.tchild = tracing.link_child("compile.bg", shape=shape)
        _ensure_workers()
        _JOB_Q.put(job)
        _mode("async_pending")
        _publish_gauges()
        if _tsp is not None:
            _tsp.tags["mode"] = "async_submitted"
            if job.tchild is not None:
                _tsp.tags["bg_trace_id"] = job.tchild.trace_id
        tracing.event("host_degraded", reason="compile_submitted",
                      shape=shape)
        raise DeviceUnsupported(
            f"device executable for this {shape} fragment submitted for "
            "background compilation (fragment served by the host engine)")

    # sync path (async off, no shape spec, or a persistent-index hit —
    # the XLA artifact comes off disk, so inline is a deserialize)
    remote_err = None
    try:
        # chaos hook: a compile-fail here models the remote-compile
        # service refusing/failing the build on the query path
        failpoint.inject("device-compile")
        fn, remote_err = _remote_serve(key, build, spec, shape, sig, _tsp)
        if fn is None:
            # no compile server, its shape can't export, or the remote
            # path just failed (remote_err set): build INLINE — the
            # separated compile server degrades to local compilation,
            # never to a failed query
            fn = build()
    except DeviceUnsupported:
        br.release_probe(session=sid)
        raise
    except Exception as e:
        cls = classify(e)
        if cls not in (CLASS_COMPILE, CLASS_TRANSPORT):
            br.release_probe(session=sid)
            raise
        # wrap in the taxonomy's own error (errno 9010): the breaker
        # record, the log chain and any re-classification all see a
        # COMPILE-path failure — a raw transport error from a future
        # remote compiler must not masquerade as an execution fault
        from ..errors import DeviceCompileError
        err = DeviceCompileError(
            f"device compile failed ({cls}): {e}")
        err.__cause__ = e
        br.record_failure(err, session=sid, group=group)
        with _LOCK:
            _LAST_ERROR[0] = f"{cls}: {e}"
        if _tsp is not None:
            _tsp.tags["mode"] = "sync_failed"
        tracing.event("host_degraded", reason="compile_" + cls,
                      shape=shape)
        raise DeviceUnsupported(
            f"device compile failed ({cls}): {e} "
            f"({shape} fragment degraded to host engine)") from err
    if remote_err is not None:
        # the inline build saved the query, but the 9010 breaker must
        # still see the REMOTE failure: enough of these open the compile
        # circuit and obtains degrade up front until the half-open probe
        # finds the server again — a dead compile server degrades
        # workers to inline/host compile, never to failed queries
        br.record_failure(remote_err, session=sid, group=group)
        with _LOCK:
            _LAST_ERROR[0] = f"remote: {remote_err}"
    else:
        br.record_success(session=sid)
    from .device_exec import _pipe_cache_put
    _pipe_cache_put(key, fn, dict_refs)
    with _LOCK:
        STATS["sync_compiles"] += 1
    _mode("sync")
    if _tsp is not None:
        _tsp.tags["mode"] = "sync"
    _persist_record(key, shape, sig, "sync")
    return fn


# -- the worker pool ----------------------------------------------------------

def _ensure_workers():
    with _LOCK:
        want = _CFG["workers"]
        live = [t for t in _WORKERS if t.is_alive()]
        _WORKERS[:] = live
        need = want - len(live)
        for _ in range(max(need, 0)):
            t = threading.Thread(
                target=_worker_loop, daemon=True,
                name=f"compile-worker-{next(_WORKER_SEQ)}")
            _WORKERS.append(t)
            t.start()


def _worker_loop():
    from .device_exec import mark_bg_thread
    mark_bg_thread()  # route this thread's compile stats to the bg_* keys
    while True:
        job = _JOB_Q.get()
        try:
            _run_job(job)
        except BaseException:  # noqa: BLE001 — a worker must never die
            log.exception("compile worker: unexpected job failure")
            _finish_job(job, failed=True)


def _do_compile(job: "_Job"):
    """One build+warm attempt (runs under the supervisor deadline).  The
    warm call triggers the trace and the XLA compile against zero-filled
    arrays of the recorded shapes; the jit cache inside the fn then
    serves the real dispatch with zero new traces."""
    from ..utils import failpoint
    from .device_exec import mark_bg_thread
    # SCOPED bg mark: under tidb_compile_timeout this runs on a REUSED
    # supervisor worker thread, not the compile worker — the charges
    # must still route to the bg_* mirror, and the mark must not outlive
    # the job (that worker serves query fragments next)
    prev = mark_bg_thread()
    try:
        failpoint.inject("device-compile")
        if job.build is not None and job.spec is not None:
            # separated compile server first (when configured): the
            # worker traces, the server pays the XLA compile.  A remote
            # failure logs + counts and falls through to the local
            # build — background jobs already serve host-side, so the
            # right degradation is inline compile, not a failed job.
            fn, rerr = _remote_serve(job.cache_key, job.build, job.spec,
                                     job.shape, job.sig)
            if fn is not None:
                fn(*_zeros_of(job.spec))
                return fn
            if rerr is not None:
                log.warning("bg compile: remote path failed, building "
                            "inline: %s", rerr)
                with _LOCK:
                    _LAST_ERROR[0] = f"remote: {rerr}"
        fn = (job.build() if job.build is not None
              else _cached_fn(job.cache_key))
        if fn is None:
            return None
        zeros = _zeros_of(job.spec)
        fn(*zeros)
        return fn
    finally:
        mark_bg_thread(prev)


def _cached_fn(key):
    from . import device_exec
    with device_exec._PIPE_LOCK:
        hit = device_exec._PIPE_CACHE.get(key)
    return hit[0] if hit is not None else None


def _run_job(job: "_Job"):
    """Build + warm one executable with the full resilience ladder:
    supervisor deadline (a hung remote compile is abandoned + fenced like
    any device hang), compileRetry backoff on classified failures, then
    a terminal verdict into the compile-scoped breaker.  A job carrying a
    linked child trace runs UNDER it, so its supervisor/backoff spans and
    events land on the timeline attributed to the submitting query."""
    if job.tchild is not None:
        from ..session import tracing
        with tracing.adopt(job.tchild):
            return _run_job_traced(job)
    return _run_job_traced(job)


def _run_job_traced(job: "_Job"):
    from ..utils.backoff import (Backoffer, classify, CLASS_COMPILE,
                                 CLASS_DEVICE, CLASS_EXCHANGE, CLASS_HANG,
                                 CLASS_TRANSPORT)
    from . import supervisor
    from ..ops.device import DeviceUnsupported
    t0 = time.perf_counter()
    bo = Backoffer(budget_ms=2000.0)
    fn = None
    while True:
        try:
            with _LOCK:
                deadline = _CFG["timeout_s"]
            fn = supervisor.call_supervised(
                _do_compile, (job,), deadline_s=deadline, ctx=None,
                shape="compile", label=f"bg compile ({job.shape})")
            break
        except DeviceUnsupported:
            # the builder says this fragment can't run on device at all:
            # not a health verdict — drop the job quietly
            if job.br is not None:
                job.br.release_probe(session=job.sid)
            _finish_job(job, failed=True, charge=False)
            return
        except Exception as e:  # noqa: BLE001 — classified below
            cls = classify(e)
            with _LOCK:
                _LAST_ERROR[0] = f"{cls}: {e}"
            if cls not in (CLASS_COMPILE, CLASS_TRANSPORT, CLASS_DEVICE,
                           CLASS_EXCHANGE, CLASS_HANG):
                log.warning("background compile failed unclassified: %s",
                            e, exc_info=True)
                if job.br is not None:
                    job.br.release_probe(session=job.sid)
                _finish_job(job, failed=True, charge=False)
                return
            try:
                bo.backoff("compileRetry", e)
            except Exception:
                # retry budget exhausted: terminal classified failure —
                # the compile breaker opens after enough of these and
                # obtain() degrades fragments without queueing.  Wrapped
                # as DeviceCompileError (9010) so the breaker record and
                # the job's error carry the compile taxonomy class.
                from ..errors import DeviceCompileError
                term = DeviceCompileError(
                    f"background compile failed terminally ({cls}): {e}")
                term.__cause__ = e
                job.error = term
                if job.br is not None:
                    job.br.record_failure(term, session=job.sid)
                _finish_job(job, failed=True)
                return
    elapsed = time.perf_counter() - t0
    with _LOCK:
        STATS["compile_bg_seconds"] += elapsed
    if fn is None:
        # prewarm warm whose cached fn vanished (LRU/fence) and carried
        # no builder: nothing to install
        _finish_job(job, failed=True, charge=False)
        return
    import jax
    from . import device_exec
    stale = False
    with device_exec._PIPE_LOCK:
        # fence-generation read under the SAME lock the fence's cache
        # clear takes (_reinit_backend): either the clear ran first —
        # the generation this read returns is already bumped, so the
        # stale executable is discarded — or our put lands first and
        # the clear removes it.  Without the shared lock a clear could
        # slip between an unlocked gen check and the put, installing an
        # executable that pins the DEAD PJRT client.  (Lock order
        # _PIPE_LOCK → supervisor._LOCK; the supervisor never takes the
        # pipe lock while holding its own.)  The CPU client survives
        # fences, so its warms stay valid.
        if (jax.default_backend() != "cpu"
                and job.fence_gen != _fence_gen()):
            stale = True
        elif job.build is not None:
            device_exec._PIPE_CACHE[job.cache_key] = (fn, job.dict_refs)
            while len(device_exec._PIPE_CACHE) > \
                    device_exec._PIPE_CACHE_MAX:
                device_exec._PIPE_CACHE.popitem(last=False)
    if stale:
        _finish_job(job, discarded=True)
        return
    _set_origin(job.cache_key, job.origin)
    if job.br is not None:
        job.br.record_success(session=job.sid)
    _persist_record(job.cache_key, job.shape, job.sig, job.origin)
    _finish_job(job)
    log.info("background compile landed (%s, %.2fs): next same-shape "
             "query flips to device", job.shape, elapsed)


def _finish_job(job: "_Job", failed: bool = False, discarded: bool = False,
                charge: bool = True):
    with _LOCK:
        _JOBS.pop(job.jkey, None)
        if discarded:
            STATS["bg_discarded"] += 1
        elif failed:
            if charge:
                STATS["bg_failed"] += 1
            else:
                STATS["bg_discarded"] += 1
        else:
            STATS["bg_completed"] += 1
            if job.origin == "prewarm":
                STATS["compile_prewarmed"] += 1
    if job.br is not None:
        # paths that end a job WITHOUT a breaker verdict (fence discard,
        # the worker-loop catch-all) must still free a HALF_OPEN probe
        # slot the job inherited from obtain()'s allow(), or the breaker
        # wedges host-side until the grace reclaim; ownership-checked
        # and a no-op when record_success/failure already resolved it
        job.br.release_probe(session=job.sid)
    if job.tchild is not None:
        # retire the linked child trace on EVERY job outcome (finish is
        # idempotent — the worker-loop catch-all may land here twice)
        from ..session import tracing
        tracing.finish(job.tchild, succ=not failed and not discarded)
    job.done.set()
    _publish_gauges()


# -- prewarm ------------------------------------------------------------------

def _prewarm_claim_fleet(jkey) -> bool:
    """Fleet-wide prewarm dedup (ISSUE 14): N workers prewarming the
    same recipe ladder should trace each rung ONCE across the fleet —
    the persistent pipe-index already dedupes the XLA work, but the
    trace + warm dispatch are per-process; the coordination segment's
    claim makes the submission itself at-most-once.  Always True outside
    a fleet."""
    try:
        from ..fabric import state as fabric_state
        coord = fabric_state.coordinator()
        if coord is None:
            return True
        ident = hashlib.blake2b(repr(jkey).encode(),
                                digest_size=16).digest()
        return coord.prewarm_claim(ident)
    except Exception as e:  # noqa: BLE001 — dedup is best-effort
        log.warning("fleet prewarm claim failed (warming locally): %s", e)
        return True


def prewarm(ctx=None, ladder_up: int = 2, max_recipes: int = 32,
            wait: bool = False, timeout_s: float = 120.0) -> dict:
    """Background-compile the bucket ladder for the hot recipes: for each
    registered fragment signature (most-used first; signatures with
    learned capacities (device_join.learned_sigs) rank hottest — they are
    the shapes traffic converged on), warm the next `ladder_up` row
    buckets above the seen shape, plus rebuild any signature an off-CPU
    fence evicted.  `wait` blocks until the submitted warms finish
    (ADMIN COMPILE uses this so the statement returns a final count)."""
    _refresh_cfg(ctx)
    from .device_join import learned_sigs
    hot_sigs = learned_sigs()
    with _LOCK:
        warm0 = STATS["compile_prewarmed"]
        fail0 = STATS["bg_failed"]
        recipes = sorted(
            _RECIPES.values(),
            key=lambda r: (r.sig in hot_sigs if r.sig else False, r.uses),
            reverse=True)[:max_recipes]
    jobs = []
    for rec in recipes:
        targets = []
        if _cached_fn(rec.key) is None and rec.build is not None:
            # evicted/fenced: rebuild at the seen shape first
            # (builder-less join/MPP recipes can't rebuild — skip)
            targets.append((rec.spec, rec.build))
        if rec.bucket is not None:
            for nb in next_buckets(rec.bucket, ladder_up, rec.pd):
                targets.append((_scale_spec(rec.spec, rec.bucket, nb),
                                None))
        for spec, build in targets:
            # a REBUILD installs under the plain cache key, so it takes
            # the plain key as its job key too: a concurrent async
            # obtain() of the same signature then finds it in _JOBS and
            # serves host-side instead of double-submitting the same
            # multi-minute compile.  Pure shape warms (build None, never
            # install a new fn) keep a ladder-scoped key per bucket.
            jkey = (rec.key if build is not None
                    else (rec.key, ("ladder", _base_bucket(spec))))
            if not _prewarm_claim_fleet(jkey):
                continue  # another worker is already warming this rung
            with _LOCK:
                if jkey in _JOBS or rec.key in _JOBS:
                    continue
                job = _Job(jkey, rec.key, build, spec, rec.dict_refs,
                           rec.shape, rec.sig, None, None, "prewarm")
                _JOBS[jkey] = job
                STATS["bg_submitted"] += 1
            jobs.append(job)
            _ensure_workers()
            _JOB_Q.put(job)
    if wait:
        # poll in ticks and consult check_killed so ADMIN COMPILE stays
        # KILL-responsive while compiles run (same convention as the
        # scheduler's queued admission waits: KILL answers in ~a tick,
        # not after timeout_s)
        deadline = time.monotonic() + timeout_s
        check = getattr(ctx, "check_killed", None)
        for job in jobs:
            while (not job.done.wait(0.05)
                   and time.monotonic() < deadline):
                if check is not None:
                    check()
    _publish_gauges()
    with _LOCK:
        # DELTAS since this invocation started: ADMIN COMPILE reports
        # what THIS prewarm did, not process-lifetime totals
        return {"submitted": len(jobs),
                "prewarmed": STATS["compile_prewarmed"] - warm0,
                "failed": STATS["bg_failed"] - fail0}


def maybe_prewarm_on_start(domain):
    """Prewarm kick: called at Domain start and from SET GLOBAL
    ``tidb_compile_prewarm``.  Globals are in-memory only, so at Domain
    START the sysvar is never yet ON — the boot-time opt-in is the
    ``TIDB_TPU_COMPILE_PREWARM=ON`` env var (a serving process restart
    then rebuilds its ladder from the persistent index without waiting
    for a session to SET anything); the sysvar path fires the moment the
    operator SETs it (session/session.py)."""
    try:
        on = str(domain.global_vars.get("tidb_compile_prewarm",
                                        "OFF")).upper() in ("ON", "1")
    except Exception:
        on = False
    if not on:
        on = os.environ.get("TIDB_TPU_COMPILE_PREWARM",
                            "").upper() in ("ON", "1")
    if not on:
        return
    threading.Thread(target=prewarm, kwargs={"wait": False}, daemon=True,
                     name="compile-prewarm").start()


# -- fencing ------------------------------------------------------------------

def on_backend_reinit():
    """The supervisor tore down the backend (off-CPU fence): the pipe
    cache was cleared, so the origin map is stale; recipes stay — they
    are how prewarm rebuilds the ladder against the fresh client."""
    with _LOCK:
        _ORIGIN.clear()


# -- gauges / introspection ---------------------------------------------------

def queue_depth() -> int:
    with _LOCK:
        return len(_JOBS)


def snapshot() -> dict:
    with _LOCK:
        return {"compile_queue_depth": len(_JOBS),
                "recipes": len(_RECIPES),
                "workers": len([t for t in _WORKERS if t.is_alive()]),
                "last_error": _LAST_ERROR[0],
                **{k: (round(v, 3) if isinstance(v, float) else v)
                   for k, v in STATS.items()}}


def report_gauges() -> dict:
    """Surfacing policy shared by EXPLAIN ANALYZE and bench lines (same
    rule as scheduler.report_gauges): queue depth always, the counters
    only once they have ever fired."""
    s = snapshot()
    out = {"compile_queue_depth": s["compile_queue_depth"]}
    for k in ("compile_pending_fragments", "compile_persist_hits",
              "compile_prewarmed", "bg_failed"):
        if s[k]:
            out[k] = s[k]
    if s["compile_bg_seconds"]:
        out["compile_bg_seconds"] = s["compile_bg_seconds"]
    return out


def attach(ctx):
    dom = getattr(ctx, "domain", None)
    obs = getattr(dom, "observe", None)
    if obs is not None and hasattr(obs, "set_gauge"):
        with _LOCK:
            _SINKS.add(obs)


def observe_hist(name, value):
    """Record one latency sample into every attached observe registry
    (device_exec._charge_compile_s feeds `sync_compile_seconds` through
    here — the compile-layer histogram in /metrics)."""
    with _LOCK:
        sinks = list(_SINKS)
    for obs in sinks:
        f = getattr(obs, "observe_hist", None)
        if f is not None:
            f(name, value)


def _publish_gauges():
    with _LOCK:
        if not _SINKS:
            return
        sinks = list(_SINKS)
        vals = {
            "compile_queue_depth": len(_JOBS),
            "compile_pending_fragments":
                STATS["compile_pending_fragments"],
            "compile_bg_seconds": round(STATS["compile_bg_seconds"], 3),
            "compile_persist_hits": STATS["compile_persist_hits"],
        }
    for obs in sinks:
        try:
            for k, v in vals.items():
                obs.set_gauge(k, v)
        except Exception:
            pass


def wait_idle(timeout_s: float = 30.0) -> bool:
    """Block until no background compile is in flight (tests + ADMIN)."""
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        with _LOCK:
            if not _JOBS:
                return True
        time.sleep(0.01)
    with _LOCK:
        return not _JOBS


def verify_drained() -> dict:
    """Chaos invariant (mirrors scheduler.verify_drained and the PR 6
    ticket invariant): once traffic stops, no compile job is leaked —
    nothing in flight, and every submitted job is accounted completed,
    failed or discarded."""
    with _LOCK:
        in_flight = len(_JOBS)
        accounted = (STATS["bg_completed"] + STATS["bg_failed"]
                     + STATS["bg_discarded"])
        return {"ok": in_flight == 0
                and accounted == STATS["bg_submitted"],
                "in_flight": in_flight,
                "submitted": STATS["bg_submitted"],
                "accounted": accounted}


def reset_for_tests():
    """Drop recipes/origins/counters (unit tests only).  In-flight jobs
    are waited out first so a stale worker can't repopulate the stats."""
    wait_idle(timeout_s=10.0)
    with _LOCK:
        _RECIPES.clear()
        _ORIGIN.clear()
        for k in STATS:
            STATS[k] = 0.0 if k == "compile_bg_seconds" else 0
        _LAST_ERROR[0] = ""
