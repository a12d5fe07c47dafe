"""Device-runtime supervisor: hang detection, backend fencing, and
abandoned-call accounting for every device entry point.

Why this exists: a backend call that hangs (the July 2026 v5e run died
mid-bench that way, before Q5) blocks inside a C call HOLDING THE GIL, so
nothing in-process can interrupt it — SIGALRM never fires and `KILL` is
never polled; one stuck backend cost the whole run.  PR 1 made device
*failures* survivable
(classified errors → circuit breaker → host fallback); this module makes
device *hangs* survivable too.

Model (deadline → classify → fence → breaker → degrade):

1. **Supervised dispatch** — `supervised_call` runs the device call on a
   reusable daemon WORKER thread while the calling thread waits on an
   event with a hard wall-clock deadline, polling the session's
   ``check_killed`` every ~20ms.  A GIL-blocked backend call can no
   longer freeze the session: the *waiter* holds no C frames, so
   `KILL` / `max_execution_time` / the deadline all stay live.
2. **Classify** — deadline expiry raises :class:`DeviceHangError`
   (errno 9008, taxonomy class ``hang`` in ``utils/backoff.classify``)
   into the query.  ``executor/device_exec.run_device`` records it
   against the per-(Domain, fragment shape) circuit breaker, so repeated
   hangs trip degradation to the host engine exactly like repeated
   classified failures.
3. **Fence** — the abandoned call keeps its worker thread (Python cannot
   kill a thread blocked in C); the supervisor marks the backend
   QUARANTINED.  Before the next device fragment dispatches,
   `_maybe_reinit` drops every compiled-executable cache that pins the
   suspect backend (the fused-pipeline cache, the topk kernel cache, the
   MPP placement cache, jax's own jit caches) and — on a non-CPU
   backend, where the arrays behind those caches are dead anyway —
   attempts a full PJRT client teardown so the next dispatch re-dials.
4. **Account** — "abandoned calls outstanding" is an explicit gauge:
   surfaced in EXPLAIN ANALYZE (``device_abandoned_calls``),
   ``session/observe.py`` gauges (``device_abandoned_calls``) and the
   HTTP status API (``/status`` + ``/metrics``).  A worker whose
   abandoned call eventually unblocks decrements the gauge and rejoins
   the pool.

Deadline sources (`effective_deadline`): the ``tidb_device_call_timeout``
sysvar (seconds, 0 = unsupervised inline dispatch — the default, so the
hot path pays nothing) and the remaining ``max_execution_time`` window of
the current statement; the tighter one wins.

Thread-local bridging: the compiled-fragment stats
(``device_exec._PIPE_TLS``) and paged-stage stats
(``device_join.LAST_PAGED_STATS``) are thread-local so concurrent
sessions don't cross-charge compiles.  A supervised call runs `fn` on a
worker thread, so the worker captures its own deltas and the waiter
merges them back into the calling thread — EXPLAIN ANALYZE and bench
compile attribution survive supervision.
"""

from __future__ import annotations

import itertools
import logging
import queue
import threading
import time
import weakref

from ..errors import DeviceHangError

log = logging.getLogger("tidb_tpu.supervisor")

#: waiter poll period — bounds KILL / deadline detection latency
_POLL_S = 0.02

_LOCK = threading.Lock()
_REINIT_LOCK = threading.Lock()
_IDLE: list["_Worker"] = []
_WORKER_SEQ = itertools.count()

#: abandoned calls still blocked on their worker threads (the gauge)
_ABANDONED = [0]
#: backend suspect: fence before the next supervised/inline dispatch.
#: The generation counter bumps on every NEW quarantine so a reinit in
#: flight never clears a fence requested concurrently (by a second hang
#: against the freshly re-dialed client) — that fence gets its own reinit
_QUARANTINED = [False]
_QUAR_GEN = [0]

STATS = {
    "supervised": 0,   # calls dispatched through a worker thread
    "coproc": 0,       # hybrid-join host passes run via submit_coproc
    "hangs": 0,        # deadline expiries (DeviceHangError raised)
    "kills": 0,        # waits abandoned by KILL/external interrupt
    "abandoned": 0,    # total calls ever abandoned (hangs + kills)
    "reclaimed": 0,    # abandoned calls that eventually completed
    "fences": 0,       # backend quarantine → reinit cycles performed
    "workers": 0,      # worker threads ever spawned
}

#: Observability sinks (session/observe.py) that mirror the gauge —
#: auto-registered from the contexts supervised calls run under
_SINKS: "weakref.WeakSet" = weakref.WeakSet()


class _Job:
    __slots__ = ("fn", "args", "kw", "done", "result", "exc", "orphaned",
                 "tls", "label", "group", "trace")

    def __init__(self, fn, args, kw, label):
        self.fn = fn
        self.args = args
        self.kw = kw
        self.done = threading.Event()
        self.result = None
        self.exc = None
        self.orphaned = False  # waiter gave up: discard result, re-pool
        self.tls = None        # worker-thread stats bridged to the waiter
        self.label = label
        # the dispatching session's resource group: bridged onto the
        # worker thread so residency charges supervised uploads to the
        # right tenant (ops/residency per-group shares), not "default"
        self.group = "default"
        # the dispatching thread's (trace, span) — adopted by the worker
        # so spans/events recorded inside the supervised call still nest
        # under the statement's supervisor.call span (session/tracing.py)
        self.trace = None


class _Worker(threading.Thread):
    """One reusable supervised-dispatch thread.  A worker abandoned
    mid-hang stays blocked until the backend call returns (or never);
    when it does return it decrements the abandoned gauge and rejoins
    the idle pool — worker threads are lost only to PERMANENT hangs."""

    def __init__(self):
        super().__init__(daemon=True,
                         name=f"device-supervisor-{next(_WORKER_SEQ)}")
        self.inbox: "queue.SimpleQueue[_Job]" = queue.SimpleQueue()
        with _LOCK:
            STATS["workers"] += 1
        self.start()

    def run(self):
        while True:
            job = self.inbox.get()
            # the supervisor's own bookkeeping must never prevent
            # job.done from flipping — a stats-capture failure here would
            # otherwise strand the waiter into a FALSE hang (fence, gauge
            # stuck >0) for a perfectly healthy call
            try:
                st0 = _tls_begin()
            except Exception:
                st0 = None
            try:
                from ..ops import residency
                residency.set_group(job.group)
            except Exception:
                pass
            try:
                if job.trace is not None:
                    from ..session import tracing
                    with tracing.adopt(*job.trace):
                        job.result = job.fn(*job.args, **job.kw)
                else:
                    job.result = job.fn(*job.args, **job.kw)
            except BaseException as e:  # noqa: BLE001 — re-raised in waiter
                job.exc = e
            if st0 is not None:
                try:
                    job.tls = _tls_end(st0)
                except Exception:
                    pass
            # done must flip inside the SAME lock hold that reads the
            # orphaned flag: _abandon checks done.is_set() under _LOCK, so
            # a completion racing the deadline is seen by exactly one side
            # — otherwise a call finishing at the deadline double-accounts
            # (gauge leaks, healthy backend fenced)
            with _LOCK:
                orphaned = job.orphaned
                if orphaned:
                    _ABANDONED[0] -= 1
                    STATS["reclaimed"] += 1
                job.done.set()
            if orphaned:
                _publish()
                log.info("abandoned device call %s completed after the "
                         "deadline (result discarded)", job.label)
            with _LOCK:
                _IDLE.append(self)


def _get_worker() -> _Worker:
    with _LOCK:
        if _IDLE:
            return _IDLE.pop()
    return _Worker()


# -- thread-local stats bridging --------------------------------------------

def _tls_begin():
    from .device_exec import pipe_cache_stats
    from .device_join import LAST_PAGED_STATS
    LAST_PAGED_STATS.clear()  # this worker's stale stats from a prior job
    return pipe_cache_stats(thread_local=True)


def _tls_end(st0):
    from .device_exec import pipe_cache_stats
    from .device_join import LAST_PAGED_STATS
    st1 = pipe_cache_stats(thread_local=True)
    return ({k: st1[k] - st0[k] for k in st1},
            dict(LAST_PAGED_STATS.items()))


def _tls_apply(tls):
    """Merge the worker's per-call stats deltas into the CALLING thread's
    thread-locals (process-wide totals were already bumped by the worker —
    only the attribution view moves)."""
    if tls is None:
        return
    delta, paged = tls
    from .device_exec import _tls_stats
    st = _tls_stats()
    for k, v in delta.items():
        st[k] += v
    if paged:
        from .device_join import LAST_PAGED_STATS
        LAST_PAGED_STATS.clear()
        LAST_PAGED_STATS.update(paged)


# -- gauge / observability ---------------------------------------------------

def abandoned_calls() -> int:
    """Device calls abandoned by the supervisor and still blocked on
    their worker threads (the "abandoned calls outstanding" gauge)."""
    with _LOCK:
        return _ABANDONED[0]


def snapshot() -> dict:
    with _LOCK:
        return {"abandoned_outstanding": _ABANDONED[0],
                "quarantined": _QUARANTINED[0], **STATS}


def _publish():
    n = abandoned_calls()
    with _LOCK:
        # materialize under the registration lock: a WeakSet being added
        # to concurrently raises mid-iteration (GC-driven discards are
        # already deferred by WeakSet's own iteration guard)
        sinks = list(_SINKS)
    for obs in sinks:
        try:
            obs.set_gauge("device_abandoned_calls", n)
        except Exception:
            pass


def _register_sink(ctx):
    dom = getattr(ctx, "domain", None)
    obs = getattr(dom, "observe", None)
    if obs is not None and hasattr(obs, "set_gauge"):
        with _LOCK:
            _SINKS.add(obs)


# -- backend fencing ---------------------------------------------------------

def _quarantine_locked():
    """Mark the backend suspect (caller holds _LOCK) — the ONE mutation
    both fence() and the hang-abandon path share.  Bumping the DEVICE
    EPOCH here (ops/residency.py) invalidates every cached HBM upload
    (`Column._device`, join-leaf dcols) at the same instant the backend
    becomes suspect: a restarted PJRT client can never serve a stale
    pre-fence buffer (ROADMAP "device-epoch on Column caches" — DONE).
    Lock order is supervisor._LOCK → residency._LOCK; residency never
    calls back into the supervisor."""
    _QUARANTINED[0] = True
    _QUAR_GEN[0] += 1
    try:
        from ..ops import residency
        residency.bump_epoch("backend quarantined")
    except Exception:
        log.warning("device-epoch bump failed during quarantine",
                    exc_info=True)


def fence(reason: str = ""):
    """Mark the JAX backend suspect: the next device dispatch (supervised
    or inline — run_device checks too) reinitializes before running."""
    with _LOCK:
        _quarantine_locked()
    if reason:
        log.warning("device backend fenced: %s", reason)


def quarantined() -> bool:
    return _QUARANTINED[0]


def fence_generation() -> int:
    """Monotonic count of backend quarantines (bumps on every NEW fence
    request).  The compile service stamps each background job with the
    generation at submit time: a job whose build straddled an off-CPU
    fence produced an executable pinning the DEAD client — comparing
    generations at landing time makes that discard exact."""
    with _LOCK:
        return _QUAR_GEN[0]


def _maybe_reinit():
    """If the backend is quarantined, drop every cache pinning compiled
    executables / placements of the suspect client and reinitialize.
    Never raises — a failed fence must not take down the query that
    merely came next."""
    if not _QUARANTINED[0]:
        return
    with _REINIT_LOCK:
        with _LOCK:
            if not _QUARANTINED[0]:
                return
            gen = _QUAR_GEN[0]
        try:
            _reinit_backend()
        except Exception as e:
            log.warning("backend reinit failed (continuing): %s", e)
        with _LOCK:
            if _QUAR_GEN[0] == gen:
                # no NEW quarantine arrived while reinitializing — clear;
                # otherwise leave the flag set so the fresh fence request
                # gets its own reinit on the next dispatch
                _QUARANTINED[0] = False
            STATS["fences"] += 1


def _reinit_backend():
    import jax
    if jax.default_backend() == "cpu":
        # the in-process XLA-CPU client cannot lose its device: its
        # compiled executables stay valid through any stall (test hangs
        # are injected sleeps), so flushing them would only force cold
        # recompiles — and a deadline shorter than compile time would
        # livelock on hang→flush→cold-compile→hang. The fence is pure
        # accounting here; real reinit work is the off-CPU path below.
        return
    # compiled-executable caches first: they pin jitted programs (and the
    # dictionaries/arrays they close over) against the suspect client
    from ..utils.backoff import classify
    try:
        from . import device_exec
        # under the pipe-stats lock: _pipe_cache_get's locked
        # get/move_to_end pair must never interleave with this clear,
        # and a _topk_indices install racing it unlocked could
        # re-publish a kernel pinning the dead client
        with device_exec._PIPE_LOCK:
            device_exec._PIPE_CACHE.clear()
            device_exec._TOPK_CACHE.clear()
    except Exception as e:
        # best-effort: the fence proceeds, but a cache that would not
        # clear may still pin dead-client executables — log it
        log.warning("fence: pipe-cache clear failed (%s): %s",
                    classify(e), e)
    try:
        from . import mpp_exec
        # under the placement lock: _place_col's locked check/popitem
        # pair must never interleave with this clear
        with mpp_exec._PLACE_LOCK:
            mpp_exec._MPP_PLACE_CACHE.clear()
    except Exception as e:
        log.warning("fence: mpp placement-cache clear failed (%s): %s",
                    classify(e), e)
    try:
        # the compile service's origin map described entries of the pipe
        # cache just cleared above; its RECIPES survive — they are how
        # the prewarm ladder rebuilds against the fresh client
        from . import compile_service
        compile_service.on_backend_reinit()
    except Exception as e:
        log.warning("fence: compile-service reinit hook failed (%s): %s",
                    classify(e), e)
    try:
        jax.clear_caches()
    except Exception as e:
        log.warning("fence: jax.clear_caches failed (%s): %s",
                    classify(e), e)
    # hard teardown: a hung client's arrays are dead anyway, so
    # re-creating the PJRT client is the only road back
    try:
        from jax.extend.backend import clear_backends
        clear_backends()
        log.warning("JAX backend torn down after hang; next "
                    "dispatch re-initializes the PJRT client")
    except Exception as e:
        log.warning("fence: backend teardown failed (%s): %s",
                    classify(e), e)


# -- deadlines ---------------------------------------------------------------

def deadline_for(ctx) -> tuple:
    """(deadline_s, fence_on_expiry) for one device call.

    deadline_s is min(`tidb_device_call_timeout`, remaining
    `max_execution_time` window); 0 = unsupervised (inline dispatch,
    today's default).  fence_on_expiry is False when the BINDING
    constraint is the user's max_execution_time: its expiry is a
    statement-time limit, not evidence the backend hung — the call is
    abandoned but the backend is neither fenced nor charged to the
    breaker (expiry surfaces as QueryInterrupted, the same answer the
    racing kill Timer gives)."""
    if ctx is None:
        return 0.0, True
    t = 0.0
    try:
        t = float(ctx.get_sysvar("tidb_device_call_timeout"))
    except Exception:
        pass
    met_ms = 0.0
    try:
        met_ms = float(ctx.get_sysvar("max_execution_time"))
    except Exception:
        pass
    if met_ms > 0:
        rem = met_ms / 1000.0
        start = getattr(ctx, "stmt_start", None)
        if start:
            # floor, not zero: the kill Timer is the authority on expiry;
            # the supervisor just needs the wait to stay interruptible
            rem = max(rem - (time.time() - start), 0.05)
        if t <= 0 or rem < t:
            return rem, False
    return max(t, 0.0), True


def effective_deadline(ctx) -> float:
    """Seconds of wall clock a device call may take before it is declared
    hung (see :func:`deadline_for` for the expiry semantics)."""
    return deadline_for(ctx)[0]


# -- the supervised dispatch -------------------------------------------------

class _DeadlineExpired(Exception):
    pass


def supervised_call(fn, /, *args, deadline_s: float = 0.0, ctx=None,
                    shape: str = "", label: str = "", **kw):
    """Convenience form of :func:`call_supervised` — safe only when `fn`
    takes no keyword that collides with the supervisor's own parameters
    (run_device dispatches fragments whose kwargs include ``ctx=``, so it
    uses the explicit core instead)."""
    return call_supervised(fn, args, kw, deadline_s=deadline_s, ctx=ctx,
                           shape=shape, label=label)


def call_supervised(fn, args=(), kw=None, *, deadline_s: float = 0.0,
                    ctx=None, shape: str = "", label: str = "",
                    fence_on_expiry: bool = True):
    """Run ``fn(*args, **kw)`` under the supervisor.

    deadline_s <= 0: inline call (after the fence check) — zero overhead,
    the default when no timeout sysvar is set.  Otherwise the call runs
    on a worker thread; the waiter polls ``ctx.check_killed`` and the
    deadline.  Raises :class:`DeviceHangError` on expiry (call abandoned,
    backend fenced); a KILL raises the session's QueryInterruptedError
    with the call abandoned but the backend NOT fenced (no evidence it is
    unhealthy — its verdict simply stopped mattering)."""
    kw = kw or {}
    _maybe_reinit()
    from ..session import tracing
    if deadline_s is None or deadline_s <= 0:
        # the unsupervised hot path stays a bool check + plain call —
        # sink registration only matters once supervision can fire
        # (tracing off adds exactly the one active() branch)
        if tracing.active() is None:
            return fn(*args, **kw)
        with tracing.span("supervisor.call", inline=True, shape=shape):
            return fn(*args, **kw)
    with tracing.span("supervisor.call", deadline_s=round(deadline_s, 3),
                      shape=shape):
        return _call_on_worker(fn, args, kw, deadline_s, ctx, shape,
                               label, fence_on_expiry)


def _call_on_worker(fn, args, kw, deadline_s, ctx, shape, label,
                    fence_on_expiry):
    from ..session import tracing
    _register_sink(ctx)
    label = label or getattr(fn, "__name__", "device call")
    job = _Job(fn, args, kw, label)
    job.trace = tracing.capture()
    try:
        from ..ops import residency
        job.group = residency.current_group()
    except Exception:
        pass
    with _LOCK:
        STATS["supervised"] += 1
    _get_worker().inbox.put(job)
    check = getattr(ctx, "check_killed", None)
    deadline = time.monotonic() + deadline_s
    try:
        while not job.done.wait(_POLL_S):
            if check is not None:
                check()
            if time.monotonic() >= deadline:
                raise _DeadlineExpired()
    except _DeadlineExpired:
        if not _abandon(job, hang=fence_on_expiry):
            # the call completed inside the deadline race window (one
            # poll tick): nothing was abandoned or fenced — use the
            # finished result instead of raising a hang that the
            # gauges/stats would contradict
            _tls_apply(job.tls)
            if job.exc is not None:
                raise job.exc
            return job.result
        tracing.event("supervisor.abandoned", label=label,
                      deadline_s=round(deadline_s, 3),
                      fenced=fence_on_expiry)
        if not fence_on_expiry:
            # the binding deadline was the user's max_execution_time: a
            # statement-time limit, not a backend-health verdict — no
            # fence, no breaker charge, same answer as the kill Timer
            from ..errors import QueryInterruptedError
            raise QueryInterruptedError(
                "Query execution was interrupted, maximum statement "
                f"execution time exceeded (device call '{label}' "
                "abandoned)") from None
        exc = DeviceHangError(
            f"device call '{label}' exceeded its {deadline_s:.3f}s "
            "deadline (tidb_device_call_timeout/max_execution_time); "
            "call abandoned on its worker thread, backend fenced for "
            "reinit before the next fragment")
        exc.shape = shape
        exc.deadline_s = deadline_s
        raise exc from None  # the internal deadline marker is noise
    except BaseException:
        # KILL (check_killed), SIGALRM-driven timeouts in the waiter,
        # Ctrl-C: the in-flight call is orphaned but the backend earned
        # no hang verdict — account, don't fence
        _abandon(job, hang=False)
        raise
    _tls_apply(job.tls)
    if job.exc is not None:
        raise job.exc
    return job.result


def submit_coproc(fn, args=(), kw=None, *, label: str = ""):
    """Dispatch ``fn`` on a pooled supervisor worker WITHOUT blocking the
    caller — the host half of a hybrid-join co-processing pass
    (executor/hybrid_join.py): the calling thread keeps driving the
    device partitions while the worker joins the spilled partitions in
    numpy.  The pair runs under the caller's ONE admission ticket (the
    WFQ already governs the dispatch this pass belongs to — this is one
    admitted fragment using host and device at once, not a second
    dispatch, so no new ticket and no breaker interaction here).

    Trace context and residency tenant group bridge onto the worker like
    any supervised call.  Returns ``join(ctx=None)``: wait for
    completion (KILL-interruptible through ``ctx.check_killed``),
    re-raise the worker's exception, or return its result.  A waiter
    that gives up (kill/exception) abandons the job kill-style: no fence
    — the worker is running numpy, not a suspect backend."""
    kw = kw or {}
    job = _Job(fn, args, kw, label or getattr(fn, "__name__", "coproc"))
    from ..session import tracing
    job.trace = tracing.capture()
    try:
        from ..ops import residency
        job.group = residency.current_group()
    except Exception:
        pass
    with _LOCK:
        STATS["supervised"] += 1
        STATS["coproc"] += 1
    _get_worker().inbox.put(job)

    def join(ctx=None):
        check = getattr(ctx, "check_killed", None)
        try:
            while not job.done.wait(_POLL_S):
                if check is not None:
                    check()
        except BaseException:
            _abandon(job, hang=False)
            raise
        _tls_apply(job.tls)
        if job.exc is not None:
            raise job.exc
        return job.result

    return join


def _abandon(job: _Job, hang: bool) -> bool:
    """Mark the job orphaned; returns False when it actually COMPLETED in
    the race window (nothing outstanding — the caller should use the
    result instead of reporting an abandonment)."""
    with _LOCK:
        if job.done.is_set():
            return False  # completed in the race window
        job.orphaned = True
        _ABANDONED[0] += 1
        STATS["abandoned"] += 1
        if hang:
            STATS["hangs"] += 1
            _quarantine_locked()
        else:
            STATS["kills"] += 1
    if hang:
        log.warning("device call '%s' abandoned after deadline; backend "
                    "quarantined (%d abandoned calls outstanding)",
                    job.label, abandoned_calls())
    _publish()
    return True
