"""Unified retry/backoff budgets (reference: store/tikv/backoff.go).

The reference routes EVERY retriable distributed call through one
``Backoffer``: each retry *kind* (boTxnLock, boRegionMiss, ...) has a
capped exponential sleep curve with jitter, and the backoffer as a whole
carries a per-request sleep budget (``maxSleep`` scaled by
``tidb_backoff_weight``).  Exhausting the budget surfaces a *classified*
error that names every error the retries saw — never an unbounded loop.

This module is the in-process translation: the five ad-hoc retry loops
that grew in kv/store.py, session.py, ddl_worker.py and mpp_exec.py all
route through one Backoffer so a query's total retry budget is a single
number, KILL/max_execution_time can interrupt a sleeping retry, and
exhaustion is always a classified error.

Error taxonomy (classify()): the classes the distributed path can see —

    region     lock waits, write conflicts (the reference's region/lock
               errors: another writer owns the range right now)
    lease      leader-election or lease loss (coordinator campaigns)
    exchange   MPP exchange send/recv failure or shuffle overflow
    device     accelerator compile/OOM/runtime failure
    transport  a socket peer (the compile server) refused, reset or
               timed out ("Connection refused")
    compile    the compile service could not BUILD a device executable
               (executor/compile_service.py — a remote-compile RPC died
               mid-build or an injected compile fault fired; distinct
               from `device`, which is an executable that RAN and failed)
    hang       a supervised device call blew its wall-clock deadline
               (executor/supervisor.py — the backend froze inside a
               GIL-holding C call, distinct from a device that ERRORS)
    admission  the serving scheduler refused the fragment a device slot
               (executor/scheduler.py — load pressure, not ill-health:
               the fragment degrades to the host engine)
    fault      an injected failpoint fired
    other      anything unclassified
"""

from __future__ import annotations

import logging
import random
import threading
import time

from ..errors import (BackoffExhaustedError, DeadlockError, LockedError,
                      SchemaChangedError, TiDBError, WriteConflictError)

log = logging.getLogger("tidb_tpu.backoff")

# -- error taxonomy ---------------------------------------------------------

CLASS_REGION = "region"
CLASS_LEASE = "lease"
CLASS_EXCHANGE = "exchange"
CLASS_DEVICE = "device"
CLASS_TRANSPORT = "transport"
CLASS_COMPILE = "compile"
CLASS_HANG = "hang"
CLASS_ADMISSION = "admission"
CLASS_FAULT = "fault"
CLASS_OTHER = "other"


#: message substrings (lowercased match) that mark a device runtime error
#: as OUT-OF-MEMORY — the jaxlib/XLA phrasings seen across backends:
#: "RESOURCE_EXHAUSTED: Out of memory allocating 12345 bytes", PJRT's
#: "Resource exhausted: Failed to allocate request for ...", the TPU
#: runtime's "Attempting to allocate ... exceeds ... memory available",
#: plus the allocator's generic failure lines. One table so the
#: classifier, the OOM-recovery ladder (executor/device_exec.run_device)
#: and the taxonomy unit test all agree.
DEVICE_OOM_MARKERS = (
    "resource_exhausted",
    "resource exhausted",
    "out of memory",
    "out_of_memory",
    "failed to allocate",
    "allocation failure",
    "exceeds the amount of memory available",
)

#: exception TYPE NAMES (matched anywhere in the MRO — jaxlib moves and
#: subclasses its runtime error across versions) that mark a device
#: runtime failure
DEVICE_ERROR_TYPE_NAMES = ("XlaRuntimeError", "JaxRuntimeError")


def _mro_names(err) -> set:
    return {c.__name__ for c in type(err).__mro__}


def classify(err) -> str:
    """Map an exception to its resilience class (one label the breaker,
    the backoffer and the slow log all agree on)."""
    from .failpoint import (FailpointError, InjectedCompileError,
                            InjectedSpillError)
    from ..errors import (DeviceAdmissionError, DeviceCompileError,
                          DeviceHangError)
    if isinstance(err, DeviceHangError):
        return CLASS_HANG
    if isinstance(err, DeviceAdmissionError):
        return CLASS_ADMISSION
    if isinstance(err, (DeviceCompileError, InjectedCompileError)):
        return CLASS_COMPILE
    if isinstance(err, (LockedError, WriteConflictError, DeadlockError,
                        SchemaChangedError)):
        return CLASS_REGION
    if isinstance(err, ExchangeError):
        return CLASS_EXCHANGE
    if isinstance(err, LeaseExpiredError):
        return CLASS_LEASE
    if isinstance(err, (FailpointError, InjectedSpillError)):
        # a spill-write failure mid-hybrid-join degrades to host like any
        # other injected fault (breaker-charged, spill pages drained)
        return CLASS_FAULT
    # deliberately NOT all of OSError: FileNotFoundError/PermissionError
    # and friends are programming/environment bugs that must surface, not
    # be retried or fed to the breaker as device-health signals
    if isinstance(err, (ConnectionError, BrokenPipeError, TimeoutError)):
        return CLASS_TRANSPORT
    msg = str(err)
    low = msg.lower()
    # the MRO walk (not just the leaf type name) catches jaxlib subclasses
    # of XlaRuntimeError whose leaf name says nothing about the runtime
    if (any(n in _mro_names(err) for n in DEVICE_ERROR_TYPE_NAMES)
            or any(m in low for m in DEVICE_OOM_MARKERS)):
        return CLASS_DEVICE
    if "Connection refused" in msg:
        return CLASS_TRANSPORT
    return CLASS_OTHER


def is_device_oom(err) -> bool:
    """Is this a device OUT-OF-MEMORY specifically (the errors worth an
    evict-all + retry before host degradation), as opposed to any other
    classified device failure (compile bug, lost device) where retrying
    against an emptied HBM would change nothing?"""
    if classify(err) != CLASS_DEVICE:
        return False
    low = str(err).lower()
    return any(m in low for m in DEVICE_OOM_MARKERS)


class ExchangeError(TiDBError):
    """MPP exchange send/recv failed (reference: ErrTiFlashServerTimeout
    9012 — the store-side fragment could not be reached/completed)."""

    code = 9012
    sqlstate = "HY000"


class LeaseExpiredError(TiDBError):
    """A coordinator lease/election was lost mid-operation."""

    code = 8229  # reference: ErrTxnAbortedByGC-adjacent domain errors
    sqlstate = "HY000"


# -- retry kinds ------------------------------------------------------------

class Kind:
    """One retry curve: capped exponential sleep + optional attempt cap
    (reference: the backoff fn table in store/tikv/backoff.go NewBackoffFn)."""

    __slots__ = ("name", "base_ms", "cap_ms", "jitter", "max_attempts")

    def __init__(self, name, base_ms, cap_ms, jitter="full", max_attempts=0):
        self.name = name
        self.base_ms = base_ms
        self.cap_ms = cap_ms
        self.jitter = jitter  # "full" | "equal" | "none"
        self.max_attempts = max_attempts  # 0 = budget-bound only


#: the kind table — names follow the reference's bo* constants
KINDS = {k.name: k for k in [
    # reads waiting out a committing writer's prewrite locks (boTxnLockFast)
    Kind("txnLockFast", base_ms=2, cap_ms=30, jitter="equal"),
    # pessimistic lock waits (boTxnLock)
    Kind("txnLock", base_ms=5, cap_ms=60, jitter="equal"),
    # optimistic commit conflict replay (boTxnConflict-ish)
    Kind("txnRetry", base_ms=1, cap_ms=20, jitter="full"),
    # independent meta txns: autoid / sequence batch allocation
    Kind("autoid", base_ms=0.5, cap_ms=10, jitter="full", max_attempts=20),
    # DDL backfill batch vs concurrent DML
    Kind("ddlBackfill", base_ms=0.5, cap_ms=10, jitter="full",
         max_attempts=20),
    # MPP exchange capacity regrowth (recompile, no sleep — the "retry"
    # is a bigger buffer, not waiting for a remote)
    Kind("exchangeGrow", base_ms=0, cap_ms=0, jitter="none",
         max_attempts=12),
    # MPP exchange send/recv transport failure (boTiFlashRPC)
    Kind("exchangeRetry", base_ms=2, cap_ms=40, jitter="equal",
         max_attempts=6),
    # background-compile RPC/transport failure (executor/compile_service):
    # a flaky compile endpoint is retried on a short curve before the
    # job fails classified and charges the compile-scoped breaker
    Kind("compileRetry", base_ms=5, cap_ms=100, jitter="equal",
         max_attempts=4),
    # WAL fsync failure (kv/wal.py): ONE budgeted retry before the owner
    # aborts the commit — a transient EIO/ENOSPC blip should not abort a
    # durable txn, but a sick disk must fail fast, not spin
    Kind("walSyncRetry", base_ms=5, cap_ms=50, jitter="equal",
         max_attempts=2),
    # network-coordinator transport failure (fabric/coord_net.py): a few
    # short attempts before the client opens its down-window and degrades
    # to local-only admission
    Kind("coordRetry", base_ms=2, cap_ms=50, jitter="equal",
         max_attempts=4),
    # fleet-frontier freshness wait (kv/shared_store.fresh_read_ts): a
    # snapshot blocking until the local replica applies through every
    # live origin's durable commit frontier.  Short sleeps — the tailer
    # normally closes the gap in one TAIL_INTERVAL_S tick; exhaustion is
    # the LOUD stale-read refusal (FreshnessWaitError 9011) and trips
    # the lagging origin's freshness breaker
    Kind("freshnessWait", base_ms=2, cap_ms=40, jitter="equal"),
    # waiting out a foreign DDL owner lease (ddl.ddl_owner_lease): the
    # segment's epoch-fenced DDL cell is held by another worker running
    # a job; poll until it releases or its lease dies
    Kind("ddlOwnerWait", base_ms=20, cap_ms=200, jitter="equal"),
]}
# (no "lease"/"device" kinds yet: campaign losses degrade by skipping the
# round, and device failures route through the circuit breaker, not a
# retry curve — add entries here only when a caller actually backs off)

#: default per-request sleep budget before tidb_backoff_weight scaling
#: (the reference's copNextMaxBackoff is 20s; in-process sleeps are ms-scale
#: so the budget is too)
DEFAULT_BUDGET_MS = 1000.0


class Backoffer:
    """Per-request retry budget (reference: tikv.Backoffer).

    One Backoffer spans one logical request (a statement, a DDL job step,
    an MPP fragment dispatch).  Every retry calls :meth:`backoff`, which
    sleeps per the kind's curve and raises :class:`BackoffExhaustedError`
    — carrying the classified history of everything that went wrong —
    once the sleep budget or the kind's attempt cap is exhausted.

    ``seed`` makes the jitter deterministic for tests that assert on the
    sleep curve (production Backoffers are entropy-seeded; the chaos
    harness's bit-for-bit replays rest on its single-threaded schedule,
    not on retry timing); ``check_killed`` lets KILL and the
    max_execution_time watchdog interrupt a sleeping retry loop.
    """

    def __init__(self, budget_ms: float | None = None, weight: float = 1.0,
                 seed: int | None = None, check_killed=None,
                 sleep: bool = True, wall_clock: bool = False):
        base = DEFAULT_BUDGET_MS if budget_ms is None else float(budget_ms)
        self.budget_ms = base * max(float(weight), 0.0)
        self.slept_ms = 0.0
        self.attempts: dict[str, int] = {}
        self.errors: list[tuple[str, str, str]] = []  # (kind, class, msg)
        self._rng = random.Random(seed)
        self._check_killed = check_killed
        self._sleep = sleep
        # wall_clock: the budget is a hard ELAPSED-time deadline (user-
        # facing lock waits), not just accumulated sleep — retries whose
        # re-execution is itself slow must still stop at the deadline
        self._wall_clock = wall_clock
        self._t0 = time.monotonic()

    # -- construction helpers ------------------------------------------

    @classmethod
    def for_session(cls, session, budget_ms: float | None = None,
                    seed: int | None = None) -> "Backoffer":
        """Budget drawn from the session: scaled by tidb_backoff_weight,
        clamped to the remaining max_execution_time window, interruptible
        by the KILL watchdog (reference: the backoffer created per
        coprocessor request under the stmt context)."""
        weight = 1.0
        try:
            weight = max(float(session.get_sysvar("tidb_backoff_weight")),
                         1.0)
        except Exception:
            pass
        base = DEFAULT_BUDGET_MS if budget_ms is None else float(budget_ms)
        budget = base * weight
        try:
            exec_ms = float(session.get_sysvar("max_execution_time"))
        except Exception:
            exec_ms = 0.0
        if exec_ms > 0:
            # the execution-time cap clamps the WEIGHTED budget: no
            # tidb_backoff_weight setting may stretch retries past it
            budget = min(budget, exec_ms)
        return cls(budget_ms=budget, seed=seed,
                   check_killed=getattr(session, "check_killed", None))

    # -- the core step --------------------------------------------------

    def backoff(self, kind: str, err=None) -> int:
        """Record one failed attempt of `kind` and sleep its curve.
        Returns the attempt number (1-based).  Raises BackoffExhaustedError
        when the attempt cap or the sleep budget is exhausted, chaining
        the triggering error."""
        k = KINDS[kind]
        n = self.attempts.get(kind, 0) + 1
        self.attempts[kind] = n
        cls = ""
        if err is not None:
            cls = classify(err)
            self.errors.append((kind, cls, str(err)))
        if self._check_killed is not None:
            self._check_killed()
        if k.max_attempts and n >= k.max_attempts:
            raise self._exhausted(kind, err, f"{kind} attempt cap "
                                  f"{k.max_attempts} reached")
        sleep_ms = self._sleep_ms(k, n)
        if self._wall_clock:
            elapsed_ms = (time.monotonic() - self._t0) * 1000
            if elapsed_ms + sleep_ms > self.budget_ms:
                raise self._exhausted(kind, err, "deadline "
                                      f"{self.budget_ms:.0f}ms exceeded")
        if self.slept_ms + sleep_ms > self.budget_ms:
            raise self._exhausted(kind, err, "sleep budget "
                                  f"{self.budget_ms:.0f}ms exhausted")
        # span tracing (session/tracing.py): each backoff sleep is an
        # event on the statement's trace with its errno CLASS — "where
        # did the time go" includes retry waits, not just device work
        # (lazy import: this module sits under the session package in
        # the import graph; one branch inside event() when not tracing)
        from ..session.tracing import event as _trace_event
        _trace_event("backoff.sleep", kind=kind, cls=cls,
                     ms=round(sleep_ms, 2), attempt=n)
        if sleep_ms > 0 and self._sleep:
            time.sleep(sleep_ms / 1000.0)
        self.slept_ms += sleep_ms
        if self._check_killed is not None:
            self._check_killed()
        return n

    def _sleep_ms(self, k: Kind, attempt: int) -> float:
        if k.base_ms <= 0:
            return 0.0
        raw = min(k.cap_ms, k.base_ms * (2 ** (attempt - 1)))
        if k.jitter == "full":
            return self._rng.uniform(0, raw)
        if k.jitter == "equal":
            return raw / 2 + self._rng.uniform(0, raw / 2)
        return raw

    def _exhausted(self, kind, err, why) -> BackoffExhaustedError:
        history = "; ".join(f"{k}:{c}:{m}" for k, c, m in self.errors[-8:])
        exc = BackoffExhaustedError(
            f"backoff exhausted ({why}) after {self.attempts.get(kind, 0)} "
            f"{kind} attempts, slept {self.slept_ms:.1f}ms"
            + (f" [errors: {history}]" if history else ""))
        exc.retry_kind = kind
        exc.error_class = classify(err) if err is not None else CLASS_OTHER
        exc.__cause__ = err
        log.warning("backoff exhausted: kind=%s class=%s why=%s",
                    kind, exc.error_class, why)
        return exc

    # -- introspection ---------------------------------------------------

    def total_attempts(self) -> int:
        return sum(self.attempts.values())

    def remaining_ms(self) -> float:
        spent = self.slept_ms
        if self._wall_clock:
            spent = max(spent, (time.monotonic() - self._t0) * 1000)
        return max(self.budget_ms - spent, 0.0)
