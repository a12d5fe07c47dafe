"""Failpoint-style fault injection (reference: pingcap/failpoint, used in
103 reference files; kv/fault_injection.go).

Production code calls ``inject("name")`` at interesting points; tests
activate behaviors with ``enable`` — or, better, the ``enabled`` context
manager, which cannot leak an active failpoint past the test:

    failpoint.enable("commit-after-prewrite", "panic")     # raise
    failpoint.enable("backfill-batch", "sleep(0.05)")
    failpoint.enable("scan-rows", "return(7)")
    failpoint.enable("device-upload-oom", "2*oom")
    failpoint.enable("device-admission", "admission-queue-full")
    failpoint.enable("device-admission", "2*admission-wait(0.05)")
    with failpoint.enabled("txn-before-commit", "2*panic"):
        ...

Disabled failpoints cost one dict lookup. ``inject`` returns the
``return(...)`` payload (or None), raises FailpointError for ``panic``
and InjectedOOMError for ``oom`` / ``N*oom`` (a synthetic device
RESOURCE_EXHAUSTED that utils/backoff.classify labels ``device`` and
is_device_oom recognizes — NOT a FailpointError, which would classify
``fault`` and skip the OOM-recovery ladder)."""

from __future__ import annotations

import contextlib
import re
import threading
import time


class FailpointError(Exception):
    """Raised by an enabled `panic` failpoint."""


class InjectedAdmissionError(Exception):
    """Raised by an enabled ``admission-queue-full`` failpoint: a
    synthetic scheduler refusal.  The admission layer
    (executor/scheduler.py) converts it into the real classified
    DeviceAdmissionError so the injected refusal walks the genuine
    degrade-to-host ladder.  Deliberately NOT a FailpointError: that
    would classify ``fault`` instead of ``admission``."""


class InjectedCompileError(Exception):
    """Raised by an enabled ``compile-fail`` / ``N*compile-fail``
    failpoint: a synthetic remote-compile failure (a compile endpoint
    answering "Connection refused", at the COMPILE boundary instead of
    the dispatch boundary).  The compile service
    (executor/compile_service.py) retries it on the ``compileRetry``
    backoff curve, then charges the compile-scoped circuit breaker and
    degrades the fragment to the host engine.  Deliberately NOT a
    FailpointError: that would classify ``fault`` instead of ``compile``
    and skip the retry/breaker ladder this failpoint exists to test."""


class InjectedSpillError(Exception):
    """Raised by an enabled ``spill-fail`` / ``N*spill-fail`` failpoint:
    a synthetic host-columnar-page spill failure (disk full / IO error
    while the hybrid hash join writes an overflow partition,
    executor/hybrid_join.py via storage/paged.SpillSet).  classify labels
    it ``fault`` so run_device records it against the join breaker and
    degrades the fragment to the host engine — and the chaos invariant
    is that the abort leaks NO spilled pages (spill_outstanding() drains
    to zero) and no residency-ledger bytes.  Deliberately NOT a
    FailpointError subclass so tests can assert the spill path
    specifically fired."""


class InjectedOOMError(Exception):
    """Raised by an enabled ``oom`` / ``N*oom`` failpoint: a synthetic
    device out-of-memory whose MESSAGE mimics jaxlib's XlaRuntimeError
    RESOURCE_EXHAUSTED phrasing, so the error taxonomy
    (utils/backoff.classify → ``device``, is_device_oom → True) treats it
    exactly like a real HBM exhaustion.  Deliberately NOT a subclass of
    FailpointError: that would classify ``fault`` and bypass the
    evict-all → retry → degrade ladder this failpoint exists to test."""


def _oom_message(name: str) -> str:
    return ("RESOURCE_EXHAUSTED: Out of memory allocating 1073741824 "
            f"bytes (injected by failpoint {name})")


_lock = threading.Lock()
_active: dict[str, str] = {}
_hits: dict[str, int] = {}


def enable(name: str, action: str):
    with _lock:
        _active[name] = action
        _hits[name] = 0


def disable(name: str):
    with _lock:
        _active.pop(name, None)


def disable_all():
    with _lock:
        _active.clear()


@contextlib.contextmanager
def enabled(name: str, action: str):
    """Scoped activation: the failpoint is disabled on exit even when the
    body raises, so tests can't leak active failpoints into each other."""
    enable(name, action)
    try:
        yield
    finally:
        disable(name)


def list_active() -> dict[str, str]:
    """Snapshot of the currently enabled failpoints (name -> action)."""
    with _lock:
        return dict(_active)


def hits(name: str) -> int:
    with _lock:
        return _hits.get(name, 0)


def inject(name: str):
    # read + count under the SAME lock acquisition: the old lock-free
    # probe could tear against a concurrent disable() and count a hit
    # for a failpoint that no longer exists (satellite: utils/failpoint
    # race); the uncontended-lock cost is ~100ns, fine for fault points
    with _lock:
        action = _active.get(name)
        if action is None:
            return None
        _hits[name] = _hits.get(name, 0) + 1
        hit = _hits[name]
    if action == "panic":
        raise FailpointError(f"failpoint {name} triggered")
    if action == "oom":
        raise InjectedOOMError(_oom_message(name))
    m = re.fullmatch(r"(\d+)\*oom", action)
    if m:  # N*oom: synthetic device OOM for the first N hits, then no-op
        #   — models transient HBM pressure the evict+retry ladder absorbs
        if hit <= int(m.group(1)):
            raise InjectedOOMError(_oom_message(name))
        return None
    if action == "spill-fail":
        raise InjectedSpillError(
            f"spill write failed (injected by failpoint {name})")
    m = re.fullmatch(r"(\d+)\*spill-fail", action)
    if m:  # N*spill-fail: fail the first N partition spills, then
        #   succeed — models a transient disk hiccup mid-spill
        if hit <= int(m.group(1)):
            raise InjectedSpillError(
                f"spill write failed (injected by failpoint {name})")
        return None
    if action == "compile-fail":
        raise InjectedCompileError(
            "Connection refused: remote compile service unreachable "
            f"(injected by failpoint {name})")
    m = re.fullmatch(r"(\d+)\*compile-fail", action)
    if m:  # N*compile-fail: fail the first N compiles, then succeed —
        #   models a flaky compile endpoint the retry curve absorbs
        if hit <= int(m.group(1)):
            raise InjectedCompileError(
                "Connection refused: remote compile service unreachable "
                f"(injected by failpoint {name})")
        return None
    m = re.fullmatch(r"(?:(\d+)\*)?compile-slow\(([\d.]+)\)", action)
    if m:  # [N*]compile-slow(s): stall the first N compiles (all when N
        #   omitted) — models a slow remote compile; under
        #   tidb_compile_timeout the supervisor abandons it like a hang
        if m.group(1) is None or hit <= int(m.group(1)):
            time.sleep(float(m.group(2)))
        return None
    if action == "admission-queue-full":
        raise InjectedAdmissionError(
            f"admission queue full (injected by failpoint {name})")
    m = re.fullmatch(r"(?:(\d+)\*)?admission-wait\(([\d.]+)\)", action)
    if m:  # [N*]admission-wait(s): stall admission for the first N hits
        #   (all hits when N omitted) — models a contended queue; the
        #   scheduler counts the stall into sched_admission_waits_ms
        if m.group(1) is None or hit <= int(m.group(1)):
            time.sleep(float(m.group(2)))
        return None
    m = re.fullmatch(r"sleep\(([\d.]+)\)", action)
    if m:
        time.sleep(float(m.group(1)))
        return None
    m = re.fullmatch(r"return\((.*)\)", action)
    if m:
        raw = m.group(1)
        try:
            return int(raw)
        except ValueError:
            return raw.strip("'\"")
    m = re.fullmatch(r"(\d+)\*panic", action)
    if m:  # N*panic: raise for the first N hits, then no-op
        if hit <= int(m.group(1)):
            raise FailpointError(f"failpoint {name} triggered")
        return None
    m = re.fullmatch(r"(\d+)\*sleep\(([\d.]+)\)", action)
    if m:  # N*sleep(s): stall the first N hits (hang injection), then
        #   no-op — lets a schedule hang ONE dispatch and run clean after
        if hit <= int(m.group(1)):
            time.sleep(float(m.group(2)))
        return None
    m = re.fullmatch(r"(\d+)\*return\((.*)\)", action)
    if m:  # N*return(v): payload for the first N hits, then no-op
        if hit <= int(m.group(1)):
            raw = m.group(2)
            try:
                return int(raw)
            except ValueError:
                return raw.strip("'\"")
        return None
    raise ValueError(f"unknown failpoint action {action!r}")
