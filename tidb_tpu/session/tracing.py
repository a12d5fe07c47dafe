"""Query-lifecycle span tracing: a low-overhead hierarchical span
recorder for one statement's causal timeline (reference: util/tracing —
TiDB's opentracing shim behind ``TRACE <stmt>`` and the trace memtables).

Why this exists (ISSUE 10): when the July 2026 v5e run died (Q5's compile
request refused, 147-379s compiles dominating the queries that ran) the
gauges said *that* things were slow but never *where inside one query*
the time went — admission wait vs compile vs supervisor deadline vs
backoff sleeps vs device dispatch vs host degradation.  This module is
the per-query instrument: every resilience-layer chokepoint
(scheduler.admit, compile_service.obtain, supervisor.call_supervised,
device_exec.run_device, Backoffer.backoff, residency evictions) records
a span or event into the statement's trace when one is active, and
stays a SINGLE BRANCH when none is (sampling off ⇒ near-zero cost —
micro-checked in tier-1).

Model:

* A :class:`Trace` is one statement's span tree — monotonic-clock spans
  with tags and point events, bounded per-trace (``MAX_SPANS`` /
  ``MAX_EVENTS``; overflow counts ``dropped``, never grows).
* The ACTIVE trace is thread-local.  :func:`span` / :func:`event` read
  one TLS slot and return the shared no-op when nothing is active.
* **Thread hops**: :func:`capture` + :func:`adopt` carry the (trace,
  current span) pair onto supervisor worker threads (``_Job``), so a
  span opened inside a supervised device call still nests under the
  dispatching statement's ``supervisor.call`` span.
* **Linked child traces**: a background compile job gets its OWN trace
  (:func:`link_child`) carrying ``parent_id`` — an async compile's
  lifetime is attributable to the query that triggered it even though
  it outlives the statement.
* Finished traces land in a bounded process-wide ring, read back through
  ``information_schema.trace_records``, the ``TRACE`` statement, slow-log
  items and the bench error lines; ring stats surface in ``/status``
  (``device_tracing``).

Sampling: ``tidb_trace_sampling_rate`` (session/session.py decides per
statement); ``TRACE <stmt>`` is always-on, and a sampled statement that
crosses the slow-log threshold always keeps its rendered tree on the
:class:`~tidb_tpu.session.observe.SlowQueryItem`.

One clock with the device trace: every span that opens under an active
trace also enters a ``jax.profiler.TraceAnnotation`` of its name on the
thread that opened it, so when a ``jax.profiler`` session is running the
spans are host events of the SAME trace as the device's operations (the
benchmark's ``idle.*`` metrics give each idle gap of the device to the
innermost span open at that instant).  This module never imports jax: a
process that has not imported it (the benchmark's parent, every client)
gets no annotations and stays JAX-free.  Durations, offsets and the
ring keep their own monotonic clock; the annotation adds nothing to
what ``DIAG TRACEJSON`` / ``TRACE`` / the slow log render.

Locking: each trace has its own tiny lock (span/event appends from
worker threads); the ring has one.  Neither is ever held across a
blocking call, and no serving mutex (scheduler/supervisor/residency/
compile-service) is ever taken by this module — the recorder appends,
full stop (the ``blocking-while-locked`` lint audits tracing.py like
every other module-level lock owner).
"""

from __future__ import annotations

import collections
import itertools
import os
import sys
import threading
import time

#: per-trace bounds: spans/events beyond these count `dropped` instead of
#: growing the trace (a pathological plan must not turn the recorder into
#: a memory leak)
MAX_SPANS = 256
MAX_EVENTS = 1024

#: finished traces retained for information_schema.trace_records / the
#: bench post-mortem dumps (process-wide, like the supervisor STATS)
RING_CAP = 64

#: remote subtrees grafted into one trace (cross-process hops piggy-
#: backed on RPC responses) beyond this count `dropped`
MAX_REMOTE = 32

_TLS = threading.local()
_RING: "collections.deque" = collections.deque(maxlen=RING_CAP)
_RING_LOCK = threading.Lock()
_SEQ = itertools.count(1)

STATS = {
    "started": 0,       # traces begun (statements sampled + TRACE + children)
    "finished": 0,      # traces finished (ring candidates)
    "spans_dropped": 0,  # spans/events lost to the per-trace bounds
    "ring_dropped": 0,  # finished traces evicted from the bounded ring
    #   before any reader pulled them (/metrics trace_ring_dropped_total)
    "child_links": 0,   # background jobs linked as child traces
    "remote_hops": 0,   # remote subtrees grafted across process hops
    "remote_traces": 0,  # traces recorded on BEHALF of a remote origin
}


class Span:
    __slots__ = ("sid", "parent_sid", "name", "t0", "_m0", "dur_s", "tags",
                 "events")

    def __init__(self, sid, parent_sid, name, t0, m0, tags):
        self.sid = sid
        self.parent_sid = parent_sid
        self.name = name
        self.t0 = t0          # seconds since trace start
        self._m0 = m0         # monotonic at open (duration source)
        self.dur_s = None     # None until the span closes
        self.tags = tags
        self.events = []      # (t_offset_s, name, tags)


class Trace:
    """One statement's (or background job's) span tree."""

    __slots__ = ("trace_id", "parent_id", "origin", "name", "conn_id",
                 "started_at", "_t0", "spans", "dropped", "_lock", "root",
                 "finished", "dur_s", "succ", "n_events", "gid",
                 "origin_gid", "remote", "_ann")

    def __init__(self, name, origin="sampled", conn_id=None, parent_id=None,
                 tags=None):
        self.trace_id = next(_SEQ)
        #: fleet-global trace id: _SEQ is per-process, so cross-process
        #: stitching keys on pid-qualified ids (one machine hosts the
        #: whole simulated fleet — the pid disambiguates)
        self.gid = f"{os.getpid():x}-{self.trace_id:x}"
        self.parent_id = parent_id    # linking trace id (bg compile jobs)
        self.origin = origin          # sampled | trace_stmt | child | remote
        #: the ORIGIN trace's gid when this trace was recorded on behalf
        #: of a remote caller (origin == "remote"), else None
        self.origin_gid = None
        self.name = name
        self.conn_id = conn_id
        self.started_at = time.time()
        self._t0 = time.monotonic()
        self.spans: list[Span] = []
        #: remote subtrees grafted under local spans: (span sid, dict)
        self.remote: list = []
        self.dropped = 0
        self._lock = threading.Lock()
        self.finished = False
        self.dur_s = None
        self.succ = True
        self.n_events = 0
        #: the root span's profiler annotation, with the thread that
        #: entered it (begin); left by finish on that thread only
        self._ann = None
        self.root = self._start_span(name, -1, dict(tags or ()))

    # -- recording (any thread holding this trace via TLS) -------------------

    def _start_span(self, name, parent_sid, tags) -> "Span | None":
        now = time.monotonic()
        with self._lock:
            if self.finished:
                # an abandoned supervisor worker unsticking AFTER the
                # statement's trace finished must not mutate a trace
                # already published to the ring (renders would drift,
                # and its drops were already tallied into STATS)
                return None
            if len(self.spans) >= MAX_SPANS:
                self.dropped += 1
                return None
            sp = Span(len(self.spans), parent_sid, name, now - self._t0,
                      now, tags)
            self.spans.append(sp)
            return sp

    def _end_span(self, sp: Span, error: "str | None" = None):
        # only the opening _SpanCtx closes a span; the finished-gate
        # (under the lock, like _start_span/add_event) keeps an
        # abandoned worker's late exit from mutating a ring-published
        # trace — its span stays open-ended ('-') exactly as the slow
        # log and bench error line already rendered it
        with self._lock:
            if self.finished:
                return
            if error is not None:
                sp.tags["error"] = error
            sp.dur_s = time.monotonic() - sp._m0

    def add_event(self, sp: "Span | None", name, tags):
        now = time.monotonic() - self._t0
        with self._lock:
            if self.finished:
                return  # see _start_span: ring-published traces freeze
            if self.n_events >= MAX_EVENTS:
                self.dropped += 1
                return
            self.n_events += 1
            (sp if sp is not None else self.root).events.append(
                (now, name, tags))

    def add_remote(self, sp: "Span | None", subtree: dict):
        """Graft a remote process's finished trace dict under a local
        span (the RPC span the hop crossed on).  Same freeze/bound rules
        as spans: a ring-published trace never mutates, overflow counts
        ``dropped``."""
        with self._lock:
            if self.finished:
                return
            if len(self.remote) >= MAX_REMOTE:
                self.dropped += 1
                return
            self.remote.append(
                (sp.sid if sp is not None else 0, subtree))

    def _finish(self, succ: bool):
        with self._lock:
            if self.finished:
                return False
            self.finished = True
            self.succ = succ
            self.dur_s = time.monotonic() - self._t0
            if self.root.dur_s is None:
                self.root.dur_s = self.dur_s
            return True

    # -- read-back (finished traces; mid-flight reads tolerate None durs) ----

    class _SpanSnap:
        """Immutable copy of one span for render-time reads: a LIVE
        trace (the bench watchdog renders mid-statement) may still be
        appending spans/events — and _end_span may be inserting an
        error tag — from supervisor workers while a renderer iterates,
        so every renderer works from copies taken under the lock."""

        __slots__ = ("sid", "parent_sid", "name", "t0", "dur_s", "tags",
                     "events")

        def __init__(self, sp):
            self.sid = sp.sid
            self.parent_sid = sp.parent_sid
            self.name = sp.name
            self.t0 = sp.t0
            self.dur_s = sp.dur_s
            self.tags = dict(sp.tags)
            self.events = list(sp.events)

    def _snapshot(self):
        """(span copies, kids-by-parent, root, dropped, dur_s, remote
        grafts by span sid) under one lock hold — the single source
        every renderer works from.  The root is always spans[0]:
        __init__ creates it before the trace is shared."""
        with self._lock:
            spans = [Trace._SpanSnap(sp) for sp in self.spans]
            dropped, dur_s = self.dropped, self.dur_s
            remote = list(self.remote)
        kids: dict[int, list] = {}
        for sp in spans:
            kids.setdefault(sp.parent_sid, []).append(sp)
        hops: dict[int, list] = {}
        for sid, subtree in remote:
            hops.setdefault(sid, []).append(subtree)
        return spans, kids, spans[0], dropped, dur_s, hops

    def children_of(self) -> dict:
        return self._snapshot()[1]

    def to_dict(self) -> dict:
        spans, kids, root, dropped, dur_s, hops = self._snapshot()

        def node(sp):
            d = {"name": sp.name, "start_s": round(sp.t0, 6),
                 "duration_s": (round(sp.dur_s, 6)
                                if sp.dur_s is not None else None)}
            if sp.tags:
                d["tags"] = sp.tags
            if sp.events:
                d["events"] = [
                    {"at_s": round(t, 6), "name": n, **({"tags": tg}
                                                        if tg else {})}
                    for t, n, tg in sp.events]
            ch = [node(c) for c in kids.get(sp.sid, ())]
            # stitched cross-process subtrees hang under the RPC span
            # they crossed on, marked as hops
            ch += [{**sub, "hop": True} for sub in hops.get(sp.sid, ())]
            if ch:
                d["children"] = ch
            return d

        out = {"trace_id": self.trace_id, "gid": self.gid,
               "parent_id": self.parent_id,
               "origin": self.origin, "conn_id": self.conn_id,
               "started_at": self.started_at,
               "duration_s": (round(dur_s, 6)
                              if dur_s is not None else None),
               "succ": self.succ, "spans": len(spans),
               "dropped": dropped, "root": node(root)}
        if self.origin_gid:
            out["origin_gid"] = self.origin_gid
        if _PROC_LABEL[0]:
            out["process"] = _PROC_LABEL[0]
        return out


# -- the hot-path API ---------------------------------------------------------

#: this process's fabric identity ("slot3"), stamped into rendered trace
#: headers and to_dict payloads — set once at worker boot
#: (fabric/state.activate), empty outside a fleet
_PROC_LABEL = [""]


def set_process_label(label: str):
    _PROC_LABEL[0] = str(label or "")


class _NoopCtx:
    """The shared do-nothing span: sampling off costs one TLS read + this
    singleton — no Trace, no Span, no lock (micro-checked in tier-1)."""

    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, *a):
        return False


_NOOP = _NoopCtx()


def _annotate(name):
    """Enter a ``jax.profiler.TraceAnnotation`` called `name` on the
    calling thread and return it (leave it with ``__exit__`` on the SAME
    thread), or None in a process that never imported jax.  Outside a
    profiler session the annotation is a flag check in the runtime."""
    prof = sys.modules.get("jax.profiler")
    if prof is None:
        return None
    ann = prof.TraceAnnotation(name)
    ann.__enter__()
    return ann


class _SpanCtx:
    __slots__ = ("tr", "name", "tags", "sp", "prev", "ann")

    def __init__(self, tr, name, tags):
        self.tr = tr
        self.name = name
        self.tags = tags

    def __enter__(self):
        parent = getattr(_TLS, "span", None)
        sp = self.tr._start_span(
            self.name, parent.sid if parent is not None else 0, self.tags)
        self.sp = sp
        self.prev = parent
        if sp is not None:
            _TLS.span = sp
            self.ann = _annotate(self.name)
        return sp

    def __exit__(self, et, ev, tb):
        sp = self.sp
        if sp is not None:
            if self.ann is not None:
                self.ann.__exit__(et, ev, tb)
            self.tr._end_span(
                sp, error=et.__name__ if et is not None else None)
            _TLS.span = self.prev
        return False


def active() -> "Trace | None":
    """The calling thread's live trace, or None (THE one-branch check
    every chokepoint reduces to when sampling is off)."""
    return getattr(_TLS, "trace", None)


def span(name, **tags):
    """Context manager opening a child span of the calling thread's
    current span — or the shared no-op when no trace is active."""
    tr = getattr(_TLS, "trace", None)
    if tr is None:
        return _NOOP
    return _SpanCtx(tr, name, tags)


def event(name, **tags):
    """Record a point event on the current span (one branch when off)."""
    tr = getattr(_TLS, "trace", None)
    if tr is None:
        return
    tr.add_event(getattr(_TLS, "span", None), name, tags)


# -- lifecycle ----------------------------------------------------------------

def begin(name, *, origin="sampled", conn_id=None, parent_id=None,
          **tags) -> Trace:
    """Start a trace and bind it to the calling thread."""
    tr = Trace(name, origin, conn_id, parent_id, tags)
    _TLS.trace = tr
    _TLS.span = tr.root
    with _RING_LOCK:
        STATS["started"] += 1
    ann = _annotate(name)
    if ann is not None:
        tr._ann = (ann, threading.get_ident())
    return tr


def finish(tr: Trace, succ: bool = True):
    """Finish a trace (idempotent), unbind it from this thread if bound,
    and retain it in the ring."""
    if getattr(_TLS, "trace", None) is tr:
        _TLS.trace = None
        _TLS.span = None
    ann = tr._ann
    if ann is not None and ann[1] == threading.get_ident():
        # the root's annotation belongs to the thread that began the
        # trace; a finish from elsewhere leaves it to that thread
        tr._ann = None
        ann[0].__exit__(None, None, None)
    if not tr._finish(succ):
        return
    with _RING_LOCK:
        STATS["finished"] += 1
        STATS["spans_dropped"] += tr.dropped
        if len(_RING) >= RING_CAP:
            STATS["ring_dropped"] += 1
        _RING.append(tr)


def link_child(name, **tags) -> "Trace | None":
    """A NEW unbound trace linked under the calling thread's active trace
    (``parent_id`` = the active trace's id) — how a background compile
    job stays attributable to the query that submitted it.  The worker
    binds it with :func:`adopt`; :func:`finish` retires it.  None when
    no trace is active."""
    tr = getattr(_TLS, "trace", None)
    if tr is None or tr.finished:
        # finished: the binding thread is an ABANDONED supervisor worker
        # unsticking after its statement's trace was published — the
        # parent can no longer record the link, so a child would be an
        # orphan that misattributes ring lookups (and the straggler's
        # spans are noise, not a query's timeline)
        return None
    child = Trace(name, "child", tr.conn_id, tr.trace_id, tags)
    with _RING_LOCK:
        STATS["started"] += 1
        STATS["child_links"] += 1
    event("linked_child_trace", trace_id=child.trace_id, child=name)
    return child


def capture():
    """(trace, current span) of the calling thread, or None — recorded at
    a thread-hop submit site (supervisor ``_Job``) and re-bound on the
    worker with :func:`adopt`."""
    tr = getattr(_TLS, "trace", None)
    if tr is None:
        return None
    return tr, getattr(_TLS, "span", None)


class adopt:
    """Bind (trace, span) on the CURRENT thread for a scope (worker-side
    half of the thread hop; also used by bg-compile workers to run under
    their linked child trace)."""

    __slots__ = ("tr", "sp", "_prev")

    def __init__(self, tr, sp=None):
        self.tr = tr
        self.sp = sp if sp is not None else tr.root

    def __enter__(self):
        self._prev = (getattr(_TLS, "trace", None),
                      getattr(_TLS, "span", None))
        _TLS.trace = self.tr
        _TLS.span = self.sp
        return self.tr

    def __exit__(self, *a):
        _TLS.trace, _TLS.span = self._prev
        return False


# -- cross-process propagation ------------------------------------------------
#
# The fleet hops on the framed codec (compile server, net coordinator,
# worker diag ports).  Propagation is dict-shaped so it rides inside the
# existing pickled request/response dicts — the codec itself is untouched:
#
#   client:  obj["trace"] = wire_ctx()          (None when sampling off)
#   server:  rtr = begin_remote(obj.get("trace"), "rpc.op")
#            ... handle, recording spans ...
#            resp["_trace"] = finish_remote(rtr)
#   client:  attach_remote(resp.pop("_trace", None))
#
# The remote side records a FULL trace into ITS OWN ring tagged with the
# origin's gid (``origin_gid`` — queryable via traces_for_origin / the
# diag endpoint even when the response is lost), AND the finished subtree
# piggybacks on the response so the caller's TRACE FORMAT='json' renders
# the stitched tree synchronously.  Every helper is one branch when no
# trace is active (micro-checked in tier-1 like span/event).

def wire_ctx() -> "dict | None":
    """The calling thread's trace context for an outgoing RPC request
    dict, or None when no trace is active (the one-branch off path —
    callers attach it as ``obj["trace"]`` only when non-None)."""
    tr = getattr(_TLS, "trace", None)
    if tr is None:
        return None
    sp = getattr(_TLS, "span", None)
    return {"gid": tr.gid,
            "span": sp.name if sp is not None else tr.name,
            "sampled": True,
            "proc": _PROC_LABEL[0]}


def begin_remote(ctx: "dict | None", name, **tags) -> "Trace | None":
    """Server-side half: start a trace on BEHALF of the remote caller
    described by ``ctx`` (a :func:`wire_ctx` dict from the request), bind
    it to this thread, and tag it with the origin's gid.  None in → None
    out (unsampled request: one branch, nothing recorded)."""
    if not ctx:
        return None
    if ctx.get("proc"):
        tags.setdefault("origin_proc", ctx["proc"])
    tr = begin(name, origin="remote", **tags)
    tr.origin_gid = ctx.get("gid")
    with _RING_LOCK:
        STATS["remote_traces"] += 1
    return tr


def finish_remote(tr: "Trace | None", succ: bool = True) -> "dict | None":
    """Finish a :func:`begin_remote` trace and return its dict form for
    response piggybacking (``resp["_trace"]``).  None in → None out."""
    if tr is None:
        return None
    finish(tr, succ)
    return tr.to_dict()


def attach_remote(subtree: "dict | None"):
    """Client-side half: graft a remote process's finished trace dict
    (a response's ``_trace`` payload) under the calling thread's current
    span.  One branch when no trace is active or the response carried
    none."""
    if subtree is None:
        return
    tr = getattr(_TLS, "trace", None)
    if tr is None:
        return
    tr.add_remote(getattr(_TLS, "span", None), subtree)
    with _RING_LOCK:
        STATS["remote_hops"] += 1


def traces_for_origin(gid: str) -> list:
    """Finished traces THIS process recorded on behalf of origin ``gid``
    — the diag-endpoint lookup that stitches a hop even when the RPC
    response (and its piggybacked subtree) was lost."""
    with _RING_LOCK:
        return [tr for tr in _RING if tr.origin_gid == gid]


# -- rendering ----------------------------------------------------------------

def _fmt_s(s) -> str:
    if s is None:
        return "-"
    if s >= 1.0:
        return f"{s:.3f}s"
    if s >= 0.001:
        return f"{s * 1e3:.3f}ms"
    return f"{s * 1e6:.0f}µs"


def tree_rows(tr: Trace) -> list:
    """Depth-first (operation, startTS, duration) rows — the TRACE
    FORMAT='row' resultset shape (reference: executor/trace.go).  Events
    render as zero-duration rows prefixed ``@``.  Works entirely on the
    locked span snapshot: the watchdog renders LIVE traces whose spans
    and tags are still being written from worker threads."""
    _spans, kids, root, _dropped, _dur, hops = tr._snapshot()
    rows = []

    def walk_hop(d, depth):
        """Render a grafted remote subtree (dict form) — hop rows are
        marked with the remote process so a stitched fleet trace reads
        'which worker' at a glance."""
        pad = "  " * depth
        node = d.get("root") or {}
        proc = d.get("process") or "remote"
        rows.append((f"{pad}[hop:{proc}] {node.get('name', '?')}",
                     _fmt_s(node.get("start_s")),
                     _fmt_s(node.get("duration_s"))))
        for c in node.get("children", ()):
            rows.append((f"{pad}  [hop:{proc}] {c.get('name', '?')}",
                         _fmt_s(c.get("start_s")),
                         _fmt_s(c.get("duration_s"))))

    def walk(sp, depth):
        pad = "  " * depth
        rows.append((pad + sp.name, _fmt_s(sp.t0), _fmt_s(sp.dur_s)))
        items = [("s", c.t0, c) for c in kids.get(sp.sid, ())]
        items += [("e", t, (t, n, tg)) for t, n, tg in sp.events]
        for kind, _at, payload in sorted(items, key=lambda x: x[1]):
            if kind == "s":
                walk(payload, depth + 1)
            else:
                t, n, tg = payload
                tag_s = (" " + ",".join(f"{k}={v}" for k, v in tg.items())
                         if tg else "")
                rows.append((f"{pad}  @{n}{tag_s}", _fmt_s(t), "-"))
        for sub in hops.get(sp.sid, ()):
            walk_hop(sub, depth + 1)

    walk(root, 0)
    return rows


def render_tree(tr: Trace) -> str:
    """One text block per trace — what slow-log items and the bench error
    lines carry (the Q5 post-mortem artifact).  Under the serving fabric
    the header names the WORKER PROCESS that served the statement (the
    tracing context across process hops: a fleet post-mortem's first
    question is "which worker"), and dedup/remote-compile events inside
    tag the peer slot they crossed to."""
    lines = [f"trace {tr.trace_id}"
             + (f" (child of {tr.parent_id})" if tr.parent_id else "")
             + (f" @{_PROC_LABEL[0]}" if _PROC_LABEL[0] else "")
             + f" [{tr.origin}] dur={_fmt_s(tr.dur_s)}"
             + ("" if tr.succ else " FAILED")
             + (f" dropped={tr.dropped}" if tr.dropped else "")]
    for op, start, dur in tree_rows(tr):
        lines.append(f"  {dur:>10}  {start:>10}  {op}")
    return "\n".join(lines)


# -- ring / introspection -----------------------------------------------------

def recent_traces() -> list:
    """Newest-last snapshot of the finished-trace ring."""
    with _RING_LOCK:
        return list(_RING)


def last_trace(conn_id=None, include_children=False) -> "Trace | None":
    """The most recent finished STATEMENT trace (optionally for one
    connection) — the bench watchdog's post-mortem lookup.  Background
    ``compile.bg`` child traces are skipped unless asked for: a child
    finishing after the failed statement must not shadow it."""
    with _RING_LOCK:
        for tr in reversed(_RING):
            if not include_children and tr.origin == "child":
                continue
            if conn_id is None or tr.conn_id == conn_id:
                return tr
    return None


def last_trace_text(conn_id=None, cap: int = 4000) -> str:
    """Rendered post-mortem timeline, capped — THE bench-error helper
    (one implementation for bench.py / bench_multichip.py /
    bench_serve.py; pass the failing session's ``conn_id`` so a
    concurrent healthy session's timeline is never misattributed to the
    failure).  The CALLING thread's still-open trace wins over the ring:
    a watchdog firing MID-statement (SIGALRM on the main thread) renders
    the hung query's live timeline instead of the previous statement's
    finished one.  "" when nothing matches; never raises (the
    post-mortem extra must not mask the error line)."""
    try:
        tr = active()
        if tr is not None and conn_id is not None \
                and tr.conn_id != conn_id:
            # live trace belongs to ANOTHER session multiplexed on this
            # thread: the conn filter applies to the live path too
            tr = None
        if tr is None:
            tr = last_trace(conn_id)
        return render_tree(tr)[:cap] if tr is not None else ""
    except Exception:  # noqa: BLE001 — diagnostics-only sink
        return ""


def snapshot() -> dict:
    """The ``/status`` ``device_tracing`` payload."""
    with _RING_LOCK:
        return {"ring_traces": len(_RING), "ring_cap": RING_CAP,
                "max_spans": MAX_SPANS, "outstanding":
                    STATS["started"] - STATS["finished"], **STATS}


def verify_drained() -> dict:
    """Chaos invariant (mirrors scheduler/compile_service
    verify_drained): once traffic stops, every begun trace was finished
    — no trace object left bound/unfinished holding span refs."""
    with _RING_LOCK:
        out = {"ok": STATS["started"] == STATS["finished"],
               "outstanding": STATS["started"] - STATS["finished"],
               **STATS}
    return out


def reset_for_tests():
    """Drop the ring/counters and this thread's binding (unit tests)."""
    _TLS.trace = None
    _TLS.span = None
    with _RING_LOCK:
        _RING.clear()
        for k in STATS:
            STATS[k] = 0
