"""Device (TPU) execution paths for the hot operators.

The fused scan→filter→aggregate pipeline: when a HashAgg sits directly on a
TableScan, the pushed-down filters, the aggregate input expressions and the
grouping all compile into ONE jitted XLA program — the host only dict-encodes
strings and reads back `capacity`-bounded results. This is the engine-side
realization of the reference's coprocessor pushdown (the whole DAG executes
storage-side there, device-side here).
"""

from __future__ import annotations

import collections
import time as _time

import numpy as np
import jax
import jax.numpy as jnp

from ..errors import TiDBError
from ..expression import phys_kind, K_DEC, K_FLOAT, K_STR, K_DATE
from ..expression.core import Column as ExprColumn
from ..ops import device as dev
from ..ops.device import DeviceUnsupported
from ..sqltypes import POW10
from ..utils.chunk import Chunk, Column, np_dtype_for


def engine_mode(ctx) -> str:
    # a statement-scoped /*+ READ_FROM_STORAGE(...) */ pin outranks the
    # session sysvar (set per root executor build; see executor/__init__)
    eh = getattr(ctx, "stmt_engine_hint", None)
    if eh:
        return eh
    try:
        return ctx.get_sysvar("tidb_executor_engine")
    except Exception:
        return "auto"


def run_device(ctx, fn, /, *args, shape="agg", batch_key=None, **kw):
    """Dispatch one device fragment through the serving admission layer
    (executor/scheduler.py), the circuit breaker (executor/circuit.py)
    and the device-runtime supervisor (executor/supervisor.py) — the four
    layers every fragment passes, in order: ADMISSION (may this fragment
    occupy the shared device now?) → SUPERVISOR deadline → BREAKER →
    RESIDENCY budget.

    Admission: the fragment holds a scheduler ticket for the duration of
    the device call — weighted fair queueing across resource groups
    (`tidb_resource_group`), bounded queue depth, per-tenant running
    caps.  A refusal (queue full / wait timeout, classified
    DeviceAdmissionError 9009) degrades this fragment to the host engine
    exactly like an OPEN breaker — overload means host and device serve
    DIFFERENT work concurrently, not an error.  `batch_key` (the
    compiled-pipeline identity of the fragment, when the dispatch site
    can compute it cheaply) lets queued same-shaped fragments coalesce
    onto one scheduling slot, sharing the compiled program and resident
    uploads cross-session.

    An OPEN breaker degrades to the host engine up front
    (DeviceUnsupported → the caller's existing fallback), and a
    classified device/transport failure — an XLA runtime error, a
    refused compile endpoint, an injected fault — records into the breaker
    and ALSO degrades instead of killing the query.  DeviceUnsupported
    and TiDBError pass through untouched: "this fragment doesn't fit the
    device" and genuine user errors are not health signals.

    When a deadline is in force (`tidb_device_call_timeout` sysvar or a
    running `max_execution_time` window) the fragment executes on a
    supervised worker thread: a backend HANG raises a classified
    DeviceHangError into the query (recorded against the breaker, so
    repeated hangs trip degradation), the abandoned call is fenced, and
    the wait stays KILL-interruptible even while the backend blocks
    inside a GIL-holding C call.

    A classified device OUT-OF-MEMORY walks the recovery ladder before
    degrading: evict every residency-tracked HBM upload
    (ops/residency.recover_oom) → retry the fragment ONCE against the
    emptied device → only then record the failure and degrade to host.
    Transient HBM pressure (another session's working set, a one-off
    giant intermediate) costs one re-upload instead of a cooldown.

    `shape` scopes the breaker per fragment class (agg / join / window):
    one failing shape cools down without degrading healthy paths.

    Under the serving fabric (tidb_tpu/fabric) a batch_key'd dispatch
    first consults the FLEET fragment-dedup table: identical concurrent
    fragments — same structural batch key AND same input-chunk content
    hash — anywhere in the fleet dispatch ONE device call; followers
    wait (before admission, so they hold no device slot) and map the
    leader's result page back in.  No fleet, no batch key, or no
    hashable input -> the plain dispatch below."""
    if batch_key is not None:
        from ..fabric import state as fabric_state
        ded = fabric_state.dedup_handle()
        if ded is not None:
            kh = ded.key_hash(batch_key, args)
            if kh is not None:
                return ded.coalesce(
                    ctx, shape, kh,
                    lambda: _run_device_dispatch(ctx, fn, args, kw, shape,
                                                 batch_key))
    return _run_device_dispatch(ctx, fn, args, kw, shape, batch_key)


def _run_device_dispatch(ctx, fn, args, kw, shape, batch_key):
    """The admitted dispatch (layer 1 onward) for one fragment — the
    fabric dedup leader's compute path, and the whole of run_device
    outside a fleet."""
    from ..errors import DeviceAdmissionError
    from ..fabric import perf as fabric_perf
    from ..session import tracing
    from . import scheduler
    group = scheduler.resource_group(ctx)
    scheduler.attach(ctx)
    # shared fragment-perf store feed (fabric/perf.py): this dispatch's
    # admission wait, sync-compile share and device wall time accumulate
    # under the fragment's (sig, bucket) — fleet-mergeable observe-only
    # data, buffered locally and flushed off the hot path
    psig, pbucket = fabric_perf.dispatch_key(batch_key, shape)
    with tracing.span("device.dispatch", shape=shape, group=group):
        ta0 = _time.perf_counter()
        try:
            ticket = scheduler.admit(ctx, shape=shape, batch_key=batch_key)
        except DeviceAdmissionError as e:
            # load pressure, not device ill-health: no breaker charge —
            # the fragment runs on the host engine (per-tenant gauge
            # records it; the trace carries the classified reason)
            scheduler.note_degradation(group)
            tracing.event("host_degraded", reason="admission", shape=shape)
            raise DeviceUnsupported(
                f"device admission refused for {shape} fragment "
                f"(resource group '{group}'; degraded to host engine): "
                f"{e}") from e
        finally:
            # refusals contribute too: the timeout wait a refused
            # fragment paid is exactly the tail this series exists for
            fabric_perf.note(psig, pbucket, "device", "admission_wait",
                             _time.perf_counter() - ta0)
        t0 = _time.perf_counter()
        c0 = _tls_stats()["compile_s"]
        try:
            return _run_device_admitted(ctx, fn, args, kw, shape, group)
        finally:
            scheduler.release(ticket)
            # per-fragment latency histogram (session/observe.py
            # HIST_BUCKETS): one admitted dispatch end-to-end — in the
            # finally so FAILED dispatches (supervisor-deadline hangs,
            # post-OOM degrades) contribute too; the pathological
            # latencies are exactly the p99 this series exists to show
            dt = _time.perf_counter() - t0
            # the TLS pipe-stats mirror attributes exactly this thread's
            # sync-compile seconds to this dispatch (concurrent sessions
            # can't cross-charge — same contract as pipe_cache_stats)
            dc = _tls_stats()["compile_s"] - c0
            if dc > 0:
                fabric_perf.note(psig, pbucket, "device", "compile", dc)
            fabric_perf.note(psig, pbucket, "device", "dispatch", dt)
            obs = getattr(getattr(ctx, "domain", None), "observe", None)
            if obs is not None and hasattr(obs, "observe_hist"):
                obs.observe_hist("device_dispatch_seconds", dt)


def _run_device_admitted(ctx, fn, args, kw, shape, group):
    """Layers 2-4 (supervisor deadline → breaker → residency) for a
    fragment that holds its admission ticket."""
    from ..errors import DeviceHangError
    from ..ops import residency
    from ..session import tracing
    from ..utils.backoff import (classify, is_device_oom, CLASS_DEVICE,
                                 CLASS_EXCHANGE, CLASS_FAULT,
                                 CLASS_TRANSPORT)
    from . import supervisor
    from .circuit import get_breaker
    br = get_breaker(ctx, shape=shape)
    sid = getattr(ctx, "conn_id", None)
    if not br.allow(session=sid, group=group):
        tracing.event("host_degraded", reason="breaker_open", shape=shape)
        raise DeviceUnsupported(
            f"device circuit open for {shape} fragments (cooling down; "
            "fragment degraded to host engine)")
    residency.attach(ctx)  # budget sysvar + tenant + observe gauge sink
    deadline_s, fence_on_expiry = supervisor.deadline_for(ctx)
    oom_retried = False
    while True:
        try:
            out = supervisor.call_supervised(
                fn, args, kw, deadline_s=deadline_s, ctx=ctx, shape=shape,
                fence_on_expiry=fence_on_expiry)
        except DeviceHangError as e:
            # the hang IS a health verdict: count it toward opening the
            # breaker, then surface the classified error — the query fails
            # (its device call is still in flight; a silent host fallback
            # would hide that the deadline fired) but the NEXT queries
            # degrade once the breaker trips
            br.record_failure(e, session=sid, group=group)
            tracing.event("breaker.recorded", cls="hang", shape=shape)
            raise
        except (DeviceUnsupported, TiDBError):
            # no health verdict: if this fragment held the HALF_OPEN probe
            # slot, free it — otherwise the breaker wedges with no prober
            br.release_probe(session=sid)
            raise
        except (KeyboardInterrupt, SystemExit):
            # Ctrl-C mid-probe must not wedge the breaker in HALF_OPEN
            br.release_probe(session=sid)
            raise
        except Exception as e:
            cls = classify(e)
            if cls not in (CLASS_DEVICE, CLASS_TRANSPORT, CLASS_FAULT,
                           CLASS_EXCHANGE):
                # an UNCLASSIFIED error is a programming bug, not a device
                # health signal: surface it instead of silently degrading
                br.release_probe(session=sid)
                raise
            if not oom_retried and is_device_oom(e):
                # OOM ladder step 1+2: evict all cached HBM, ONE retry.
                # No breaker charge yet — an OOM the eviction absorbs is
                # pressure, not device ill-health; a SECOND failure of any
                # class takes the normal degrade path below.
                oom_retried = True
                tracing.event("oom_ladder", step="evict_all_retry",
                              shape=shape)
                residency.recover_oom(e)
                continue
            br.record_failure(e, session=sid, group=group)
            tracing.event("host_degraded", reason=cls, shape=shape)
            raise DeviceUnsupported(
                f"device failure ({cls}): {e}") from e
        br.record_success(session=sid)
        return out


def want_device(ctx, n_rows: int) -> bool:
    mode = engine_mode(ctx)
    if mode == "host":
        return False
    if mode == "tpu":
        return True
    try:  # auto: device dispatch overhead beneath this row floor
        floor = int(ctx.get_sysvar("tidb_device_dispatch_rows"))
    except Exception:
        floor = 65536
    if floor <= 0:
        # derive the floor from the calibrated cost constants (one
        # currency for planner placement AND runtime gating — with
        # uncalibrated defaults this is the historical 65536)
        from ..planner.cost_model import CostModel
        floor = CostModel.from_ctx(ctx).device_breakeven_rows()
    return n_rows >= floor


#: jitted fused pipelines keyed by plan signature — the whole
#: filter→keys→values→aggregate program is ONE XLA computation, traced once
#: and re-dispatched on later executions (reference analog: coprocessor DAG
#: compiled per plan digest). LRU-bounded; each entry pins strong refs to
#: the string dictionaries whose codes are baked into the traced program.
#: Key components that depend on a dictionary use its CONTENT signature
#: (utils/chunk.py dict_content_sig), not its id: a delta append re-encodes
#: into new dictionary objects whose content — and therefore every baked
#: code LUT — is usually unchanged, and shape bucketing (ops/device.py
#: bucket_rows) keeps the traced array shapes stable too, so the compiled
#: program survives the delta.
_PIPE_CACHE: "collections.OrderedDict" = collections.OrderedDict()
_PIPE_CACHE_MAX = 256

#: compiled-fragment cache observability: hits/misses are _PIPE_CACHE
#: lookups; traces counts actual jax retraces (one per XLA compile);
#: compile_s is wall time of dispatches that triggered a trace. Surfaced
#: per query through EXPLAIN ANALYZE annotations and bench.py compile_s.
#: Process totals are lock-guarded; a THREAD-LOCAL mirror gives per-query
#: delta attribution that concurrent sessions can't cross-charge.
import threading as _threading

_PIPE_STATS = {"hits": 0, "misses": 0, "traces": 0, "compiles": 0,
               "compile_s": 0.0,
               # background mirror: compile work done on compile-service
               # worker threads lands here instead, so per-query compile_s
               # stays the SYNC cost (bench splits sync_compile_s vs
               # bg_compile_s from these)
               "bg_traces": 0, "bg_compiles": 0, "bg_compile_s": 0.0,
               # compile-mode counters (executor/compile_service.py):
               # how each pipeline resolution was served — drives the
               # per-fragment compile_mode EXPLAIN ANALYZE annotation
               "mode_cached": 0, "mode_prewarmed": 0,
               "mode_async_pending": 0, "mode_sync": 0,
               # aggregate fragments dispatched, by the arm of
               # ops/device._agg_impl that dev.agg_arm named for them —
               # note_agg_arm
               "agg_dense": 0, "agg_sorted": 0,
               # sort-arm programs dispatched whose group starts come
               # from ONE sort at input length and not from a binary
               # search per output slot (dev.spans_one_pass names the
               # side from the capacity and the input length) —
               # note_agg_spans
               "agg_spans_one_pass": 0,
               # sort-arm scan aggregates dispatched whose first capacity
               # came from the group count their last kept run stored /
               # from _estimate_groups (device_agg's _scan_agg_start)
               "agg_start_learned": 0, "agg_start_estimated": 0,
               # never bumped: benchmark/layer_metrics/agg.dense_share.py
               # reads the key
               "agg_scatter": 0,
               # host-indexed joins of dispatched join fragments, by how
               # a probe key finds its build rows: by address (a `dense`
               # JoinIndex) or by binary search (`sorted`); of the
               # searched, those whose index carries a prefix table, so
               # that the search starts from the key's bucket —
               # note_join_layouts
               "join_direct": 0, "join_search": 0,
               "join_search_prefixed": 0,
               # the same joins by kind (an inner join counts under
               # none), and those whose OUTPUT is CSR-expanded: an inner
               # or left join over a non-unique build (a semi / anti
               # existence count over one is probe-shaped and is not) —
               # note_join_layouts
               "join_left": 0, "join_semi": 0, "join_anti": 0,
               "join_expand": 0,
               # of the semi / anti joins, those whose residual (a
               # correlated conjunct that is not a key: Q21's `<>`) ran
               # in the program — note_join_layouts; and per such join
               # over a non-unique build, the pairs its kept program
               # tested through a CSR expansion of the probe's live rows
               # and the static capacity it ran at — note_join_residual
               "join_residual": 0,
               "join_residual_rows": 0, "join_residual_capacity": 0,
               # per expanded join of a dispatched join fragment: the
               # rows its expansion emitted (the `total` the program
               # counts beside the overflow check) and the static
               # capacity the program ran at; rows over capacity is how
               # full the learned capacity is — note_join_expansion
               "join_expand_rows": 0, "join_expand_capacity": 0,
               # of join_expand, the expansions whose KEPT program maps
               # its output slots to probe rows in one pass and not by a
               # binary search per slot (device_join.expand_one_pass) —
               # note_join_expansion
               "join_expand_one_pass": 0,
               # compaction points of dispatched join fragments whose
               # KEPT program cut the probe path's relation to its live
               # rows (device_join.compact_to) — note_join_compactions
               "join_compactions": 0,
               # dispatched join fragments whose inner joins
               # device_join._reorder_fact_first chained, and of them
               # those where a step attached a build whose filter lets
               # the probe path cut ahead of a smaller candidate —
               # note_join_chain
               "join_chains": 0, "join_chains_selective": 0,
               # column / mask / row-map gathers of dispatched join
               # fragments' programs, and those the program holds the
               # result of already (a leaf read in place, a NULL-free
               # column's mask) — note_join_gathers
               "join_gathers": 0, "join_gathers_elided": 0,
               # scan-aggregate fragments dispatched, by the path
               # scan_stream_rows (or tidb_device_stream_rows) chose:
               # device_agg over resident columns / device_agg_streaming
               # in blocks; and the bytes the streamed blocks carried to
               # the device, which the residency ledger never sees
               "scan_resident": 0, "scan_streamed": 0,
               "stream_upload_bytes": 0,
               # join fragments dispatched, by where the probe leaf's
               # rows came from: columns resident through the residency
               # ledger (whole, or cut into pages on the device) / pages
               # the statement cut from the host's columns and sent,
               # whose bytes count under stream_upload_bytes —
               # note_join_probe
               "join_probe_resident": 0, "join_probe_sent": 0,
               # aggregate subqueries a join fragment materialised and
               # folded into an in-set filter of its probe side (the
               # uncorrelated IN -> semi rewrite, Q18) — note_semi_inset
               "semi_insets": 0,
               # build leaves of dispatched join fragments that are
               # another operator's result (a derived aggregate: Q17's
               # lineitem group by l_partkey), and the rows they held —
               # note_join_derived
               "join_derived": 0, "join_derived_rows": 0,
               # fragments a PINNED device engine (tpu / tpu-mpp) left
               # to the host executors because they are outside the
               # device language — note_unsupported
               "unsupported": 0,
               # host join indexes built in numpy (a miss of the one
               # index a key column caches: join_index.build_join_index,
               # under the span join.index_build) — note_join_index_build
               "join_index_builds": 0,
               # programs a fragment ran AGAIN at another capacity
               # because the run before it overflowed (or was far too
               # wide): every turn of a fragment's capacity loop after a
               # program has run — note_rerun
               "capacity_reruns": 0}
_PIPE_LOCK = _threading.Lock()
_PIPE_TLS = _threading.local()

#: process-total keys a compile-service worker thread redirects into the
#: bg_* mirror (its own TLS keeps the plain names so observed_jit's
#: trace-delta compile detection still works on that thread)
_BG_ROUTED = frozenset({"traces", "compiles", "compile_s"})


def mark_bg_thread(on: bool = True) -> bool:
    """Mark the CALLING thread as a background compile worker: its
    trace/compile charges route to the process bg_* keys (query-path
    compile accounting must not absorb background work).  Returns the
    previous mark so a SCOPED marking (compile_service._do_compile under
    a supervisor deadline runs on a REUSED supervisor worker thread)
    can restore it — a lingering mark would mis-route that worker's
    later query-fragment compiles into the bg mirror."""
    prev = getattr(_PIPE_TLS, "bg", False)
    _PIPE_TLS.bg = on
    return prev


def _tls_stats() -> dict:
    st = getattr(_PIPE_TLS, "stats", None)
    if st is None:
        st = _PIPE_TLS.stats = {"hits": 0, "misses": 0, "traces": 0,
                                "compiles": 0, "compile_s": 0.0,
                                "mode_cached": 0, "mode_prewarmed": 0,
                                "mode_async_pending": 0, "mode_sync": 0,
                                "agg_dense": 0, "agg_sorted": 0,
                                "join_direct": 0, "join_search": 0,
                                "join_search_prefixed": 0,
                                "join_left": 0, "join_semi": 0,
                                "join_anti": 0, "join_expand": 0,
                                "join_residual": 0,
                                "join_expand_one_pass": 0,
                                "join_compactions": 0,
                                "join_chains_selective": 0,
                                "join_gathers": 0,
                                "join_gathers_elided": 0,
                                "join_probe_resident": 0,
                                "join_probe_sent": 0,
                                "join_derived": 0,
                                "join_derived_rows": 0}
    return st


def _bump(key, amt=1):
    pkey = key
    if key in _BG_ROUTED and getattr(_PIPE_TLS, "bg", False):
        pkey = "bg_" + key
    with _PIPE_LOCK:
        _PIPE_STATS[pkey] += amt
    st = _tls_stats()
    if key in st:
        st[key] += amt


#: dev.agg_arm's name -> its counter
AGG_ARM_STATS = {"dense": "agg_dense", "sort": "agg_sorted"}


def note_agg_arm(pack, agg_ops, gathered=False):
    """Count one dispatched aggregate fragment (scan pipeline, join
    fragment, mesh partial) under the arm its program aggregates by;
    EXPLAIN ANALYZE's ``agg:`` annotation and the benchmark's
    ``agg.dense_share`` read the counters."""
    _bump(AGG_ARM_STATS[dev.agg_arm(pack, tuple(agg_ops), gathered)])


def note_agg_spans(pack, agg_ops, capacity, n, gathered=False):
    """Count one dispatched sort-arm program whose group starts come from
    the one-pass side of ops/device._group_spans: the side
    dev.spans_one_pass names for the program's static `capacity` and the
    `n` rows its aggregate reads.  Called on every turn of a fragment's
    capacity loop (a turn's pages, blocks or shards run one program text
    and count once); the dense arm and the searched side count nothing.
    The benchmark's ``agg.one_pass_spans_share`` reads the counter over
    ``agg_sorted``."""
    if (dev.agg_arm(pack, tuple(agg_ops), gathered) == "sort"
            and dev.spans_one_pass(capacity, n)):
        _bump("agg_spans_one_pass")


def join_expands(jn) -> bool:
    """Does this host-indexed join (a device_join._JoinNode with its
    strategy planned) emit a CSR-expanded output?  An inner or left join
    over a non-unique build does; a unique build is one gather at the
    probe's capacity, a semi / anti join an existence count."""
    return (jn.kind in ("inner", "left") and jn.strategy is not None
            and jn.strategy[2] is not None and jn.strategy[0] != "uniq")


def exists_expands(jn) -> bool:
    """Does this host-indexed semi / anti join test its residual pair by
    pair over a CSR expansion of the probe's live rows (a non-unique
    build)?  Its output stays probe-shaped (the pairs reduce back to
    their probe row); a unique build tests the residual on its one
    gathered row, a join with no residual counts matches."""
    return (jn.kind in ("semi", "anti") and bool(jn.other_conds)
            and jn.strategy is not None and jn.strategy[2] is not None
            and jn.strategy[0] != "uniq")


def note_join_layouts(joins):
    """Count the host-indexed joins of one dispatched join fragment (its
    ``_JoinNode``s with their strategies planned) by the layout of
    their index; EXPLAIN ANALYZE's ``join:`` annotation and the
    benchmark's ``join.direct_share`` read the counters.  A searched
    join whose index carries a prefix table (join_index._bucket_prefix)
    counts under ``join_search_prefixed`` too
    (``join.prefixed_search_share``).  The same joins count by kind
    (``join_left`` / ``join_semi`` / ``join_anti``; an inner join under
    none) and, where their output is CSR-expanded, under
    ``join_expand`` (``join.non_inner_share``, ``join.expanded_share``);
    a semi / anti join with a residual under ``join_residual`` too
    (``join.residual_share``).
    Joins built inside the program (no index) count under none; a mesh
    fragment on the indexed path (mpp_exec._indexed_chain) counts as one
    chip's."""
    for jn in joins:
        st = jn.strategy
        if st is None or st[2] is None:
            continue
        _bump("join_direct" if st[2].kind == "dense" else "join_search")
        if st[2].prefix is not None:
            _bump("join_search_prefixed")
        if jn.kind != "inner":
            _bump("join_" + jn.kind)
        if jn.kind in ("semi", "anti") and jn.other_conds:
            _bump("join_residual")
        if join_expands(jn):
            _bump("join_expand")


def note_join_expansion(rows, capacity, one_pass):
    """Count one expanded join of a dispatched join fragment: the `rows`
    its expansion emitted, the static `capacity` of the program that
    emitted them and whether that program took the one-pass side of
    device_join._expand_rows (`one_pass`: device_join.expand_one_pass
    asked with the capacity and the probe rows the trace asked it with).
    Once per fragment, for the run whose result is kept (a capacity
    retry's totals are not counted); the benchmark's
    ``join.expand_fill`` reads rows over capacity,
    ``join.one_pass_expand_share`` ``join_expand_one_pass`` over
    ``join_expand``, and EXPLAIN ANALYZE's ``join:`` annotation says
    ``expand x1 one-pass``."""
    _bump("join_expand_rows", int(rows))
    _bump("join_expand_capacity", int(capacity))
    if one_pass:
        _bump("join_expand_one_pass")


def note_join_compactions(cuts):
    """Count the `cuts` of one dispatched join fragment's KEPT program:
    its compaction points whose relation the program cut to the live
    rows (device_join.compact_to answered a capacity for them, and the
    program was built with it).  Once per fragment, after its capacity
    loop, traced or not; the benchmark's ``join.compactions_per_query``
    reads the counter, and EXPLAIN ANALYZE prints ``compact:x2`` beside
    the ``join:`` annotation."""
    _bump("join_compactions", int(cuts))


def note_join_chain(joins):
    """Count one dispatched join fragment whose inner joins
    device_join._reorder_fact_first chained (its nodes carry global
    keys) under ``join_chains``, and under ``join_chains_selective`` too
    where a step of the chain attached a build that lets the probe path
    cut ahead of a smaller candidate (a node marked ``selective``: the
    order the size rule alone gives changed).  Once per fragment,
    whatever its capacity retries and pages, traced or not; the mesh's
    indexed path counts as one chip's.  The benchmark's
    ``join.selective_first_share`` reads the two, and EXPLAIN ANALYZE
    prints ``order:selective`` beside ``join:``."""
    if not any(jn.global_keys for jn in joins):
        return
    _bump("join_chains")
    if any(jn.selective for jn in joins):
        _bump("join_chains_selective")


def note_join_residual(rows, capacity):
    """Count one semi / anti join whose residual its program tested over
    a CSR expansion (exists_expands): the pairs (probe row, build row)
    the expansion held and the static capacity it ran at.  Once per
    fragment, for the run whose result is kept; the benchmark's
    ``join.residual_fill`` reads rows over capacity."""
    _bump("join_residual_rows", int(rows))
    _bump("join_residual_capacity", int(capacity))


def note_join_gathers(fn):
    """Count one dispatched join fragment's gather chain: the column /
    mask / row-map gathers its program (`fn`, what
    device_join.compile_fragment returned, traced by now) emits, and
    those it elides because the leaf's row map is still the identity or
    the host knows the column holds no NULL.  Once per fragment, whatever
    its capacity retries, pages and shards (the mesh's indexed path calls
    this as one chip does; its in-program joins do not); EXPLAIN
    ANALYZE's ``gathers:`` annotation and the benchmark's
    ``join.elided_gather_share`` read the counters."""
    _bump("join_gathers", fn.gathers["emitted"])
    _bump("join_gathers_elided", fn.gathers["elided"])


def note_join_probe(resident: bool):
    """Count one dispatched join fragment by where its probe leaf's rows
    came from: columns resident in HBM through the residency ledger
    (read whole, or page by page as slices on the device), or pages the
    statement cut from the host's columns and sent.  Once per fragment,
    whatever its capacity restarts; EXPLAIN ANALYZE's ``probe:``
    annotation and the benchmark's ``join.probe_resident_share`` read
    the counters."""
    _bump("join_probe_resident" if resident else "join_probe_sent")


def note_semi_inset():
    """Count one aggregate subquery that a join fragment's plan walk
    (device_join.collect_tree) ran through its own executors and folded
    into an in-set filter on the probe subtree, under the span
    ``subquery.materialize``.  Once per fragment, whatever its capacity
    retries (the walk runs before them)."""
    _bump("semi_insets")


def note_join_derived(rows):
    """Count one build leaf of a dispatched join fragment that is another
    operator's result, run through its own executors under the span
    ``join.derived_build`` (device_join.collect_tree), and the `rows` it
    held.  Once per fragment, whatever its capacity retries, traced or
    not; EXPLAIN ANALYZE prints ``derived:x1 (rows 200000)`` and the
    benchmark's ``join.derived_rows_per_query`` reads the rows."""
    _bump("join_derived")
    _bump("join_derived_rows", int(rows))


def note_join_index_build():
    """Count one host join index built in numpy: a miss of the one index
    a key column caches (join_index.build_join_index, which runs the
    build under the span ``join.index_build``).  The benchmark's
    ``join.index_builds_per_query`` reads the counter."""
    _bump("join_index_builds")


def note_rerun(shape, capacity, groups, **tags):
    """A fragment's program has RUN and its loop goes round again at
    another capacity (an aggregate that found more groups than it had
    slots for, a join that expanded past its output, a learned capacity
    that was far too wide): count it, and mark the statement's trace
    with ``fragment.rerun`` (`capacity` = what the next program gets,
    `groups` = what this one counted).  A turn that follows a deferred
    compile or a transport fault is not one.  The benchmark's
    ``fragment.reruns_per_query`` reads the counter."""
    _bump("capacity_reruns")
    from ..session import tracing
    tracing.event("fragment.rerun", shape=shape, capacity=int(capacity),
                  groups=int(groups), **tags)


def note_unsupported(ctx, reason) -> "str | None":
    """A fragment raised DeviceUnsupported and its caller is about to run
    it on the host executors.  Under a PINNED device engine (`tpu`,
    `tpu-mpp`) that is worth saying: count it, and return the text of the
    ``device_unsupported:`` note for EXPLAIN ANALYZE.  Under `auto` (or a
    row floor) leaving a fragment to the host is the engine's own choice:
    None, nothing counted."""
    if engine_mode(ctx) not in ("tpu", "tpu-mpp"):
        return None
    _bump("unsupported")
    # one line, no ", " (the separator of EXPLAIN ANALYZE's notes)
    return " ".join(str(reason).replace(",", ";").split())[:200]


def pipe_cache_stats(thread_local: bool = False) -> dict:
    """Cache/compile counters: process-wide totals by default, or this
    thread's own (for before/after deltas around one dispatch — the
    process totals would charge a concurrent session's compile here)."""
    if thread_local:
        return dict(_tls_stats())
    with _PIPE_LOCK:
        return dict(_PIPE_STATS)


def _pipe_cache_get(key):
    # OrderedDict LRU mutation is NOT thread-safe; concurrent sessions
    # (threaded chaos, server connections) share this cache, so every
    # structural touch happens under the stats lock
    with _PIPE_LOCK:
        hit = _PIPE_CACHE.get(key)
        if hit is not None:
            _PIPE_CACHE.move_to_end(key)
    if hit is None:
        _bump("misses")
        return None
    _bump("hits")
    return hit[0]


def _pipe_cache_put(key, fn, dict_refs):
    with _PIPE_LOCK:
        _PIPE_CACHE[key] = (fn, dict_refs)
        if len(_PIPE_CACHE) > _PIPE_CACHE_MAX:
            _PIPE_CACHE.popitem(last=False)


def acquire_pipeline(key, build, dict_refs, *, ctx=None, args=None,
                     spec=None, shape="agg", sig="", ladder=True):
    """THE pipeline resolution chokepoint: every compiled query pipeline
    (scan-agg, streamed, window, join fragment, MPP) resolves through
    here — cache hit, or the compile service (async background compile /
    persistent-index warm start / sync build; executor/compile_service).

    `build` is a zero-arg builder returning the jitted fn; `args` the
    concrete call arguments (shapes recorded for background warming and
    the prewarm ladder — pass them whenever the dispatch site has them).
    Raises DeviceUnsupported when the fragment should run host-side
    while its executable compiles in the background."""
    fn = _pipe_cache_get(key)
    if fn is not None:
        from . import compile_service
        compile_service.note_hit(key)
        from ..session import tracing
        tracing.event("compile.cached", shape=shape)
        return fn
    from . import compile_service
    return compile_service.obtain(key, build, dict_refs, ctx=ctx,
                                  args=args, spec=spec, shape=shape,
                                  sig=sig, ladder=ladder)


def _count_trace():
    """Called from INSIDE a traced pipeline body: runs once per jax
    retrace (i.e. per XLA compile), never on a cached dispatch — and on
    the thread that dispatched, so the thread-local mirror attributes the
    compile to the right query."""
    _bump("traces")


def _charge_compile_s(seconds):
    _bump("compiles")
    _bump("compile_s", seconds)
    from ..session import tracing
    tracing.event("compile.xla", s=round(seconds, 4))
    if not getattr(_PIPE_TLS, "bg", False):
        # sync compiles only: the query path PAID this wall time, so it
        # belongs in the scrapeable per-layer histogram — background
        # builds overlap host serving and would poison the p99
        from . import compile_service
        compile_service.observe_hist("sync_compile_seconds", seconds)


# kernel-layer observability hooks: installing these makes
# ops/device.observed_jit meter retraces and compile seconds into the
# stats above — for the fused pipelines here AND the standalone
# join-match / topk / graft-agg kernels (one wrapper implementation,
# hook-wired so ops/device never imports the executor layer)
dev._trace_cb = _count_trace
dev._tls_traces = lambda: _tls_stats()["traces"]
dev._charge_compile = _charge_compile_s


def _timed_jit(fn):
    """jax.jit with compile accounting (ops/device.observed_jit with the
    hooks above installed): a dispatch that triggered a retrace — the
    body calls _count_trace — charges its wall time (trace + XLA compile
    + dispatch) to compile_s; cached dispatches pay only a counter
    read."""
    return dev.observed_jit(fn)


def _dc_sig(dc) -> str:
    """Content signature of a DeviceCol's dictionary for cache keys (falls
    back to id() only when no backing host column exists)."""
    if dc.dictionary is None:
        return ""
    hc = dc.host_col
    if hc is not None:
        try:
            return hc.dict_sig()
        except Exception:
            pass
    from ..utils.chunk import dict_content_sig
    return dict_content_sig(dc.dictionary)


def _expr_sig(e) -> str:
    """Structural signature of an expression (type-aware; reprs alone drop
    decimal scales, which change the traced program)."""
    from ..expression.core import Constant as _Const, ScalarFunc as _SF
    ft = e.ftype
    base = f"{ft.tp}.{ft.scale}"
    if isinstance(e, ExprColumn):
        return f"c{e.idx}:{base}"
    if isinstance(e, _Const):
        return f"k{e.value!r}:{base}"
    if isinstance(e, _SF):
        part = dev.date_part(e)
        if part is not None:
            # YEAR(d) and EXTRACT(YEAR FROM d): one program, one signature
            return f"{part[0]}({_expr_sig(part[1])}):{base}"
        extra = f"|{e.extra!r}" if e.extra is not None else ""
        return (f"{e.op}({','.join(_expr_sig(a) for a in e.args)})"
                f"{extra}:{base}")
    # apply-subqueries etc. never run on device
    raise DeviceUnsupported(f"{type(e).__name__} in device fragment")


def _build_pipeline(cond_fns, key_fns, n_keys, val_plan, agg_ops,
                    capacity, pack):
    """Close the compiled expression fns over one traceable program and jit
    it: mask, keys, values and the aggregate all fuse into a single XLA
    executable — no eager op dispatch between operators.

    The program takes `(env, n_live)` where env arrays may be BUCKET-PADDED
    past the live rows (ops/device.py bucket_rows): rows at positions >=
    n_live are masked out before the aggregate, so padding can never
    survive a filter or contribute to any group. n_live is a traced
    scalar — within-bucket row-count changes re-dispatch without a
    retrace."""

    def pipeline(env, n_live):
        _count_trace()
        first = next(iter(env.values()))[0]
        n = first.shape[0]
        with jax.named_scope("k_filter"):
            live = jnp.arange(n) < n_live
            if cond_fns:
                mask = None
                for f in cond_fns:
                    d, nl = f(env)
                    m = (d != 0) & ~nl
                    mask = m if mask is None else (mask & m)
                mask = jnp.broadcast_to(mask, (n,)) & live
            else:
                mask = live
        # key expressions count as k_agg_sort, aggregate inputs as
        # k_agg_gather: XLA fuses each into its first consumer there
        key_cols, key_nulls = [], []
        with jax.named_scope("k_agg_sort"):
            for f in key_fns:
                d, nl = dev.broadcast_1d(*f(env), n)
                key_cols.append(d.astype(jnp.int64))
                key_nulls.append(nl)
            if not key_cols:
                key_cols = [jnp.zeros(n, dtype=jnp.int64)]
                key_nulls = [jnp.zeros(n, dtype=bool)]
        val_cols, val_nulls = [], []
        # one eval per distinct compiled expr: AVG plans (sum, count) over
        # the SAME fn — sharing the traced (d, nl) lets the kernel's
        # identity-based null-row dedup fire and XLA CSE the value rows
        evaled = {}
        with jax.named_scope("k_agg_gather"):
            for f, conv in val_plan:
                hit = evaled.get(id(f))
                if hit is None:
                    hit = dev.broadcast_1d(*f(env), n)
                    evaled[id(f)] = hit
                d, nl = hit
                if conv == "int":
                    d = d.astype(jnp.int64)
                val_cols.append(d)
                val_nulls.append(nl)
        return dev._agg_impl(tuple(key_cols), tuple(key_nulls),
                             tuple(val_cols), tuple(val_nulls), mask,
                             n_keys=n_keys, agg_ops=agg_ops,
                             capacity=capacity, pack=pack)

    return _timed_jit(pipeline)


def _agg_used_columns(plan, conds) -> set:
    used = set()
    for e in plan.group_exprs:
        e.columns_used(used)
    for d in plan.aggs:
        for a in d.args:
            a.columns_used(used)
    for c in conds:
        c.columns_used(used)
    return used


#: the longest input a program that sorts may take whole.  The dense
#: arm's programs compile in seconds at any length and keep nothing at
#: input length.  One with an argsort and segment scans can cost the TPU
#: compiler tens of GB of HOST memory: a `group by l_suppkey` over the
#: resident SF10 lineitem (67,108,864-row bucket) ended its worker at
#: the v5e host's 40 GiB (PERF.md §6, PR 27).  Beyond this bound such a
#: scan runs in page-sized blocks, as every long input did before the
#: choice went from rows to bytes, and a join fragment (whose aggregate
#: always sorts) runs its probe leaf page by page or not on the device
#: at all (device_join.probe_pages).
_SORTED_SCAN_MAX_ROWS = 1 << 24


def _scan_arm(plan, chunk: Chunk, used) -> str:
    """The arm `_agg_impl` would aggregate this scan by, planned over the
    columns' metadata alone (nothing is uploaded)."""
    dcols = {i: dev.meta_device_col(chunk.columns[i])[0] for i in used}
    _kf, _km, key_pack, _vp, agg_ops, _sl = _plan_agg(plan, dcols)
    return dev.agg_arm(key_pack, tuple(agg_ops))


def resident_block_rows(cols, num_rows: int, ctx=None) -> int:
    """THE resident-or-sent rule of an in-memory input, for scans and for
    a join fragment's probe alike: 0 = `cols` (the used columns) stay
    resident, because at their row bucket they and the program's working
    set fit the tenant's share of the residency budget
    (`residency.scan_fits_resident`); else the length of the blocks the
    statement sends instead: the largest power of two (at most a page)
    whose rows fit."""
    from ..ops import residency
    from ..storage.paged import DEFAULT_PAGE_ROWS
    residency.attach(ctx)       # the budget and the tenant of THIS session
    nb = dev.bucket_rows(num_rows, dev.shape_buckets(ctx))
    if residency.scan_fits_resident(
            False, residency.upload_nbytes(cols, nb)):
        return 0
    fit = (residency.resident_scan_bytes()
           // max(residency.upload_nbytes(cols, 1), 1))
    return min(DEFAULT_PAGE_ROWS, 1 << max(fit.bit_length() - 1, 10))


def scan_stream_rows(plan, chunk: Chunk, conds, ctx=None) -> int:
    """Block length for a scan-aggregate when the session sets no
    ``tidb_device_stream_rows``: 0 = the input stays resident and runs
    `device_agg`; else `device_agg_streaming`'s block rows.  Decided by
    `resident_block_rows` from the bytes the used columns take at their
    row bucket against the tenant's share of the residency budget; a
    paged input streams by pages, one that does not fit in the largest
    power-of-two blocks (at most a page) that do, and one past
    `_SORTED_SCAN_MAX_ROWS` whose program would sort by pages too."""
    from ..storage.paged import DEFAULT_PAGE_ROWS, chunk_is_paged
    if chunk_is_paged(chunk):
        return DEFAULT_PAGE_ROWS
    used = sorted(_agg_used_columns(plan, conds))
    block = resident_block_rows([chunk.columns[i] for i in used],
                                chunk.num_rows, ctx)
    if block:
        return block
    if chunk.num_rows > _SORTED_SCAN_MAX_ROWS:
        try:
            if _scan_arm(plan, chunk, used) != "dense":
                return DEFAULT_PAGE_ROWS
        except DeviceUnsupported:
            pass        # device_agg raises it again, to the host engine
    return 0


def _agg_struct_parts(plan, conds) -> list:
    """The STRUCTURAL part of a scan-agg fragment's signature (conds,
    group exprs, agg descs — everything except dictionary content).  One
    helper feeds both _agg_sig and agg_batch_key so the admission batch
    key can never silently diverge from the compiled-pipeline identity
    it claims to prefix."""
    return (
        [_expr_sig(c) for c in conds] + ["|g|"] +
        [_expr_sig(e) for e in plan.group_exprs] + ["|a|"] +
        [f"{d.name}:{_expr_sig(d.args[0]) if d.args else ''}"
         for d in plan.aggs])


def _agg_sig(plan, conds, dcols) -> tuple:
    """(signature string, dictionary refs) for the pipeline cache — shared
    by the whole-table and streamed paths so their caches never diverge.
    Dictionaries contribute their CONTENT signature: a delta append that
    re-encodes the same value set must hit the cached pipeline."""
    sig = ";".join(
        _agg_struct_parts(plan, conds) +
        [f"{idx}:{_dc_sig(dc)}" for idx, dc in sorted(dcols.items())
         if dc.dictionary is not None])
    refs = tuple(dc.dictionary for dc in dcols.values()
                 if dc.dictionary is not None)
    return sig, refs


def agg_batch_key(plan, conds, n_rows: int, ctx=None):
    """Cheap admission-batching identity for a scan-agg fragment: the
    structural (plan sig, bucket shape) prefix of the compiled-pipeline
    cache key — dictionary CONTENT sigs are deliberately omitted (they
    require the columns in hand; admission runs before the upload).
    Queued fragments sharing this key coalesce onto one scheduling slot
    (executor/scheduler.py): identical keys re-dispatch the same cached
    XLA program against the same bucket, so N concurrent same-shaped
    queries cost ~one device call.  None when the fragment contains
    expressions the device can't sign (it won't batch, just queue)."""
    try:
        sig = ";".join(_agg_struct_parts(plan, conds))
        return ("agg", sig, dev.bucket_rows(n_rows, dev.shape_buckets(ctx)))
    except Exception:
        return None


#: learned exact sizes per fragment, through `learned` / `learn` alone:
#: the join fragments' (device_join) (sig, join_pos) → last observed
#: match total, (sig, ("live", pos)) → a compaction point's live count,
#: (sig, "agg") → group count ("groups": the pages' merged one), the
#: mesh's converged "caps", "xcaps" and aggregate capacity; and the
#: sort-arm scan aggregate's (sig, "scan_agg") → group count of its kept
#: run (device_agg). In-process, LRU-bounded like _PIPE_CACHE (sig
#: strings embed data-dependent packs, so stale data versions must age
#: out); repeat fragments (bench steady state, plan-cache hits) start
#: tight and never pay a discovery recompile again.
_CAP_STORE: "collections.OrderedDict" = collections.OrderedDict()
_CAP_STORE_MAX = 4096


def learned(sig, what):
    """What the last run of fragment `sig` stored under `what`, or None."""
    return _CAP_STORE.get((sig, what))


def learn(sig, what, value):
    """Store `value` under `what` for the next run of fragment `sig`."""
    key = (sig, what)
    _CAP_STORE[key] = value
    _CAP_STORE.move_to_end(key)
    if len(_CAP_STORE) > _CAP_STORE_MAX:
        _CAP_STORE.popitem(last=False)


def learned_sigs() -> set:
    """The signatures the store holds something for: the shapes traffic
    converged on."""
    # snapshot: concurrent queries mutate the store un-locked, and a
    # resize mid-iteration raises
    try:
        return {k[0] for k in list(_CAP_STORE)}
    except RuntimeError:
        return set()


def device_agg(plan, chunk: Chunk, conds, ctx=None) -> Chunk:
    """Fused filter+group+aggregate on device. Raises DeviceUnsupported to
    trigger host fallback."""
    from ..utils import failpoint
    # chaos/breaker hook: a `panic` here models a device runtime failure
    # (lost device, OOM) at the fragment boundary
    failpoint.inject("device-agg-exec")
    n = chunk.num_rows
    if n == 0:
        raise DeviceUnsupported("empty input")
    # canonicalize the traced shape: upload at the row bucket, mask live
    # rows in-program — a within-bucket delta reuses the compiled pipeline
    nb = dev.bucket_rows(n, dev.shape_buckets(ctx))
    used = _agg_used_columns(plan, conds)
    dcols = {}
    env = {}
    from ..session import tracing
    with tracing.span("upload.h2d") as usp:
        up0 = _upload_mark(usp)
        for idx in used:
            dc = dev.to_device_col(chunk.columns[idx], bucket=nb)
            dcols[idx] = dc
            env[idx] = (dc.data, dc.nulls)
        _upload_tags(usp, up0, len(env))
    if not env:
        raise DeviceUnsupported("no columns")
    tracing.event("device.upload", cols=len(env), bucket=nb, rows=n)

    # --- host-side planning only below (no device ops until dispatch) ---
    cond_fns = [dev.compile_expr(c, dcols) for c in conds]
    (key_fns, key_meta, key_pack, val_plan, agg_ops,
     slots) = _plan_agg(plan, dcols)
    n_keys = max(len(key_fns), 1)
    sig_exprs, dict_refs = _agg_sig(plan, conds, dcols)
    capacity, cap_sig = _scan_agg_start(plan, n, sig_exprs, key_pack,
                                        agg_ops, ctx)
    note_agg_arm(key_pack, agg_ops)
    _bump("scan_resident")
    while True:
        key = (sig_exprs, capacity, key_pack, tuple(agg_ops))
        cap = capacity

        def build(cap=cap):
            return _build_pipeline(cond_fns, key_fns, n_keys, val_plan,
                                   tuple(agg_ops), cap, key_pack)
        fn = acquire_pipeline(key, build, dict_refs, ctx=ctx,
                              args=(env, np.int64(n)), shape="agg",
                              sig=sig_exprs)
        f = AggFetch(fn(env, np.int64(n)), topn=resolve_topn(plan, slots))
        note_agg_spans(key_pack, agg_ops, capacity, nb)
        ng = f.ng
        if ng <= capacity:
            break
        capacity = dev.next_pow2(ng)
        note_rerun("agg", capacity, ng)
    if cap_sig is not None:
        learn(cap_sig, "scan_agg", ng)
    if ng == 0 and not plan.group_exprs:
        # global aggregate over zero kept rows still yields ONE row
        # (count=0, sum/min/max NULL) — host path has the special case
        raise DeviceUnsupported("empty global aggregate")
    body = f.body()
    return _assemble_agg(plan, key_meta, slots, dcols, body, f.out_rows)


def _scan_agg_start(plan, n, sig_exprs, key_pack, agg_ops, ctx=None):
    """(first capacity, the store's key or None) of `device_agg`'s loop:
    the tightest capacity the fragment knows before it runs, never past
    its `n` rows.  The dense arm compacts at most its bucket count, so it
    starts there and never overflows (nothing to learn); the sort arm
    starts at the group count its last kept run stored under the
    pipeline's key less the capacity, else at `_estimate_groups`.  Counts
    the sort arm's start in ``agg_start_learned`` / ``agg_start_estimated``
    (the benchmark's ``agg.learned_start_share``)."""
    if dev.agg_arm(key_pack, agg_ops) == "dense":
        first, cap_sig = 1 << sum(b for b, _o in key_pack), None
    else:
        cap_sig = (sig_exprs, key_pack, tuple(agg_ops))
        first = learned(cap_sig, "scan_agg")
        _bump("agg_start_estimated" if first is None
              else "agg_start_learned")
        if first is None:
            first = _estimate_groups(plan, n, ctx)
    return dev.next_pow2(min(n, max(first, 16))), cap_sig


def _upload_mark(sp):
    """What this thread had uploaded when the ``upload.h2d`` span `sp`
    opened (None: no trace is active, nothing is read)."""
    if sp is None:
        return 0
    from ..ops import residency
    return residency.thread_upload_bytes()


def _upload_tags(sp, mark, cols):
    """Close an ``upload.h2d`` span's account: the columns it placed and
    the bytes of them that were not resident already (the residency
    ledger's publishes on this thread, a join index's arrays among
    them)."""
    if sp is not None:
        from ..ops import residency
        sp.tags.update(cols=cols,
                       bytes=residency.thread_upload_bytes() - mark)


def _stream_block(col_arrays, lo, hi, batch_rows):
    """One block of a streamed scan on the device, under an
    ``upload.h2d`` span: rows [lo, hi) of every used column and null
    mask, padded to `batch_rows` so one compiled program serves every
    block (live rows are masked by the traced n_live).  The copies are
    enqueued, not waited for: block k+1's transfer overlaps block k's
    program.  Nothing keeps these arrays, so their bytes count under
    ``device_pipelines.stream_upload_bytes`` and not in the residency
    ledger."""
    from ..session import tracing
    with tracing.span("upload.h2d") as sp:
        env = {idx: (jnp.asarray(dev.pad_host(d[lo:hi], batch_rows)),
                     jnp.asarray(dev.pad_host(nl[lo:hi], batch_rows, True)))
               for idx, (d, nl) in col_arrays.items()}
        nbytes = sum(a.nbytes for pair in env.values() for a in pair)
        _bump("stream_upload_bytes", nbytes)
        if sp is not None:
            sp.tags.update(cols=len(env), bytes=nbytes)
    return env


def _fetch(make_tree):
    """``jax.device_get(make_tree())`` under a ``fetch.d2h`` span: the
    slices `make_tree` dispatches (each a device program of its own), the
    wait for the device to finish what they depend on, and the copy
    back.  Under a live span the last two are told apart: ``device.wait``
    blocks until the tree is ready (the programs' own run time),
    ``fetch.copy`` is the ``device_get`` of arrays that are (tags
    `arrays`, `bytes`).  Without one it is the one call."""
    from ..session import tracing
    with tracing.span("fetch.d2h") as sp:
        if sp is None:
            return jax.device_get(make_tree())
        tree = make_tree()
        with tracing.span("device.wait"):
            jax.block_until_ready(tree)
        leaves = jax.tree_util.tree_leaves(tree)
        nbytes = sum(a.nbytes for a in leaves)
        with tracing.span("fetch.copy", arrays=len(leaves), bytes=nbytes):
            out = jax.device_get(tree)
        sp.tags["bytes"] = nbytes
    return out


#: below this payload, one batched round trip beats two (per-transfer
#: latency dominates small copies)
_SMALL_FETCH_BYTES = 1 << 18


class AggFetch:
    """Device→host fetch of an _agg_impl result tree, minimizing
    transferred bytes: big capacities read the group count (+ any convergence scalars)
    first and then ONE batched copy of just the live [:ng] prefix — a
    capacity-sized fetch of a TopN-bound or overflowing result wastes most
    of the payload. Small results keep the single batched round trip
    (device_exec historically batched everything for exactly that reason).
    On a retry (caller sees ng/overflow and recompiles) the body is never
    fetched at all."""

    def __init__(self, agg_out, extras=(), topn=None):
        (self._keys, self._key_nulls, self._results, self._result_nulls,
         n_groups, _valid) = agg_out
        arrays = (*self._keys, *self._key_nulls, *self._results,
                  *self._result_nulls)
        self._cap = int(arrays[0].shape[0]) if arrays else 0
        row_bytes = sum(a.dtype.itemsize for a in arrays) or 1
        self._topn = topn
        self._body = None
        self.out_rows = None  # rows in body(); set on fetch
        if self._cap * row_bytes <= _SMALL_FETCH_BYTES:
            out = _fetch(lambda: (agg_out[:4], n_groups, tuple(extras)))
            self._body, ngv, self.extras = out
            self.ng = self.out_rows = int(ngv)
        else:
            out = _fetch(lambda: (n_groups, tuple(extras)))
            self.ng = int(out[0])
            self.extras = out[1]

    def body(self):
        """(key_out, key_null_out, results, result_nulls): the live groups
        — or, under a TopN annotation, just the top candidate groups in
        TopN-key order (selected on-device, so the host fetches k rows
        instead of millions)."""
        if self._body is None:
            k = min(max(self.ng, 1), self._cap)
            if self._topn is not None and self.ng > self._topn[1]:
                specs, kf = self._topn
                idx = _topk_indices(self._keys, self._key_nulls,
                                    self._results, self._result_nulls,
                                    self.ng, self._cap, specs, kf)
                self._body = _fetch(lambda: tuple(
                    tuple(a[idx] for a in t)
                    for t in (self._keys, self._key_nulls,
                              self._results, self._result_nulls)))
                self.out_rows = kf
                return self._body

            def sl(t):
                return tuple(a[:k] for a in t)
            self._body = _fetch(lambda: (
                sl(self._keys), sl(self._key_nulls),
                sl(self._results), sl(self._result_nulls)))
            self.out_rows = self.ng
        return self._body


#: jitted top-k kernels by (cap, k, spec, dtype) signature.  Structural
#: access happens under _PIPE_LOCK, same as _PIPE_CACHE: the fence path
#: (supervisor._reinit_backend) clears this cache while executor threads
#: install into it, and an install racing the clear unlocked would
#: re-publish an executable pinning the torn-down PJRT client
_TOPK_CACHE: dict = {}


def _topk_indices(keys, key_nulls, results, result_nulls, ng, cap, specs,
                  k):
    """Indices of the top-k live groups ordered by `specs` (device-side).
    specs: (("key"|"res", j, desc), ...). Null ordering matches the host
    comparator (ops/host.py sort_indices: NULLs first ASC, last DESC);
    descending ints use bitwise-not (exact, unlike negation at int64.min);
    rows past ng sort behind everything."""
    by = []
    for src, j, _desc in specs:
        d = keys[j] if src == "key" else results[j]
        nl = key_nulls[j] if src == "key" else result_nulls[j]
        by.append((d, nl))
    sig = (cap, k, tuple((s[0], s[2]) for s in specs),
           tuple(d.dtype.str for d, _ in by))
    with _PIPE_LOCK:
        fn = _TOPK_CACHE.get(sig)
    if fn is None:
        descs = [s[2] for s in specs]

        @jax.named_scope("k_topk")
        def run(by_arrays, ng_):
            _count_trace()
            lex = []  # sort keys, minor → major
            for (d, nl), desc in zip(reversed(by_arrays), reversed(descs)):
                if jnp.issubdtype(d.dtype, jnp.floating):
                    v = -d if desc else d
                else:
                    v = d.astype(jnp.int64)
                    if desc:
                        v = ~v
                lex.append(jnp.where(nl, 0, v))
                lex.append(jnp.where(nl, 1 if desc else 0,
                                     0 if desc else 1))
            lex.append(jnp.arange(cap) >= ng_)  # live rows first
            # one stable single-key argsort per key, minor → major —
            # the same order as jnp.lexsort(lex), whose ONE variadic
            # sort over all 2·n+1 operands the TPU compiler takes
            # minutes to build (v5e, cap 131072, 5 keys: 322 s against
            # 40 s for this chain; both run in milliseconds)
            order = jnp.arange(cap)
            for key in lex:
                order = order[jnp.argsort(key[order], stable=True)]
            return order[:k]

        with _PIPE_LOCK:
            # setdefault: a racing builder's kernel wins once installed
            # (both are valid; one object keeps jit's internal cache hot)
            fn = _TOPK_CACHE.setdefault(sig, _timed_jit(run))
    return fn(by, ng)


def resolve_topn(plan, slots):
    """plan.topn_fetch (agg-OUTPUT indices) → AggFetch specs over the
    device result arrays: group keys map 1:1; aggregate outputs map
    through their result slot. None when not annotated or unmappable."""
    tf = getattr(plan, "topn_fetch", None)
    if not tf or not plan.group_exprs:
        return None
    ngk = len(plan.group_exprs)
    specs = []
    for oi, desc in tf[0]:
        if oi < ngk:
            specs.append(("key", oi, desc))
        else:
            slot = slots[oi - ngk]
            if slot[0] == "avg":
                return None
            specs.append(("res", slot[1], desc))
    return tuple(specs), int(tf[1])


def _plan_agg(plan, dcols):
    """Host-side agg planning shared by the scan-agg pipeline and the join
    fragment: compile group keys and aggregate inputs against `dcols`
    (global column idx → DeviceCol). Returns
    (key_fns, key_meta, key_pack, val_plan, agg_ops, slots)."""
    key_fns = []
    key_meta = []  # (expr, decode dictionary or None)
    key_sizes = []  # dict size for string keys (packing), None otherwise
    for e in plan.group_exprs:
        k = phys_kind(e.ftype)
        if k == K_STR:
            # any string-valued expression: codes into its key dictionary
            # (ops/device.py compile_str_expr — CASE/SUBSTRING/… included)
            fn, key_dict, reps = dev.compile_str_expr(e, dcols)
            key_meta.append((e, reps))
            key_fns.append(fn)
            key_sizes.append(len(key_dict))
        elif k == K_FLOAT:
            raise DeviceUnsupported("float group keys")
        else:
            key_meta.append((e, None))
            key_fns.append(dev.compile_expr(e, dcols))
            key_sizes.append(None)
    if key_fns:
        key_pack = _key_pack(plan.group_exprs, key_sizes, dcols)
    else:
        key_pack = ((1, 0),)

    # aggregate value columns + op names; avg = sum + count pair
    val_plan, agg_ops = [], []
    slots = []  # per desc: ("plain", j) | ("avg", j_sum, j_cnt) | ("strcol", j, col)
    for desc in plan.aggs:
        if desc.distinct:
            # COUNT(DISTINCT x): the sorted kernel counts value runs per
            # group (ops/device.py cnt_dist). Other distinct aggs (and
            # multi-arg forms) stay host-side.
            if (desc.name == "count" and len(desc.args) == 1
                    and phys_kind(desc.args[0].ftype)
                    not in (K_FLOAT, K_STR)):
                val_plan.append((dev.compile_expr(desc.args[0], dcols),
                                 "int"))
                agg_ops.append("cnt_dist")
                slots.append(("plain", len(val_plan) - 1))
                continue
            if (desc.name == "count" and len(desc.args) == 1
                    and phys_kind(desc.args[0].ftype) == K_STR):
                # dict codes are value-faithful: distinct codes ==
                # distinct strings
                fn, _kd, _reps = dev.compile_str_expr(desc.args[0], dcols)
                val_plan.append((fn, "int"))
                agg_ops.append("cnt_dist")
                slots.append(("plain", len(val_plan) - 1))
                continue
            raise DeviceUnsupported("distinct agg on device")
        arg = desc.args[0] if desc.args else None
        name = desc.name
        if name == "count":
            val_plan.append((dev.compile_expr(arg, dcols), "int"))
            agg_ops.append("count")
            slots.append(("plain", len(val_plan) - 1))
            continue
        if name not in ("sum", "avg", "min", "max", "first_row"):
            raise DeviceUnsupported(f"agg {name} on device")
        k = phys_kind(arg.ftype)
        if k == K_STR and name in ("min", "max", "first_row"):
            # key dictionaries are sorted → code order == value order
            fn, _key_dict, reps = dev.compile_str_expr(arg, dcols)
            val_plan.append((fn, "int"))
            agg_ops.append({"min": "min", "max": "max",
                            "first_row": "first"}[name])
            slots.append(("strcol", len(val_plan) - 1, reps))
            continue
        if k == K_STR:
            raise DeviceUnsupported("string sum/avg")
        f = dev.compile_expr(arg, dcols)
        is_float = k == K_FLOAT
        if name in ("min", "max", "first_row"):
            val_plan.append((f, "raw"))
            agg_ops.append({"min": "min", "max": "max",
                            "first_row": "first"}[name])
            slots.append(("plain", len(val_plan) - 1))
        elif name == "sum":
            val_plan.append((f, "raw"))
            agg_ops.append("sum_f" if is_float else "sum_i")
            slots.append(("plain", len(val_plan) - 1))
        else:  # avg
            val_plan.append((f, "raw"))
            agg_ops.append("sum_f" if is_float else "sum_i")
            j_sum = len(val_plan) - 1
            val_plan.append((f, "raw" if is_float else "int"))
            agg_ops.append("count")
            slots.append(("avg", j_sum, len(val_plan) - 1))
    return key_fns, key_meta, key_pack, val_plan, agg_ops, slots


def _assemble_agg(plan, key_meta, slots, dcols, out_host, ng):
    """Device agg outputs (already copied to host) → result Chunk, under
    a ``host.assemble`` span."""
    from ..session import tracing
    with tracing.span("host.assemble", rows=ng):
        return _assemble_agg_chunk(plan, key_meta, slots, dcols, out_host,
                                   ng)


def _assemble_agg_chunk(plan, key_meta, slots, dcols, out_host, ng):
    from .agg_cache import note_agg_pass
    note_agg_pass()
    key_out, key_null_out, results, result_nulls = out_host
    out_cols = []
    for (e, dictionary), kd, kn in zip(key_meta, key_out, key_null_out):
        kd = np.asarray(kd[:ng])
        kn = np.asarray(kn[:ng])
        if dictionary is not None:
            data = np.where(kn, b"", dictionary[np.clip(kd, 0, len(dictionary) - 1)])
            out_cols.append(Column(e.ftype, data, kn))
        else:
            dt = np_dtype_for(e.ftype)
            out_cols.append(Column(e.ftype, kd.astype(dt), kn))
    if not plan.group_exprs:
        out_cols = []
    for desc, slot in zip(plan.aggs, slots):
        ft = desc.ftype
        if slot[0] == "avg":
            _tag, j_sum, j_cnt = slot
            s = np.asarray(results[j_sum][:ng])
            c = np.asarray(results[j_cnt][:ng])
            nulls = np.asarray(result_nulls[j_sum][:ng])
            if phys_kind(ft) == K_FLOAT:
                vals = s / np.maximum(c, 1)
                out_cols.append(Column(ft, vals, nulls))
            else:
                arg = desc.args[0]
                from .agg_cache import note_avg_partial
                note_avg_partial(s.astype(object), c)
                s_arg = arg.ftype.scale if phys_kind(arg.ftype) == K_DEC else 0
                shift = POW10[ft.scale - s_arg]
                num = s.astype(object) * shift
                den = np.maximum(c, 1).astype(object)
                sign = np.where(num < 0, -1, 1)
                q = (2 * np.abs(num) + den) // (2 * den)
                vals = np.array([int(x) for x in sign * q], dtype=np.int64)
                out_cols.append(Column(ft, vals, nulls))
            continue
        if slot[0] == "strcol":
            _tag, j, dictionary = slot  # decode dict captured at plan time
            codes = np.asarray(results[j][:ng])
            nulls = np.asarray(result_nulls[j][:ng])
            data = np.where(nulls, b"", dictionary[np.clip(codes, 0, len(dictionary) - 1)])
            out_cols.append(Column(ft, data, nulls))
            continue
        _tag, j = slot
        vals = np.asarray(results[j][:ng])
        nulls = np.asarray(result_nulls[j][:ng])
        if desc.name == "count":
            nulls = np.zeros(ng, dtype=bool)
        dt = np_dtype_for(ft)
        if dt is not object and vals.dtype != dt:
            vals = vals.astype(dt)
        out_cols.append(Column(ft, vals, nulls))
    if not out_cols:
        raise DeviceUnsupported("agg with no outputs")
    return Chunk(out_cols)


_DATE_PACK = (24, 1 << 22)  # MySQL DATE days: [-354285, 2932896] + margin

_EPOCH_DATE = np.datetime64("1970-01-01")


def _expr_bounds(e, dcols):
    """Host-known (min, max) of an integer-kinded group expression, from
    the cached column min/max (utils/chunk.py Column.minmax). Bare columns
    read it directly; YEAR(col) maps bounds through the monotone
    conversion. None when unknown — the caller falls back to the generic
    (multi-sort) agg path."""
    if dcols is None:
        return None
    if isinstance(e, ExprColumn):
        dc = dcols.get(e.idx)
        if dc is None or dc.host_col is None or dc.dictionary is not None:
            return None
        return dc.host_col.minmax()
    part = dev.date_part(e)
    if (part is not None and part[0] == "year"
            and isinstance(part[1], ExprColumn)
            and phys_kind(part[1].ftype) == K_DATE):
        b = _expr_bounds(part[1], dcols)
        if b is None:
            return None

        def to_year(days):
            return int(str((_EPOCH_DATE + np.timedelta64(days, "D")
                            ).astype("datetime64[Y]")))
        return to_year(b[0]), to_year(b[1])
    return None


def _key_pack(group_exprs, key_sizes, dcols=None):
    """Static (bits, offset) per group key when every key's value range is
    known a priori — dict codes (cardinality = key dictionary size, from
    _plan_agg), host column min/max for bare keys and YEAR() (cached on
    the Column, so the bound is exact per table version), and DATE days
    (bounded by MySQL's DATE domain) as the date fallback. Enables the
    single-argsort packed path in _agg_kernel. None when any key is
    unbounded or the total exceeds 62 bits."""
    pack = []
    total = 0
    for e, size in zip(group_exprs, key_sizes):
        k = phys_kind(e.ftype)
        if k == K_STR and size is not None:
            bits = max(int(size + 1).bit_length(), 1)
            pack.append((bits, 0))
        else:
            b = _expr_bounds(e, dcols)
            if b is not None:
                mn, mx = b
                span = mx - mn + 1
                pack.append((max((span + 1).bit_length(), 1), -mn))
            elif k == K_DATE:
                pack.append(_DATE_PACK)
            else:
                return None
        total += pack[-1][0]
    if total > 62:
        return None
    return tuple(pack)


def _estimate_groups(plan, n, ctx=None):
    """Group-count bound for the agg kernel's static capacity: product of
    the group columns' ANALYZE NDVs (reference: statistics-driven agg
    cardinality, planner/core/stats.go), falling back to 64 per key, both
    capped at the input size. With a multi-key GROUP BY the NDV product
    overshoots the true joint cardinality, but overshoot only pads the
    sort — undershoot costs a recompile."""
    if not plan.group_exprs:
        return 1
    from ..planner.optimizer import _expr_ndv
    est = 1
    for e in plan.group_exprs:
        nd = None
        if ctx is not None:
            try:
                nd = _expr_ndv(plan.child, e, ctx, n)
            except Exception:
                nd = None
        est *= int(nd * 2) if nd else 64
    return min(est, n)


_MERGE_OPS = {"count": "sum_i", "sum_i": "sum_i", "sum_f": "sum_f",
              "min": "min", "max": "max", "first": "first"}


def device_agg_streaming(plan, chunk: Chunk, conds, batch_rows: int,
                         ctx=None, allow_single=False) -> Chunk:
    """Streamed fused filter+group+aggregate: the input is cut into
    `batch_rows` blocks; each block's columns transfer to HBM and run the
    SAME jitted partial-agg program while the next block's transfer is
    queued (async dispatch = the cop-iterator worker overlap, reference:
    store/copr/coprocessor.go:399); per-block partial states stay on
    device and one merge kernel + one device_get finish the query.

    Device memory is bounded by batch_rows + n_blocks*capacity instead of
    the full table — the long-operand scaling path (SURVEY §5)."""
    n = chunk.num_rows
    if n == 0:
        raise DeviceUnsupported("empty input")
    if batch_rows <= 0 or (n <= batch_rows and not allow_single):
        # whole-input kernel is cheaper — except for paged inputs, whose
        # memmap slices must flow through here regardless of block count
        raise DeviceUnsupported("input fits one batch")
    used = _agg_used_columns(plan, conds)
    if not used:
        raise DeviceUnsupported("no columns")

    # full-column dictionaries (cached on the parent Column): batch slices
    # share codes, so group keys agree across blocks
    col_arrays = {}
    dcols = {}
    for idx in used:
        col = chunk.columns[idx]
        if col.is_object():
            from ..utils.collate import is_ci
            if is_ci(col.ftype.collate):
                codes, key_dict, reps = col.dict_encode_ci(col.ftype.collate)
                col_arrays[idx] = (codes, col.nulls)
                dcols[idx] = dev.DeviceCol(None, None, col.ftype,
                                           dictionary=key_dict, reps=reps,
                                           host_col=col)
            else:
                codes, uniq = col.dict_encode()
                col_arrays[idx] = (codes, col.nulls)
                dcols[idx] = dev.DeviceCol(None, None, col.ftype,
                                           dictionary=uniq, host_col=col)
        else:
            col_arrays[idx] = (col.data, col.nulls)
            dcols[idx] = dev.DeviceCol(None, None, col.ftype,
                                        host_col=col)

    cond_fns = [dev.compile_expr(c, dcols) for c in conds]
    (key_fns, key_meta, key_pack, val_plan, agg_ops,
     slots) = _plan_agg(plan, dcols)
    n_keys = max(len(key_fns), 1)
    if tuple(agg_ops) == ("cnt_dist",):
        # COUNT(DISTINCT x) streams through pair dedup: each block
        # deduplicates (group, x) PAIRS (an agg whose keys are
        # group+value), and the final cnt_dist over the concatenated
        # pair rows is exact even with cross-block duplicates — the
        # sorted kernel counts distinct value runs per group (reference:
        # the two-phase distinct agg, executor/aggregate.go partial
        # dedup + final count)
        _bump("scan_streamed")
        return _stream_count_distinct(plan, conds, chunk, col_arrays,
                                      dcols, cond_fns, key_fns, key_meta,
                                      key_pack, val_plan, slots,
                                      batch_rows, ctx)
    if any(op not in _MERGE_OPS for op in agg_ops):
        # other distinct/non-mergeable partial states can't merge across
        # blocks; the whole-input kernel handles them
        raise DeviceUnsupported("non-mergeable agg in streamed pipeline")
    merge_ops = tuple(_MERGE_OPS[op] for op in agg_ops)
    sig_exprs, dict_refs = _agg_sig(plan, conds, dcols)
    _bump("scan_streamed")
    est = _estimate_groups(plan, n, ctx)
    capacity = dev.next_pow2(min(batch_rows, max(est, 16)))
    merge_cap = capacity  # grows to the true total on merge overflow
    note_agg_arm(key_pack, agg_ops)
    for _attempt in range(8):
        key = (sig_exprs, "stream", capacity, key_pack, tuple(agg_ops))
        cap = capacity

        def build(cap=cap):
            return _build_pipeline(cond_fns, key_fns, n_keys, val_plan,
                                   tuple(agg_ops), cap, key_pack)
        fn = acquire_pipeline(key, build, dict_refs, ctx=ctx,
                              spec=_stream_spec(col_arrays, batch_rows),
                              shape="agg", sig=sig_exprs, ladder=False)
        note_agg_spans(key_pack, agg_ops, capacity, batch_rows)
        k_flush = max(1, _MERGE_BUDGET_ROWS // capacity)
        state = None
        buffered = []
        max_ng = 0
        overflow = False
        for lo in range(0, n, batch_rows):
            hi = min(lo + batch_rows, n)
            env = _stream_block(col_arrays, lo, hi, batch_rows)
            buffered.append(fn(env, np.int64(hi - lo)))
            if len(buffered) >= k_flush:
                # incremental fold: HBM holds at most k_flush partials +
                # the running state, never all n/batch_rows of them
                ngs = [int(g) for g in
                       _fetch(lambda: [p[4] for p in buffered])]
                max_ng = max(max_ng, *ngs)
                if max_ng > capacity:
                    overflow = True
                    break
                state, merge_cap = merge_partial_states(
                    state, buffered, merge_cap, n_keys, len(val_plan),
                    merge_ops, key_pack)
                buffered = []
        if not overflow and buffered:
            ngs = [int(g) for g in _fetch(lambda: [p[4] for p in buffered])]
            max_ng = max(max_ng, *ngs)
            if max_ng <= capacity:
                state, merge_cap = merge_partial_states(
                    state, buffered, merge_cap, n_keys, len(val_plan),
                    merge_ops, key_pack)
                buffered = []
        if overflow or max_ng > capacity:
            capacity = dev.next_pow2(max_ng)
            note_rerun("agg.stream", capacity, max_ng)
            continue
        break
    else:
        raise DeviceUnsupported("streamed agg capacity did not converge")
    if state is None:
        raise DeviceUnsupported("empty streamed input")
    out = _fetch(lambda: state[:5])
    key_out, key_null_out, results, result_nulls, n_groups = out
    ng = int(n_groups)
    if ng == 0 and not plan.group_exprs:
        raise DeviceUnsupported("empty global aggregate")
    return _assemble_agg(plan, key_meta, slots, dcols,
                         (key_out, key_null_out, results, result_nulls), ng)


def _stream_spec(col_arrays, batch_rows: int):
    """Arg-shape spec of one streamed block dispatch — (env, n_live)
    with every column padded to `batch_rows` — for the compile service's
    background warm (the env itself is built per block in the loop, so
    the shapes are described instead of materialized)."""
    import jax
    env_spec = {idx: (jax.ShapeDtypeStruct((batch_rows,),
                                           np.asarray(d).dtype),
                      jax.ShapeDtypeStruct((batch_rows,), np.bool_))
                for idx, (d, _nl) in col_arrays.items()}
    return (env_spec, jax.ShapeDtypeStruct((), np.int64))


#: partial-aggregate rows buffered on device before a merge flush (shared
#: by the streamed scan-agg and the paged probe join)
_MERGE_BUDGET_ROWS = 1 << 25


def _stream_count_distinct(plan, conds, chunk, col_arrays, dcols, cond_fns,
                           key_fns, key_meta, key_pack, val_plan, slots,
                           batch_rows, ctx):
    """Streamed COUNT(DISTINCT x): per-block dedup of (group, x) pairs,
    then one cnt_dist aggregate over the concatenated pair rows."""
    n = chunk.num_rows
    val_fn = val_plan[0][0]
    # block program: group keys + value as ONE key set, dedup via 'first'
    pair_fns = list(key_fns) + [val_fn]
    n_pair_keys = len(pair_fns)
    est = _estimate_groups(plan, n, ctx)
    # distinct pairs per block bounded by the block; estimate via group
    # est * a small per-group distinct factor, discovered on overflow
    capacity = dev.next_pow2(min(batch_rows, max(est * 4, 64)))
    n_blocks = (n + batch_rows - 1) // batch_rows
    sig_exprs, dict_refs = _agg_sig(plan, conds, dcols)
    for _attempt in range(8):
        if n_blocks * capacity > 4 * _MERGE_BUDGET_ROWS:
            # unlike the mergeable path this buffers EVERY block's pair
            # state — past the budget, degrade to the fallback instead of
            # exhausting device memory
            raise DeviceUnsupported(
                "distinct pair state exceeds the stream budget")
        key = (sig_exprs, "cntd", capacity)

        def build(cap=capacity):
            return _build_pipeline(cond_fns, pair_fns, n_pair_keys,
                                   [(val_fn, "int")], ("first",), cap,
                                   None)
        fn = acquire_pipeline(key, build, dict_refs, ctx=ctx,
                              spec=_stream_spec(col_arrays, batch_rows),
                              shape="agg", sig=sig_exprs, ladder=False)
        partials = []
        for lo in range(0, n, batch_rows):
            hi = min(lo + batch_rows, n)
            partials.append(fn(_stream_block(col_arrays, lo, hi,
                                             batch_rows),
                               np.int64(hi - lo)))
        counts = [int(c) for c in _fetch(lambda: [p[4] for p in partials])]
        if max(counts) <= capacity:
            break
        capacity = dev.next_pow2(max(counts))
        note_rerun("agg.stream", capacity, max(counts))
    else:
        raise DeviceUnsupported("distinct pair capacity did not converge")

    n_keys = max(len(key_fns), 1)
    # concatenated pair rows: group keys back apart from the value key
    if key_fns:
        key_cat = tuple(jnp.concatenate([p[0][k] for p in partials])
                        for k in range(n_keys))
        key_null_cat = tuple(jnp.concatenate([p[1][k] for p in partials])
                             for k in range(n_keys))
    else:
        # global COUNT(DISTINCT): one group — constant key, NOT the value
        tot = sum(int(p[0][0].shape[0]) for p in partials)
        key_cat = (jnp.zeros(tot, dtype=jnp.int64),)
        key_null_cat = (jnp.zeros(tot, dtype=bool),)
    val_cat = (jnp.concatenate([p[0][n_pair_keys - 1] for p in partials]),)
    val_null_cat = (jnp.concatenate([p[1][n_pair_keys - 1]
                                     for p in partials]),)
    mask = jnp.concatenate([jnp.arange(capacity) < p[4] for p in partials])
    total = int(mask.shape[0])
    final_cap = dev.next_pow2(max(est, 16))
    while True:
        out = _fetch(lambda: dev._agg_impl(
            key_cat, key_null_cat, val_cat, val_null_cat, mask,
            n_keys=n_keys, agg_ops=("cnt_dist",),
            capacity=min(final_cap, dev.next_pow2(total)), pack=key_pack))
        key_out, key_null_out, results, result_nulls, n_groups, _v = out
        ng = int(n_groups)
        if ng <= final_cap:
            break
        final_cap = dev.next_pow2(ng)
        note_rerun("agg.stream", final_cap, ng)
    if ng == 0 and not plan.group_exprs:
        raise DeviceUnsupported("empty global aggregate")
    return _assemble_agg(plan, key_meta, slots, dcols,
                         (key_out, key_null_out, results, result_nulls), ng)


def merge_partial_states(state, parts, merge_cap, n_keys, nvals, merge_ops,
                         key_pack):
    """Fold buffered partial-agg states (+ the running state) into ONE
    merged state of `merge_cap` output slots via the mergeable-agg kernel;
    grows merge_cap on overflow (inputs stay alive, so the retry is
    exact). Returns (state, merge_cap) — state is an _agg_impl output
    tuple whose [4] is the live group count. The states stay on the
    device; only the group count comes back."""
    alls = ([state] if state is not None else []) + list(parts)
    key_cat = tuple(jnp.concatenate([p[0][k] for p in alls])
                    for k in range(n_keys))
    key_null_cat = tuple(jnp.concatenate([p[1][k] for p in alls])
                         for k in range(n_keys))
    val_cat = tuple(jnp.concatenate([p[2][j] for p in alls])
                    for j in range(nvals))
    val_null_cat = tuple(jnp.concatenate([p[3][j] for p in alls])
                         for j in range(nvals))
    mask = jnp.concatenate([
        jnp.arange(p[0][0].shape[0]) < p[4] for p in alls])
    while True:
        out = dev._agg_impl(key_cat, key_null_cat, val_cat, val_null_cat,
                            mask, n_keys=n_keys, agg_ops=merge_ops,
                            capacity=merge_cap, pack=key_pack)
        ng = int(_fetch(lambda: out[4]))
        if ng <= merge_cap:
            return out, merge_cap
        merge_cap = dev.next_pow2(ng)
        note_rerun("merge", merge_cap, ng)


def page_singleton_state(key_cols, key_nulls, val_cols, val_nulls, mask,
                         agg_ops):
    """A raw fragment page (see compile_fragment raw_tail) viewed as a
    partial-agg state of SINGLETON groups, mergeable by
    _merge_states_host: a count op's singleton value is its 0/1 pre-count
    (its merge op is sum_i, and a count result is 0, never NULL); every
    other op's singleton value is the row's own value + null flag."""
    vals, vnulls = [], []
    for j, op in enumerate(agg_ops):
        v = np.asarray(val_cols[j])
        vn = np.asarray(val_nulls[j])
        if op == "count":
            vals.append((~vn).astype(np.int64))
            vnulls.append(np.zeros(vn.shape[0], dtype=bool))
        else:
            vals.append(v)
            vnulls.append(vn)
    m = np.asarray(mask)
    return (tuple(np.asarray(k) for k in key_cols),
            tuple(np.asarray(kn) for kn in key_nulls),
            tuple(vals), tuple(vnulls),
            int(np.count_nonzero(m)), m)


def _merge_states_host(alls, merge_cap, n_keys, nvals, merge_ops, key_pack):
    """numpy fold of partial-agg states, for the hybrid join
    (hybrid_join.py), which folds its device partitions and its host
    partitions together on the host on every backend. Packs the key
    tuple EXACTLY like _agg_impl (null -> slot 0, value+offset+1), stable
    argsort so the first-occurrence row of every group is the earliest
    partial's representative (matching the kernel's stable-sort 'first'
    semantics), then reduceat per aggregate. Output layout mirrors an
    _agg_impl return: (keys, key_nulls, results, result_nulls, n_groups,
    valid)."""
    keys = [np.concatenate([np.asarray(p[0][k]) for p in alls])
            for k in range(n_keys)]
    knulls = [np.concatenate([np.asarray(p[1][k]) for p in alls])
              for k in range(n_keys)]
    vals = [np.concatenate([np.asarray(p[2][j]) for p in alls])
            for j in range(nvals)]
    vnulls = [np.concatenate([np.asarray(p[3][j]) for p in alls])
              for j in range(nvals)]
    # p[5] is each state's validity mask: arange<ng for compact kernel
    # states, an arbitrary row mask for raw singleton pages
    live = np.concatenate([np.asarray(p[5]) for p in alls])
    packed = np.zeros(live.shape[0], dtype=np.int64)
    for (bits, offset), k, kn in zip(key_pack, keys, knulls):
        shifted = k.astype(np.int64) + np.int64(offset + 1)
        packed = (packed << np.int64(bits)) | np.where(kn, 0, shifted)
    idx = np.nonzero(live)[0]
    order = np.argsort(packed[idx], kind="stable")
    sidx = idx[order]
    sk = packed[idx][order]
    m = sk.shape[0]
    new = np.empty(m, dtype=bool)
    if m:
        new[0] = True
        np.not_equal(sk[1:], sk[:-1], out=new[1:])
    bounds = np.nonzero(new)[0]
    ng = int(bounds.shape[0])
    cap = merge_cap
    while ng > cap:
        cap *= 2
    rep = sidx[bounds]

    def pad(a):
        out = np.zeros(cap, dtype=a.dtype)
        out[:ng] = a
        return out

    key_out = tuple(jnp.asarray(pad(k[rep])) for k in keys)
    key_null_out = tuple(jnp.asarray(pad(kn[rep])) for kn in knulls)
    results = []
    result_nulls = []
    for j, opn in enumerate(merge_ops):
        v = vals[j]
        vn = vnulls[j]
        if opn == "first":
            results.append(jnp.asarray(pad(v[rep])))
            result_nulls.append(jnp.asarray(pad(vn[rep])))
            continue
        svn = vn[sidx]
        nonnull = np.add.reduceat(
            (~svn).astype(np.int64), bounds) if ng else np.zeros(
                0, dtype=np.int64)
        if opn == "sum_i":
            sv = np.where(vn, 0, v.astype(np.int64))[sidx]
            seg = (np.add.reduceat(sv, bounds) if ng
                   else np.zeros(0, dtype=np.int64))
        elif opn == "sum_f":
            sv = np.where(vn, 0.0, v.astype(np.float64))[sidx]
            seg = (np.add.reduceat(sv, bounds) if ng
                   else np.zeros(0, dtype=np.float64))
        elif opn in ("min", "max"):
            if np.issubdtype(v.dtype, np.floating):
                sent = np.inf if opn == "min" else -np.inf
            else:
                ii = np.iinfo(v.dtype)
                sent = ii.max if opn == "min" else ii.min
            sv = np.where(vn, sent, v)[sidx]
            red = np.minimum if opn == "min" else np.maximum
            seg = (red.reduceat(sv, bounds) if ng
                   else np.zeros(0, dtype=v.dtype))
        else:
            raise ValueError(opn)
        results.append(jnp.asarray(pad(seg)))
        result_nulls.append(jnp.asarray(pad(nonnull == 0)
                                        if ng else np.zeros(cap, bool)))
    valid = jnp.arange(cap) < ng
    return (key_out, key_null_out, tuple(results), tuple(result_nulls),
            jnp.asarray(ng), valid), cap


#: window functions the device kernel computes (reference:
#: executor/window.go; unistore runs window fragments storage-side)
_WIN_RANKS = {"row_number", "rank", "dense_rank", "percent_rank",
              "cume_dist"}
_WIN_AGGS = {"sum", "count", "avg", "min", "max"}

def device_window(p, chunk: Chunk, ctx=None) -> Chunk:
    """Window functions as ONE jitted program: a single stable lexsort by
    (partition, order), then log-depth prefix scans for every function —
    no per-partition host loop (the host path iterates partitions in
    Python; reference executor/window.go processes them serially too).
    Default frames only: with ORDER BY, RANGE UNBOUNDED PRECEDING..CURRENT
    ROW (peer-aware); without, the whole partition. Raises
    DeviceUnsupported outside that language (ntile/lead/lag, explicit
    frames, distinct args) — the host executor covers the rest."""
    n = chunk.num_rows
    if n == 0:
        raise DeviceUnsupported("empty window input")
    for f in p.funcs:
        if f.frame is not None:
            raise DeviceUnsupported("explicit window frame")
        if f.name in _WIN_RANKS:
            continue
        if f.name not in _WIN_AGGS or len(f.args) != 1:
            raise DeviceUnsupported(f"window func {f.name}")
        if phys_kind(f.args[0].ftype) == K_STR and f.name not in ("count",):
            raise DeviceUnsupported("string window aggregate")

    used = set()
    for e in p.partition_exprs:
        e.columns_used(used)
    for e, _d in p.order_by:
        e.columns_used(used)
    for f in p.funcs:
        for a in f.args:
            a.columns_used(used)
    # bucketed upload: padding rows sort behind every live row (validity is
    # the most-major sort key) and form their own trailing partition, so no
    # rank/aggregate of a real partition ever sees them
    nb = dev.bucket_rows(n, dev.shape_buckets(ctx))
    dcols = {}
    env = {}
    for idx_ in used:
        dc = dev.to_device_col(chunk.columns[idx_], bucket=nb)
        dcols[idx_] = dc
        env[idx_] = (dc.data, dc.nulls)

    part_fns = [dev.compile_expr(e, dcols) for e in p.partition_exprs]
    order_fns = [(dev.compile_expr(e, dcols), d) for e, d in p.order_by]
    agg_fns = [dev.compile_expr(f.args[0], dcols)
               if f.name in _WIN_AGGS else None for f in p.funcs]
    has_order = bool(p.order_by)
    names = tuple(f.name for f in p.funcs)
    kinds = tuple(phys_kind(f.args[0].ftype) if f.name in _WIN_AGGS else None
                  for f in p.funcs)

    def run(env, n_live):
        _count_trace()
        # padded (bucket) length from the closure, NOT an env array: a
        # window over no columns at all (count(*) OVER ()) has an empty
        # env, and the cache key already pins nb
        n = nb
        i = jnp.arange(n)
        in_live = i < n_live
        lex = []  # minor → major: tiebreak, order keys reversed, partition

        def push_key(d, nl, desc):
            if jnp.issubdtype(d.dtype, jnp.floating):
                v = -d if desc else d
            else:
                v = d.astype(jnp.int64)
                if desc:
                    v = ~v
            lex.append(jnp.where(nl, 0, v))
            # MySQL: NULLs first ASC, last DESC
            lex.append(jnp.where(nl, 1 if desc else 0, 0 if desc else 1))

        order_kvs = []
        for fn, desc in order_fns:
            d, nl = dev.broadcast_1d(*fn(env), n)
            order_kvs.append((d, nl))
        part_kvs = []
        for fn in part_fns:
            d, nl = dev.broadcast_1d(*fn(env), n)
            part_kvs.append((d, nl))
        for (d, nl), (_f, desc) in zip(reversed(order_kvs),
                                       reversed(order_fns)):
            push_key(d, nl, desc)
        for d, nl in reversed(part_kvs):
            push_key(d, nl, False)
        # validity is the MOST-major key: bucket-padding rows sort behind
        # every live row (stable, so a keyless window keeps input order)
        lex.append(~in_live)
        idx = jnp.lexsort(lex)
        inv = jnp.argsort(idx)

        def change(kvs):
            ch = jnp.zeros(n, dtype=bool).at[0].set(True)
            for d, nl in kvs:
                # NULL rows carry arbitrary raw data (_agg_impl invariant,
                # ops/device.py): value-mask before comparing, or NULL runs
                # split on garbage and every rank/agg restarts mid-group
                dm = jnp.where(nl, jnp.zeros((), dtype=d.dtype), d)
                ds, ns = dm[idx], nl[idx]
                delta = jnp.concatenate([
                    jnp.ones(1, dtype=bool),
                    (ds[1:] != ds[:-1]) | (ns[1:] != ns[:-1])])
                ch = ch | delta
            return ch

        part_change = (change(part_kvs) if part_kvs
                       else jnp.zeros(n, dtype=bool).at[0].set(True))
        # sorted position n_live is the first padding row (validity-major
        # sort): force a partition boundary there so padding forms its own
        # trailing segment and never extends a real partition's frame
        part_change = part_change | (i == n_live)
        peer_change = part_change | (change(order_kvs) if order_kvs
                                     else jnp.zeros(n, dtype=bool))
        spos = jax.lax.cummax(jnp.where(part_change, i, -1))
        ppos = jax.lax.cummax(jnp.where(peer_change, i, -1))

        def seg_end(chg):
            # smallest later index starting a new segment, minus one
            nxt = jnp.concatenate([
                jnp.where(chg[1:], i[1:], n), jnp.full(1, n)])
            fut = jnp.flip(jax.lax.cummin(jnp.flip(nxt)))
            return fut - 1

        epos = seg_end(part_change)
        pe = seg_end(peer_change) if has_order else epos
        m = epos - spos + 1

        outs = []
        for name, fn, k in zip(names, agg_fns, kinds):
            if name == "row_number":
                outs.append(((i - spos + 1)[inv], jnp.zeros(n, dtype=bool)))
                continue
            if name == "rank":
                outs.append(((ppos - spos + 1)[inv],
                             jnp.zeros(n, dtype=bool)))
                continue
            if name == "dense_rank":
                c = jnp.cumsum(peer_change)
                outs.append(((c - c[spos] + 1)[inv],
                             jnp.zeros(n, dtype=bool)))
                continue
            if name == "percent_rank":
                r = (ppos - spos).astype(jnp.float64)
                outs.append((jnp.where(m > 1, r / jnp.maximum(m - 1, 1),
                                       0.0)[inv],
                             jnp.zeros(n, dtype=bool)))
                continue
            if name == "cume_dist":
                v = (pe - spos + 1).astype(jnp.float64) / m
                outs.append((v[inv], jnp.zeros(n, dtype=bool)))
                continue
            d, nl = dev.broadcast_1d(*fn(env), n)
            ds, ns = d[idx], nl[idx]
            end = pe  # default frame: through the current peer group
            cnt_v = (~ns).astype(jnp.int64)
            ccs = jnp.cumsum(cnt_v)
            cnt_run = ccs[end] - ccs[spos] + cnt_v[spos]
            if name == "count":
                outs.append((cnt_run[inv], jnp.zeros(n, dtype=bool)))
                continue
            if name in ("sum", "avg"):
                if k == K_FLOAT:
                    # segmented scan, NOT prefix-sum differences: the
                    # global cumsum carries earlier partitions' magnitude
                    # into this partition's rounding error (same invariant
                    # as the agg kernel, ops/device.py _agg_impl)
                    z = jnp.where(ns, 0.0, ds)
                    s = dev._seg_running(jnp.add, part_change, z)[end]
                else:
                    z = jnp.where(ns, 0, ds)
                    cs = jnp.cumsum(z)  # ints: differences are exact
                    s = cs[end] - cs[spos] + z[spos]
                outs.append((s[inv], (cnt_run == 0)[inv]))
                if name == "avg":  # host assembly divides sum by count
                    outs.append((cnt_run[inv], jnp.zeros(n, dtype=bool)))
                continue
            # min / max: flagged segmented running scan, read at `end`;
            # the null identity must match the column's DEVICE dtype —
            # int64 extremes silently wrap on int32-backed DATE columns
            if k == K_FLOAT:
                ident = jnp.inf if name == "min" else -jnp.inf
            else:
                info = jnp.iinfo(ds.dtype)
                ident = info.max if name == "min" else info.min
            z = jnp.where(ns, ident, ds)
            comb = jnp.minimum if name == "min" else jnp.maximum
            scan = dev._seg_running(comb, part_change, z)
            v = scan[end]
            outs.append((v[inv], (cnt_run == 0)[inv]))
        return tuple(outs)

    # dictionary CONTENT is the load-bearing key component: compiled
    # str-expr LUTs bake the dictionary's codes, exactly like the agg
    # pipeline cache (_agg_sig / _pipe_cache_put); the shape key is the
    # BUCKET, so a within-bucket delta re-dispatches the compiled program
    dict_refs = tuple(dc.dictionary for dc in dcols.values()
                      if dc.dictionary is not None)
    sig = (nb, names, kinds, has_order,
           tuple(_expr_sig(e) for e in p.partition_exprs),
           tuple((_expr_sig(e), d) for e, d in p.order_by),
           tuple(_expr_sig(f.args[0]) if f.name in _WIN_AGGS else None
                 for f in p.funcs),
           tuple(f"{idx_}:{_dc_sig(dc)}" for idx_, dc in sorted(dcols.items())
                 if dc.dictionary is not None))
    fn = acquire_pipeline(("win",) + sig, lambda: _timed_jit(run),
                          dict_refs, ctx=ctx, args=(env, np.int64(n)),
                          shape="window", sig=sig)
    outs = jax.device_get(fn(env, np.int64(n)))

    # outputs are padded to the bucket; positions past the live rows belong
    # to the trailing padding partition — slice them away
    outs = tuple((np.asarray(d)[:n], np.asarray(nl)[:n]) for d, nl in outs)
    out_cols = list(chunk.columns)
    oi = 0
    for f in p.funcs:
        ft = f.ftype
        if f.name == "avg":
            s = np.asarray(outs[oi][0])
            s_null = np.asarray(outs[oi][1])
            c = np.asarray(outs[oi + 1][0])
            oi += 2
            arg = f.args[0]
            if phys_kind(ft) == K_FLOAT:
                vals = s / np.maximum(c, 1)
                if phys_kind(arg.ftype) == K_DEC:
                    # decimal args evaluate as scaled ints — unscale for
                    # the double-typed window AVG
                    vals = vals / POW10[arg.ftype.scale]
                out_cols.append(Column(ft, vals, s_null))
            else:
                s_arg = (arg.ftype.scale
                         if phys_kind(arg.ftype) == K_DEC else 0)
                shift = POW10[ft.scale - s_arg]
                num = s.astype(object) * shift
                den = np.maximum(c, 1).astype(object)
                sign = np.where(num < 0, -1, 1)
                q = (2 * np.abs(num) + den) // (2 * den)
                vals = np.array([int(x) for x in sign * q], dtype=np.int64)
                out_cols.append(Column(ft, vals, s_null))
            continue
        vals, nulls = outs[oi]
        oi += 1
        vals = np.asarray(vals)
        nulls = np.asarray(nulls)
        dt = np_dtype_for(ft)
        if dt is not object and vals.dtype != dt:
            vals = vals.astype(dt)
        out_cols.append(Column(ft, vals, nulls))
    return Chunk(out_cols)


def device_join_keys(lkeys, rkeys):
    """Combine multi-column join keys into single int64 codes host-side
    (shared factorization), then match on device. Returns (li, ri).

    Single raw-int64 keys skip the factorization pass entirely — the
    device matcher is sort-based and handles arbitrary int64 values
    (null rows are masked by the kernel / the keep filter)."""
    if (len(lkeys) == 1 and lkeys[0][0].dtype == np.int64
            and rkeys[0][0].dtype == np.int64):
        (pd, pn), = lkeys
        (bd, bn), = rkeys
        return dev.device_join_match((bd, bn), (pd, pn))
    nb = len(rkeys[0][0])
    npr = len(lkeys[0][0])
    from ..ops import host as hops
    acc_b = np.zeros(nb, dtype=np.int64)
    acc_p = np.zeros(npr, dtype=np.int64)
    b_null = np.zeros(nb, dtype=bool)
    p_null = np.zeros(npr, dtype=bool)
    for (pd, pn), (bd, bn) in zip(lkeys, rkeys):
        both = np.concatenate([bd, pd])
        codes, card = hops.factorize_column(both, np.concatenate([bn, pn]))
        acc_b = acc_b * np.int64(card + 1) + (codes[:nb] + 1)
        acc_p = acc_p * np.int64(card + 1) + (codes[nb:] + 1)
        b_null |= bn
        p_null |= pn
    return dev.device_join_match((acc_b, b_null), (acc_p, p_null))
