"""MPP distributed operators over a jax.sharding.Mesh.

Reference mapping:
- fragment exchanges (planner/core/fragment.go:37,64; exchange types
  PassThrough/Broadcast/Hash at store/copr/mpp.go) → XLA collectives inside
  `shard_map`: hash exchange = `all_to_all`, broadcast = `all_gather`,
  final merge = `psum` / `pmin` / `pmax`.
- parallel partial/final hash aggregation (executor/aggregate.go:85-165)
  → per-shard sort-based partial aggregation, `all_gather` of bounded
  partial states, replicated final merge. One jitted program; no host hop
  between partial and final.
- shuffled hash join (planner/core/exhaust_physical_plans.go MPP joins)
  → hash-partition both sides by key over the mesh via `all_to_all`,
  local sort-join per shard, `psum` the joined aggregate.

Everything is static-shape: partial states are `capacity`-bounded, shuffle
buckets are `cap`-bounded with overflow *counted and reported* so the host
can retry with a larger capacity (never silently wrong).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P


def _observed_jit(fn):
    """jit with compile accounting (ops/device.observed_jit): the
    library-embedder dist_* steps meter their traces/compile seconds into
    the shared pipe stats, and the AST lint in tests/test_compile_service
    confines raw ``jax.jit`` of query programs to the compile service +
    kernel layer."""
    from ..ops.device import observed_jit
    return observed_jit(fn)



def make_mesh(n_devices: int | None = None, axis: str = "part") -> Mesh:
    """1-D device mesh over the partition axis. Regions (the reference's
    ~100MiB shards) map to equal row-slices over this axis."""
    devs = jax.devices()
    if n_devices is not None:
        if n_devices > len(devs):
            raise ValueError(
                f"requested {n_devices}-device mesh but only {len(devs)} "
                f"devices visible (platform {devs[0].platform}); for virtual "
                "multi-chip set jax_platforms=cpu + "
                "xla_force_host_platform_device_count")
        devs = devs[:n_devices]
    return Mesh(np.asarray(devs), (axis,))


def shard_batch(mesh: Mesh, *arrays, axis: str = "part"):
    """Pad each 1-D array to a multiple of the mesh size and device_put it
    sharded over the mesh. Returns (padded_arrays, valid_mask)."""
    n_shards = mesh.shape[axis]
    n = arrays[0].shape[0]
    pad = (-n) % n_shards
    spec = jax.sharding.NamedSharding(mesh, P(axis))
    out = []
    for a in arrays:
        a = np.asarray(a)
        if pad:
            a = np.concatenate([a, np.zeros(pad, dtype=a.dtype)])
        out.append(jax.device_put(a, spec))
    valid = np.ones(n + pad, dtype=bool)
    if pad:
        valid[n:] = False
    return out, jax.device_put(valid, spec)


# ---------------------------------------------------------------------------
# local bounded sort-based aggregation (shared by partial and final stages)
# ---------------------------------------------------------------------------

def _local_agg(keys, valid, vals, kinds, capacity):
    """Group `vals` by int64 `keys` (invalid rows ignored) into at most
    `capacity` groups. Returns (group_keys[cap], outs tuple[cap],
    out_valid[cap], n_groups). Pure traced code — static shapes only.

    Scatter-free (XLA serializes scatters on TPU): sort + boundary
    cumsum/gather for sums, segmented associative scan for min/max — the
    same scheme as ops/device._agg_impl, single-key variant.

    Sorts by (validity, key) — valid rows occupy the first `kept` sorted
    positions for ANY key domain, including a genuine int64.max key (the
    old single-key sentinel scheme interleaved such keys with padding)."""
    from ..ops.device import _group_spans, _seg_running

    n = keys.shape[0]
    order = jnp.lexsort((keys, ~valid))  # valid-first, then key-sorted
    sk = keys[order]
    kept = jnp.sum(valid)
    pos = jnp.arange(n)
    in_range = pos < kept
    prev = jnp.concatenate([sk[:1], sk[:-1]])
    is_new = jnp.zeros(n, dtype=bool).at[0].set(n > 0) | (sk != prev)
    is_new = is_new & in_range
    n_groups = jnp.sum(is_new)
    starts, _ends, end_idx, span_sum = _group_spans(is_new, kept, n, capacity)
    safe = jnp.clip(starts, 0, jnp.maximum(n - 1, 0))
    group_keys = sk[safe]

    outs = []
    for v, kind in zip(vals, kinds):
        sv = v[order]
        if kind in ("sum", "count"):
            z = jnp.where(in_range, sv, jnp.zeros((), dtype=sv.dtype))
            if jnp.issubdtype(sv.dtype, jnp.floating):
                # keep float rounding error group-local (see _group_spans)
                outs.append(_seg_running(jnp.add, is_new, z)[end_idx])
            else:
                outs.append(span_sum(z))
        elif kind == "min":
            big = (jnp.inf if jnp.issubdtype(sv.dtype, jnp.floating)
                   else jnp.iinfo(sv.dtype).max)
            run = _seg_running(jnp.minimum, is_new,
                               jnp.where(in_range, sv, big))
            outs.append(run[end_idx])
        elif kind == "max":
            small = (-jnp.inf if jnp.issubdtype(sv.dtype, jnp.floating)
                     else jnp.iinfo(sv.dtype).min)
            run = _seg_running(jnp.maximum, is_new,
                               jnp.where(in_range, sv, small))
            outs.append(run[end_idx])
        else:
            raise ValueError(kind)
    out_valid = jnp.arange(capacity) < jnp.minimum(n_groups, capacity)
    return group_keys, tuple(outs), out_valid, n_groups


def _supervised_step(step, ctx):
    """Route a jitted exchange-dispatch step through the device-runtime
    supervisor (executor/supervisor.py) when the caller's context carries
    a deadline (`tidb_device_call_timeout` / `max_execution_time`): a
    collective hung inside the PJRT client raises a classified
    DeviceHangError instead of freezing the caller.  With no context (or
    no deadline) the step dispatches inline, unchanged.

    Note the SQL path's MPP fragments don't come through here — they are
    built by executor/mpp_exec.py and admitted + supervised one level up,
    inside run_device.  The `ctx=` hook exists for direct library
    embedders of dist_agg_step / dist_join_agg_step, who otherwise have
    no supervised wrapper between them and a hung collective
    (tests/test_mpp.py exercises it).  The embedder path holds an
    ADMISSION ticket too (executor/scheduler.py — every MPP dispatch
    enqueues a fragment ticket): a refusal surfaces as the classified
    DeviceAdmissionError (9009) since there is no host fallback at this
    level to degrade to."""
    if ctx is None:
        return step

    def call(*args, **kw):
        from ..executor import scheduler
        from ..executor.supervisor import call_supervised, deadline_for
        ticket = scheduler.admit(ctx, shape="mpp")
        try:
            # deadline AFTER the admission wait (run_device's ordering):
            # the supervised window must reflect what remains of
            # max_execution_time once the ticket is granted, or a queued
            # step runs past the statement bound by the whole wait
            deadline_s, fence = deadline_for(ctx)
            return call_supervised(step, args, kw, deadline_s=deadline_s,
                                   ctx=ctx, shape="mpp",
                                   label="mpp exchange",
                                   fence_on_expiry=fence)
        finally:
            scheduler.release(ticket)

    return call


def dist_agg_step(mesh: Mesh, kinds: tuple, capacity: int,
                  axis: str = "part", ctx=None):
    """Build the jitted distributed group-by step (partial → all_gather →
    final). Inputs are row-sharded over `axis`:
        keys  int64[N]      group key codes
        valid bool[N]       row mask (filter result & padding)
        *vals               one array per aggregate, aligned with `kinds`
    `kinds`: tuple of "sum" | "count" | "min" | "max" ("count" vals should
    be 0/1 int64). Returns replicated
    (group_keys[cap], outs, out_valid[cap], n_groups, overflowed).
    """
    in_specs = (P(axis), P(axis)) + tuple(P(axis) for _ in kinds)

    @functools.partial(
        shard_map, mesh=mesh, in_specs=in_specs,
        out_specs=(P(), tuple(P() for _ in kinds), P(), P(), P()),
        check_vma=False)
    def step(keys, valid, *vals):
        # stage 1: per-shard partial aggregation into bounded state
        pk, pouts, pvalid, png = _local_agg(keys, valid, vals, kinds, capacity)
        # exchange: gather every shard's partial state (capacity * n_shards
        # rows — tiny next to N), replicated final merge on every shard
        gk = jax.lax.all_gather(pk, axis, tiled=True)
        gvalid = jax.lax.all_gather(pvalid, axis, tiled=True)
        gouts = tuple(jax.lax.all_gather(o, axis, tiled=True) for o in pouts)
        # stage 2: min/max merge with same kind; partial sums re-sum
        merge_kinds = tuple("sum" if k == "count" else k for k in kinds)
        fk, fouts, fvalid, fng = _local_agg(gk, gvalid, gouts, merge_kinds,
                                            capacity)
        overflow = jnp.maximum(jnp.max(jax.lax.all_gather(png, axis)),
                               fng) > capacity
        return fk, fouts, fvalid, fng, overflow

    return _supervised_step(_observed_jit(step), ctx)


# ---------------------------------------------------------------------------
# hash-partition shuffle join (+ aggregate) over the mesh
# ---------------------------------------------------------------------------

#: sub-buckets per destination shard in the two-level radix partition
#: (power of two: the sub index is a low-bit mask of the mixed hash)
RADIX_SUB = 4


def _mix64(k):
    """murmur3 fmix64 over int64 lanes — decorrelates FK-stride keys from
    the destination-shard choice (the reference hashes partition keys with
    murmur, unistore/cophandler/mpp_exec.go). Shared by the library-level
    steps here and the SQL-path exchange (executor/mpp_exec.py)."""
    u = k.astype(jnp.uint64)
    u = u ^ (u >> 33)
    u = u * jnp.uint64(0xFF51AFD7ED558CCD)
    u = u ^ (u >> 33)
    u = u * jnp.uint64(0xC4CEB9FE1A85EC53)
    u = u ^ (u >> 33)
    return u


def _radix_bucket(h, valid, n_dest, n_sub):
    """The two-level radix split, shared by the library-level steps here
    and the SQL-path exchange (executor/mpp_exec.py) so the two partition
    layouts can never diverge: the mixed hash's HIGH bits pick the
    destination, its LOW bits one of `n_sub` sub-buckets. Returns
    (flattened bucket id per row, n_buckets); invalid rows park at
    n_buckets, past every real bucket."""
    dest = ((h >> jnp.uint64(32)) % jnp.uint64(n_dest)).astype(jnp.int64)
    sub = (h & jnp.uint64(n_sub - 1)).astype(jnp.int64)
    nb = n_dest * n_sub
    return jnp.where(valid, dest * n_sub + sub, nb), nb


@jax.named_scope("k_exchange")
def _bucketize(keys, vals, valid, n_dest, cap, n_sub=RADIX_SUB):
    """Two-level RADIX partition ("Efficient Multiway Hash Join on
    Reconfigurable Hardware", PAPERS.md): the mix64 hash's HIGH bits pick
    the destination shard, its LOW bits pick one of `n_sub` sub-buckets,
    and each (dest, sub) bucket is `cap`-bounded.  Layout is
    [n_dest, n_sub, cap] flattened, so each destination's region is
    contiguous and equal-sized — exactly what a tiled all_to_all splits.

    vs the old single-pass ``key % n_dest``: stride-correlated FK keys no
    longer pile onto one shard, and overflow is measured per SUB-bucket as
    an exact max count, so a retry jumps straight to the required
    capacity instead of doubling blind.

    Returns flattened (keys, vals tuple, valid, n_dropped)."""
    n = keys.shape[0]
    h = _mix64(keys.astype(jnp.int64))
    bucket, nb = _radix_bucket(h, valid, n_dest, n_sub)
    order = jnp.argsort(bucket, stable=True)
    sb = bucket[order]
    start = jnp.searchsorted(sb, jnp.arange(nb))
    pos = jnp.arange(n) - start[jnp.clip(sb, 0, nb - 1)]
    ok = (sb < nb) & (pos < cap)
    slot = jnp.where(ok, sb * cap + pos, nb * cap)
    size = nb * cap + 1
    bk = jnp.zeros(size, dtype=keys.dtype).at[slot].set(
        jnp.where(ok, keys[order], 0))[:-1]
    bvalid = jnp.zeros(size, dtype=bool).at[slot].set(ok)[:-1]
    bvals = tuple(
        jnp.zeros(size, dtype=v.dtype).at[slot].set(
            jnp.where(ok, v[order], jnp.zeros((), dtype=v.dtype)))[:-1]
        for v in vals)
    dropped = jnp.sum((sb < nb) & (pos >= cap))
    return bk, bvals, bvalid, dropped


@jax.named_scope("k_exchange")
def _exchange_hash(keys, vals, valid, axis, n_dest, cap):
    """Radix-partition exchange: two-level bucketize locally, one tiled
    all_to_all over ICI.  After this, every row on shard i satisfies
    mix64(key) high bits mod n_shards == i (both join sides use the same
    fold, so equal keys meet on the same shard)."""
    bk, bvals, bvalid, dropped = _bucketize(keys, vals, valid, n_dest, cap)
    a2a = functools.partial(jax.lax.all_to_all, axis_name=axis,
                            split_axis=0, concat_axis=0, tiled=True)
    return (a2a(bk), tuple(a2a(v) for v in bvals), a2a(bvalid), dropped)


def dist_join_agg_step(mesh: Mesh, cap: int, axis: str = "part", ctx=None):
    """Build the jitted distributed shuffled-hash-join + aggregate step
    (the MPP shuffle join fragment: Q3-shaped `SUM(probe_val *
    matched_build_sum)` — e.g. revenue over lineitem ⋈ filtered orders).

    Inputs row-sharded over `axis`:
        bk int64[Nb], bv [Nb], bvalid bool[Nb]   build side (smaller table)
        pk int64[Np], pv [Np], pvalid bool[Np]   probe side
    Returns replicated (total, n_pairs, dropped) where
        total  = Σ over join pairs of pv * bv
        n_pairs = join cardinality
        dropped = rows lost to bucket overflow (retry bigger cap if > 0)
    `cap` bounds each RADIX SUB-bucket of the exchange ([n_shards,
    RADIX_SUB, cap] per side, see _bucketize) — per destination shard the
    exchange holds RADIX_SUB * cap rows.
    """
    n_shards = mesh.shape[axis]

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(P(axis),) * 6,
        out_specs=(P(), P(), P()),
        check_vma=False)
    def step(bk, bv, bvalid, pk, pv, pvalid):
        bk2, (bv2,), bvalid2, bdrop = _exchange_hash(
            bk, (bv,), bvalid, axis, n_shards, cap)
        pk2, (pv2,), pvalid2, pdrop = _exchange_hash(
            pk, (pv,), pvalid, axis, n_shards, cap)
        # local sort join: per probe row, sum + count of matching build rows
        sort_key = jnp.where(bvalid2, bk2, jnp.iinfo(jnp.int64).max)
        order = jnp.argsort(sort_key)
        sb = sort_key[order]
        sv = jnp.where(bvalid2, bv2, jnp.zeros((), dtype=bv2.dtype))[order]
        csum = jnp.concatenate([jnp.zeros(1, dtype=sv.dtype), jnp.cumsum(sv)])
        ccnt = jnp.concatenate([
            jnp.zeros(1, dtype=jnp.int64),
            jnp.cumsum(bvalid2[order].astype(jnp.int64))])
        lo = jnp.searchsorted(sb, pk2, side="left")
        hi = jnp.searchsorted(sb, pk2, side="right")
        match_sum = csum[hi] - csum[lo]
        match_cnt = ccnt[hi] - ccnt[lo]
        pz = jnp.where(pvalid2, pv2, jnp.zeros((), dtype=pv2.dtype))
        total = jax.lax.psum(jnp.sum(pz * match_sum), axis)
        pairs = jax.lax.psum(
            jnp.sum(jnp.where(pvalid2, match_cnt, 0)), axis)
        dropped = jax.lax.psum(bdrop + pdrop, axis)
        return total, pairs, dropped

    return _supervised_step(_observed_jit(step), ctx)
