"""MPP SQL execution: fused scan/join/agg fragments run SPMD over a
device mesh — the reference's MPP fragment execution wired into the SQL
path (planner/core/fragment.go cuts plans at exchange boundaries;
store/copr/mpp.go:65 constructs per-node tasks; executor/mpp_gather.go
streams fragments back; unistore/cophandler/mpp_exec.go runs them).

TPU-native translation: one `shard_map`-jitted SPMD program per fragment.
- The probe-spine fact table is row-sharded over the mesh axis (the
  reference's region sharding, §2.2 DP); every dimension table is
  replicated (broadcast hash join — the PhysicalExchangeSender Broadcast
  type).
- Each shard runs a fused scan→filter→join→partial-agg body, producing a
  `capacity`-bounded partial aggregate state.  Where the fragment is in
  the paged join's language and its builds have host-built direct indexes
  (`_indexed_chain`), the body IS the one the single-chip path compiles
  (device_join.compile_fragment, wrapped by `_shard_program`), and its
  turn is the single-chip one (device_join.FragmentRunner): indexes
  read by address, the shard's slice of the probe leaf read in place, a
  NULL-free column's mask a constant.  Every other fragment runs
  `_build_mpp_pipeline`'s own body, which joins inside the program
  (a build lexsort and three searches a join) and can shuffle the bottom
  join's two sides.
- Exchange = `all_gather` of the bounded partial states over ICI; the
  final merge is simply a second `_agg_impl` over the gathered partials
  (partial/final parallel hash agg, executor/aggregate.go:85-165),
  replicated on every shard. No host hop anywhere inside the fragment.

The single-chip compile-amortization stack carries across the mesh
(ROADMAP item 1):
- **Bucketed shard shapes**: per-shard leaf placements pad to geometric
  row buckets (ops/device.py bucket_rows applied per shard), replicated
  dimensions pad to whole-table buckets, and every leaf's LIVE row count
  is a TRACED scalar null-masked in-program — a within-bucket INSERT
  re-dispatches the already-compiled SPMD program with ZERO new XLA
  compiles.
- **Compiled-fragment cache**: pipelines key on (mesh shape, per-leaf
  bucket tuple, fragment signature incl. dictionary-CONTENT sigs,
  capacities) and flow through the shared _PIPE_CACHE with its
  hit/miss/compile_s stats; converged capacities are LEARNED per
  signature (the join fragment's store: device_join.learned / learn) so
  repeat executions start tight.
- **Residency + epoch fencing**: every mesh placement registers its
  bytes in the ops/residency.py ledger via a CacheOwner (per-group
  charging, LRU eviction, OOM evict-all) and carries the device epoch —
  a post-fence/restart mesh can never serve stale shards.
- **Radix-partitioned exchange**: the shuffle join's repartition is a
  two-level radix partition (mix64 high bits → destination shard, low
  bits → cap-bounded sub-buckets; "Efficient Multiway Hash Join on
  Reconfigurable Hardware", PAPERS.md) through ONE tiled lax.all_to_all,
  reporting the exact worst-bucket count so an overflow retry jumps
  straight to the required capacity.

Static shapes throughout: join expansions and agg states are capacity-
bounded with overflow flags max-reduced across the mesh; the host
retries with grown capacities — one extra compile, never wrong results.
"""

from __future__ import annotations

import collections
import functools
import threading

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from jax import shard_map

from ..ops import device as dev
from ..ops.device import DeviceUnsupported
from ..parallel.mpp import RADIX_SUB, _mix64, _radix_bucket
from .device_exec import (
    _assemble_agg, _estimate_groups, acquire_pipeline, engine_mode,
    note_agg_spans, note_join_gathers, note_rerun)
from .device_join import (
    FragmentRunner, _JoinNode, _Leaf, _combined_join_keys,
    _dim_resident_budget, _fragment_used_cols, _join_expand, _leaf_index,
    _leaf_used_bytes, _probe_spine, _reorder_fact_first, _shift_expr,
    collect_tree, fragment_sig, learn, learned)

AXIS = "part"

#: merge op per partial op for the final stage: partial counts re-sum,
#: partial sums re-sum, min/max merge with themselves, first takes any
_MERGE_OP = {"count": "sum_i", "sum_i": "sum_i", "sum_f": "sum_f",
             "min": "min", "max": "max", "first": "first"}

#: observability: fragments actually executed through the mesh path.
#: exchange_retries = transport faults re-dispatched on the same shapes;
#: exchange_overflow_retries = radix sub-bucket overflow recompiles at a
#: larger exchange capacity (the hot-key convergence counter);
#: retries = all capacity-growth recompiles (joins, agg, exchange);
#: indexed_fragments = the fragments among `fragments` whose shards ran
#: device_join.compile_fragment's body over host-built direct indexes
#: (capacity retries count once, as in `fragments`).
MPP_STATS = {"fragments": 0, "retries": 0, "shuffle_joins": 0,
             "skew_broadcasts": 0, "exchange_retries": 0,
             "exchange_overflow_retries": 0, "indexed_fragments": 0}

_MESH_CACHE: dict[int, object] = {}


def mpp_mesh(ctx):
    """The session's mesh, or None when the MPP engine isn't selected.
    `tidb_mpp_devices` = 0 means every visible device."""
    if engine_mode(ctx) != "tpu-mpp":
        return None
    try:
        n = int(ctx.get_sysvar("tidb_mpp_devices"))
    except Exception:
        n = 0
    ndev = len(jax.devices())
    if n <= 0:
        n = ndev
    n = min(n, ndev)
    if n < 2:
        return None  # nothing to distribute over
    mesh = _MESH_CACHE.get(n)
    if mesh is None:
        from ..parallel import make_mesh
        mesh = make_mesh(n, axis=AXIS)
        _MESH_CACHE[n] = mesh
    return mesh


# ---------------------------------------------------------------------------
# mesh placement cache (the HBM-resident working set, per mesh) — every
# entry's bytes live on the ops/residency.py ledger through a CacheOwner:
# per-tenant charging, LRU eviction under budget pressure, the OOM
# evict-all ladder, and the device epoch all apply to mesh shards exactly
# as to single-chip Column uploads.  An epoch bump (backend fence, OOM
# recovery) invalidates every placement: residency.lookup refuses the
# stale entry and the next dispatch re-places from the host columns.
# ---------------------------------------------------------------------------

#: (id(col), id(mesh), sharded, total_rows) → (CacheOwner, pinned col).
#: The pinned Column keeps the id() key sound (a live object never shares
#: its id with a new allocation) — same convention as _PIPE_CACHE's
#: dict_refs.  The cached device arrays themselves live on the owner via
#: the residency manager, NOT here, so eviction works owner-by-owner.
_MPP_PLACE_CACHE: "collections.OrderedDict" = collections.OrderedDict()
_PLACE_CACHE_MAX = 128
_PLACE_LOCK = threading.Lock()


def _placed(key, pin, rows, build):
    """The arrays cached under `key` through the residency ledger, at
    least `rows` long, or what `build()` makes, published.  `pin` is the
    host object whose id() the key holds."""
    from ..ops import residency
    with _PLACE_LOCK:
        hit = _MPP_PLACE_CACHE.get(key)
        if hit is not None:
            _MPP_PLACE_CACHE.move_to_end(key)
            owner = hit[0]
        else:
            owner = residency.CacheOwner()
            _MPP_PLACE_CACHE[key] = (owner, pin)
            while len(_MPP_PLACE_CACHE) > _PLACE_CACHE_MAX:
                _MPP_PLACE_CACHE.popitem(last=False)
    cached = residency.lookup(owner, rows)
    if cached is None:
        # compare-and-keep publish: a racing placement's loser arrays are
        # accounted as immediately evicted, never leaked off-ledger
        cached = residency.publish(owner, *build())
    return cached


def _place_col(col, data, nulls, mesh, sharded, total):
    """Pad `col`'s host arrays to `total` rows and device_put them onto
    the mesh (row-sharded over AXIS or replicated), cached through the
    residency ledger.  `total` is a bucket shape (multiple of the mesh
    size when sharded): a within-bucket delta re-places (new column
    identity) but re-dispatches the same compiled program."""
    def build():
        d = dev.pad_host(np.asarray(data), total)
        nl = dev.pad_host(np.asarray(nulls), total, True)
        spec = NamedSharding(mesh, P(AXIS) if sharded else P())
        return jax.device_put(d, spec), jax.device_put(nl, spec)
    return _placed((id(col), id(mesh), sharded, total), col, total, build)


def _place_index(idx, mesh):
    """A host-built join index's lookup tuple (a0, a1, n_valid), its
    arrays replicated over the mesh: placed once an index (one is built
    a table version and filter), cached through the residency ledger
    like a replicated column and counted with the columns in
    ``device_residency.upload_bytes``.  What `JoinIndex.device_arrays`
    is to one device."""
    a0, a1 = idx.host_arrays()

    def build():
        spec = NamedSharding(mesh, P())
        return (jax.device_put(a0, spec),
                None if a1 is None else jax.device_put(a1, spec))
    d0, d1 = _placed((id(idx), id(mesh), "jidx"), idx, len(a0), build)
    return d0, d1, np.int64(idx.n_valid)


def place_cache_bytes() -> int:
    """Bytes of mesh placements currently live on the residency ledger
    (the ``mpp_place_bytes`` gauge).  Reads through the ledger so the
    value can never drift from what verify_ledger() accounts."""
    return _place_cache_view()[1]


def _place_cache_view():
    """(entry count, ledger bytes) from ONE placement-lock acquisition
    (and one ledger-lock acquisition inside resident_nbytes_total) — the
    gauge pass runs per query and per /status//metrics scrape."""
    from ..ops import residency
    with _PLACE_LOCK:
        owners = [ent[0] for ent in _MPP_PLACE_CACHE.values()]
    return len(owners), residency.resident_nbytes_total(owners)


def snapshot() -> dict:
    """MPP observability snapshot for /status and bench lines."""
    entries, nbytes = _place_cache_view()
    return {**MPP_STATS, "place_entries": entries,
            "mpp_place_bytes": nbytes}


def report_gauges() -> dict:
    """Surfacing policy shared by EXPLAIN ANALYZE / bench lines (mirrors
    residency.report_gauges): placement bytes always once the mesh path
    has run, counters only when they have ever fired."""
    s = snapshot()
    if not s["fragments"] and not s["mpp_place_bytes"]:
        return {}
    out = {"mpp_place_bytes": s["mpp_place_bytes"],
           "mpp_fragments": s["fragments"]}
    for k in ("retries", "exchange_retries", "exchange_overflow_retries",
              "shuffle_joins", "skew_broadcasts", "indexed_fragments"):
        if s[k]:
            out["mpp_" + k] = s[k]
    return out


def _publish_gauges(ctx):
    obs = getattr(getattr(ctx, "domain", None), "observe", None)
    if obs is not None and hasattr(obs, "set_gauge"):
        try:
            for k, v in report_gauges().items():
                obs.set_gauge(k, v)
        except Exception:
            pass


# ---------------------------------------------------------------------------
# radix hash-shuffle exchange (the Hash exchange type — reference:
# planner/core/fragment.go:37,64 ExchangeSender{HashPartition},
# store/copr/mpp.go:65; here: two-level radix bucketize + one tiled
# lax.all_to_all over ICI; partition shape per "Efficient Multiway Hash
# Join on Reconfigurable Hardware")
# ---------------------------------------------------------------------------

def _dest_hash(key_ds):
    """mix64 fold of the (multi-)column join key. Both join sides use the
    same fold, so equal keys land on the same shard; the HIGH bits pick
    the destination and the LOW bits the radix sub-bucket (independent
    for a well-mixed hash)."""
    h = jnp.zeros(key_ds[0].shape[0], dtype=jnp.uint64)
    for d in key_ds:
        h = _mix64(h ^ _mix64(d.astype(jnp.int64)))
    return h


@jax.named_scope("k_exchange")
def _exchange_leaf(col_pairs, h, valid, n_shards, n_sub, cap):
    """Repartition one leaf's per-shard rows by the key hash `h`:
    two-level radix partition (high bits → destination shard, low bits →
    one of `n_sub` sub-buckets, each `cap`-bounded) via a sort-based
    gather (no scatter), then one tiled all_to_all per column so each
    shard ends up holding exactly the rows hashed to it.

    col_pairs: [(data, nulls)] local slices; returns (new_col_pairs,
    new_valid, need) with n_shards*n_sub*cap rows per shard — each
    destination's region is the contiguous, equal-sized [n_sub, cap]
    block the tiled all_to_all splits on.  `need` is the EXACT worst
    sub-bucket row count: when it exceeds `cap` rows were dropped and the
    host retries with capacity next_pow2(need) — one jump, not a blind
    doubling ladder under a hot key."""
    m = valid.shape[0]
    bucket, nb = _radix_bucket(h, valid, n_shards, n_sub)
    order = jnp.argsort(bucket)
    sb = bucket[order]
    bucket_ids = jnp.arange(nb, dtype=sb.dtype)
    starts = jnp.searchsorted(sb, bucket_ids, side="left")
    cnt = jnp.searchsorted(sb, bucket_ids, side="right") - starts
    need = jnp.max(cnt)
    b_grid = jnp.repeat(bucket_ids, cap)
    c_grid = jnp.tile(jnp.arange(cap, dtype=sb.dtype), nb)
    src = jnp.clip(starts[b_grid] + c_grid, 0, jnp.maximum(m - 1, 0))
    rows = order[src]
    slot_valid = c_grid < cnt[b_grid]

    def x(a):
        return jax.lax.all_to_all(a, AXIS, 0, 0, tiled=True)

    out_cols = [(x(d[rows]), x(nl[rows])) for d, nl in col_pairs]
    return out_cols, x(slot_valid), need



def _merge_partials(partial, overflows, span_ovfs, xneeds, n_keys,
                    merge_ops, capacity, key_pack):
    """The tail of every shard's body: the shards' bounded partial
    aggregate states (`partial`: what `_agg_impl` returned) gathered to
    every shard and merged there, the per-shard group count, join totals,
    span flags and exchange needs reduced to the mesh's maximum."""
    pk, pkn, pres, presn, png, pvalid = partial

    # exchange: every shard's bounded partial state (capacity rows —
    # tiny next to N) rides ICI to every shard
    def g(x):
        return jax.lax.all_gather(x, AXIS, tiled=True)

    with jax.named_scope("k_exchange"):
        gk = tuple(g(k) for k in pk)
        gkn = tuple(g(k) for k in pkn)
        gres = tuple(g(r) for r in pres)
        gresn = tuple(g(r) for r in presn)
        gvalid = g(pvalid)

        # stage 2: replicated final merge — just another _agg_impl
        # over the gathered partials with partial→merge op mapping;
        # its own scopes nest under k_exchange, and the outermost
        # names the kernel: the merge is a cost of the exchange
        f_out = dev._agg_impl(gk, gkn, gres, gresn, gvalid,
                              n_keys=n_keys, agg_ops=merge_ops,
                              capacity=capacity, pack=key_pack,
                              gathered=True)

    @jax.named_scope("k_exchange")
    def mesh_max(x):
        # not lax.pmax: the TPU compiler lowers a 64-bit all-reduce
        # only for Sum ("UNIMPLEMENTED: Supported lowering only of
        # Sum all reduce" on a v5e); gathering one scalar per shard
        # and reducing locally gives every shard the same maximum
        return jnp.max(jax.lax.all_gather(x, AXIS))

    png_max = mesh_max(png)
    # exact per-join required totals (the worst shard governs the
    # static capacity); int64 — totals exceed int32 at TPC-H scale
    ovfs = tuple(mesh_max(o.astype(jnp.int64)) for o in overflows)
    sovfs = tuple(mesh_max(o.astype(jnp.int32)) for o in span_ovfs)
    # exact worst radix sub-bucket counts (not booleans): the retry
    # jumps straight to next_pow2(need)
    xneeds_out = tuple(mesh_max(o.astype(jnp.int64)) for o in xneeds)
    return f_out, png_max, ovfs, sovfs, xneeds_out


def _shard_program(run, mesh, probe_id, shard_rows, env_specs, n_keys,
                   agg_ops, capacity, key_pack):
    """`device_join.compile_fragment`'s `program` on the mesh: its body
    `run(env, jidx, n_lives)` on every shard, over that shard's
    `shard_rows` rows of the probe leaf (`P(AXIS)` in `env_specs`) and
    the whole of every other leaf and of every join index (replicated),
    then `_merge_partials`.  A shard is to the mesh what a page is to
    `device_join._paged_join_agg`: the probe leaf's live count is rebased
    to the slice, the rule `_build_mpp_pipeline`'s `base_mask` applies,
    and the body masks the rest.  Returns what `_build_mpp_pipeline`
    returns, with no exchange needs."""
    merge_ops = tuple(_MERGE_OP[o] for o in agg_ops)

    def indexed_shard(env, jidx, n_lives):
        off = jax.lax.axis_index(AXIS).astype(jnp.int64) * shard_rows
        lives = list(n_lives)
        lives[probe_id] = jnp.clip(n_lives[probe_id] - off, 0, shard_rows)
        partial, totals, span_ovfs = run(env, jidx, tuple(lives))
        return _merge_partials(partial, totals, span_ovfs, (), n_keys,
                               merge_ops, capacity, key_pack)

    # no trace marker around it, unlike `_build_mpp_pipeline`'s entry: the
    # body counts its own trace (device_exec._count_trace)
    return dev.observed_jit(shard_map(
        indexed_shard, mesh=mesh, in_specs=(env_specs, P(), P()),
        out_specs=P(), check_vma=False))


# ---------------------------------------------------------------------------
# the SPMD fragment program
# ---------------------------------------------------------------------------

def _build_mpp_pipeline(mesh, leaves, joins, root, sharded_ids, leaf_cond_fns,
                        cond_fns, key_fns, n_keys, val_plan, agg_ops,
                        capacity, key_pack, env_specs, shuffle=None):
    """shard_map + jit a fragment OUTSIDE the indexed language (see
    `_indexed_chain`; inside it `_shard_program` wraps
    device_join.compile_fragment's body instead): per-shard fused body →
    partial agg → `_merge_partials`.  The body's structure is
    compile_fragment's of PR 27, kept for joins built in the program:
    every leaf starts at `arange(n)` and every column and mask is
    gathered through its row map.  Per-shard shapes come from the traced
    env and each leaf masks its rows at its TRACED live count (`n_lives`,
    one scalar per leaf): env arrays are bucket-padded past the live rows,
    and padding can never survive a filter, an exchange, a join probe or
    the aggregate — the single-chip bucketing invariant, meshwide.

    shuffle: None (broadcast join) or (node, left_leaf, right_leaf,
    cap_l, cap_r) — radix-repartition BOTH sides of `node` by join key
    over the mesh before the local join (the Hash exchange type); cap_*
    bound each radix SUB-bucket."""
    merge_ops = tuple(_MERGE_OP[o] for o in agg_ops)
    n_joins = len(joins)
    n_shards = mesh.shape[AXIS]
    n_xovf = 2 if shuffle is not None else 0
    sharded_set = frozenset(sharded_ids)
    n_sub = RADIX_SUB

    def body(env, n_lives):
        overflows = []
        span_ovfs = []
        env = dict(env)
        leaf_valid = {}
        conds_consumed = set()
        xneeds = []

        def base_mask(leaf, n):
            # the bucketed-shape live mask: a sharded leaf holds rows
            # [i*psb, (i+1)*psb) of the padded global array, so its live
            # rows are the ones whose GLOBAL index is < the traced count
            nl = n_lives[leaf.leaf_id]
            if leaf.leaf_id in sharded_set:
                off = jax.lax.axis_index(AXIS).astype(jnp.int64) * n
                return off + jnp.arange(n) < nl
            return jnp.arange(n) < nl

        if shuffle is not None:
            node, llid, rlid, cap_l, cap_r = shuffle
            for leaf_id, kfns, xcap in ((llid, node._lk_fns, cap_l),
                                        (rlid, node._rk_fns, cap_r)):
                leaf = leaves[leaf_id]
                n = env[leaf.offset][0].shape[0]
                with jax.named_scope("k_filter"):
                    valid = base_mask(leaf, n)
                    # pre-exchange filter: leaf conds cut exchange volume
                    for f in leaf_cond_fns[leaf_id]:
                        d, nl = f(env)
                        valid = valid & jnp.broadcast_to((d != 0) & ~nl,
                                                         (n,))
                conds_consumed.add(leaf_id)
                with jax.named_scope("k_exchange"):
                    kds, knulls = zip(*[dev.broadcast_1d(*f(env), n)
                                        for f in kfns])
                    for nl in knulls:
                        valid = valid & ~nl  # null keys never match: drop
                    h = _dest_hash(kds)
                cols = [env[leaf.offset + i] for i in range(leaf.ncols)]
                out_cols, out_valid, need = _exchange_leaf(
                    cols, h, valid, n_shards, n_sub, xcap)
                for i in range(leaf.ncols):
                    env[leaf.offset + i] = out_cols[i]
                leaf_valid[leaf_id] = out_valid
                xneeds.append(need)

        @jax.named_scope("k_filter")
        def leaf_rel(leaf):
            n = env[leaf.offset][0].shape[0]
            mask = leaf_valid.get(leaf.leaf_id)
            if mask is None:
                mask = base_mask(leaf, n)
            if leaf.leaf_id not in conds_consumed:
                for f in leaf_cond_fns[leaf.leaf_id]:
                    d, nl = f(env)
                    mask = mask & jnp.broadcast_to((d != 0) & ~nl, (n,))
            return {leaf.leaf_id: jnp.arange(n)}, mask

        @jax.named_scope("k_join_probe")
        def gather_env(idxmap, node):
            out = {}
            for leaf in leaves:
                if leaf.leaf_id in idxmap:
                    if not (node.offset <= leaf.offset
                            < node.offset + node.ncols):
                        continue
                    idx = idxmap[leaf.leaf_id]
                    for i in range(leaf.ncols):
                        d, nl = env[leaf.offset + i]
                        out[leaf.offset + i] = (d[idx], nl[idx])
            return out

        def eval_node(node):
            if isinstance(node, _Leaf):
                return leaf_rel(node)
            lidx, lvalid = eval_node(node.left)
            ridx, rvalid = eval_node(node.right)
            lenv = gather_env(lidx, node.left)
            renv = gather_env(ridx, node.right)
            with jax.named_scope("k_join_probe"):
                lkds, lknulls = zip(*[
                    dev.broadcast_1d(*f(lenv), lvalid.shape[0])
                    for f in node._lk_fns])
            with jax.named_scope("k_join_build"):
                rkds, rknulls = zip(*[
                    dev.broadcast_1d(*f(renv), rvalid.shape[0])
                    for f in node._rk_fns])
            pk_d, pvalid, bk_d, bvalid, sovf = _combined_join_keys(
                lkds, lknulls, lvalid, rkds, rknulls, rvalid)
            span_ovfs.append(sovf)
            pi, bi, valid, ovf = _join_expand(
                bk_d, bvalid, pk_d, pvalid, node.cap)
            overflows.append(ovf)
            with jax.named_scope("k_join_probe"):
                idxmap = {k: v[pi] for k, v in lidx.items()}
                idxmap.update({k: v[bi] for k, v in ridx.items()})
            if node._oc_fns:
                jenv = gather_env(idxmap, node)
                with jax.named_scope("k_filter"):
                    for f in node._oc_fns:
                        d, nl = f(jenv)
                        valid = valid & (d != 0) & ~nl
            return idxmap, valid

        idxmap, valid = eval_node(root)
        fenv = gather_env(idxmap, root)
        with jax.named_scope("k_filter"):
            mask = valid
            for f in cond_fns:
                d, nl = f(fenv)
                mask = mask & (d != 0) & ~nl
        n_out = mask.shape[0]
        # as in the scan pipeline: key expressions are k_agg_sort,
        # aggregate inputs k_agg_gather
        key_cols, key_nulls = [], []
        with jax.named_scope("k_agg_sort"):
            for f in key_fns:
                d, nl = dev.broadcast_1d(*f(fenv), n_out)
                key_cols.append(d.astype(jnp.int64))
                key_nulls.append(nl)
            if not key_cols:
                key_cols = [jnp.zeros(n_out, dtype=jnp.int64)]
                key_nulls = [jnp.zeros(n_out, dtype=bool)]
        val_cols, val_nulls = [], []
        with jax.named_scope("k_agg_gather"):
            for f, conv in val_plan:
                d, nl = dev.broadcast_1d(*f(fenv), n_out)
                if conv == "int":
                    d = d.astype(jnp.int64)
                val_cols.append(d)
                val_nulls.append(nl)

        # stage 1: per-shard partial aggregation into bounded state
        partial = dev._agg_impl(
            tuple(key_cols), tuple(key_nulls),
            tuple(val_cols), tuple(val_nulls), mask,
            n_keys=n_keys, agg_ops=agg_ops, capacity=capacity,
            pack=key_pack, gathered=True)
        return _merge_partials(partial, overflows, span_ovfs, xneeds,
                               n_keys, merge_ops, capacity, key_pack)

    n_res = len(val_plan)
    out_specs = (
        ((P(),) * n_keys, (P(),) * n_keys, (P(),) * n_res, (P(),) * n_res,
         P(), P()),
        P(),
        (P(),) * n_joins,
        (P(),) * n_joins,
        (P(),) * n_xovf,
    )
    wrapped = shard_map(
        body, mesh=mesh,
        in_specs=(env_specs, (P(),) * len(leaves)),
        out_specs=out_specs, check_vma=False)

    def entry(env, n_lives):
        # trace marker OUTSIDE the shard_map body (which tracing may
        # evaluate more than once): mpp fragment compiles meter into the
        # same pipe-cache stats as the single-chip pipelines
        dev._note_trace()
        return wrapped(env, n_lives)

    return dev.observed_jit(entry)


# ---------------------------------------------------------------------------
# host entry points
# ---------------------------------------------------------------------------

def mpp_agg(plan, chunk, conds, ctx, mesh):
    """scan→filter→group-by fragment over the mesh (partition-parallel
    partial agg + collective merge — the shuffle-agg MPP fragment)."""
    if chunk.num_rows == 0:
        raise DeviceUnsupported("empty input")
    leaf = _Leaf(0, chunk, list(conds), 0)
    return _run_mpp(plan, [], leaf, [leaf], [], ctx, mesh)


def mpp_join_agg(agg_plan, agg_conds, child_exec, ctx, mesh):
    """join-tree→group-by fragment over the mesh: probe spine sharded,
    build sides broadcast (the broadcast hash join MPP variant) and
    probed through their host-built direct indexes where the fragment
    has them (`_indexed_chain`); else joined inside the program, the
    bottom join's two sides shuffled when its build is over
    ``tidb_broadcast_join_threshold_count`` rows."""
    root, leaves, joins = collect_tree(child_exec)
    if any(jn.kind != "inner" for jn in joins):
        # the mesh fragment compiler shards/broadcasts inner joins only
        raise DeviceUnsupported("non-inner join in MPP fragment")
    from ..storage.paged import chunk_is_paged
    from .device_join import _col_row_bytes
    paged_est = 0
    for leaf in leaves:
        if not chunk_is_paged(leaf.chunk):
            continue
        paged_est += sum(_col_row_bytes(c)
                         for c in leaf.chunk.columns) * leaf.chunk.num_rows
    if paged_est:
        # paged leaves ARE legal on the mesh now (the last PR 7 gap) —
        # placement materializes their pages into per-shard slices, so
        # the whole placed footprint must fit the residency budget (the
        # same threshold the single-chip resident-build path uses); a
        # bigger disk table still streams through the single-chip paged
        # pipeline or the hybrid partitioned join instead
        from .device_join import _dim_resident_budget
        if paged_est > _dim_resident_budget():
            raise DeviceUnsupported(
                "paged leaves exceed the mesh residency budget")
    return _run_mpp(agg_plan, agg_conds, root, leaves, joins, ctx, mesh)


def _build_key_leaf(node, leaves):
    """The leaf inside `node`'s build (right) subtree holding ALL of the
    right-key columns — the one a Hash exchange must repartition; None
    when the keys span leaves (or reference none)."""
    used = set()
    for k in node.right_keys:
        k.columns_used(used)
    if not used:
        return None
    gls = {node.right.offset + u for u in used}
    for leaf in leaves:
        if (leaf.offset >= node.right.offset
                and leaf.offset + leaf.ncols
                <= node.right.offset + node.right.ncols
                and all(leaf.offset <= g < leaf.offset + leaf.ncols
                        for g in gls)):
            return leaf
    return None


def _indexed_chain(root, leaves, joins, plan, agg_conds, ctx):
    """(root, joins, used columns) of the fragment as a chain the shards
    can run with `device_join.compile_fragment`'s body, strategies
    assigned, or None: the in-program joins then take it.  It engages when
    - the tree, chained fact-first as `device_join_agg` chains it
      (`_reorder_fact_first`; a single join keeps the oriented tree), is
      in the paged join's language: the probe spine ends in the sharded
      leaf and every join is inner with a UNIQUE host index on its right
      (so no join expands, and a shard's slice of the probe is a page);
    - every index is direct (`JoinIndex.kind == "dense"`: what
      `join_index.direct_table_fits` decided from its bytes);
    - what every chip then holds whole fits: each build leaf's used
      columns take at most ``tidb_broadcast_join_threshold_size`` bytes
      (the reference's checkChildFitBC lets the size decide where it is
      known, planner/core/exhaust_physical_plans.go; 0 or less: no
      limit) and the resident-build budget.
    It asks for a join's right index alone, so a fragment it refuses has
    paid for no index the skew guard would not build."""
    if not joins:
        return None
    chained = _reorder_fact_first(leaves, joins)
    if chained is not None:
        root, joins = chained
        strategies = [jn.strategy for jn in joins]
    else:
        strategies = []
        for jn in joins:
            idx = _leaf_index(jn.right, jn.right_keys)
            if idx is None or not idx.unique:
                return None
            strategies.append(("uniq", "right", idx))
    if any(st[2].kind != "dense" for st in strategies):
        return None
    bc_bytes = int(ctx.get_sysvar("tidb_broadcast_join_threshold_size"))
    limit = _dim_resident_budget()
    if bc_bytes > 0:
        limit = min(limit, bc_bytes)
    used = _fragment_used_cols(leaves, joins, plan, agg_conds)
    probe = _probe_spine(root)
    if any(_leaf_used_bytes(leaf, used) > limit
           for leaf in leaves if leaf is not probe):
        return None
    for jn, st in zip(joins, strategies):
        jn.strategy = st
    return root, joins, used


def _run_mpp(plan, agg_conds, root, leaves, joins, ctx, mesh):
    # span tracing (session/tracing.py): one span per MPP fragment
    # dispatch, tagged with the mesh width — per-shard placement, the
    # radix exchange and the SPMD dispatch all happen inside it, and the
    # supervisor's thread-hop propagation keeps worker-side events
    # (backoff sleeps, exchange retries) on this timeline
    from ..session import tracing
    with tracing.span("mpp.fragment", shards=mesh.shape[AXIS],
                      leaves=len(leaves), joins=len(joins)):
        return _run_mpp_impl(plan, agg_conds, root, leaves, joins, ctx,
                             mesh)


def _run_mpp_impl(plan, agg_conds, root, leaves, joins, ctx, mesh):
    from ..utils import failpoint as _fp
    # chaos/supervisor hook: a `sleep(...)` here models a hung collective
    # at the MPP fragment boundary (the exchange-dispatch analog of
    # device-agg-exec / device-join-exec)
    _fp.inject("device-mpp-exec")
    n_shards = mesh.shape[AXIS]

    # The shard leaf must sit on the probe (left) spine: every join's
    # build side must be complete on every shard. Orient the tree so the
    # LARGEST table is that leaf — inner-join probe/build sides are a
    # physical choice (swapping is legal), and the global column offsets
    # are untouched (a node's column range spans both subtrees either
    # way). This also minimizes broadcast volume: big table sharded,
    # dimensions replicated.
    bottom = None
    if joins:
        target = max(leaves, key=lambda lf: lf.chunk.num_rows).leaf_id
        node = root
        prev = None
        while isinstance(node, _JoinNode):
            if target in node.right.leaf_ids:
                node.left, node.right = node.right, node.left
                node.left_keys, node.right_keys = (
                    node.right_keys, node.left_keys)
            prev = node
            node = node.left
        shard_leaf = node.leaf_id
        bottom = prev  # the spine join directly over the sharded leaf
    else:
        shard_leaf = root.leaf_id
    # a fragment in the paged join's language whose builds have direct
    # host indexes runs compile_fragment's body on every shard
    chain = _indexed_chain(root, leaves, joins, plan, agg_conds, ctx)
    indexed = chain is not None
    if indexed:
        root, joins, used = chain
        shard_leaf = _probe_spine(root).leaf_id
    shard_rows = leaves[shard_leaf].chunk.num_rows
    if shard_rows < n_shards:
        raise DeviceUnsupported("too few rows to shard over the mesh")

    # broadcast-vs-shuffle for the bottom join (reference: the planner
    # picks Broadcast vs HashPartition exchange by build-side size,
    # exhaust_physical_plans.go MPP join variants): when the build-key
    # leaf is itself fact-sized, replicating it per shard would blow
    # HBM — hash-repartition it (and the probe fact) over the mesh
    # instead. The exchanged leaf is the one holding ALL the bottom
    # join's right-key columns; any other build-subtree leaves stay
    # replicated, so the subtree's local joins remain co-partitioned
    # by the exchanged key.
    # An indexed fragment is a broadcast join by construction, whatever
    # its build's row count: a host-built index addresses the HOST's
    # rows, not rows an all_to_all has moved, and `_indexed_chain` held
    # every build it replicates to tidb_broadcast_join_threshold_size.
    shuffle_build = None
    if bottom is not None and not indexed:
        bleaf = _build_key_leaf(bottom, leaves)
        if bleaf is not None:
            try:
                bc_rows = int(ctx.get_sysvar(
                    "tidb_broadcast_join_threshold_count"))
            except Exception:
                bc_rows = 10 * 1024
            build_rows = bleaf.chunk.num_rows
            if (bc_rows > 0 and build_rows > bc_rows
                    and build_rows >= n_shards):
                shuffle_build = bleaf.leaf_id
                # skew guard (SURVEY §7 "MPP shuffle skew"): a Hash
                # exchange sends every row of a key to ONE shard, so a
                # hot key turns balanced buckets into one overflowing
                # bucket — capacity growth chases the hottest key while
                # the other shards idle. The host knows the hottest
                # key's row count from the build-side join index
                # (numpy, cached per table version); when it dwarfs the
                # uniform share, fall back to the Broadcast exchange
                # (reference: the planner picks Broadcast vs
                # HashPartition by cost, exhaust_physical_plans.go MPP
                # variants — skew is a cost input here)
                from .device_join import _leaf_index
                # right_keys are subtree-relative; rebase to bleaf-local
                local = [_shift_expr(k, bottom.right.offset - bleaf.offset)
                         for k in bottom.right_keys]
                bidx = _leaf_index(bleaf, local)
                if bidx is not None:
                    even_share = max(build_rows // n_shards, 1)
                    if bidx.max_cnt > 4 * even_share:
                        shuffle_build = None
                        MPP_STATS["skew_broadcasts"] = (
                            MPP_STATS.get("skew_broadcasts", 0) + 1)
    sharded_ids = [shard_leaf] + (
        [shuffle_build] if shuffle_build is not None else [])

    # canonical BUCKET shapes per leaf (ops/device.py bucket_rows carried
    # across the mesh): a sharded leaf buckets its PER-SHARD row count
    # (total = psb * n_shards keeps the shard split exact); a replicated
    # leaf buckets its whole length.  Uploads pad to the bucket and the
    # compiled program masks each leaf at its traced live count, so a
    # within-bucket INSERT re-dispatches with zero new XLA compiles.
    per_double = dev.shape_buckets(ctx)
    leaf_total = {}
    leaf_psb = {}
    for leaf in leaves:
        rows = leaf.chunk.num_rows
        if leaf.leaf_id in sharded_ids:
            per_shard = -(-rows // n_shards)
            psb = dev.bucket_rows(per_shard, per_double)
            leaf_psb[leaf.leaf_id] = psb
            leaf_total[leaf.leaf_id] = psb * n_shards
        else:
            leaf_total[leaf.leaf_id] = dev.bucket_rows(rows, per_double)

    # metadata-only planning view (no uploads — placement happens once,
    # below, straight onto the mesh): the expression compiler and agg
    # planner read only ftype/dictionary/host_col
    host_cols = {}
    dcols = {}
    leaf_metas = []
    for leaf in leaves:
        metas = {}
        for i, c in enumerate(leaf.chunk.columns):
            dc, (hd, hn) = dev.meta_device_col(c)
            metas[i] = dc
            dcols[leaf.offset + i] = dc
            host_cols[leaf.offset + i] = (c, hd, hn)
        leaf_metas.append(metas)

    run = FragmentRunner(root, leaves, joins, plan, agg_conds, dcols)
    n_keys = max(len(run.key_fns), 1)
    agg_ops = tuple(run.agg_ops)
    if any(op not in _MERGE_OP for op in agg_ops):
        # cnt_dist partial states don't merge across shards (counts, not
        # sets) — single-chip kernel handles distinct
        raise DeviceUnsupported("non-mergeable agg on the mesh path")

    if not indexed:  # compile_fragment compiles its own
        leaf_cond_fns = [
            [dev.compile_expr(_shift_expr(c, leaf.offset),
                              {leaf.offset + i: dc for i, dc
                               in leaf_metas[leaf.leaf_id].items()})
             for c in leaf.conds] for leaf in leaves]
        for jn in joins:
            jn._lk_fns = [
                dev.compile_expr(_shift_expr(k, jn.left.offset), dcols)
                for k in jn.left_keys]
            jn._rk_fns = [
                dev.compile_expr(_shift_expr(k, jn.right.offset), dcols)
                for k in jn.right_keys]
            jn._oc_fns = [dev.compile_expr(_shift_expr(c, jn.offset), dcols)
                          for c in jn.other_conds]
        cond_fns = [dev.compile_expr(c, dcols) for c in agg_conds]

    # mesh placement: sharded fact (and shuffled build) columns +
    # replicated dimensions, bucket-padded, residency-ledgered
    env, env_specs = {}, {}
    from ..session import tracing
    from .device_exec import _upload_mark, _upload_tags
    with tracing.span("upload.h2d") as usp:
        up0 = _upload_mark(usp)
        for leaf in leaves:
            sharded = leaf.leaf_id in sharded_ids
            spec = (P(AXIS), P(AXIS)) if sharded else (P(), P())
            for i in range(leaf.ncols):
                c, hd, hn = host_cols[leaf.offset + i]
                env[leaf.offset + i] = _place_col(
                    c, hd, hn, mesh, sharded, leaf_total[leaf.leaf_id])
                env_specs[leaf.offset + i] = spec
        # replicated like a dimension's columns, not through the
        # one-device JoinIndex.device_arrays()
        jidx = (tuple(_place_index(jn.strategy[2], mesh) for jn in joins)
                if indexed else None)
        _upload_tags(usp, up0, len(env))
    # per-leaf LIVE row counts as TRACED scalars (leaf_id order): the
    # program masks padding in-body, so a row-count change inside the
    # bucket is a re-dispatch, never a retrace
    n_lives = tuple(np.int64(leaf.chunk.num_rows) for leaf in leaves)

    # the cache signature carries the mesh shape, the per-leaf bucket
    # tuple and (inside fragment_sig) every dictionary CONTENT sig — the
    # exact identity of the compiled SPMD program
    sig = ("mpp", n_shards, str(mesh.devices.flat[0].platform),
           fragment_sig(leaves, joins, agg_conds, plan),
           tuple(sharded_ids),
           tuple(leaf_total[leaf.leaf_id] for leaf in leaves))
    bottom_idx = joins.index(bottom) if shuffle_build is not None else -1

    # static capacities: per-shard bucketed probe rows bound the bottom
    # join; each join's output bounds the next (FK heuristic, grown on
    # overflow). With shuffle, each exchanged side gets a per-SUB-bucket
    # capacity (~2x the uniform share), and the bottom join's probe side
    # becomes the post-exchange n_shards*RADIX_SUB*cap_l rows.  All of
    # them start from the LEARNED converged values when this signature
    # has run before (device_join.learned): a repeat execution reuses
    # the cached compiled pipeline with zero discovery retries.
    per_shard_b = leaf_psb[shard_leaf]  # always sharded: filled above
    xcaps = None
    if shuffle_build is not None:
        learned_x = learned(sig, "xcaps")
        if learned_x is not None:
            xcaps = list(learned_x)
        else:
            nb = n_shards * RADIX_SUB
            build_psb = leaf_psb[shuffle_build]
            xcaps = [dev.next_pow2(max(2 * (-(-per_shard_b // nb)), 8)),
                     dev.next_pow2(max(2 * (-(-build_psb // nb)), 8))]

    def leaf_rows(nd):
        if xcaps is not None and nd.leaf_id == shard_leaf:
            return n_shards * RADIX_SUB * xcaps[0]
        if nd.leaf_id == shard_leaf:
            return per_shard_b
        return leaf_total[nd.leaf_id]

    def est_rows(nd):
        # FK-join heuristic: output ≈ larger input, composed over the
        # subtree (see device_join.py est_rows) — starting from the probe
        # side alone needed a recompile per doubling to reach fact scale
        if isinstance(nd, _Leaf):
            return max(leaf_rows(nd), 8)
        return max(est_rows(nd.left), est_rows(nd.right))

    def init_caps():
        caps = []
        for jn in joins:
            jn.cap = dev.next_pow2(est_rows(jn))
            caps.append(jn.cap)
        return caps

    learned_caps = learned(sig, "caps")
    if indexed:
        # every join is a probe-shaped gather at a shard's rows, which the
        # body reads as a page: nothing to learn or grow
        leaves[shard_leaf].bucket = per_shard_b
        run.plan(sig, used, compacts=False)
        caps = [jn.cap for jn in joins]
    elif learned_caps is not None and len(learned_caps) == len(joins):
        caps = list(learned_caps)
    else:
        caps = init_caps()
    n_frag = caps[-1] if caps else per_shard_b
    learned_cap = learned(sig, "agg")
    if learned_cap is not None:
        capacity = learned_cap
    else:
        est = _estimate_groups(plan, n_frag, ctx)
        capacity = dev.next_pow2(min(max(n_frag, 16), max(est, 16)))

    # retry discipline (reference: the Backoffer every coprocessor/MPP
    # dispatch carries, store/tikv/backoff.go): exchange transport faults
    # back off and retry on the SAME capacities; bucket/group overflow
    # "retries" are recompiles at larger capacity and draw from a separate
    # attempt budget.  Exhausting the transport budget surfaces a
    # classified BackoffExhaustedError (and trips the device breaker);
    # exhausting the growth budget degrades to the host engine.
    from ..utils import failpoint
    from ..utils.backoff import (Backoffer, ExchangeError)
    from ..utils.failpoint import FailpointError
    from ..errors import BackoffExhaustedError
    bo = Backoffer.for_session(ctx)
    run.begin()
    while True:
        for jn, cap in zip(joins, caps):
            jn.cap = cap
        shuffle = None
        if shuffle_build is not None:
            shuffle = (bottom, shard_leaf, shuffle_build,
                       xcaps[0], xcaps[1])
        # mesh pipelines compile SYNC through the service (no arg spec):
        # a background warm would dispatch zero-filled HOST arrays against
        # a shard_map program traced for mesh-placed shardings — a
        # different program than the one traffic dispatches.  The compile
        # still gets the breaker/persist/failpoint ladder.
        if indexed:
            fn = run.program(
                ctx, capacity, shape="mpp", lead=(tuple(xcaps or ()),),
                program=functools.partial(
                    _shard_program, mesh=mesh, probe_id=shard_leaf,
                    shard_rows=per_shard_b, env_specs=env_specs,
                    n_keys=n_keys, agg_ops=agg_ops, capacity=capacity,
                    key_pack=run.key_pack))
            args = (env, jidx, n_lives)
        else:
            def build(shuffle=shuffle, cap=capacity):
                return _build_mpp_pipeline(
                    mesh, leaves, joins, root, sharded_ids, leaf_cond_fns,
                    cond_fns, run.key_fns, n_keys, run.val_plan, agg_ops,
                    cap, run.key_pack, env_specs, shuffle=shuffle)
            key = (sig, tuple(caps), tuple(xcaps or ()), capacity,
                   run.key_pack, agg_ops)
            fn = acquire_pipeline(key, build, run.dict_refs, ctx=ctx,
                                  shape="mpp", sig=sig)
            args = (env, n_lives)
        try:
            failpoint.inject("mpp-exchange-send")
            agg_out, png_d, ovfs_d, sovfs_d, xneeds_d = fn(*args)
            note_agg_spans(run.key_pack, agg_ops, capacity,
                           caps[-1] if caps else per_shard_b, gathered=True)
            from .device_exec import AggFetch
            f = AggFetch(agg_out, extras=(png_d, ovfs_d, sovfs_d, xneeds_d))
            failpoint.inject("mpp-exchange-recv")
        except (FailpointError, ExchangeError, ConnectionError,
                TimeoutError) as e:
            # narrow on purpose: FileNotFoundError-class OSErrors are
            # bugs, not transient exchange weather — they must surface
            exc = (e if isinstance(e, ExchangeError)
                   else ExchangeError(f"mpp exchange failed: {e}"))
            try:
                bo.backoff("exchangeRetry", exc)
            except BackoffExhaustedError:
                from .circuit import get_breaker
                # same SESSION owner token AND the same fragment shape
                # run_device's allow() used (join trees dispatch under
                # shape="join" — charging "agg" would open the healthy
                # agg breaker and orphan the join probe's verdict); the
                # session token stays valid even though a supervised
                # dispatch runs this on a worker thread
                get_breaker(ctx,
                            shape="join" if joins else "agg").record_failure(
                    exc, session=getattr(ctx, "conn_id", None))
                raise
            MPP_STATS["exchange_retries"] += 1
            continue
        png, ovfs, sovfs, xneeds = f.extras
        fng = f.ng
        if any(int(s) for s in sovfs):
            raise DeviceUnsupported(
                "multi-key join value ranges exceed int64 packing")
        retry = False
        x_grew = False
        for i, need in enumerate(xneeds):
            if int(need) > xcaps[i]:
                # jump straight to the worst sub-bucket's exact
                # requirement (≥ a doubling — caps are powers of two):
                # one retry converges even under a dominant hot key
                xcaps[i] = dev.next_pow2(int(need))
                retry = True
                x_grew = True
                MPP_STATS["exchange_overflow_retries"] += 1
        if x_grew:
            # the bottom join's probe side grew with the exchange bucket
            caps[bottom_idx] = max(
                caps[bottom_idx],
                dev.next_pow2(max(n_shards * RADIX_SUB * xcaps[0], 8)))
        for i, o in enumerate(ovfs):
            if int(o) > caps[i]:
                # jump to the worst shard's exact requirement in one step
                caps[i] = dev.next_pow2(int(o))
                retry = True
        max_ng = max(int(png), int(fng))
        if max_ng > capacity:
            capacity = dev.next_pow2(max_ng)
            retry = True
        if not retry:
            break
        MPP_STATS["retries"] += 1
        note_rerun("mpp", capacity, max_ng, caps=[int(c) for c in caps])
        try:
            bo.backoff("exchangeGrow")
        except BackoffExhaustedError as e:
            raise DeviceUnsupported(
                "mpp fragment capacities did not converge") from e
    # remember the converged shapes per signature: the next execution —
    # another session, the warm bench round, the post-INSERT re-run —
    # starts at these exact capacities and hits the compiled pipeline
    learn(sig, "caps", tuple(caps))
    if xcaps is not None:
        learn(sig, "xcaps", tuple(xcaps))
    learn(sig, "agg", capacity)
    ng = int(fng)
    if ng == 0 and not plan.group_exprs:
        raise DeviceUnsupported("empty global aggregate")
    MPP_STATS["fragments"] += 1
    if shuffle_build is not None:
        MPP_STATS["shuffle_joins"] += 1
    if indexed:
        # counted as device_join_agg counts its fragment: once, whatever
        # the retries above (the last program's gathers are every one's)
        MPP_STATS["indexed_fragments"] += 1
        note_join_gathers(fn)
    _publish_gauges(ctx)
    key_out, key_null_out, results, result_nulls = f.body()
    return _assemble_agg(plan, run.key_meta, run.slots, dcols,
                         (key_out, key_null_out, results, result_nulls), ng)
