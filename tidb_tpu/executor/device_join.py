"""Fused device join+aggregate fragments — the engine's answer to the
reference's MPP fragment execution (planner/core/fragment.go cuts plans at
exchange boundaries; unistore/cophandler/mpp_exec.go runs join/agg
fragments storage-side). Here the whole scan→filter→join→…→aggregate tree
compiles into ONE jitted XLA program over HBM-resident base tables:

- joins whose build side is a base-table leaf use HOST-BUILT indexes
  (executor/join_index.py): the ordering work runs once per table version
  in numpy and the compiled program only gathers: a probe key addresses
  a table over the key span directly whenever that table's bytes are
  affordable, and binary-searches the host-sorted keys only when they
  are not (a `sorted` build): from the two ends of the key's bucket
  where the index carries a prefix table over the key's high bits (a
  step or two a probe row), over the whole array where it does not
  (log2(n) dependent gathers). A
  UNIQUE build side (every TPC-H fact⋈dim join) adds nothing to the
  output shape — the join is one gather of a row id with the probe
  side's exact capacity, no expansion pass and no overflow retry at all.
- non-unique indexed builds expand through a static-capacity CSR walk
  (cnt → cumsum → the row map of _expand_rows: one scatter and one
  running sum, or a search per output slot where the slots are few
  against the probe rows), still sort-free on device.
- joins outside the index language (bushy subtrees, computed keys) fall
  back to the in-program lexsort + searchsorted build, expanded through
  the same row map.
- intermediate results are row-index vectors into the base tables, not
  materialized rows: each join composes gathers lazily, and only the
  aggregate at the top reads actual column values.
- ONE host↔device round trip per execution (batched device_get of the
  aggregate outputs + overflow/validity scalars).
- expansion capacities and the aggregate group capacity are LEARNED: the
  exact totals observed on a run are remembered per fragment signature,
  so the overflow (or shrink-to-fit) recompile happens once per fragment
  ever, not once per session — and repeat executions jump straight to
  tight shapes (reference analog: the plan cache reusing learned sizes,
  planner/core/cache.go).

Supported fragment shape: equi-joins over table scans with pushed-down
filters, topped by a group-by aggregate. Join kinds:
- inner: anywhere in the tree (reorderable, any strategy);
- left outer: anywhere, with an indexed build side — the build side
  null-extends in-program (nullmaps thread the ~matched flags through the
  gathers), ON-residuals fold into the match on the unique-gather path;
- semi / anti: at the fragment ROOT, or a chain of them there, each the
  probe of the one above (the decorrelated EXISTS / IN conjuncts of one
  WHERE; the inner joins under the chain reorder fact-first). Without a
  residual an existence is a match count; with one (Q21's `<>`) a
  unique build tests it on its gathered row and a non-unique build on
  every pair of a CSR expansion of the probe's live rows, reduced back
  to the probe row (probe-shaped either way).
Anything else raises DeviceUnsupported and falls back to the host path.
"""

from __future__ import annotations

import collections
import functools
import threading

import numpy as np
import jax
import jax.numpy as jnp

from ..expression import phys_kind, K_FLOAT, K_STR
from ..expression.core import Column as ExprColumn, ScalarFunc as _SF
from ..ops import device as dev
from ..ops.device import DeviceUnsupported
from .device_exec import (
    _assemble_agg, _count_trace, _estimate_groups, _expr_sig,
    _plan_agg, _timed_jit, acquire_pipeline, exists_expands, join_expands,
    note_agg_arm, note_agg_spans, note_join_chain, note_join_compactions,
    note_join_derived, note_join_expansion, note_join_gathers,
    note_join_layouts, note_join_probe, note_join_residual, note_rerun,
    note_semi_inset)
from .join_index import build_join_index


class _Leaf:
    __slots__ = ("leaf_id", "chunk", "conds", "offset", "ncols", "dcols",
                 "dcols_bucket", "dcols_epoch", "leaf_ids", "bucket",
                 "derived")

    def __init__(self, leaf_id, chunk, conds, offset, derived=False):
        self.leaf_id = leaf_id
        self.chunk = chunk
        self.conds = conds
        self.offset = offset
        self.ncols = chunk.num_cols
        self.dcols = None  # {local_idx: DeviceCol}
        self.dcols_bucket = None  # bucket the cached dcols were built at
        self.dcols_epoch = None  # device epoch the dcols were built under
        self.leaf_ids = frozenset((leaf_id,))
        self.bucket = None  # padded upload rows (ops/device.py bucket_rows)
        # the chunk is another operator's result, made by this statement
        # (collect_tree's derived build): its uploads and index are the
        # statement's own, released when the fragment ends
        self.derived = derived


class _JoinNode:
    def __init__(self, left, right, left_keys, right_keys, other_conds,
                 offset, kind="inner"):
        self.left = left
        self.right = right
        self.left_keys = left_keys    # exprs over left subtree schema
        self.right_keys = right_keys  # exprs over right subtree schema
        self.other_conds = other_conds
        self.offset = offset
        self.kind = kind          # inner | left | semi | anti
        self.ncols = left.ncols + right.ncols
        self.leaf_ids = left.leaf_ids | right.leaf_ids
        self.cap = 0            # static output capacity (set by _fill_caps)
        self.pos = 0            # index into the fragment's join list
        self.strategy = None    # None | (kind, side, JoinIndex)
        self.exp_cap = None     # requested capacity for expansion joins
        self.probe_cap = 0      # an expansion's probe rows (_fill_caps)
        self.global_keys = False  # keys/conds already in global indices
        # _reorder_fact_first attached this build ahead of a smaller
        # candidate, because its filter lets the probe path cut
        self.selective = False


def _run_build(build, span, finish):
    """Run a join's build subtree through its own executors (their device
    fragments nest under `span`) and hand its chunk to `finish`, which
    returns (what the caller keeps, the span's tags).  THE one way a
    fragment's plan walk materialises a build: the in-set fold
    (``subquery.materialize``) and the derived leaf
    (``join.derived_build``)."""
    from ..session import tracing
    with tracing.span(span) as sp:
        out, tags = finish(build.execute())
        if sp is not None:
            sp.tags.update(tags)
    return out


def collect_tree(node, derived=False):
    """executor node → (_Leaf | _JoinNode) tree; DeviceUnsupported if the
    shape is outside the fragment language.  `derived`: a join's build
    child that is neither scan-shaped nor a join (and not folded into an
    in-set) runs through its own executors and becomes a derived leaf;
    without it such a build is DeviceUnsupported."""
    from .exec_select import HashJoinExec, SelectionExec, TableScanExec

    leaves = []
    joins = []

    def walk(n, offset):
        if isinstance(n, TableScanExec):
            raw, conds = n.execute_raw()
            leaf = _Leaf(len(leaves), raw, list(conds), offset)
            leaves.append(leaf)
            return leaf
        if isinstance(n, SelectionExec) and isinstance(
                n.children[0], TableScanExec):
            raw, conds = n.children[0].execute_raw()
            leaf = _Leaf(len(leaves), raw,
                         list(conds) + list(n.plan.conds), offset)
            leaves.append(leaf)
            return leaf
        if isinstance(n, HashJoinExec):
            p = n.plan
            if p.kind not in ("inner", "left", "semi", "anti"):
                raise DeviceUnsupported(
                    f"{p.kind} join in device fragment")
            if not p.left_keys:
                raise DeviceUnsupported(
                    "cartesian join (no equi keys) in device fragment")
            _scan_shaped = (isinstance(n.children[1], TableScanExec)
                            or (isinstance(n.children[1], SelectionExec)
                                and isinstance(n.children[1].children[0],
                                               TableScanExec)))
            if (p.kind == "semi" and not _scan_shaped
                    and len(p.left_keys) == 1 and not p.other_conds):
                # mid-tree semi join over a non-scan build (the
                # uncorrelated IN→semi rewrite: an aggregate subquery):
                # materialize the build side — through its own
                # (device-capable) executor — and fold the membership
                # into an in-set filter on the probe subtree, restoring
                # the fused single-program fragment (Q18's shape).
                # Anti is excluded: NOT IN's NULL semantics differ from
                # a negated in-set.
                # Probe walks FIRST: a DeviceUnsupported below must not
                # discard an already-executed aggregate subquery (the
                # fallback would run it again — and tpu-mpp a third time)
                lnode = walk(n.children[0], offset)
                if (isinstance(lnode, _JoinNode)
                        and lnode.kind != "inner"):
                    # other_conds on an outer join are ON-residuals (part
                    # of the MATCH), not a WHERE filter — attaching the
                    # membership there would null-extend instead of drop
                    raise DeviceUnsupported(
                        "semi membership over a non-inner probe")
                def in_set(values_chunk):
                    # the subquery's own fragments nest under the span;
                    # what is left of it is the host's: its executors'
                    # tail (a HAVING), the values as Python objects, the
                    # in-set
                    from .exec_select import eval_expr_to_column
                    col = eval_expr_to_column(p.right_keys[0], values_chunk)
                    vals = [None if col.nulls[i] else col.value_at(i)
                            for i in range(len(col.data))]
                    from ..expression.builder import build_in_set
                    cond = build_in_set(p.left_keys[0], vals,
                                        p.right_keys[0].ftype)
                    return cond, {"rows": values_chunk.num_rows,
                                  "kept": len(cond.extra[0])}
                cond = _run_build(n.children[1], "subquery.materialize",
                                  in_set)
                note_semi_inset()
                if isinstance(lnode, _Leaf):
                    lnode.conds.append(cond)  # left-local schema == leaf's
                else:
                    # over the left subtree's schema, which starts at the
                    # node's own offset — exactly other_conds' convention
                    lnode.other_conds.append(cond)
                return lnode
            left = walk(n.children[0], offset)
            for lk, rk in zip(p.left_keys, p.right_keys):
                kl, kr = phys_kind(lk.ftype), phys_kind(rk.ftype)
                if K_STR in (kl, kr) or K_FLOAT in (kl, kr):
                    raise DeviceUnsupported("string/float join keys")
                if (lk.ftype.scale or 0) != (rk.ftype.scale or 0):
                    raise DeviceUnsupported("mismatched decimal key scales")
            if (derived and not _scan_shaped
                    and not isinstance(n.children[1], HashJoinExec)):
                right = _derived_leaf(n.children[1], len(leaves),
                                      offset + left.ncols)
                leaves.append(right)
            else:
                right = walk(n.children[1], offset + left.ncols)
            other_conds = list(p.other_conds)
            gap = left.ncols - _width(left)
            if p.kind in ("semi", "anti") and gap:
                # a residual reads [probe | build] in the plan's schema,
                # where the probe (a semi / anti join's rows) is narrower
                # than the columns its subtree holds: the build half
                # moves to the build leaf's own columns
                lw = _width(left)
                other_conds = [c.transform_columns(
                    lambda col, lw=lw, gap=gap: col if col.idx < lw
                    else ExprColumn(col.idx + gap, col.ftype,
                                    name=col.name))
                    for c in other_conds]
            jn = _JoinNode(left, right, list(p.left_keys),
                           list(p.right_keys), other_conds, offset,
                           kind=p.kind)
            jn.pos = len(joins)
            joins.append(jn)
            return jn
        raise DeviceUnsupported(
            f"{type(n).__name__} not supported in device fragment")

    root = walk(node, 0)
    if not joins:
        raise DeviceUnsupported("no joins in fragment")
    # semi/anti joins expose only their probe (left) schema, so upstream
    # column indices stay valid only on the chain of such joins at the
    # fragment ROOT, each the probe of the one above (the decorrelated
    # EXISTS / NOT EXISTS conjuncts of one WHERE: Q21's): nothing is
    # joined beside them, so no sibling's offsets can collide
    chain = {id(jn) for jn in _exists_chain(root)}
    if any(jn.kind in ("semi", "anti") and id(jn) not in chain
           for jn in joins):
        raise DeviceUnsupported("semi/anti join below fragment root")
    return root, leaves, joins


def _derived_leaf(build, leaf_id, offset):
    """A join's build child that is another operator (Q17's ``lineitem
    group by l_partkey``), run once under the span ``join.derived_build``
    (tags ``rows``, ``cols``, ``bytes``) and taken as a leaf like a
    table's: indexed by join_index.build_join_index, its columns passed
    to the program as arguments.  Only the keys were checked before it
    runs; a column the device cannot hold as an argument (a string, whose
    dictionary the program would bake in) is refused before it runs
    too."""
    for ref in build.plan.schema.refs:
        if phys_kind(ref.ftype) == K_STR:
            raise DeviceUnsupported(
                f"derived build column {ref.name or '?'} is a string")

    def take(chunk):
        return chunk, {"rows": chunk.num_rows, "cols": chunk.num_cols,
                       "bytes": sum(c.data.nbytes + c.nulls.nbytes
                                    for c in chunk.columns)}
    chunk = _run_build(build, "join.derived_build", take)
    return _Leaf(leaf_id, chunk, [], offset, derived=True)


def release_derived(leaves):
    """What a fragment's derived leaves placed is the statement's: drop
    their columns' uploads and their indexes (host and device) from the
    caches, so that the ledger does not grow over requests and no later
    statement is served them."""
    from ..ops import residency
    from . import join_index
    for leaf in leaves:
        if leaf.derived:
            for c in leaf.chunk.columns:
                join_index.release(c)
            residency.release(leaf.chunk.columns)
            leaf.dcols = None


def _width(node) -> int:
    """Columns of a tree node's schema in the plan: a semi / anti join's
    is its probe's, though its subtree holds its build's columns too."""
    if isinstance(node, _Leaf):
        return node.ncols
    if node.kind in ("semi", "anti"):
        return _width(node.left)
    return _width(node.left) + _width(node.right)


def _exists_chain(root) -> list:
    """The semi / anti joins stacked at a fragment's root, top first,
    each the probe (left side) of the one above."""
    chain = []
    node = root
    while isinstance(node, _JoinNode) and node.kind in ("semi", "anti"):
        chain.append(node)
        node = node.left
    return chain


def _leaf_env(leaf, bucket=None):
    """Device columns for one leaf, cached on the host Columns. `bucket`
    pads the upload to a canonical row bucket (ops/device.py bucket_rows);
    the compiled fragment masks rows past the leaf's traced live count.
    The cache is keyed by the bucket it was built at: a declined earlier
    attempt (mpp, paged) must not leave exact-shape dcols that the
    bucketed resident path would silently trace against (to_device_col
    reuses/slices the underlying column upload, so a rebuild is cheap).
    It is also stamped with the DEVICE EPOCH (ops/residency.py): a
    backend fence or OOM evict-all mid-query invalidates the dict, so no
    pre-fence DeviceCol array can reach a post-fence dispatch.  The byte
    accounting rides on the underlying Column entries — the dict holds
    views/slices of the residency-tracked uploads, no extra HBM."""
    from ..ops import residency
    epoch = residency.device_epoch()
    if (leaf.dcols is None or leaf.dcols_bucket != bucket
            or leaf.dcols_epoch != epoch):
        leaf.dcols = {i: dev.to_device_col(c, bucket=bucket,
                                           scoped=leaf.derived)
                      for i, c in enumerate(leaf.chunk.columns)}
        leaf.dcols_bucket = bucket
        leaf.dcols_epoch = epoch
    return leaf.dcols


def _leaf_meta(leaf):
    """Metadata-only DeviceCols for one leaf (no HBM transfer): what the
    expression compiler and agg planner read. The actual arrays reach the
    compiled program through `env` — whole columns on the resident path,
    page slices on the paged path."""
    return {i: dev.meta_device_col(c)[0]
            for i, c in enumerate(leaf.chunk.columns)}


def _global_dcols(leaves, meta_leaf_ids=frozenset(), buckets=None):
    """DeviceCol lookup keyed by global (join-output) column index.
    Leaves in `meta_leaf_ids` contribute metadata-only DeviceCols —
    their columns must never be uploaded whole (paged probe side)."""
    out = {}
    for leaf in leaves:
        dcs = (_leaf_meta(leaf) if leaf.leaf_id in meta_leaf_ids
               else _leaf_env(leaf, (buckets or {}).get(leaf.leaf_id)))
        for i, dc in dcs.items():
            out[leaf.offset + i] = dc
    return out


# ---------------------------------------------------------------------------
# join strategy planning (host-side, once per fragment)
# ---------------------------------------------------------------------------

def _leaf_key_cols(side, keys):
    """Host Columns for `keys` when `side` is a leaf and every key is a
    bare integer column of it; None otherwise."""
    if not isinstance(side, _Leaf):
        return None
    cols = []
    for k in keys:
        if not isinstance(k, ExprColumn) or not 0 <= k.idx < side.ncols:
            return None
        c = side.chunk.columns[k.idx]
        if (c.is_object()
                or not np.issubdtype(c.data.dtype, np.integer)):
            return None
        from ..storage.paged import is_paged
        if (is_paged(c)
                and side.chunk.num_rows * 16 > _dim_resident_budget()):
            # indexing (argsort + order arrays) a fact-sized memmap would
            # materialize it into RAM at PLAN time — a paged fact is only
            # ever the streamed probe, never a build index (an oversized
            # build instead goes through the hybrid partitioned path)
            return None
        cols.append(c)
    return cols


def _leaf_index(side, keys, filtered=True):
    """Host join index for `side` (a leaf with bare int keys), built over
    the rows passing the leaf's pushed-down filters — evaluated host-side
    with the host engine's own predicate path, so index membership matches
    the device mask exactly — or over all its rows where not `filtered`
    (the program then tests the leaf's mask on every row it reads
    through the index).  None when out of the index language."""
    cols = _leaf_key_cols(side, keys)
    if cols is None:
        return None
    tag = ""
    mask_fn = None
    if side.conds and filtered:
        try:
            tag = ";".join(_expr_sig(c) for c in side.conds)
        except DeviceUnsupported:
            tag = ""
        if tag:
            def mask_fn():
                from .exec_select import eval_conds_mask
                return eval_conds_mask(side.conds, side.chunk)
    return build_join_index(cols, mask_fn=mask_fn, cache_tag=tag,
                            scoped=side.derived)


def _plan_strategy(jn):
    """Pick the cheapest build layout: a UNIQUE host index wins outright
    (gather join, probe-shaped output); a non-unique one still beats the
    in-program sort (CSR expansion); neither → device lexsort. The right
    (conventional build) side indexes first, and a unique hit returns
    before the left index is ever built — indexing the probe side would
    argsort the (typically huge) fact table for nothing.

    Non-inner kinds (left/semi/anti) preserve their LEFT side: the probe
    must be the left relation, so only right-side builds qualify.  A
    semi / anti join with a residual tests its build leaf's filter pair by
    pair beside the residual, so it takes the leaf's UNFILTERED index: two
    existence tests on one key column under different filters (Q21's l2
    and l3) then share the one index the column caches, and neither
    rebuilds it for the other."""
    ridx = _leaf_index(jn.right, jn.right_keys, filtered=not (
        jn.kind in ("semi", "anti") and jn.other_conds))
    if jn.kind != "inner":
        if ridx is None:
            return None
        return ("uniq" if ridx.unique else "expand", "right", ridx)
    if ridx is not None and ridx.unique:
        return ("uniq", "right", ridx)
    lidx = None
    if (not isinstance(jn.right, _Leaf) or not isinstance(jn.left, _Leaf)
            or ridx is None
            or jn.left.chunk.num_rows <= jn.right.chunk.num_rows):
        # only index the left side when it could plausibly win: a left
        # leaf LARGER than a non-unique right leaf is the fact side — a
        # fact-sized argsort would buy nothing over ('expand', 'right')
        lidx = _leaf_index(jn.left, jn.left_keys)
    if lidx is not None and lidx.unique:
        return ("uniq", "left", lidx)
    if ridx is not None:
        return ("expand", "right", ridx)
    if lidx is not None:
        return ("expand", "left", lidx)
    return None


def _reorder_fact_first(leaves, joins, assume_unique=frozenset()):
    """Rebuild the fragment's inner-join tree as a FACT-FIRST left-deep
    chain of unique-build gather joins. The device cost model inverts the
    host planner's greedy smallest-intermediate order (optimizer.py
    _greedy_join, reference rule_join_reorder.go): starting from the
    LARGEST leaf and attaching each dimension through its unique key makes
    every join a probe-shaped gather — the 'intermediate result' never
    grows, selectivity lives in the validity mask, and no expansion
    capacity (or overflow recompile) exists anywhere in the program. Inner
    equi-joins reorder freely, so this is pure engine-side physical
    planning.

    Which candidate a step attaches is _attach_rank's: first a build
    whose filter keeps at most 1 / _COMPACT_FACTOR of its rows (the share
    at which compact_to cuts the probe path after its join), the one whose
    cut would leave the fewest rows first; then the rest by size.  Every
    lookup and gather before the first cut runs at the fact's length, so
    the filtered build that cuts goes ahead of a smaller one that does
    not (SSB Q2.1's `part` ahead of `date` and `supplier`, TPC-H Q9's
    `part` ahead of `supplier`, Q5's `orders` ahead of `supplier`); a
    chain with no such build keeps the size order and its program.  A
    step that took a cutting build ahead of a smaller candidate marks
    its node `selective` (device_exec.note_join_chain counts it).

    assume_unique: leaf ids whose whole-table index must NOT be built at
    plan time (it would exceed the residency budget — exactly the hybrid
    hash join's partitioned build, executor/hybrid_join.py).  Such a leaf
    joins the chain with a DEFERRED strategy ``("uniq", "right", None)``
    on bare-integer-column keys, ranked by its size; the hybrid path
    builds per-partition indexes at execution and verifies uniqueness
    there.  A deferred node must never reach the resident/paged dispatch
    paths — device_join_agg raises if the hybrid attempt falls through.

    Returns (root, new_joins) with strategies assigned, or None when the
    chain can't be built expansion-free (multi-leaf key exprs, a
    disconnected graph, or a non-unique build somewhere) — the caller
    keeps the planner's tree and per-join strategy planning."""
    if len(joins) < 2 and not assume_unique:
        return None
    from ..sqltypes import FieldType, TYPE_LONGLONG
    by_id = {leaf.leaf_id: leaf for leaf in leaves}

    def cover_of(e):
        used = set()
        e.columns_used(used)
        ids = set()
        for g in used:
            for leaf in leaves:
                if leaf.offset <= g < leaf.offset + leaf.ncols:
                    ids.add(leaf.leaf_id)
                    break
        return ids

    pairs = []   # [gl_expr, gr_expr, l_leaf, r_leaf]
    others = []  # [g_expr, cover_set]
    for jn in joins:
        off_l = 0 if jn.global_keys else jn.left.offset
        off_r = 0 if jn.global_keys else jn.right.offset
        off_o = 0 if jn.global_keys else jn.offset
        for lk, rk in zip(jn.left_keys, jn.right_keys):
            gl = _shift_expr(lk, off_l)
            gr = _shift_expr(rk, off_r)
            cl, cr = cover_of(gl), cover_of(gr)
            if len(cl) != 1 or len(cr) != 1:
                return None
            pairs.append((gl, gr, next(iter(cl)), next(iter(cr))))
        for c in jn.other_conds:
            g = _shift_expr(c, off_o)
            others.append((g, cover_of(g)))

    remaining = set(by_id)
    start = max(remaining, key=lambda i: by_id[i].chunk.num_rows)
    fact_rows = by_id[start].chunk.num_rows
    remaining.discard(start)
    spine_ids = {start}
    cur = by_id[start]
    new_joins = []
    pend_pairs = list(pairs)
    pend_others = list(others)
    bool_ft = FieldType(tp=TYPE_LONGLONG)
    while remaining:
        cands = {}  # leaf_id -> [(pair, spine_expr, leaf_expr)]
        for p in pend_pairs:
            gl, gr, cl, cr = p
            if cl in spine_ids and cr in remaining:
                cands.setdefault(cr, []).append((p, gl, gr))
            elif cr in spine_ids and cl in remaining:
                cands.setdefault(cl, []).append((p, gr, gl))
        if not cands:
            return None
        ranked = []  # (rank, leaf_id, key pairs, index)
        for lid, kps in cands.items():
            leaf = by_id[lid]
            if lid in assume_unique:
                # deferred partition-indexed build: accept on bare int
                # leaf columns without materializing the whole index
                local = [_shift_expr(lx, -leaf.offset)
                         for _p, _s, lx in kps]
                if any(not isinstance(e, ExprColumn)
                       or not 0 <= e.idx < leaf.ncols
                       or leaf.chunk.columns[e.idx].is_object()
                       or not np.issubdtype(
                           leaf.chunk.columns[e.idx].data.dtype,
                           np.integer)
                       for e in local):
                    continue
                idx = None
            else:
                # the index builder addresses the leaf's LOCAL schema; the
                # chain's key exprs are global — rebase before the lookup
                idx = _leaf_index(leaf, [_shift_expr(lx, -leaf.offset)
                                         for _p, _s, lx in kps])
                if idx is None or not idx.unique:
                    continue
            ranked.append((_attach_rank(leaf, idx, fact_rows), lid, kps,
                           idx))
        if not ranked:
            return None  # a non-unique build would expand: keep the
            #              planner's tree instead
        _rank, lid, kps, idx = min(ranked, key=lambda r: r[0])
        smallest = min((by_id[r[1]].chunk.num_rows, r[1]) for r in ranked)[1]
        leaf = by_id[lid]
        jn = _JoinNode(cur, leaf,
                       [s for _p, s, _l in kps], [l for _p, _s, l in kps],
                       [], 0)
        jn.global_keys = True
        jn.strategy = ("uniq", "right", idx)
        jn.selective = lid != smallest
        spine_ids.add(lid)
        remaining.discard(lid)
        consumed = {id(p) for p, _s, _l in kps}
        rest = []
        for p in pend_pairs:
            gl, gr, cl, cr = p
            if id(p) in consumed:
                continue
            if cl in spine_ids and cr in spine_ids:
                # an equi-cond between two already-joined leaves (Q5's
                # c_nationkey = s_nationkey shape) becomes a plain filter
                # at the first node covering both sides
                jn.other_conds.append(_SF("eq", [gl, gr], bool_ft))
            else:
                rest.append(p)
        pend_pairs = rest
        keep_o = []
        for o in pend_others:
            g, cov = o
            if cov <= spine_ids:
                jn.other_conds.append(g)
            else:
                keep_o.append(o)
        pend_others = keep_o
        jn.pos = len(new_joins)
        new_joins.append(jn)
        cur = jn
    if pend_pairs or pend_others:
        return None  # anything unplaced means the rewrite lost a predicate
    return cur, new_joins


def _attach_rank(leaf, idx, fact_rows):
    """Where a candidate build stands in _reorder_fact_first's step:
    (0, cut, rows, leaf id) for one whose index was built under the
    leaf's filter and keeps at most 1 / _COMPACT_FACTOR of its rows —
    past its join compact_to would cut the probe path, to `cut` =
    next_pow2 of its kept share of the fact's `fact_rows` — and (1, 0,
    rows, leaf id) for the rest (no filter, a wider share, a deferred
    build with no index), which keep the size order.  The smaller build
    breaks a tie."""
    size = (leaf.chunk.num_rows, leaf.leaf_id)
    if (idx is not None and idx.filtered
            and idx.n_valid * _COMPACT_FACTOR <= idx.n_rows):
        live = -(-idx.n_valid * fact_rows // max(idx.n_rows, 1))
        return (0, dev.next_pow2(max(live, 1))) + size
    return (1, 0) + size


def _reorder_below_chain(leaves, joins):
    """_reorder_fact_first for the inner joins under a fragment's chain of
    semi / anti joins (_exists_chain), the existence chain put back on
    top of the fact-first one: the probe of every existence test is then
    the fact leaf's rows, read in place, whose mask says which the inner
    joins keep.  -> (root, joins) in postorder with the inner joins' strategies
    assigned, or None where the chain's builds are not leaves or what is
    under it is not all inner joins that chain expansion-free."""
    chain = _exists_chain(joins[-1])
    if not chain or any(not isinstance(jn.right, _Leaf) for jn in chain):
        return None
    below = chain[-1].left
    inner = [jn for jn in joins if jn.leaf_ids <= below.leaf_ids]
    if (not isinstance(below, _JoinNode) or len(inner) + len(chain)
            != len(joins) or any(jn.kind != "inner" for jn in inner)):
        return None
    got = _reorder_fact_first(
        [leaf for leaf in leaves if leaf.leaf_id in below.leaf_ids], inner)
    if got is None:
        return None
    sub, new_joins = got
    chain[-1].left = sub
    for jn in reversed(chain):
        jn.pos = len(new_joins)
        new_joins.append(jn)
    return chain[0], new_joins


def _strategy_sig(jn):
    st = jn.strategy
    if st is None:
        return f"S{jn.pos}:-"
    kind, side, idx = st
    return f"S{jn.pos}:{kind}/{side}/{idx.sig()}"


#: learned exact sizes per fragment, through `learned` / `learn` alone:
#: (sig, join_pos) → last observed match total, (sig, ("live", pos)) → a
#: compaction point's live count, (sig, "agg") → group count ("groups":
#: the pages' merged one); the mesh's: converged "caps", "xcaps" and
#: aggregate capacity. In-process, LRU-bounded like _PIPE_CACHE (sig
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


def _null_extend(nulls, bidx_map, hit):
    """Left-join null extension: every build-side leaf's columns read as
    NULL on rows without a surviving match (shared by the uniq-gather and
    CSR-expand paths so their semantics can never diverge)."""
    for lid in bidx_map:
        prev = nulls.get(lid)
        nulls[lid] = ~hit if prev is None else (prev | ~hit)


#: one slot-step of the per-slot search priced in scattered probe rows of
#: the one pass (expand_one_pass): 15-27 ns against 8.8 ns on the v5e
_EXPAND_SEARCH_PRICE = 2


def expand_one_pass(cap, n_probe) -> bool:
    """Which side of _expand_rows maps an expansion's `cap` output slots
    to its `n_probe` probe rows: True = one scatter at probe length and
    two running scans at output length, False = a binary search per
    output slot.  Host-callable (the dispatcher counts kept programs by
    it: device_exec.note_join_expansion) and what _expand_rows itself
    asks at trace time; it reads its two arguments only, both static
    shapes, so every backend traces the program the chip runs.

    The search costs cap x ceil(log2(n_probe + 1)) gathered slot-steps,
    the pass n_probe scattered rows (its scans over the slots are a
    fiftieth of a slot-step each); the pass is taken where a slot-step at
    _EXPAND_SEARCH_PRICE scattered rows makes the search the dearer:
    TPC-H Q13's 2,097,152 slots over the 185,364-row customer bucket
    pass, a learned capacity of 16,384 slots over 8,388,608 probe rows
    searches."""
    n_probe = int(n_probe)
    steps = max(n_probe, 1).bit_length()        # ceil(log2(n_probe + 1))
    return int(cap) * steps * _EXPAND_SEARCH_PRICE >= n_probe


def _expand_rows(cnt, cap):
    """The row map of a static-capacity expansion: probe row i emits
    cnt[i] consecutive output slots, in row order.  Returns (pi, within,
    total): pi[s] = the probe row of slot s, clipped to [0, n_probe - 1]
    (slots at and past `total` read the last row); within[s] = s less the
    first slot of pi[s]'s run; total = sum(cnt), exact in cnt's dtype
    whatever `cap` (the overflow the capacity retry reads).

    pi[s] = #{i : cum[i + 1] <= s} is a count, not a search.  Two ways to
    the same arrays, slot for slot past `total` too, chosen by
    expand_one_pass(cap, n_probe) from the static shapes alone:

    - one pass: a one scattered at the end of every row but the last
      (ends non-decreasing, equal where a row emits nothing, those past
      `cap` dropped), then a running sum over the `cap` slots: the count
      of ends at or before a slot, at most n_probe - 1 by construction.
      A marked slot starts a run, so a running max of the marked
      positions is every slot's run start.  Positions are int32 while
      cap and n_probe fit; no gather, no loop.
    - search: searchsorted(cum, arange(cap)), one binary search per
      output slot: ceil(log2(n_probe + 1)) DEPENDENT gathers into the
      int64 `cum`, two `u32` halves each, then cum[pi].

    Standalone on the v5e (PERF.md section 6, PR 38; ms, the cumsum of
    `cnt` included: 0.9 / 10.3 / 10.2 / 3.4 / 1.0 by itself), at
    (n_probe, cap) = (262,144, 2,097,152: Q13's, whose customer bucket
    is 185,364 rows) / (8,388,608, 16,384) /
    (8,388,608, 2,097,152) / (2,097,152, 2,097,152) / (65,536, 1,024):
    search 1,148.7 / 16.2 / 1,532.4 / 1,719.1 / 1.2 (over an int32 `cum`
    302.0 / 13.3 / 385.4 / 349.3 / 1.1); this pass 4.6 / 84.0 / 85.0 /
    23.1 / 1.6 (18.95 / 84.1 / 99.4 / 37.4 / 1.6 with `within` gathered
    as start[pi]); a scatter-max of row ids at the starts and two
    running maxes 4.9 / 84.0 / 85.3 / 23.4 / 1.6; jnp.repeat 34.1 / 93.9
    / 123.9 / 54.7 / 1.6; two single-operand sorts of the ends merged
    with the slots (`pi` alone) 5.1 / 28.9 / 31.7 / 9.8 / 1.0.  The
    scatter is 8.8 ns a probe row, a scan 0.3 ns a slot, the search 15-27
    ns a slot-step, the sorts 2 ns a row of n_probe + cap: cheapest where
    the probe is as long as the output, 1.3 ms behind this pass at Q13's
    shape, and not taken: no cell has such a shape (PERF.md section 7)."""
    n_probe = cnt.shape[0]
    cum = jnp.concatenate([jnp.zeros(1, dtype=cnt.dtype), jnp.cumsum(cnt)])
    total = cum[-1]
    if expand_one_pass(cap, n_probe):
        pos_dt = jnp.int32 if max(cap, n_probe) < (1 << 31) else jnp.int64
        # an end past `cap` is past every slot: clipped to `cap`, dropped
        ends = jnp.minimum(cum[1:n_probe], cap).astype(pos_dt)
        marks = jnp.zeros(cap, dtype=pos_dt).at[ends].add(
            1, mode="drop", indices_are_sorted=True)
        pi = jnp.cumsum(marks)
        posn = jnp.arange(cap, dtype=pos_dt)
        within = posn - jax.lax.cummax(jnp.where(marks > 0, posn, 0))
    else:
        posn = jnp.arange(cap)
        pi = jnp.clip(jnp.searchsorted(cum, posn, side="right") - 1,
                      0, n_probe - 1)
        within = posn - cum[pi]
    return pi, within, total


def _join_expand(bk, bvalid, pk, pvalid, cap):
    """Static-capacity inner equi-join expansion (device-sort fallback).
    Returns (probe_slot, build_slot, valid, total): slot arrays index the
    *input relations* (length cap; garbage where ~valid).

    Join keys are arbitrary user int64 columns, so invalid rows are pushed
    behind ALL valid rows by a (validity, key) lexsort and the searchsorted
    bounds are clamped to the valid prefix — a plain int64.max sentinel
    would interleave genuine max-valued keys with padding and overcount."""
    nb = bk.shape[0]
    with jax.named_scope("k_join_build"):
        nb_valid = jnp.sum(bvalid)
        order = jnp.lexsort((bk, ~bvalid))  # valid-first, then key-sorted
        in_prefix = jnp.arange(nb) < nb_valid
        sb = jnp.where(in_prefix, bk[order], jnp.iinfo(jnp.int64).max)
    with jax.named_scope("k_join_probe"):
        lo = jnp.minimum(jnp.searchsorted(sb, pk, side="left"), nb_valid)
        hi = jnp.minimum(jnp.searchsorted(sb, pk, side="right"), nb_valid)
        cnt = jnp.where(pvalid, hi - lo, 0)
        pi, within, total = _expand_rows(cnt, cap)
        valid = jnp.arange(cap) < total
        bpos = lo[pi] + within
        bi = order[jnp.clip(bpos, 0, jnp.maximum(nb - 1, 0))]
        valid = valid & bvalid[bi] & pvalid[pi]
    # report the EXACT required size, not a boolean: an overflow retry can
    # then jump straight to next_pow2(total) instead of doubling — each
    # doubling is a full XLA recompile, and starting from a tiny dimension
    # table the doublings (12+ recompiles at TPC-H scale) dwarf the query
    return pi, bi, valid, total


@jax.named_scope("k_join_build")
def _combined_join_keys(lkds, lknulls, lvalid, rkds, rknulls, rvalid):
    """Fold multi-column equi-join keys into ONE int64 key per side using
    DATA-DEPENDENT range packing: per key column, [min, max] over both
    sides' valid rows gives a span; combined = Σ (kᵢ - mnᵢ)·Π spanⱼ.
    Dynamic VALUES are free under jit (only shapes must be static), so no
    host round trip and no host-side factorization (reference: hash join
    builds a multi-column hash key, executor/join.go:192).

    Returns (pk, pvalid, bk, bvalid, span_ovf) — span_ovf is a traced
    flag set when Π span exceeds int64 (caller must fall back, not
    retry)."""
    pvalid, bvalid = lvalid, rvalid
    for nl in lknulls:
        pvalid = pvalid & ~nl
    for nl in rknulls:
        bvalid = bvalid & ~nl
    if len(lkds) == 1:
        return (lkds[0].astype(jnp.int64), pvalid,
                rkds[0].astype(jnp.int64), bvalid,
                jnp.zeros((), dtype=bool))
    big = jnp.iinfo(jnp.int64).max
    small = jnp.iinfo(jnp.int64).min
    pk = jnp.zeros(lvalid.shape[0], dtype=jnp.int64)
    bk = jnp.zeros(rvalid.shape[0], dtype=jnp.int64)
    total = jnp.ones((), dtype=jnp.float64)
    for lk, rk in zip(lkds, rkds):
        lk = lk.astype(jnp.int64)
        rk = rk.astype(jnp.int64)
        mn = jnp.minimum(jnp.min(jnp.where(pvalid, lk, big)),
                         jnp.min(jnp.where(bvalid, rk, big)))
        mx = jnp.maximum(jnp.max(jnp.where(pvalid, lk, small)),
                         jnp.max(jnp.where(bvalid, rk, small)))
        mn = jnp.minimum(mn, mx)  # both-empty guard
        # guard span in float64 FIRST: `mx - mn + 1` wraps in int64 when a
        # key column spans more than half the int64 range, which would
        # collapse the span to 1 and silently defeat the overflow flag
        span_f = jnp.maximum(
            mx.astype(jnp.float64) - mn.astype(jnp.float64) + 1.0, 1.0)
        total = total * span_f
        span = jnp.maximum(mx - mn + 1, 1)
        pk = pk * span + jnp.where(pvalid, lk - mn, 0)
        bk = bk * span + jnp.where(bvalid, rk - mn, 0)
    return pk, pvalid, bk, bvalid, total > jnp.asarray(2.0**62)


def _pack_probe(kds, knulls, pvalid, packs):
    """Probe-side key folding with the BUILD index's static (min, span)
    per column. Rows whose key falls outside the build range (or is NULL)
    can't match; they're excluded via `ok` and clamped so the packing
    arithmetic never overflows."""
    ok = pvalid
    key = jnp.zeros(pvalid.shape, dtype=jnp.int64)
    for d, nl, (mn, span) in zip(kds, knulls, packs):
        v = d.astype(jnp.int64) - mn
        ok = ok & ~nl & (v >= 0) & (v < span)
        key = key * span + jnp.clip(v, 0, span - 1)
    return key, ok


def _bucket_search(a0, probe, blo, bhi, steps, right=False):
    """`jnp.searchsorted(a0, probe, side)` for rows whose answer is known
    to lie in [blo, bhi] with bhi - blo < 2**steps: `steps` unrolled
    bisection steps, one gather of `a0` each.  Returns (pos, eq); eq says
    that a0[pos] == probe INSIDE the interval, read from the value of the
    step that last drew the upper end in (position bhi is the next
    bucket's, and is never compared)."""
    lo, hi = blo, bhi
    eq = jnp.zeros(probe.shape, dtype=bool)
    for _ in range(steps):
        mid = (lo + hi) >> 1
        # lo <= mid < hi while the interval is open; a closed one reads
        # an entry it ignores (past the array only when every row is live)
        v = a0[jnp.minimum(mid, a0.shape[0] - 1)]
        up = (lo < hi) & ((v <= probe) if right else (v < probe))
        down = (lo < hi) & ~up
        eq = jnp.where(down, v == probe, eq)
        lo = jnp.where(up, mid + 1, lo)
        hi = jnp.where(down, mid, hi)
    return lo, eq


#: compaction's floor: a relation shorter than this keeps its length
_COMPACT_MIN_ROWS = 1 << 16
#: ... and one whose live rows would fill more than a quarter of it
_COMPACT_FACTOR = 4


def compact_to(live, n) -> "int | None":
    """The static length a probe-path relation of `n` rows whose last run
    held `live` live rows is cut to at a compaction point
    (compaction_points), or None where it keeps its `n`.  Host-callable:
    _fill_caps asks it with the learned count and the relation's static
    length, and the dispatcher counts the kept program's cuts by what it
    answered (device_exec.note_join_compactions).

    A cut is one unstable single-operand sort of the rows' positions at
    length `n` (5.7 ms at 8,388,608 rows on the v5e, ops/device
    _group_spans' one-pass side) and one gather a surviving row map or
    null map at the cut's length; every later lookup, column gather,
    existence test and the aggregate then run at that length, where each
    was a gather of 60-72 ms at `n`.  So a cut is taken from
    _COMPACT_MIN_ROWS rows up wherever next_pow2(live) is at most
    1 / _COMPACT_FACTOR of `n`: TPC-H Q3's 151,000 rows after `orders`
    in the 8,388,608-row bucket go to 262,144, Q4's 57,000 orders to
    65,536 of 2,097,152; Q13's 150,000 customers of 262,144 stay."""
    n = int(n)
    if live is None or n < _COMPACT_MIN_ROWS:
        return None
    cap = dev.next_pow2(max(int(live), 1))
    return cap if cap * _COMPACT_FACTOR <= n else None


def _probe_child(node):
    """The child whose rows a join's output is indexed by: its probe, and
    never a host-indexed build (a build leaf's index addresses its own
    rows, so its relation must keep them)."""
    st = node.strategy
    return node.right if st is not None and st[1] == "left" else node.left


def compaction_points(root) -> dict:
    """{id(node): pos} of the relations a fragment may compact, in the
    order the program evaluates them: the probe leaf after its filter
    (pos -1), then every probe-shaped step on the probe path up to the
    root (pos = the join's): an inner or left join on the `uniq` arm, a
    semi or anti join (a residual existence test among them).  An
    expansion, a build side and a subtree beside the probe path are
    never one."""
    path = [root]
    while isinstance(path[-1], _JoinNode):
        path.append(_probe_child(path[-1]))
    points = {id(path[-1]): -1}
    for jn in reversed(path[:-1]):
        st = jn.strategy
        if st is not None and (st[0] == "uniq"
                               or jn.kind in ("semi", "anti")):
            points[id(jn)] = jn.pos
    return points


def compile_fragment(root, leaves, joins, agg_plan, agg_conds, caps,
                     capacity, key_pack, agg_meta, nonnull, *, strategies,
                     raw_tail=False, program=None, compact=None):
    """Build the jitted end-to-end program. caps / strategies: per-join
    static capacities and strategies aligned with `joins`, as the builder
    was made with them (the traced body never reads a node's mutable
    `.strategy`: a deferred background build can trace long after its
    execution restored or replaced it, as the hybrid join swaps a
    partition-shaped stub in and out around its run). Returns jitted
    fn(env, jidx, n_lives)
    where env is {global_col: (data, nulls)} and jidx is a per-join tuple
    of host-index device arrays (passed as arguments, not baked, so a data
    refresh with unchanged shapes reuses the compiled program).

    Its one caller is FragmentRunner.build.  The program returns (the
    aggregate, the joins' totals and the points' live counts, the
    multi-key span flags).

    program: what turns the body `run(env, jidx, n_lives)` into the
    program that is dispatched; `_timed_jit` unless given.  The mesh
    gives mpp_exec._shard_program, which runs the body on every shard
    over that shard's slice of the probe leaf, as the paged probe runs
    it over a page, and merges the partial states after it.

    n_lives: per-leaf traced live-row counts, ordered by leaf_id. Env
    arrays may be padded past them — bucket-padded resident uploads, the
    paged probe's last page — and every leaf masks its rows at
    `arange(n) < n_lives[leaf_id]`, so padding can never survive the scan
    filter, probe a join, or reach the aggregate. Traced scalars: a
    within-bucket row-count change re-dispatches without recompiling.

    The aggregate runs at the fact length unless the relation was cut
    (`compact`, below): the fragment's output is fact-shaped with a
    sparse validity mask (the price of the gather-join design).

    The gather chain emits a gather only where its result can differ from
    what the program already holds.  A leaf's row map starts as the
    identity and stays it until a join re-indexes the leaf: its columns
    and masks are read in place, and a map composed through the identity
    is the index itself.  `nonnull` (nonnull_cols: global column indices
    the host knows hold no NULL) gives a re-indexed column a constant
    mask.  The facts change the program, so they belong in the caller's
    pipeline key.  The returned fn carries `gathers`: {"emitted",
    "elided"} column / mask / row-map gathers of the traced program, one
    per distinct (source, indices) pair as XLA's CSE leaves them
    (device_exec.note_join_gathers reads it after a dispatch).

    raw_tail: stop BEFORE the in-kernel aggregate and return the evaluated
    (key_cols, key_nulls, val_cols, val_nulls, mask) row arrays instead.
    The hybrid join (hybrid_join.py) asks for it: it aggregates the rows
    of its device partitions and of its host partitions together in
    numpy on every backend; the join/filter/expression work stays fused
    in the program.

    compact: ((pos, cap or None), ...) for every compaction point
    (compaction_points, in its order), as _fill_caps decided them; the
    runner passes it where the fragment compacts (FragmentRunner.plan).
    At each point the program counts the relation's live rows, and where
    `cap` is given it cuts the relation
    to its first `cap` live rows: their positions, in ascending order,
    become one more index of every row map (rows_of composes it as an
    expansion's `pi`), the null maps are gathered through them, and the
    rows past the live count are invalid.  Everything after the point
    runs at `cap`.  A cut never drops a row unseen: the live counts
    follow the joins' totals in the returned overflows, and a count past
    its `cap` makes the caller run again.  The returned fn carries
    `compacted`: the positions of the points the trace cut."""
    for jn, cap in zip(joins, caps):
        jn.cap = cap

    # metadata-only planning view: compiling expressions must not upload
    # any column (the paged probe's columns never transfer whole)
    leaf_metas = [_leaf_meta(leaf) for leaf in leaves]
    dcols = {leaf.offset + i: dc
             for leaf, m in zip(leaves, leaf_metas) for i, dc in m.items()}
    # compile every expression up-front (host-side planning); leaf conds
    # are written against the scan's LOCAL schema → rebase to global
    leaf_cond_fns = [
        [dev.compile_expr(_shift_expr(c, leaf.offset),
                          {leaf.offset + i: dc
                           for i, dc in leaf_metas[leaf.leaf_id].items()})
         for c in leaf.conds] for leaf in leaves]
    # key/other-cond/agg expressions are compiled against global offsets
    # (reordered nodes carry globally-indexed exprs already)
    for jn in joins:
        off_l = 0 if jn.global_keys else jn.left.offset
        off_r = 0 if jn.global_keys else jn.right.offset
        off_o = 0 if jn.global_keys else jn.offset
        jn._lk_fns = [dev.compile_expr(_shift_expr(k, off_l), dcols)
                      for k in jn.left_keys]
        jn._rk_fns = [dev.compile_expr(_shift_expr(k, off_r), dcols)
                      for k in jn.right_keys]
        jn._oc_fns = [dev.compile_expr(_shift_expr(c, off_o), dcols)
                      for c in jn.other_conds]
        # what each of them reads: gather_env gathers just that
        jn._lk_cols = sorted(_expr_cols(jn.left_keys, off_l))
        jn._rk_cols = sorted(_expr_cols(jn.right_keys, off_r))
        jn._oc_cols = sorted(_expr_cols(jn.other_conds, off_o))
    cond_fns = [dev.compile_expr(c, dcols) for c in agg_conds]
    top_cols = sorted(_expr_cols(agg_conds) | _agg_cols(agg_plan))
    leaf_of = {leaf.offset + i: leaf.leaf_id
               for leaf in leaves for i in range(leaf.ncols)}
    key_fns, val_plan, agg_ops, slots = agg_meta
    gathers = {}
    points = compaction_points(root) if compact is not None else {}
    cut_at = dict(compact or ())
    compacted = []

    def run(env, jidx, n_lives):
        _count_trace()
        compacted.clear()

        # env keyed by global column index → (data, nulls) on device
        def leaf_rel(leaf):
            # row count off the leaf's first env-present column (a pruned
            # env — paged path — carries only the fragment's used columns)
            n = next(env[leaf.offset + i][0].shape[0]
                     for i in range(leaf.ncols)
                     if leaf.offset + i in env)
            with jax.named_scope("k_filter"):
                if leaf_cond_fns[leaf.leaf_id]:
                    mask = None
                    for f in leaf_cond_fns[leaf.leaf_id]:
                        d, nl = f(env)
                        m = (d != 0) & ~nl
                        mask = m if mask is None else mask & m
                    mask = jnp.broadcast_to(mask, (n,))
                    mask = mask & (jnp.arange(n) < n_lives[leaf.leaf_id])
                else:
                    mask = jnp.arange(n) < n_lives[leaf.leaf_id]
                return {leaf.leaf_id: ()}, mask

        overflows = []
        span_ovfs = []
        # the fragment's column / mask / row-map gathers, one per distinct
        # (source, indices): emitted key -> (result, indices), elided key
        # -> indices.  The indices are held so that their id() stays
        # theirs for the whole trace.
        emitted, elided = {}, {}

        def take(key, arr, idx):
            """`arr[idx]`, traced once per (key, idx): XLA folds repeats
            anyway, and the counts want the distinct ones."""
            k = key + (id(idx),)
            if k not in emitted:
                emitted[k] = (arr[idx], idx)
            return emitted[k][0]

        def rows_of(lid, chain):
            """Leaf `lid`'s row for every row of the relation.  A row map
            is the chain of indices the joins re-indexed the leaf by:
            () is the identity (None here: read in place), and one index
            IS the map (the gather of an arange it stood for is elided).
            Composed on first use, so a leaf nothing reads costs none."""
            if not chain:
                return None
            if len(chain) == 1:
                elided[("map", lid, id(chain[0]))] = chain[0]
                return chain[0]
            return take(("map", lid) + tuple(id(i) for i in chain[:-1]),
                        rows_of(lid, chain[:-1]), chain[-1])

        @jax.named_scope("k_join_probe")
        def gather_env(idxmap, cols, nullmaps):
            """env of gathered (relation-space) columns, keyed by global
            column index, for the columns `cols` that the expressions
            about to be evaluated read.
            nullmaps[leaf_id] marks rows where that leaf contributed no
            match (left-join null extension): its columns read as NULL."""
            out = {}
            for g in cols:
                lid = leaf_of[g]
                d, nl = env[g]
                idx = rows_of(lid, idxmap[lid])
                if idx is None:
                    # the leaf's own rows: nothing to gather, and padding
                    # rows keep their null=True
                    elided[(g, "d")] = elided[(g, "n")] = None
                else:
                    d = take((g, "d"), d, idx)
                    if g in nonnull:
                        # no live row holds a NULL, and a row the
                        # relation keeps valid addresses a live row
                        elided[(g, "n", id(idx))] = idx
                        nl = jnp.zeros(idx.shape, dtype=bool)
                    else:
                        nl = take((g, "n"), nl, idx)
                ext = nullmaps.get(lid)
                if ext is not None:
                    nl = nl | ext
                out[g] = (d, nl)
            return out

        @jax.named_scope("k_join_probe")
        def eval_indexed(node, lidx_map, lvalid, lnull, ridx_map, rvalid,
                         rnull):
            """Host-indexed join paths ('uniq' gather / 'expand' CSR), for
            inner / left / semi / anti kinds. Output row space:
            probe-shaped for uniq and for semi/anti (existence is a count,
            never an expansion), CSR-expanded otherwise."""
            kind, side, idx = strategies[node.pos]
            jkind = node.kind
            if side == "right":
                pidx_map, pvalid = lidx_map, lvalid
                bidx_map, bvalid = ridx_map, rvalid
                pnull, bnull = lnull, rnull
                key_fns_p, key_cols_p = node._lk_fns, node._lk_cols
            else:
                pidx_map, pvalid = ridx_map, rvalid
                bidx_map, bvalid = lidx_map, lvalid
                pnull, bnull = rnull, lnull
                key_fns_p, key_cols_p = node._rk_fns, node._rk_cols
            penv = gather_env(pidx_map, key_cols_p, pnull)
            n_probe = pvalid.shape[0]
            kds, knulls = zip(*[
                dev.broadcast_1d(*f(penv), n_probe) for f in key_fns_p])
            key, ok = _pack_probe(kds, knulls, pvalid, idx.packs)
            # nv is TRACED (a same-bucket index refresh re-dispatches
            # without retracing); every bound derived from it is traced
            a0, a1, nv = jidx[node.pos][:3]
            safe_hi = jnp.maximum(nv - 1, 0)
            if idx.slots is not None:
                # unique dense build: the slot holds the row id, or -1
                slot = a0[jnp.clip(key, 0, idx.span - 1)].astype(jnp.int64)
                cnt = jnp.where(ok & (slot >= 0), 1, 0)
            elif idx.kind == "dense":
                k_c = jnp.clip(key, 0, idx.span - 1)
                pos0 = a0[k_c].astype(jnp.int64)
                cnt = jnp.where(ok, (a0[k_c + 1] - a0[k_c]).astype(jnp.int64),
                                0)
            else:
                if idx.prefix is None:
                    lo = jnp.searchsorted(a0, key, side="left")
                    if kind == "uniq":
                        lo_c = jnp.clip(lo, 0, a0.shape[0] - 1)
                        found = a0[lo_c] == key
                    else:
                        hi = jnp.searchsorted(a0, key, side="right")
                else:
                    # the key's high bits address its bucket, whose keys
                    # differ in their low bits only: a0 holds those
                    ends = jidx[node.pos][3][key >> idx.shift]
                    bucket = ends[:, 0], ends[:, 1], idx.steps
                    low = (key & ((1 << idx.shift) - 1)).astype(a0.dtype)
                    lo, found = _bucket_search(a0, low, *bucket)
                    if kind != "uniq":
                        hi, _ = _bucket_search(a0, low, *bucket, right=True)
                pos0 = jnp.minimum(lo, nv).astype(jnp.int64)
                if kind == "uniq":
                    cnt = jnp.where(ok & (lo < nv) & found, 1, 0)
                else:
                    cnt = jnp.where(
                        ok, jnp.minimum(hi, nv) - jnp.minimum(lo, nv), 0)

            if jkind in ("semi", "anti") and kind != "uniq":
                # existence only: probe-shaped regardless of match counts
                if node._oc_fns:
                    hit, total = exists_pairs(node, cnt, pos0, a1, safe_hi,
                                              pidx_map, pnull, bidx_map,
                                              bnull, bvalid)
                else:
                    hit = cnt > 0
                valid = pvalid & (hit if jkind == "semi" else ~hit)
                # a residual's expansion reports its pairs: the capacity
                overflows.append(total if node._oc_fns else jnp.sum(valid))
                return dict(pidx_map), valid, dict(pnull)

            if kind == "uniq":
                if idx.slots is not None:
                    bi = jnp.maximum(slot, 0)
                else:
                    bi = a1[jnp.clip(pos0, 0, safe_hi)].astype(jnp.int64)
                bside = node.right if side == "right" else node.left
                # a slot table built under the leaf's whole filter (or
                # over a leaf without one) reads -1 for every row the
                # build mask removes; any other index may list rows the
                # mask does not keep
                slots_hold_mask = (
                    idx.slots is not None and isinstance(bside, _Leaf)
                    and (idx.filtered or not bside.conds))
                hit = cnt > 0 if slots_hold_mask else (cnt > 0) & bvalid[bi]
                if node._oc_fns and jkind != "inner":
                    # ON-clause residuals are part of the MATCH for outer
                    # joins, a semi / anti join's residual part of what
                    # exists — evaluate on the joined candidate row first
                    cand_idx = dict(pidx_map)
                    for lid, v in bidx_map.items():
                        cand_idx[lid] = v + (bi,)
                    cand_null = dict(pnull)
                    for lid, v in bnull.items():
                        cand_null[lid] = v[bi]
                    jenv = gather_env(cand_idx, node._oc_cols, cand_null)
                    for f in node._oc_fns:
                        d, nl = f(jenv)
                        hit = hit & (d != 0) & ~nl
                if jkind == "semi":
                    overflows.append(jnp.sum(pvalid & hit))
                    return dict(pidx_map), pvalid & hit, dict(pnull)
                if jkind == "anti":
                    overflows.append(jnp.sum(pvalid & ~hit))
                    return dict(pidx_map), pvalid & ~hit, dict(pnull)
                valid = pvalid if jkind == "left" else (pvalid & hit)
                out = dict(pidx_map)
                nulls = dict(pnull)
                for lid, v in bidx_map.items():
                    out[lid] = v + (bi,)
                for lid, v in bnull.items():
                    nulls[lid] = v[bi]
                if jkind == "left":
                    _null_extend(nulls, bidx_map, hit)
                overflows.append(jnp.sum(valid))  # ≤ cap by construction
                return out, valid, nulls

            # CSR expansion (non-unique build)
            cap = node.cap
            if jkind == "left":
                # unmatched probe rows emit exactly one null-extended row
                cnt_eff = jnp.where(pvalid, jnp.maximum(cnt, 1), 0)
            else:
                cnt_eff = cnt
            pi, within, total = _expand_rows(cnt_eff, cap)
            posn = jnp.arange(cap)
            real = within < cnt[pi]  # False on a left join's null emission
            bpos = pos0[pi] + jnp.minimum(within,
                                          jnp.maximum(cnt[pi] - 1, 0))
            bi = a1[jnp.clip(bpos, 0, safe_hi)].astype(jnp.int64)
            hit = real & bvalid[bi]
            if jkind == "left":
                valid = (posn < total) & pvalid[pi]
            else:
                valid = (posn < total) & hit & pvalid[pi]
            overflows.append(total)
            out = {k: v + (pi,) for k, v in pidx_map.items()}
            nulls = {k: v[pi] for k, v in pnull.items()}
            for lid, v in bidx_map.items():
                out[lid] = v + (bi,)
            for lid, v in bnull.items():
                nulls[lid] = v[bi]
            if jkind == "left":
                _null_extend(nulls, bidx_map, hit)
            return out, valid, nulls

        @jax.named_scope("k_join_exists")
        def exists_pairs(node, cnt, pos0, a1, safe_hi, pidx_map, pnull,
                         bidx_map, bnull, bvalid):
            """Which probe rows have a build row that passes the build
            leaf's mask and the join's residual: the CSR expansion of the
            probe's LIVE rows (a dead row's cnt is 0 and emits nothing)
            into node.cap pairs, the mask and the residual tested on each
            pair, the pairs reduced back to their probe row by a
            scatter-max.  -> (hit at probe length, the pairs' total)."""
            cap = node.cap
            pi, within, total = _expand_rows(cnt, cap)
            bi = a1[jnp.clip(pos0[pi] + within, 0, safe_hi)].astype(
                jnp.int64)
            pair = (jnp.arange(cap) < total) & bvalid[bi]
            cand_idx = {k: v + (pi,) for k, v in pidx_map.items()}
            cand_null = {k: v[pi] for k, v in pnull.items()}
            for lid, v in bidx_map.items():
                cand_idx[lid] = v + (bi,)
            for lid, v in bnull.items():
                cand_null[lid] = v[bi]
            jenv = gather_env(cand_idx, node._oc_cols, cand_null)
            for f in node._oc_fns:
                d, nl = f(jenv)
                pair = pair & (d != 0) & ~nl
            hit = jnp.zeros(cnt.shape[0], dtype=jnp.int32).at[pi].max(
                pair.astype(jnp.int32), indices_are_sorted=True)
            return hit > 0, total

        lives = {}

        @jax.named_scope("k_join_probe")
        def at_point(node, idxmap, valid, nullmaps):
            """The relation past a compaction point: counted, and cut to
            the point's capacity where the caller gave one."""
            pos = points.get(id(node))
            if pos is None:
                return idxmap, valid, nullmaps
            with jax.named_scope("k_join_compact"):
                lives[pos] = total = jnp.sum(valid)
                cap = cut_at[pos]
                if cap is None:
                    return idxmap, valid, nullmaps
                compacted.append(pos)
                n = valid.shape[0]
                pos_dt = jnp.int32 if n < (1 << 31) else jnp.int64
                # the live rows' positions, ascending, the dead ones' n
                # behind them: one single-operand sort, unstable as
                # _group_spans' (the equal entries are all n)
                keyed = jnp.where(valid, jnp.arange(n, dtype=pos_dt),
                                  jnp.asarray(n, dtype=pos_dt))
                p = jnp.minimum(jnp.sort(keyed, stable=False)[:cap], n - 1)
                return ({lid: v + (p,) for lid, v in idxmap.items()},
                        jnp.arange(cap) < total,
                        {lid: nl[p] for lid, nl in nullmaps.items()})

        def eval_node(node):
            if isinstance(node, _Leaf):
                idxmap, mask = leaf_rel(node)
                return at_point(node, idxmap, mask, {})
            # children always evaluate left-then-right so the overflow
            # list order matches the `joins` list (postorder walk)
            lidx, lvalid, lnull = eval_node(node.left)
            ridx, rvalid, rnull = eval_node(node.right)
            if strategies[node.pos] is not None:
                # a left join's residual is folded into its match already
                idxmap, valid, nullmaps = eval_indexed(
                    node, lidx, lvalid, lnull, ridx, rvalid, rnull)
            else:
                if node.kind != "inner":
                    raise DeviceUnsupported(
                        f"{node.kind} join needs an indexed build side")
                lenv = gather_env(lidx, node._lk_cols, lnull)
                renv = gather_env(ridx, node._rk_cols, rnull)
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
                pi, bi, valid, total = _join_expand(
                    bk_d, bvalid, pk_d, pvalid, node.cap)
                overflows.append(total)
                with jax.named_scope("k_join_probe"):
                    idxmap = {k: v + (pi,) for k, v in lidx.items()}
                    idxmap.update({k: v + (bi,) for k, v in ridx.items()})
                    nullmaps = {k: v[pi] for k, v in lnull.items()}
                    nullmaps.update({k: v[bi] for k, v in rnull.items()})
            if node._oc_fns and node.kind == "inner":
                jenv = gather_env(idxmap, node._oc_cols, nullmaps)
                with jax.named_scope("k_filter"):
                    for f in node._oc_fns:
                        d, nl = f(jenv)
                        valid = valid & (d != 0) & ~nl
            return at_point(node, idxmap, valid, nullmaps)

        idxmap, valid, nullmaps = eval_node(root)
        # the points' live counts ride behind the joins' totals
        overflows.extend(lives[pos] for pos, _cap in compact or ())
        fenv = gather_env(idxmap, top_cols, nullmaps)
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
        gathers.update(emitted=len(emitted), elided=len(elided))
        if raw_tail:
            raw = (tuple(key_cols), tuple(key_nulls), tuple(val_cols),
                   tuple(val_nulls), mask)
            return raw, tuple(overflows), tuple(span_ovfs)
        agg_out = dev._agg_impl(tuple(key_cols), tuple(key_nulls),
                                tuple(val_cols), tuple(val_nulls), mask,
                                n_keys=len(key_cols),
                                agg_ops=tuple(agg_ops),
                                capacity=capacity, pack=key_pack,
                                gathered=True)
        return agg_out, tuple(overflows), tuple(span_ovfs)

    fn = (program or _timed_jit)(run)
    fn.gathers = gathers  # filled by the trace: note_join_gathers
    fn.compacted = compacted
    return fn


def _shift_expr(e, offset):
    """Rebase column refs from subtree-local to global column indices."""
    if offset == 0:
        return e
    return e.transform_columns(
        lambda c: ExprColumn(c.idx + offset, c.ftype, name=c.name))


def _fill_caps(node, sig, points=None, cuts=None):
    """Bottom-up static output capacities. Unique-indexed joins inherit
    the probe side's capacity exactly. Expansion joins take (in order):
    the retry-adjusted/learned size, a stats-free estimate from the build
    index's average match count, or (device-sort fallback) the FK-join
    upper heuristic — a key-FK join emits about as many rows as its
    LARGER input, composed bottom-up over RAW leaf sizes. Estimates
    deliberately overshoot: undershoot costs a full recompile (minutes
    for a deep fragment), overshoot only pads the kernels; the learned
    store tightens the shapes from the second compile on.

    points / cuts: compaction_points(root), and a dict this fills with
    each point's cut (compact_to over the live count the store learned
    for it and the relation's length there; None = kept whole).  A node
    returns the length its relation has after its cut: what the node
    above it, an expansion's probe and the aggregate read."""
    if isinstance(node, _Leaf):
        # BUCKET space, not the live row count: probe-shaped capacities
        # flow into the compiled program's static shapes and the pipeline
        # cache key, and must stay stable across within-bucket deltas
        return _cut(node, node.bucket or node.chunk.num_rows, sig, points,
                    cuts)

    lc = _fill_caps(node.left, sig, points, cuts)
    rc = _fill_caps(node.right, sig, points, cuts)
    st = node.strategy
    exists = exists_expands(node)
    if not exists and (node.kind in ("semi", "anti") or (
            st is not None and st[0] == "uniq")):
        # probe-shaped: semi/anti are existence counts; uniq is a gather
        node.cap = lc if (node.kind != "inner"
                          or st[1] == "right") else rc
        return _cut(node, node.cap, sig, points, cuts)
    # the in-program expansion probes with its left side
    node.probe_cap = rc if st is not None and st[1] != "right" else lc
    if node.exp_cap is None:
        total = learned(sig, node.pos)
        if total is not None:
            node.exp_cap = dev.next_pow2(max(total, 8))
        elif st is not None:
            est = int(node.probe_cap * st[2].avg_cnt * 1.5)
            if node.kind == "left":
                est += node.probe_cap  # every unmatched probe row still emits
            elif exists:
                # the probe of an existence test is what its WHERE keeps,
                # a few rows of its bucket: the bucket is room enough to
                # start from, and an overflow reports the exact total
                est = min(est, node.probe_cap)
            node.exp_cap = dev.next_pow2(max(est, 1024))
        else:
            def fk_est(nd):
                if isinstance(nd, _Leaf):
                    return max(nd.chunk.num_rows, 8)
                return max(fk_est(nd.left), fk_est(nd.right))
            node.exp_cap = dev.next_pow2(fk_est(node))
    node.cap = node.exp_cap
    # an existence test's pairs reduce back to its probe's rows
    return _cut(node, lc, sig, points, cuts) if exists else node.cap


def _cut(node, n, sig, points, cuts):
    """_fill_caps' length of `node`'s relation past its compaction point,
    where it is one; `n` before it."""
    pos = (points or {}).get(id(node))
    if pos is None:
        return n
    cuts[pos] = compact_to(learned(sig, ("live", pos)), n)
    return cuts[pos] or n


class FragmentRunner:
    """The turn around compile_fragment, for every path that runs a join
    fragment's program: the whole input (device_join_agg), the probe page
    by page (_paged_join_agg), the mesh's indexed path (mpp_exec, a shard
    a page) and the hybrid join (its raw-tail program).  A turn plans the
    fragment's capacities, gets its program, runs it, learns from the
    counts that come back and decides whether to run again; the callers
    keep how a turn is dispatched and fetched, and what only their loop
    learns.  Construction plans the aggregate (`_plan_agg`'s six parts
    are attributes)."""

    def __init__(self, root, leaves, joins, agg_plan, agg_conds, dcols):
        self.root, self.leaves, self.joins = root, leaves, joins
        self.agg_plan, self.agg_conds = agg_plan, agg_conds
        (self.key_fns, self.key_meta, self.key_pack, self.val_plan,
         self.agg_ops, self.slots) = _plan_agg(agg_plan, dcols)
        self.dict_refs = tuple(dc.dictionary for dc in dcols.values()
                               if dc.dictionary is not None)
        self.sig = self.used = self.points = None
        self._gathers_noted = False

    def plan(self, sig, used, compacts=True):
        """Every join's static capacity (_fill_caps) and, where the
        fragment `compacts`, every compaction point's cut, from what the
        store learned for `sig`.  `used`: the fragment's used columns."""
        self.sig, self.used, self.cuts = sig, used, {}
        self.points = compaction_points(self.root) if compacts else None
        self.n_frag = _fill_caps(self.root, sig, self.points, self.cuts)

    @property
    def compact(self) -> tuple:
        """((pos, cut or None), ...) of the points, in their order."""
        return tuple((pos, self.cuts[pos])
                     for pos in (self.points or {}).values())

    def start_capacity(self, ctx, rows=None):
        """The aggregate's first capacity: from the group count the store
        learned, else estimated over the fragment's length, or over
        `rows` where fewer (a page's probe is at most its table)."""
        ng = learned(self.sig, "agg")
        if ng is not None:
            return dev.next_pow2(max(ng, 16))
        n = self.n_frag if rows is None else min(rows, self.n_frag)
        est = _estimate_groups(self.agg_plan, n, ctx)
        return dev.next_pow2(min(self.n_frag, max(est, 16)))

    def build(self, capacity, nonnull, **kw):
        """The builder acquire_pipeline calls on a miss: the program at the
        joins' capacities and strategies as they are now, and `capacity`;
        `kw` goes to compile_fragment (the mesh's `program`, the hybrid
        join's `raw_tail`)."""
        caps = [jn.cap for jn in self.joins]
        strategies = tuple(jn.strategy for jn in self.joins)
        agg_meta = (self.key_fns, self.val_plan, self.agg_ops, self.slots)

        def build():
            # the leaves/joins/plan objects are OWNED by this execution;
            # when the compile service defers this builder to a worker the
            # query has already degraded to host, so nothing mutates them
            return compile_fragment(self.root, self.leaves, self.joins,
                                    self.agg_plan, self.agg_conds, caps,
                                    capacity, self.key_pack, agg_meta,
                                    nonnull, strategies=strategies, **kw)
        return build

    def program(self, ctx, capacity, *, args=None, shape="join", lead=(),
                tag=(), **kw):
        """A turn's program, cached or compiled, under the key: signature,
        joins' capacities, `lead`, the aggregate's capacity and plan, the
        NULL-free columns, `tag` and, where the fragment compacts, the
        cuts (`lead` / `tag`: the mesh's exchange capacities, the paged
        probe's mark).  `args`: the call's arguments where one call is
        the turn."""
        compact = self.compact
        # a program that cuts gathers the leaf it would read in place
        nonnull = nonnull_cols(self.root, self.leaves, self.used, compacts=any(
            cut for _pos, cut in compact))
        key = (self.sig, tuple(jn.cap for jn in self.joins), *lead, capacity,
               self.key_pack, tuple(self.agg_ops), nonnull, *tag)
        if self.points is not None:
            key += (compact,)
            kw["compact"] = compact
        return acquire_pipeline(key, self.build(capacity, nonnull, **kw),
                                self.dict_refs, ctx=ctx, args=args,
                                shape=shape, sig=self.sig)

    def dispatched(self, fn, capacity):
        """Count a turn's program once it ran: its aggregate's side of
        _group_spans and, the fragment's first, its gathers."""
        note_agg_spans(self.key_pack, self.agg_ops, capacity, self.n_frag,
                       gathered=True)
        if not self._gathers_noted:
            self._gathers_noted = True
            note_join_gathers(fn)

    def split(self, outs):
        """(the joins' totals, the points' live counts) of the program's
        second output: the counts ride behind the totals."""
        return outs[:len(self.joins)], outs[len(self.joins):]

    def passed_cut(self, lives) -> bool:
        """Did a live count pass its point's cut?  The cut dropped rows
        (a count past it downstream is a lower bound): run the turn again
        at the size the count asks."""
        return any(cut is not None and live > cut
                   for (_pos, cut), live in zip(self.compact, lives))

    def learned_lives(self) -> list:
        """The live counts the store holds for the points (or None)."""
        return [learned(self.sig, ("live", pos))
                for pos in (self.points or {}).values()]

    def learn_lives(self, lives):
        """Store the points' live counts, which the next plan cuts by."""
        for pos, live in zip((self.points or {}).values(), lives):
            learn(self.sig, ("live", pos), live)

    def rerun(self, shape, capacity, groups, **tags):
        """Plan the next turn from the store, and count the rerun."""
        self.n_frag = _fill_caps(self.root, self.sig, self.points, self.cuts)
        note_rerun(shape, capacity, groups,
                   caps=[int(jn.cap) for jn in self.joins], **tags)

    def begin(self, resident=None):
        """Count the fragment once: its aggregate's arm, its joins'
        layouts and, on one chip, whether its probe is `resident`."""
        note_agg_arm(self.key_pack, self.agg_ops, gathered=True)
        note_join_layouts(self.joins)
        note_join_chain(self.joins)
        for leaf in self.leaves:
            if leaf.derived:
                note_join_derived(leaf.chunk.num_rows)
        if resident is not None:
            note_join_probe(resident)

    def end(self, totals=()):
        """Count what the kept turn did, once a fragment: its cuts, and
        each expansion's and residual test's rows (`totals`) and
        capacity."""
        note_join_compactions(sum(cut is not None
                                  for _pos, cut in self.compact))
        for jn, total in zip(self.joins, totals):
            if join_expands(jn):
                note_join_expansion(total, jn.cap,
                                    expand_one_pass(jn.cap, jn.probe_cap))
            elif exists_expands(jn):
                note_join_residual(total, jn.cap)


def device_join_agg(agg_plan, agg_conds, child_exec, ctx):
    """Entry: compile + run the fused join+agg fragment for a HashAgg whose
    child is a join tree over table scans. Raises DeviceUnsupported when
    out of scope (caller falls back to the host executors)."""
    from ..utils import failpoint
    # chaos/supervisor hook: a `sleep(...)` here models a backend hang at
    # the join-fragment boundary, `panic` a runtime failure
    failpoint.inject("device-join-exec")
    root, leaves, joins = collect_tree(child_exec, derived=True)
    try:
        return _join_agg(root, leaves, joins, agg_plan, agg_conds, ctx)
    finally:
        release_derived(leaves)


def _join_agg(root, leaves, joins, agg_plan, agg_conds, ctx):
    """device_join_agg past the plan walk."""
    from .device_exec import want_device
    if not want_device(ctx, max(leaf.chunk.num_rows for leaf in leaves)):
        raise DeviceUnsupported("below device threshold")
    all_inner = all(jn.kind == "inner" for jn in joins)
    reordered = (_reorder_fact_first(leaves, joins) if all_inner
                 else _reorder_below_chain(leaves, joins))
    hybrid_deferred = None
    if reordered is None and all_inner:
        # a build side too big to index whole (the paged-budget guard in
        # _leaf_key_cols) may still chain with a DEFERRED strategy — the
        # hybrid hash join partitions it at execution time
        over = _over_budget_builds(leaves, joins, agg_plan, agg_conds)
        if len(over) == 1:
            reordered = _reorder_fact_first(leaves, joins,
                                            assume_unique=over)
            if reordered is not None:
                hybrid_deferred = next(iter(over))
    if reordered is not None:
        root, joins = reordered  # inner strategies assigned (all uniq)
    if _probe_spine(root).derived:
        raise DeviceUnsupported("a derived probe (only a build may be "
                                "another operator's result)")
    derived = any(leaf.derived for leaf in leaves)
    for jn in joins:
        if jn.strategy is None:
            jn.strategy = _plan_strategy(jn)
    for jn in joins:
        if jn.kind == "inner":
            continue
        if jn.strategy is None:
            raise DeviceUnsupported(
                f"{jn.kind} join needs an indexed build side")
        if (jn.kind == "left" and jn.other_conds
                and jn.strategy[0] != "uniq"):
            # ON-residuals fold into the match only on the gather
            # path; dropping them on the CSR path would change results
            raise DeviceUnsupported(
                "left join residual conds need a unique build")

    # paged-probe dispatch: a disk-backed fact side, one that does not
    # fit the residency budget, and one too long for a program that sorts
    # run page by page
    from ..storage.paged import chunk_is_paged
    from . import device_exec
    probe = _probe_spine(root)
    any_paged = any(chunk_is_paged(leaf.chunk) for leaf in leaves)
    pageable = (isinstance(probe, _Leaf) and all(
        jn.kind == "inner" and jn.strategy is not None
        and jn.strategy[0] == "uniq"
        and jn.strategy[1] == "right" for jn in joins))
    if any_paged and not pageable:
        # the resident path would read entire memmaps into RAM + HBM; a
        # fragment shape outside the paged language goes to the host
        # executors, which stream
        raise DeviceUnsupported("paged leaf outside streamed-probe language")
    if pageable and not derived:
        # hybrid hash join: a build side larger than the residency budget
        # radix-partitions — fitting partitions stay device-resident,
        # overflow spills to host pages and probes CONCURRENTLY on a
        # supervisor worker — instead of surrendering the whole fragment
        # (a derived leaf's uploads are the statement's, never partitioned)
        hj = _maybe_hybrid(root, leaves, joins, probe, agg_plan,
                           agg_conds, ctx, deferred=hybrid_deferred)
        if hj is not None:
            return hj
    if hybrid_deferred is not None:
        # the deferred (partition-indexed) strategy exists ONLY for the
        # hybrid path; the resident/paged dispatchers would crash on its
        # None index — degrade to the host engine instead
        raise DeviceUnsupported(
            "over-budget build side outside the hybrid join language")
    # past this a leaf never enters the whole-input fragment, whose
    # aggregate sorts at the fact length
    too_long = (max(leaf.chunk.num_rows for leaf in leaves)
                > device_exec._SORTED_SCAN_MAX_ROWS)
    if pageable:
        if any_paged and not chunk_is_paged(probe.chunk):
            raise DeviceUnsupported("paged build-side leaf (resident "
                                    "uploads of a disk table are barred)")
        page_rows, resident = probe_pages(
            probe, _fragment_used_cols(leaves, joins, agg_plan, agg_conds),
            ctx)
        if page_rows:
            try:
                return _paged_join_agg(root, leaves, joins, probe, agg_plan,
                                       agg_conds, ctx, page_rows, resident)
            except DeviceUnsupported:
                if chunk_is_paged(probe.chunk) or too_long:
                    # whole-table upload of a disk-resident fact is not a
                    # fallback, and a program that sorts a fact this long
                    # costs the compiler the host's memory — let the host
                    # path stream it instead
                    raise
    if too_long:
        raise DeviceUnsupported(
            "a leaf past the longest input a sorting program takes whole, "
            "outside the paged-probe language")
    # canonical row buckets per leaf: uploads pad to the bucket, the
    # program masks each leaf at its traced live count — a delta append
    # that stays inside the bucket reuses the compiled fragment
    per_double = dev.shape_buckets(ctx)
    buckets = {}
    for leaf in leaves:
        leaf.bucket = buckets[leaf.leaf_id] = dev.bucket_rows(
            leaf.chunk.num_rows, per_double)
    from ..session import tracing
    from .device_exec import _upload_mark, _upload_tags
    with tracing.span("upload.h2d") as usp:
        up0 = _upload_mark(usp)
        # env: every base column once, device-resident (bucket-padded)
        dcols = _global_dcols(leaves, buckets=buckets)
        env = {}
        for leaf in leaves:
            for i, dc in _leaf_env(leaf, buckets[leaf.leaf_id]).items():
                env[leaf.offset + i] = (dc.data, dc.nulls)
        jidx = tuple(jn.strategy[2].device_arrays()
                     if jn.strategy is not None else () for jn in joins)
        _upload_tags(usp, up0, len(env))
    run = FragmentRunner(root, leaves, joins, agg_plan, agg_conds, dcols)
    n_lives = tuple(np.int64(leaf.chunk.num_rows) for leaf in leaves)
    sig = fragment_sig(leaves, joins, agg_conds, agg_plan)
    run.plan(sig, _fragment_used_cols(leaves, joins, agg_plan, agg_conds))
    capacity = run.start_capacity(ctx)
    run.begin(resident=True)
    from .device_exec import AggFetch, resolve_topn
    for _attempt in range(12):
        fn = run.program(ctx, capacity, args=(env, jidx, n_lives))
        agg_out, ovf_d, sovf_d = fn(env, jidx, n_lives)
        run.dispatched(fn, capacity)
        f = AggFetch(agg_out, extras=(ovf_d, sovf_d),
                     topn=resolve_topn(agg_plan, run.slots))
        outs, span_ovfs = f.extras
        overflows, lives = run.split(outs)
        lives = [int(v) for v in lives]
        ng = f.ng
        if any(bool(s) for s in span_ovfs):
            raise DeviceUnsupported(
                "multi-key join value ranges exceed int64 packing")
        retry = run.passed_cut(lives)
        run.learn_lives(lives)
        for jn, total in zip(joins, overflows):
            if not exists_expands(jn) and (
                    jn.kind in ("semi", "anti") or (
                        jn.strategy is not None
                        and jn.strategy[0] == "uniq")):
                continue  # probe-shaped: total ≤ probe cap by construction
            total = int(total)
            tight = dev.next_pow2(max(total, 8))
            if total > jn.cap:
                # jump straight to the required size (totals downstream of
                # an overflowed join are lower bounds — the next pass
                # corrects them, so convergence is O(join depth), not
                # O(log(need)) recompiles)
                jn.exp_cap = tight
                retry = True
            elif jn.cap > 4 * tight and jn.cap > 8192:
                # shrink-to-fit: a fat discovery capacity pads every
                # downstream operator on every future execution; one more
                # compile now buys tight steady-state shapes forever
                jn.exp_cap = tight
                retry = True
            learn(sig, jn.pos, total)
        tight_ng = dev.next_pow2(max(ng, 16))
        if ng > capacity:
            capacity = tight_ng
            retry = True
        elif capacity > 4 * tight_ng and capacity > 8192:
            capacity = tight_ng
            retry = True
        learn(sig, "agg", ng)
        if retry:
            run.rerun("join", capacity, ng,
                      totals=[int(o) for o in overflows], lives=lives)
            continue
        break
    else:
        raise DeviceUnsupported("join fragment capacities did not converge")
    run.end(overflows)
    if ng == 0 and not agg_plan.group_exprs:
        raise DeviceUnsupported("empty global aggregate")
    body = f.body()
    return _assemble_agg(agg_plan, run.key_meta, run.slots, dcols, body,
                         f.out_rows)


#: rows of one page of a probe that runs page by page (the session's
#: ``tidb_device_stream_rows`` when set, for pages the statement sends):
#: a constant well under `device_exec._SORTED_SCAN_MAX_ROWS`, since every
#: page runs the fragment's program, which sorts at the page's length
_PROBE_PAGE_ROWS = 1 << 22


def probe_pages(probe, used, ctx) -> "tuple[int, bool]":
    """How a pageable join fragment reads its probe leaf: (0, True) =
    whole, from columns resident in HBM; (page_rows, True) = page by page
    as slices of those resident columns; (page_rows, False) = pages cut
    from the host's columns and sent by this statement.  Sent when the
    leaf is paged on disk or its `used` columns at their row bucket do
    not fit the tenant's share of the residency budget (the rule scans
    follow, `device_exec.resident_block_rows`); in pages when it is past
    the longest input a program that sorts takes whole."""
    from ..storage.paged import chunk_is_paged
    from . import device_exec
    try:
        user_rows = int(ctx.get_sysvar("tidb_device_stream_rows"))
    except Exception:
        user_rows = 0
    if chunk_is_paged(probe.chunk):
        return (user_rows if user_rows > 0 else _PROBE_PAGE_ROWS), False
    cols = [probe.chunk.columns[i] for i in range(probe.ncols)
            if probe.offset + i in used]
    block = device_exec.resident_block_rows(cols, probe.chunk.num_rows, ctx)
    if block:
        return (min(user_rows, block) if user_rows > 0 else block), False
    if probe.chunk.num_rows > device_exec._SORTED_SCAN_MAX_ROWS:
        return _PROBE_PAGE_ROWS, True
    return 0, True


def _cut_pages(arrays, rows: int, pages: int):
    """The first `pages` pages of `rows` rows of every resident probe
    array (data and masks, all at the leaf's row bucket), cut on the
    device by ONE program: a program that reads an int64 array first
    splits the whole of it into 32-bit halves, so a cut a page would
    pass over the whole column `pages` times (0.21 s a request over
    the 67,108,864-row bucket; PERF.md §6, PR 31).  Rows past an
    array's end are zeros; the fragment masks a page at its live
    count."""
    _count_trace()

    def cut(a, lo):
        page = a[lo:lo + rows]
        return jnp.pad(page, (0, rows - page.shape[0]))
    return tuple(jax.tree_util.tree_map(
        functools.partial(cut, lo=p * rows), arrays) for p in range(pages))


_resident_pages = dev.observed_jit(_cut_pages,
                                   static_argnames=("rows", "pages"))


def _probe_spine(root):
    node = root
    while isinstance(node, _JoinNode):
        node = node.left
    return node


def _col_row_bytes(c) -> int:
    """Resident bytes per row of one column: dict columns place their
    int32 codes (4B), everything else its dtype width, +1B null mask.
    THE estimate every budget gate shares (hybrid trigger, paged-build
    refusal, mesh paged gate) — one formula, or the gates disagree about
    the same leaf's footprint."""
    return (4 if c.is_object() else c.data.dtype.itemsize) + 1


def _leaf_used_bytes(leaf, used) -> int:
    """Estimated resident bytes of a leaf's fragment-used columns."""
    per_row = sum(_col_row_bytes(leaf.chunk.columns[i])
                  for i in range(leaf.ncols) if leaf.offset + i in used)
    return per_row * leaf.chunk.num_rows


def _over_budget_builds(leaves, joins, agg_plan, agg_conds,
                        exclude_id=None) -> set:
    """Leaf ids whose fragment-used resident estimate exceeds the
    effective budget — candidates for the hybrid join's partitioned
    build.  `exclude_id` names the probe (never a build): the REAL probe
    leaf when the chain shape is known, else the largest-leaf guess.
    ONE implementation for both the deferred-reorder trigger and the
    execution-time trigger, or the two would drift."""
    budget = _dim_resident_budget()
    if budget <= 0 or len(leaves) < 2:
        return set()
    used = _fragment_used_cols(leaves, joins, agg_plan, agg_conds)
    if exclude_id is None:
        exclude_id = max(leaves, key=lambda lf: lf.chunk.num_rows).leaf_id
    return {leaf.leaf_id for leaf in leaves
            if leaf.leaf_id != exclude_id
            and _leaf_used_bytes(leaf, used) > budget}


def _maybe_hybrid(root, leaves, joins, probe, agg_plan, agg_conds, ctx,
                  deferred=None):
    """Route an over-budget build side to the hybrid hash join
    (executor/hybrid_join.py).  Returns the result Chunk, or None when
    the fragment has no over-budget build (the resident/paged paths
    proceed) or the hybrid language rejects it (fallthrough — unless the
    strategy was DEFERRED, where only the hybrid path can run it and the
    caller must degrade)."""
    big_id = deferred
    if big_id is None:
        over = _over_budget_builds(leaves, joins, agg_plan, agg_conds,
                                   exclude_id=probe.leaf_id)
        if len(over) != 1:
            return None  # nothing over budget (or >1: out of language)
        big_id = next(iter(over))
    from .hybrid_join import hybrid_join_agg
    try:
        return hybrid_join_agg(root, leaves, joins, probe, big_id,
                               agg_plan, agg_conds, ctx)
    except DeviceUnsupported:
        if deferred is not None:
            raise
        return None


#: a paged BUILD-side table may be deliberately materialized into HBM up
#: to this many bytes (needed columns only): SF100 orders as a Q3 build
#: side is ~5GB of used columns — resident is the right call on a 16GB
#: chip, but an unbounded upload would defeat the paged memory bound.
#: This constant is only the fallback when NO budget is configured —
#: see _dim_resident_budget().
_DIM_RESIDENT_BUDGET_DEFAULT = 6 << 30


def _dim_resident_budget() -> int:
    """The effective resident-build threshold in bytes: the residency
    ledger's live per-tenant share (so the paged-build refusal — and the
    hybrid-join trigger — track ``tidb_device_mem_budget`` instead of a
    hard-coded constant), falling back to the historical 6GB default
    when no budget is configured (CPU backend with auto budget)."""
    from ..ops import residency
    share = residency.group_share() or residency.effective_budget()
    return share if share > 0 else _DIM_RESIDENT_BUDGET_DEFAULT


def _inplace_leaf(root):
    """The leaf whose rows the fragment's output still addresses by
    position: the probe end of a chain of probe-shaped joins (unique
    builds, semi / anti), whose row map no join re-indexes.  None once
    an expansion stands in the way."""
    node = root
    while isinstance(node, _JoinNode):
        st = node.strategy
        if st is None or (st[0] != "uniq"
                          and node.kind not in ("semi", "anti")):
            return None
        node = _probe_child(node)
    return node


def nonnull_cols(root, leaves, used, compacts=False) -> tuple:
    """Global indices (sorted) of the fragment's `used` columns that the
    host knows hold no NULL and whose mask the program would otherwise
    gather: compile_fragment's `nonnull`, and an element of the pipeline
    key of everyone who calls it (a column's first NULL must find a new
    program).  The leaf read in place needs no fact unless the caller
    `compacts` its probe path (a cut gathers that leaf too), and a paged
    (memmap) column is never scanned for one."""
    from ..storage.paged import is_paged
    inplace = None if compacts else _inplace_leaf(root)
    return tuple(sorted(
        leaf.offset + i for leaf in leaves if leaf is not inplace
        for i, c in enumerate(leaf.chunk.columns)
        if leaf.offset + i in used and not is_paged(c)
        and not c.has_nulls()))


def _expr_cols(exprs, offset=0) -> set:
    """Column indices the expressions read, shifted by `offset`."""
    used = set()
    for e in exprs:
        e.columns_used(used)
    return {offset + i for i in used}


def _agg_cols(agg_plan) -> set:
    """Global column indices the aggregate's keys and inputs read."""
    return _expr_cols(agg_plan.group_exprs) | _expr_cols(
        a for d in agg_plan.aggs for a in d.args)


def _fragment_used_cols(leaves, joins, agg_plan, agg_conds):
    """Global column indices the fragment actually reads — per-page probe
    transfers and dim uploads carry only these (a 16-wide fact scanned
    for 4 columns must not pay 4x the transfer bytes)."""
    used = _expr_cols(agg_conds) | _agg_cols(agg_plan)
    for leaf in leaves:
        used |= _expr_cols(leaf.conds, leaf.offset)
    for jn in joins:
        off_l = 0 if jn.global_keys else jn.left.offset
        off_r = 0 if jn.global_keys else jn.right.offset
        off_o = 0 if jn.global_keys else jn.offset
        used |= _expr_cols(jn.left_keys, off_l)
        used |= _expr_cols(jn.right_keys, off_r)
        used |= _expr_cols(jn.other_conds, off_o)
    return used


class _PagedStats(threading.local):
    """Facts of the thread's most recent paged fragment run — EXPLAIN
    ANALYZE surfaces them on the HashAgg line (reference: executor
    runtime stats, util/execdetails): `pages` of the last pass,
    the partial `capacity` it ran at, the `groups` it merged to (the
    hybrid join adds its ``hj_*`` keys).  Where the seconds go is the
    spans' to say (``upload.h2d`` / ``fetch.d2h`` / ``host.assemble``,
    ``TRACE <stmt>``).  Thread-local: concurrent sessions each annotate
    their own run, never a neighbor's."""

    def __init__(self):
        self.stats = {}

    def clear(self):
        self.stats.clear()

    def update(self, kv):
        self.stats.update(kv)

    def __bool__(self):
        return bool(self.stats)

    def items(self):
        return self.stats.items()


LAST_PAGED_STATS = _PagedStats()


def _paged_join_agg(root, leaves, joins, probe, agg_plan, agg_conds, ctx,
                    page_rows, resident=False):
    """Paged-probe execution of an all-unique-build join chain: the
    fact leaf is cut into `page_rows` pages; each page runs the SAME
    compiled scan→gather-joins→partial-agg program (dimension tables and
    their join indexes stay HBM-resident across pages); per-page partial
    states buffer on device and fold into one running merged state via
    the mergeable-agg kernel.

    Where a page comes from is `probe_pages`' choice.  `resident`: the
    leaf's used columns are placed through the residency ledger once, at
    the leaf's row bucket (entries like any scan's: counted in
    ``device_residency.upload_bytes``, evictable), and a page is a slice
    of them cut on the device; a second execution sends nothing but the
    join indexes it rebuilt.  Else a page is cut from the host's columns
    and sent by this statement (`_stream_block`: under ``upload.h2d``,
    counted in ``device_pipelines.stream_upload_bytes``), and device
    memory is bounded by page + buffered partials + merge state — never
    the fact table. This is the engine's cop-paging analog (reference
    kv/kv.go:349-350: the coprocessor streams a large scan in pages; here
    each page carries the whole join+agg fragment with it).

    The program cuts its probe path as the whole-input fragment's does
    (compaction_points, compact_to), at the page's static length: where
    the largest live count any page of the last kept turn had at a point
    leaves few rows, every page is cut there.  Each page's live counts
    come back with the pages' group counts, in the same fetch; a page
    whose count passes its cut restarts the turn from the first page at
    the new size, as a group count past the capacity does."""
    if any(jn.strategy is None or jn.strategy[0] != "uniq" for jn in joins):
        raise DeviceUnsupported("paged probe requires all-unique builds")
    # planning view is metadata-only for EVERY leaf: the only uploads are
    # the pruned env_dim ones below, AFTER the resident-budget check
    dcols = _global_dcols(leaves, meta_leaf_ids=frozenset(
        leaf.leaf_id for leaf in leaves))
    run = FragmentRunner(root, leaves, joins, agg_plan, agg_conds, dcols)
    from .device_exec import (
        _MERGE_BUDGET_ROWS, _MERGE_OPS, AggFetch, resolve_topn)
    if any(op not in _MERGE_OPS for op in run.agg_ops):
        raise DeviceUnsupported("non-mergeable agg in paged fragment")
    merge_ops = tuple(_MERGE_OPS[op] for op in run.agg_ops)

    used = _fragment_used_cols(leaves, joins, agg_plan, agg_conds)
    # leaf_rel reads each leaf's row count off its first env entry — keep
    # at least one column per leaf alive
    for leaf in leaves:
        if not any(leaf.offset + i in used for i in range(leaf.ncols)):
            used.add(leaf.offset)
    from ..session import tracing
    from ..storage.paged import chunk_is_paged
    from .device_exec import _fetch, _stream_block, _upload_mark, _upload_tags
    per_double = dev.shape_buckets(ctx)
    probe_used = [(probe.offset + i, c)
                  for i, c in enumerate(probe.chunk.columns)
                  if probe.offset + i in used]
    with tracing.span("upload.h2d") as usp:
        up0 = _upload_mark(usp)
        env_dim = {}
        for leaf in leaves:
            if leaf.leaf_id == probe.leaf_id:
                continue
            lused = [i for i in range(leaf.ncols) if leaf.offset + i in used]
            if chunk_is_paged(leaf.chunk):
                est = 8 * leaf.chunk.num_rows * len(lused)
                if est > _dim_resident_budget():
                    raise DeviceUnsupported(
                        "paged build-side leaf exceeds resident budget")
            dim_bucket = dev.bucket_rows(leaf.chunk.num_rows, per_double)
            for i in lused:
                dc = dev.to_device_col(leaf.chunk.columns[i],
                                       bucket=dim_bucket,
                                       scoped=leaf.derived)
                env_dim[leaf.offset + i] = (dc.data, dc.nulls)
        if resident:
            # placed once, kept by the ledger: every page below, and every
            # later statement, reads these arrays
            probe_bucket = dev.bucket_rows(probe.chunk.num_rows, per_double)
            probe_dev = {}
            for gidx, c in probe_used:
                dc = dev.to_device_col(c, bucket=probe_bucket)
                probe_dev[gidx] = (dc.data, dc.nulls)
        else:
            probe_host = {gidx: dev.meta_device_col(c)[1]
                          for gidx, c in probe_used}
        jidx = tuple(jn.strategy[2].device_arrays() for jn in joins)
        _upload_tags(usp, up0, len(env_dim) + (len(probe_used)
                                                if resident else 0))
    sig = fragment_sig(leaves, joins, agg_conds, agg_plan) + f"|pg{page_rows}"
    n = probe.chunk.num_rows
    n_keys = max(len(run.key_fns), 1)
    nvals = len(run.val_plan)
    # every page runs one program at the page's static length, and its
    # probe path is cut where the live counts the pages of the last kept
    # turn learned (the largest of any page) leave few rows
    probe.bucket = page_rows
    run.plan(sig, used)
    capacity = run.start_capacity(ctx, rows=n)
    merge_cap = dev.next_pow2(max(learned(sig, "groups") or capacity, 16))

    base_lives = [np.int64(leaf.chunk.num_rows) for leaf in leaves]

    def page_lives(hi, lo):
        lives = list(base_lives)
        lives[probe.leaf_id] = np.int64(hi - lo)
        return tuple(lives)

    from .device_exec import merge_partial_states

    def merge_flush(state, buffered, merge_cap):
        return merge_partial_states(state, buffered, merge_cap, n_keys,
                                    nvals, merge_ops, run.key_pack)

    run.begin(resident)
    for _attempt in range(12):
        # per-page env is assembled inside the loop below, so there is no
        # whole-call arg spec to record: the paged fragment compiles sync
        # (still breaker-guarded + persisted through the compile service)
        fn = run.program(ctx, capacity, tag=("paged",))
        k_flush = max(1, _MERGE_BUDGET_ROWS // capacity)
        state = None
        buffered = []
        # each page's live counts at the points, beside its partial state
        buffered_lives = []
        max_ng = 0
        max_lives = [0] * len(run.compact)
        overflow = False
        pages = 0
        if resident:
            # popped as dispatched: a page lives until its program has
            # read it
            cut_pages = collections.deque(_resident_pages(
                probe_dev, rows=page_rows, pages=-(-n // page_rows)))
        for lo in range(0, n, page_rows):
            hi = min(lo + page_rows, n)
            env = {**env_dim, **(
                cut_pages.popleft() if resident
                else _stream_block(probe_host, lo, hi, page_rows))}
            agg_out, ovf, _sovf = fn(env, jidx, page_lives(hi, lo))
            if lo == 0:
                run.dispatched(fn, capacity)
            pages += 1
            buffered.append(agg_out)
            buffered_lives.append(run.split(ovf)[1])
            if len(buffered) < k_flush and hi < n:
                continue
            # the group counts and the live counts in one round trip
            ngs, lives = _fetch(
                lambda: ([p[4] for p in buffered], buffered_lives))
            max_ng = max(max_ng, *(int(g) for g in ngs))
            for page in lives:
                max_lives = [max(m, int(v)) for m, v in zip(max_lives, page)]
            overflow = max_ng > capacity or run.passed_cut(max_lives)
            if overflow:
                break
            state, merge_cap = merge_flush(state, buffered, merge_cap)
            buffered, buffered_lives = [], []
        if overflow:
            # a page's group count exceeded the partial capacity, or its
            # live rows a cut: restart the pass from the first page at the
            # observed sizes (remembered, so the discovery restart happens
            # once per fragment ever); the pages not run keep what the
            # last kept turn learned
            run.learn_lives([max(live, old or 0) for live, old
                             in zip(max_lives, run.learned_lives())])
            if max_ng > capacity:
                capacity = dev.next_pow2(max_ng)
                learn(sig, "agg", max_ng)
            run.rerun("join.paged", capacity, max_ng, pages=pages,
                      lives=max_lives)
            continue
        # the largest live count of any page: the cut every page's
        # program can take on the next execution
        run.learn_lives(max_lives)
        learn(sig, "agg", max(max_ng, 1))
        break
    else:
        raise DeviceUnsupported("paged fragment capacity did not converge")
    run.end()
    if state is None:
        raise DeviceUnsupported("empty paged fragment input")
    f = AggFetch(state, topn=resolve_topn(agg_plan, run.slots))
    ng = f.ng
    learn(sig, "groups", ng)
    if ng == 0 and not agg_plan.group_exprs:
        raise DeviceUnsupported("empty global aggregate")
    body = f.body()
    out = _assemble_agg(agg_plan, run.key_meta, run.slots, dcols, body,
                        f.out_rows)
    LAST_PAGED_STATS.clear()
    LAST_PAGED_STATS.update({"pages": pages, "capacity": capacity,
                             "groups": ng})
    return out


def fragment_sig(leaves, joins, agg_conds, agg_plan):
    parts = []
    for leaf in leaves:
        parts.append(f"L{leaf.leaf_id}@{leaf.offset}x{leaf.ncols}:"
                     + ";".join(_expr_sig(c) for c in leaf.conds))
        for c in leaf.chunk.columns:
            if c.is_object():
                # CONTENT signature, not id(): a delta append re-encodes
                # the same value set into a new dictionary object, and the
                # compiled fragment (whose LUTs bake the content) must
                # still hit
                parts.append(c.dict_sig())
    for jn in joins:
        keys = ",".join(f"{_expr_sig(lk)}={_expr_sig(rk)}"
                        for lk, rk in zip(jn.left_keys, jn.right_keys))
        parts.append(f"J{jn.offset}/{jn.kind}:{keys}|"
                     + ";".join(_expr_sig(c) for c in jn.other_conds))
        parts.append(_strategy_sig(jn))
    parts.append("|c|" + ";".join(_expr_sig(c) for c in agg_conds))
    parts.append("|g|" + ";".join(_expr_sig(e) for e in agg_plan.group_exprs))
    parts.append("|a|" + ";".join(
        f"{d.name}:{_expr_sig(d.args[0]) if d.args else ''}"
        for d in agg_plan.aggs))
    return "\n".join(parts)
