"""Logical optimization rules (reference: planner/core/optimizer.go:73-91 —
the rule list; here: predicate pushdown, equi-join extraction + greedy join
reorder, column pruning; constant folding happens at expression build time)."""

from __future__ import annotations

from ..expression import Column, Schema
from ..expression.aggregation import AggFuncDesc
from ..expression.core import ScalarFunc
from .logical import (
    Aggregation, DataSource, Dual, Join, Limit, LogicalPlan, MemSource,
    Projection, Selection, SetOp, Sort, TopN, Window, explain_tree,
)


def optimize(plan: LogicalPlan, ctx=None, trace=None) -> LogicalPlan:
    """`trace`, when a list, receives (rule name, rendered plan) per rule —
    the optimizer trace (reference: planner/core/optimizer.go:93-126
    logical-rule step tracer + util/tracing/opt_trace.go), surfaced by
    TRACE FORMAT='opt' SELECT ..."""
    from .access import choose_access_paths
    from .physical import choose_join_algos

    def step(rule, p):
        if trace is not None:
            trace.append((rule, "\n".join(
                f"{name} | {info}" for name, info in explain_tree(p))))

    hints = collect_sql_hints(plan)
    step("initial", plan)
    plan = push_down_predicates(plan, [])
    step("predicate_push_down", plan)
    plan = eliminate_outer_joins(plan)
    step("outer_join_elimination", plan)
    plan = eliminate_aggregation(plan, ctx)
    step("aggregation_elimination", plan)
    plan = eliminate_max_min(plan)
    step("max_min_elimination", plan)
    plan = reorder_joins(plan, ctx)
    step("join_reorder", plan)
    plan = prune_group_keys(plan, ctx)
    step("group_key_pruning", plan)
    plan = prune_columns(plan)
    step("column_pruning", plan)
    plan = pull_proj_through_semi(plan)
    step("semi_join_projection_pull", plan)
    plan = prune_partitions_rule(plan)
    step("partition_pruning", plan)
    plan = choose_access_paths(plan, ctx)
    step("access_path_selection", plan)
    plan = choose_join_algos(plan, ctx, hints=hints)
    step("physical_join_selection", plan)
    plan = push_topn_into_agg(plan)
    step("topn_push_down", plan)
    if hints:
        apply_agg_hints(plan, hints)
        eng = engine_from_hints(hints)
        if eng:
            plan.engine_hint = eng
        step("hint_application", plan)
    return plan


#: READ_FROM_STORAGE engine names, with reference-dialect aliases so
#: ported SQL keeps working: TiKV was the row/host engine, TiFlash the
#: columnar accelerator engine
_ENGINE_ALIAS = {"tpu": "tpu", "host": "host", "tpu-mpp": "tpu-mpp",
                 "tpu_mpp": "tpu-mpp", "mpp": "tpu-mpp",
                 "tikv": "host", "tiflash": "tpu"}


def collect_sql_hints(plan) -> list:
    """Union of /*+ ... */ hint lists attached by the builder across the
    statement's query blocks (reference: planner/optimize.go hint
    collection before rule application)."""
    out = []

    def walk(p):
        h = getattr(p, "sql_hints", None)
        if h:
            out.extend(h)
        for c in p.children:
            walk(c)
    walk(plan)
    return out


def apply_agg_hints(plan, hints):
    """HASH_AGG / STREAM_AGG: annotate every Aggregation in scope. The
    executor reads agg_hint — 'stream' pins the host (streaming/spillable)
    path, 'hash' the default hash/device path (reference:
    planner/core/exhaust_physical_plans.go agg hint enforcement)."""
    mode = None
    for name, _args in hints:
        if name == "hash_agg":
            mode = "hash"
        elif name == "stream_agg":
            mode = "stream"
    if mode is None:
        return

    def walk(p):
        if isinstance(p, Aggregation):
            p.agg_hint = mode
        for c in p.children:
            walk(c)
    walk(plan)


def engine_from_hints(hints):
    """READ_FROM_STORAGE(ENGINE[tables...]) → a statement-scoped engine
    pin ('tpu' | 'host' | 'tpu-mpp'). Table lists are accepted for
    reference-syntax compatibility; the pin applies statement-wide (the
    engine here is a per-statement execution mode, not a per-table
    replica choice)."""
    for name, args in hints:
        if name != "read_from_storage":
            continue
        for a in args:
            eng = _ENGINE_ALIAS.get(a.split("[", 1)[0].strip().lower())
            if eng:
                return eng
    return None


#: aggregate functions the single-row-group rewrite knows how to project
_ELIM_AGGS = frozenset({"sum", "avg", "max", "min", "first_row", "count"})


def eliminate_aggregation(plan: LogicalPlan, ctx=None) -> LogicalPlan:
    """Aggregation elimination (reference: rule_aggregation_elimination.go):
    when the GROUP BY keys contain a unique key of the single underlying
    table, every group holds exactly one row — the aggregate collapses to
    a projection: sum/avg/max/min/first_row(x) → cast(x), count(x) →
    x IS NOT NULL, count(const) → 1.

    (Aggregation PUSHDOWN through joins is deliberately absent: only a
    partial/final split is sound through an inner join, and this engine's
    device path already fuses the whole join+aggregate tree into one
    program — the fusion IS the pushdown, reference
    rule_aggregation_push_down.go's benefit shape.)"""
    from ..sqltypes import FieldType, TYPE_LONGLONG

    def key_cols_of(agg):
        """Bare DataSource columns among the group keys + the source, when
        the child chain is DataSource (± Selection)."""
        child = agg.children[0]
        while isinstance(child, Selection):
            child = child.children[0]
        if not isinstance(child, DataSource):
            return None, None
        cols = {e.idx for e in agg.group_exprs if isinstance(e, Column)}
        return child, cols

    def has_unique_key(ds, col_idxs):
        names = {ds.col_infos[i].name for i in col_idxs
                 if i < len(ds.col_infos)}
        return any(ks <= names for ks in _unique_keysets(ds.table_info))

    def visit(p):
        for i, c in enumerate(p.children):
            p.children[i] = visit(c)
        if not isinstance(p, Aggregation) or not p.group_exprs:
            return p
        if any(d.name not in _ELIM_AGGS for d in p.aggs):
            return p
        if getattr(p, "topn_fetch", None):
            return p
        ds, cols = key_cols_of(p)
        if ds is None or not cols or not has_unique_key(ds, cols):
            return p
        ll = FieldType(tp=TYPE_LONGLONG)
        exprs = list(p.group_exprs)
        for d in p.aggs:
            arg = d.args[0] if d.args else None
            if d.name == "count":
                from ..expression.core import Constant as _Const
                if arg is None or (isinstance(arg, _Const)
                                   and arg.value is not None):
                    exprs.append(_Const(1, ll))
                elif isinstance(arg, _Const):  # count(NULL) is 0
                    exprs.append(_Const(0, ll))
                else:
                    exprs.append(ScalarFunc(
                        "not", [ScalarFunc("isnull", [arg], ll)], ll))
            else:
                exprs.append(ScalarFunc("cast", [arg], d.ftype))
        return Projection(p.children[0], exprs, p.schema)

    return visit(plan)


def _unique_keysets(info, require_not_null=True):
    """Frozenset column-name sets each proven unique on the table: the
    int handle PK, and PUBLIC unique indexes (non-PUBLIC ones may still
    hold duplicates mid-backfill). With require_not_null (the FD /
    agg-elimination case) every index column must be NOT NULL — a
    nullable unique index admits any number of all-NULL rows, which are
    distinct groups. Join-match uniqueness (right_unique) doesn't need
    it: NULL keys never equi-match, so duplicate NULL rows can't fan
    out a join. Shared by eliminate_aggregation, eliminate_outer_joins
    and prune_group_keys so uniqueness semantics stay in one place."""
    from .. import model as _model
    out = []
    if info.pk_is_handle:
        pk = next((c.name for c in info.columns
                   if c.id == info.pk_col_id), None)
        if pk:
            out.append(frozenset([pk]))
    not_null = {c.name for c in info.columns
                if c.ftype is not None and c.ftype.not_null}
    for idx in info.indexes:
        if (idx.unique and idx.columns
                and idx.state == _model.SchemaState.PUBLIC
                and (not require_not_null
                     or all(c.name in not_null for c in idx.columns))):
            out.append(frozenset(c.name for c in idx.columns))
    return out


def _col_eq_pair(cond, colmap):
    """(base_a, base_b) when `cond` is eq(Column, Column) with both sides
    resolving to base-table columns; else None."""
    if (not isinstance(cond, ScalarFunc) or cond.op != "eq"
            or len(cond.args) != 2):
        return None
    a, b = cond.args
    if not (isinstance(a, Column) and isinstance(b, Column)):
        return None
    if a.idx >= len(colmap) or b.idx >= len(colmap):
        return None
    ba, bb = colmap[a.idx], colmap[b.idx]
    return (ba, bb) if ba is not None and bb is not None else None


def _base_col_info(node):
    """Walk `node`'s tree collecting (colmap, tables, equivs):
    colmap[i] = (id(ds), col_name) when output position i forwards a base
    column unchanged (None otherwise); tables = {id(ds): ds} for every
    DataSource whose rows survive into the output row-wise (so per-table
    FDs hold on the output); equivs = [(base, base)] pairs equal on every
    output row (INNER-join equi keys and selection col=col filters only —
    an outer join's null-extended rows break condition equalities, but not
    either side's own key→column dependencies)."""
    if isinstance(node, DataSource):
        dsid = id(node)
        return ([(dsid, ci.name) for ci in node.col_infos],
                {dsid: node}, [])
    if isinstance(node, Selection):
        colmap, tables, eq = _base_col_info(node.child)
        for c in node.conds:
            pr = _col_eq_pair(c, colmap)
            if pr:
                eq.append(pr)
        return colmap, tables, eq
    if isinstance(node, Projection):
        cm, tables, eq = _base_col_info(node.child)
        colmap = [cm[e.idx] if isinstance(e, Column) and e.idx < len(cm)
                  else None for e in node.exprs]
        return colmap, tables, eq
    if isinstance(node, Join):
        lcm, lt, leq = _base_col_info(node.left)
        if node.kind in ("semi", "anti", "leftouter_semi"):
            # right side absent from the output schema (the mark column
            # of leftouter_semi pads with None)
            pad = len(node.schema) - len(lcm)
            return lcm + [None] * max(pad, 0), lt, leq
        rcm, rt, req = _base_col_info(node.right)
        colmap = lcm + rcm
        tables = {**lt, **rt}
        eq = leq + req
        if node.kind == "inner":
            for le, re_ in zip(node.left_keys, node.right_keys):
                if (isinstance(le, Column) and le.idx < len(lcm)
                        and isinstance(re_, Column) and re_.idx < len(rcm)):
                    a, b = lcm[le.idx], rcm[re_.idx]
                    if a is not None and b is not None:
                        eq.append((a, b))
            for c in node.other_conds:
                pr = _col_eq_pair(c, colmap)
                if pr:
                    eq.append(pr)
        return colmap, tables, eq
    # Aggregation / set ops / window / …: opaque boundary
    return [None] * len(node.schema), {}, []


def _det_cols(e):
    """Column idx set of `e` when every node is a deterministic
    Column/Constant/ScalarFunc; None when any node is nondeterministic
    (rand()/uuid() — a fresh value per row that no FD determines) or of
    an unknown kind (subquery apply, outer ref)."""
    from ..expression.builder import _NONDETERMINISTIC
    from ..expression.core import Constant
    out = set()

    def walk(x):
        if isinstance(x, Column):
            out.add(x.idx)
            return True
        if isinstance(x, Constant):
            return True
        if isinstance(x, ScalarFunc):
            if x.op in _NONDETERMINISTIC:
                return False
            return all(walk(a) for a in x.args)
        return False

    return out if walk(e) else None


def _fd_closure(seed, tables, equivs, keysets):
    """Fixpoint of: equivalence propagation + (unique keyset covered →
    every column of that table is determined)."""
    det = set(seed)
    changed = True
    while changed:
        changed = False
        for a, b in equivs:
            if a in det and b not in det:
                det.add(b)
                changed = True
            if b in det and a not in det:
                det.add(a)
                changed = True
        for dsid, ds in tables.items():
            names = {n for (i, n) in det if i == dsid}
            for ks in keysets.get(dsid, ()):
                if ks <= names:
                    new = {(dsid, c.name) for c in ds.table_info.columns}
                    if not new <= det:
                        det |= new
                        changed = True
                    break
    return det


def prune_group_keys(plan: LogicalPlan, ctx=None) -> LogicalPlan:
    """Functional-dependency group-key pruning (reference: the FD engine
    planner/funcdep/fd_graph.go feeding rule_aggregation_elimination.go):
    a GROUP BY key whose value is determined by the remaining keys —
    through a base table's unique key plus the inner-join equality
    closure — cannot split any group, so it demotes to a first_row()
    aggregate and the key set shrinks.

    TPC-H Q3 groups by (l_orderkey, o_orderdate, o_shippriority): with
    o_orderkey the orders handle PK and l_orderkey ≡ o_orderkey from the
    join, both orders columns demote — the device kernel then packs ONE
    26-bit key instead of a 39-bit triple, which keeps the dense-scatter
    aggregation path in range. Q18's five keys shrink to o_orderkey alone.

    Output positions are preserved by a Projection over the rewritten
    Aggregation (kept keys first, then original aggs, then the demoted
    first_rows), so HAVING/TopN above see an identical schema; TopN's
    candidate-fetch annotation already looks through pure projections."""
    def visit(p):
        for i, c in enumerate(p.children):
            p.children[i] = visit(c)
        if not isinstance(p, Aggregation) or len(p.group_exprs) < 2:
            return p
        child = p.children[0]
        colmap, tables, equivs = _base_col_info(child)
        if not tables:
            return p
        keysets = {dsid: _unique_keysets(ds.table_info)
                   for dsid, ds in tables.items()}
        if not any(keysets.values()):
            return p

        def key_bases(e):
            """Base columns a group key needs determined to be droppable:
            [base] for a bare column, every referenced column's base for
            an expression; None when any part is untraceable — including
            nondeterministic or opaque nodes (rand() yields a fresh value
            per row, so no FD can ever determine it; subquery applies and
            outer refs are equally beyond the closure) and column-free
            expressions (conservative: folding already turned genuine
            constants into Constant nodes)."""
            if isinstance(e, Column):
                b = colmap[e.idx] if e.idx < len(colmap) else None
                return None if b is None else [b]
            idxs = _det_cols(e)
            if not idxs:
                return None
            out = []
            for i in idxs:
                b = colmap[i] if i < len(colmap) else None
                if b is None:
                    return None
                out.append(b)
            return out

        bases = [key_bases(e) for e in p.group_exprs]
        kept = list(range(len(p.group_exprs)))
        dropped = []
        for j in range(len(p.group_exprs)):
            if bases[j] is None or len(kept) <= 1:
                continue
            rest = [k for k in kept if k != j]
            # only bare-column keys seed the closure: knowing f(x)
            # does not determine x
            seed = {bases[k][0] for k in rest
                    if bases[k] and isinstance(p.group_exprs[k], Column)}
            det = _fd_closure(seed, tables, equivs, keysets)
            if all(b in det for b in bases[j]):
                kept = rest
                dropped.append(j)
        if not dropped:
            return p
        new_keys = [p.group_exprs[k] for k in kept]
        new_aggs = list(p.aggs) + [
            AggFuncDesc("first_row", [p.group_exprs[j]]) for j in dropped]
        refs = ([p.schema.refs[k] for k in kept]
                + p.schema.refs[len(p.group_exprs):]
                + [p.schema.refs[j] for j in dropped])
        new_agg = Aggregation(child, new_keys, new_aggs, Schema(refs))
        new_agg.agg_hint = p.agg_hint
        s, a = len(kept), len(p.aggs)
        pos = {}
        for np_, j in enumerate(kept):
            pos[j] = np_
        for np_, j in enumerate(dropped):
            pos[j] = s + a + np_
        exprs = []
        for old in range(len(p.schema)):
            if old < len(p.group_exprs):
                new_idx = pos[old]
            else:
                new_idx = s + (old - len(p.group_exprs))
            r = p.schema.refs[old]
            exprs.append(Column(new_idx, r.ftype, r.name))
        return Projection(new_agg, exprs, p.schema)

    return visit(plan)


def eliminate_max_min(plan: LogicalPlan) -> LogicalPlan:
    """Global MAX/MIN rewrite (reference: rule_max_min_eliminate.go): a
    group-less aggregate whose ONLY function is one MAX or MIN feeds from
    TopN(1) over the non-null arg instead of the full input. The
    Aggregation stays on top — over ≤1 row it still produces the NULL row
    for empty input — so only the scan volume changes, not semantics. The
    ordered access path (or the device TopN candidate fetch) then serves
    the single row."""
    from ..sqltypes import FieldType, TYPE_LONGLONG
    from .logical import Selection as _Sel, TopN as _TopN

    def visit(p):
        for i, c in enumerate(p.children):
            p.children[i] = visit(c)
        if (isinstance(p, Aggregation) and not p.group_exprs
                and len(p.aggs) == 1 and p.aggs[0].name in ("max", "min")
                and p.aggs[0].args
                and not isinstance(p.children[0], TopN)):
            arg = p.aggs[0].args[0]
            ll = FieldType(tp=TYPE_LONGLONG)
            notnull = ScalarFunc(
                "not", [ScalarFunc("isnull", [arg], ll)], ll)
            inner = _Sel(p.children[0], [notnull])
            p.children[0] = _TopN(
                inner, [(arg, p.aggs[0].name == "max")], 0, 1)
        return p

    return visit(plan)


def eliminate_outer_joins(plan: LogicalPlan) -> LogicalPlan:
    """Outer-join elimination (reference: rule_join_elimination.go): a
    LEFT join whose right side contributes no columns to anything above
    it, and whose right keys are unique on the right table, can't change
    the left side's rows (every left row matches at most once and
    survives regardless) — drop the join, keep the left child. Runs
    before join reorder/pruning; prune_columns rebuilds the schemas the
    removal narrows."""

    def right_unique(join):
        ds = join.right
        if not isinstance(ds, DataSource):
            return False
        names = set()
        for k in join.right_keys:
            if not isinstance(k, Column) or k.idx >= len(ds.col_infos):
                return False
            names.add(ds.col_infos[k.idx].name)
        # NULL right keys never equi-match, so nullable unique still
        # caps the match count at one — require_not_null off
        return any(ks <= names for ks in
                   _unique_keysets(ds.table_info, require_not_null=False))

    def visit(p, needed):
        if isinstance(p, Join):
            L = len(p.left.schema)
            if (p.kind == "left" and not p.other_conds
                    and all(i < L for i in needed)
                    and right_unique(p)):
                return visit(p.left, needed)
            oc = _used(p.other_conds)
            left_needed = ({i for i in needed if i < L}
                           | {u for u in oc if u < L} | _used(p.left_keys))
            right_needed = ({i - L for i in needed if i >= L}
                            | {u - L for u in oc if u >= L}
                            | _used(p.right_keys))
            p.children[0] = visit(p.left, left_needed)
            p.children[1] = visit(p.right, right_needed)
            return p
        if isinstance(p, Projection):
            child_needed = set()
            for i in needed:
                if i < len(p.exprs):
                    p.exprs[i].columns_used(child_needed)
            p.children[0] = visit(p.children[0], child_needed)
            return p
        if isinstance(p, Selection):
            child_needed = set(needed) | _used(p.conds)
            p.children[0] = visit(p.children[0], child_needed)
            return p
        if isinstance(p, (Sort, TopN)):
            child_needed = set(needed) | _used(
                [e for e, _d in p.by])
            p.children[0] = visit(p.children[0], child_needed)
            return p
        if isinstance(p, Limit):
            p.children[0] = visit(p.children[0], set(needed))
            return p
        if isinstance(p, Aggregation):
            child_needed = _used(p.group_exprs)
            for d in p.aggs:
                child_needed |= _used(d.args)
            p.children[0] = visit(p.children[0], child_needed)
            return p
        # unknown operators: conservatively require every child column
        for i, c in enumerate(p.children):
            p.children[i] = visit(c, set(range(len(c.schema))))
        return p

    def _used(exprs):
        s: set = set()
        for e in exprs:
            e.columns_used(s)
        return s

    return visit(plan, set(range(len(plan.schema))))


def push_topn_into_agg(plan: LogicalPlan) -> LogicalPlan:
    """Annotate Aggregation nodes under a TopN (looking through pure
    projections) with a candidate-fetch bound (reference: TopN pushdown,
    planner/core/rule_topn_push_down.go — here the bound tells the device
    fragment how many grouped rows the host actually needs: a grouped
    TPC-H Q3/Q18 produces millions of groups but the query keeps 10).

    The device returns an OVERSAMPLED candidate set ordered by the TopN
    keys; the host TopN above re-sorts it with its exact comparator, so
    semantics (ties, NULL order, collation) stay identical to the full
    path. Oversampling covers boundary tie-groups."""
    def visit(p):
        if isinstance(p, TopN):
            _annotate_topn_agg(p)
        for c in p.children:
            visit(c)
    visit(plan)
    return plan


def _annotate_topn_agg(topn: TopN) -> None:
    from ..expression.core import Column as ExprColumn
    node = topn.child
    mappings = []
    while isinstance(node, Projection):
        mappings.append(node.exprs)
        node = node.child
    if not isinstance(node, Aggregation) or not node.group_exprs:
        return
    specs = []
    for e, desc in topn.by:
        for exprs in mappings:
            if not isinstance(e, ExprColumn) or e.idx >= len(exprs):
                return
            e = exprs[e.idx]
        if not isinstance(e, ExprColumn) or e.idx >= len(node.schema):
            return
        if e.idx >= len(node.group_exprs):
            a = node.aggs[e.idx - len(node.group_exprs)]
            # avg/variance are derived from two slots post-fetch; their
            # order isn't available on-device — leave those unfetched.
            # first_row (incl. group keys prune_group_keys demoted) IS a
            # materialized per-group slot, so ordering by it works
            if a.name not in ("sum", "min", "max", "count", "first_row"):
                return
        specs.append((e.idx, bool(desc)))
    k = topn.offset + topn.count
    fetch = 4 * k + 64  # oversample: boundary tie-groups
    if fetch > 1 << 20:
        return  # huge LIMIT: candidate fetch wouldn't save anything, and
        #         a clamped bound would silently truncate the result
    node.topn_fetch = (tuple(specs), fetch)


def prune_partitions_rule(plan: LogicalPlan) -> LogicalPlan:
    """Partition pruning on pushed-down scan predicates (reference:
    planner/core/rule_partition_processor.go)."""
    if isinstance(plan, DataSource) and plan.table_info.partition is not None:
        from ..partition import prune_partitions
        if plan.partitions is None:
            plan.partitions = list(plan.table_info.partition.defs)
        plan.partitions = prune_partitions(plan.table_info, plan.partitions,
                                           plan.pushed_conds)
    for c in plan.children:
        prune_partitions_rule(c)
    return plan


# ---------------------------------------------------------------------------
# predicate pushdown (reference: rule_predicate_push_down.go)
# ---------------------------------------------------------------------------

def push_down_predicates(plan, conds):
    """conds: expressions over plan's output schema pushed from above.
    Returns a plan that incorporates them as low as possible."""
    if isinstance(plan, Selection):
        return push_down_predicates(plan.child, conds + plan.conds)
    if isinstance(plan, Join):
        return _ppd_join(plan, conds)
    if isinstance(plan, DataSource):
        if conds:
            plan.pushed_conds.extend(conds)
        return plan
    if isinstance(plan, Projection):
        pushable, kept = [], []
        for c in conds:
            used = set()
            c.columns_used(used)
            if all(isinstance(plan.exprs[i], Column) for i in used):
                pushable.append(c.transform_columns(
                    lambda col: plan.exprs[col.idx]))
            else:
                kept.append(c)
        plan.children[0] = push_down_predicates(plan.child, pushable)
        return _wrap(plan, kept)
    if isinstance(plan, Aggregation):
        n_group = len(plan.group_exprs)
        pushable, kept = [], []
        for c in conds:
            used = set()
            c.columns_used(used)
            if used and all(i < n_group for i in used):
                pushable.append(c.transform_columns(
                    lambda col: plan.group_exprs[col.idx]))
            else:
                kept.append(c)
        plan.children[0] = push_down_predicates(plan.child, pushable)
        return _wrap(plan, kept)
    if isinstance(plan, Sort):
        plan.children[0] = push_down_predicates(plan.child, conds)
        return plan
    # Limit/TopN/SetOp/Window/MemSource/Dual: cannot push through
    plan.children = [push_down_predicates(c, []) for c in plan.children]
    return _wrap(plan, conds)


def _ppd_join(join: Join, conds):
    nl = len(join.left.schema)
    left_conds, right_conds, kept = [], [], []
    for cond in conds:
        used = set()
        cond.columns_used(used)
        left_only = all(i < nl for i in used)
        right_only = used and all(i >= nl for i in used)
        if join.kind == "inner":
            if left_only:
                left_conds.append(cond)
            elif right_only:
                right_conds.append(_shift(cond, -nl))
            elif _is_equi(cond, nl):
                lhs, rhs = _equi_sides(cond, nl)
                join.left_keys.append(lhs)
                join.right_keys.append(rhs)
            else:
                join.other_conds.append(cond)
        elif join.kind == "left":
            if left_only:
                left_conds.append(cond)
            else:
                kept.append(cond)  # filters null-extended rows: stay above
        elif join.kind in ("semi", "anti"):
            if left_only:
                left_conds.append(cond)
            else:
                kept.append(cond)
        else:
            kept.append(cond)
    join.children[0] = push_down_predicates(join.left, left_conds)
    join.children[1] = push_down_predicates(join.right, right_conds)
    return _wrap(join, kept)


def _is_equi(cond, nl):
    if not (isinstance(cond, ScalarFunc) and cond.op == "eq"):
        return False
    lu, ru = set(), set()
    cond.args[0].columns_used(lu)
    cond.args[1].columns_used(ru)
    if not lu or not ru:
        return False
    return ((all(i < nl for i in lu) and all(i >= nl for i in ru)) or
            (all(i < nl for i in ru) and all(i >= nl for i in lu)))


def _equi_sides(cond, nl):
    lu = set()
    cond.args[0].columns_used(lu)
    if all(i < nl for i in lu):
        return cond.args[0], _shift(cond.args[1], -nl)
    return cond.args[1], _shift(cond.args[0], -nl)


def _shift(expr, delta):
    return expr.transform_columns(
        lambda c: Column(c.idx + delta, c.ftype, name=c.name))


def _wrap(plan, conds):
    return Selection(plan, conds) if conds else plan


# ---------------------------------------------------------------------------
# join reorder (reference: rule_join_reorder.go — greedy variant)
# ---------------------------------------------------------------------------

def reorder_joins(plan, ctx):
    if isinstance(plan, Join) and plan.kind == "inner":
        items, conds = [], []
        _flatten_join(plan, items, conds, 0)
        if len(items) > 2:
            # reorder inside each leaf first; the greedy result is final —
            # recursing into its spine would flatten and reorder forever
            items = [(off, reorder_joins(p, ctx)) for off, p in items]
            new = _greedy_join(items, conds, ctx)
            if new is not None:
                return new
    plan.children = [reorder_joins(c, ctx) for c in plan.children]
    return plan


def _flatten_join(plan, items, conds, offset):
    """Collect inner-join leaves and all conds in *global* column indices.
    Returns width of this subtree."""
    if isinstance(plan, Join) and plan.kind == "inner":
        lw = _flatten_join(plan.left, items, conds, offset)
        rw = _flatten_join(plan.right, items, conds, offset + lw)
        for lk, rk in zip(plan.left_keys, plan.right_keys):
            conds.append(("eq", _shift(lk, offset), _shift(rk, offset + lw)))
        for oc in plan.other_conds:
            conds.append(("other", _shift_join_cond(oc, offset, lw), None))
        return lw + rw
    items.append((offset, plan))
    return len(plan.schema)


def _shift_join_cond(expr, offset, lw):
    # other_conds are over the join's concat schema: left part [0,lw) shifts
    # by offset; right part shifts by offset too (contiguous in global space)
    return _shift(expr, offset)


def _resolve_base(plan, idx, ctx):
    """Trace schema position `idx` of `plan` down to the base-table column
    it forwards, returning (table_stats, ColumnInfo) or None. Used for
    NDV lookups in join cardinality (reference: statistics/selectivity.go
    resolves expression columns to their UniqueID-keyed stats)."""
    if ctx is None or not hasattr(ctx, "table_stats"):
        return None
    while True:
        if isinstance(plan, DataSource):
            if idx >= len(plan.col_infos):
                return None
            stats = ctx.table_stats(plan.table_info.id)
            if stats is None:
                return None
            return stats, plan.col_infos[idx]
        if isinstance(plan, (Selection, Sort, Limit, TopN)):
            plan = plan.child
            continue
        if isinstance(plan, Projection):
            if idx >= len(plan.exprs) or not isinstance(plan.exprs[idx],
                                                        Column):
                return None
            idx = plan.exprs[idx].idx
            plan = plan.child
            continue
        if isinstance(plan, Join):
            nl = len(plan.left.schema)
            if idx < nl:
                plan = plan.left
            else:
                idx -= nl
                plan = plan.right
            continue
        if isinstance(plan, Aggregation):
            if (idx < len(plan.group_exprs)
                    and isinstance(plan.group_exprs[idx], Column)):
                idx = plan.group_exprs[idx].idx
                plan = plan.child
                continue
            return None
        return None


def _expr_ndv(plan, expr, ctx, est_rows):
    """NDV of a join-key expression over `plan`'s output, capped at the
    estimated row count; None when untraceable or no ANALYZE stats."""
    if not isinstance(expr, Column):
        return None
    base = _resolve_base(plan, expr.idx, ctx)
    if base is None:
        return None
    stats, ci = base
    cs = stats.get("columns", {}).get(str(ci.id))
    if not cs or not cs.get("ndv"):
        return None
    return min(cs["ndv"], max(est_rows, 1))


def _join_est(lr, rr, ndv_pairs):
    """|L ⋈ R| under containment: rows(L)·rows(R) / Π max(ndv_l, ndv_r)
    per equi-key (reference: statistics join cardinality in
    planner/core/stats.go; ndv None → pseudo max(ndv)=min(rows), which
    degenerates to the FK-join guess max(lr, rr))."""
    denom = 1.0
    for lndv, rndv in ndv_pairs:
        if lndv and rndv:
            denom *= max(lndv, rndv)
        else:
            denom *= max(min(lr, rr), 1)
    return max(int(lr * rr / denom), 1)


def _est_rows(plan, ctx):
    if isinstance(plan, DataSource):
        n = 1000
        if ctx is not None and hasattr(ctx, "table_rows"):
            n = max(ctx.table_rows(plan.table_info.id), 1)
        stats = (ctx.table_stats(plan.table_info.id)
                 if ctx is not None and hasattr(ctx, "table_stats") else None)
        if stats is not None and plan.pushed_conds:
            from ..statistics.selectivity import estimate_selectivity
            return max(int(n * estimate_selectivity(
                stats, plan.col_infos, plan.pushed_conds)), 1)
        for _ in plan.pushed_conds:
            n = max(n // 4, 1)
        return n
    if isinstance(plan, Selection):
        return max(_est_rows(plan.child, ctx) // 4, 1)
    if isinstance(plan, Aggregation):
        return max(_est_rows(plan.child, ctx) // 8, 1)
    if isinstance(plan, (Limit, TopN)):
        base = _est_rows(plan.child, ctx)
        return min(base, plan.count or base)
    if isinstance(plan, Join):
        lr = _est_rows(plan.left, ctx)
        rr = _est_rows(plan.right, ctx)
        if plan.kind in ("semi", "anti", "leftouter_semi"):
            return lr
        if plan.left_keys:
            pairs = [(_expr_ndv(plan.left, lk, ctx, lr),
                      _expr_ndv(plan.right, rk, ctx, rr))
                     for lk, rk in zip(plan.left_keys, plan.right_keys)]
            est = _join_est(lr, rr, pairs)
            return max(est, lr) if plan.kind == "left" else est
        return max(lr, rr) if plan.kind != "inner" else lr * rr
    if plan.children:
        return _est_rows(plan.children[0], ctx)
    return 1


def _greedy_join(items, conds, ctx):
    """items: [(global_offset, plan)]; conds: [("eq", l, r) | ("other", e, None)]
    in global indices. Greedy smallest-first join ordering."""
    n = len(items)
    sizes = [_est_rows(p, ctx) for _off, p in items]
    widths = [len(p.schema) for _off, p in items]
    # map global index -> (item, inner_idx)
    g2item = {}
    for it, (off, p) in enumerate(items):
        for i in range(widths[it]):
            g2item[off + i] = (it, i)

    def cond_items(e):
        used = set()
        e.columns_used(used)
        return {g2item[g][0] for g in used}, used

    def global_ndv(e, cap):
        """NDV of a join-cond side expr (global indices) via its item's
        base stats; None unless the expr IS a bare column (a transformed
        key's NDV bears no relation to the underlying column's)."""
        if not isinstance(e, Column):
            return None
        it, inner = g2item[e.idx]
        return _expr_ndv(items[it][1], Column(inner, e.ftype), ctx, cap)

    remaining = set(range(n))
    # seed with the item from the cheapest eq-connected pair (by estimated
    # join output), so a small-but-exploding dimension can't anchor the
    # spine; fall back to smallest-item when nothing connects
    start = None
    best_key = None
    for kind, a, b in conds:
        if kind != "eq":
            continue
        ia, _ = cond_items(a)
        ib, _ = cond_items(b)
        if len(ia) == 1 and len(ib) == 1 and ia != ib:
            (i,), (j,) = ia, ib
            est = _join_est(sizes[i], sizes[j],
                           [(global_ndv(a, sizes[i]),
                             global_ndv(b, sizes[j]))])
            key = (est, min(sizes[i], sizes[j]))
            if best_key is None or key < best_key:
                best_key = key
                start = i if sizes[i] <= sizes[j] else j
    if start is None:
        start = min(remaining, key=lambda i: sizes[i])
    remaining.discard(start)
    joined = {start}
    # current layout: list of item ids in concat order; plan built so far
    layout = [start]
    cur = items[start][1]
    cur_rows = sizes[start]
    pend = [(kind, a, b) for kind, a, b in conds]

    def gmap(g):
        it, inner = g2item[g]
        pos = 0
        for lid in layout:
            if lid == it:
                return pos + inner
            pos += widths[lid]
        raise KeyError(g)

    while remaining:
        # candidates connected via an eq cond, with the key exprs that
        # would connect them (joined-side, candidate-side)
        cand_keys = {}
        for kind, a, b in pend:
            if kind != "eq":
                continue
            ia, _ = cond_items(a)
            ib, _ = cond_items(b)
            if ia <= joined and len(ib) == 1:
                (c,) = ib
                if c in remaining:
                    cand_keys.setdefault(c, []).append((a, b))
            if ib <= joined and len(ia) == 1:
                (c,) = ia
                if c in remaining:
                    cand_keys.setdefault(c, []).append((b, a))
        if cand_keys:
            # pick the candidate minimizing the estimated join output
            # (reference: rule_join_reorder.go greedy by estimated rows)
            def join_score(c):
                pairs = [(global_ndv(a, cur_rows), global_ndv(b, sizes[c]))
                         for a, b in cand_keys[c]]
                return _join_est(cur_rows, sizes[c], pairs)
            nxt = min(cand_keys, key=lambda c: (join_score(c), sizes[c]))
            cur_rows = join_score(nxt)
        else:
            nxt = min(remaining, key=lambda i: sizes[i])
            cur_rows = max(cur_rows * sizes[nxt], 1)
        remaining.discard(nxt)
        right = items[nxt][1]
        new_joined = joined | {nxt}
        schema = Schema(cur.schema.refs + right.schema.refs)
        j = Join(cur, right, "inner", schema)
        lw = len(cur.schema)

        def gmap_new(g, _nxt=nxt, _lw=lw):
            it, inner = g2item[g]
            if it == _nxt:
                return _lw + inner
            return gmap(g)

        consumed = []
        for ci, (kind, a, b) in enumerate(pend):
            if kind == "eq":
                ia, _ua = cond_items(a)
                ib, _ub = cond_items(b)
                if not (ia | ib) <= new_joined:
                    continue
                if ia <= joined and ib == {nxt}:
                    lk, rk = a, b
                elif ib <= joined and ia == {nxt}:
                    lk, rk = b, a
                else:
                    # both sides now available but spanning: post-join filter
                    from ..sqltypes import FieldType, TYPE_LONGLONG
                    e = ScalarFunc("eq", [_remap_final(a, gmap_new),
                                          _remap_final(b, gmap_new)],
                                   FieldType(tp=TYPE_LONGLONG))
                    j.other_conds.append(e)
                    consumed.append(ci)
                    continue
                j.left_keys.append(_remap_final(lk, gmap))
                j.right_keys.append(_remap_inner(rk, g2item, nxt))
                consumed.append(ci)
            else:
                ia, _ = cond_items(a)
                if ia <= new_joined and not ia <= joined:
                    j.other_conds.append(_remap_final(a, gmap_new))
                    consumed.append(ci)
        pend = [c for i, c in enumerate(pend) if i not in set(consumed)]
        layout.append(nxt)
        joined = new_joined
        cur = j
    # leftover conds (e.g. left-only ones missed) -> selection on top
    leftovers = []
    for kind, a, b in pend:
        if kind == "eq":
            from ..sqltypes import FieldType, TYPE_LONGLONG
            e = ScalarFunc("eq", [_remap_final(a, gmap), _remap_final(b, gmap)],
                           FieldType(tp=TYPE_LONGLONG))
            leftovers.append(e)
        else:
            leftovers.append(_remap_final(a, gmap))
    if leftovers:
        cur = Selection(cur, leftovers)
    # restore original column order with a projection
    orig_order = []
    for off, p in items:
        for i in range(len(p.schema)):
            orig_order.append(off + i)
    perm = [gmap(g) for g in sorted(orig_order)]
    refs = [cur.schema.refs[i] for i in perm]
    exprs = [Column(i, cur.schema.refs[i].ftype, name=cur.schema.refs[i].name)
             for i in perm]
    return Projection(cur, exprs, Schema(refs))


def _remap_inner(expr, g2item, item_id):
    """Remap global indices to positions inside one item (the join's right)."""
    return expr.transform_columns(
        lambda c: Column(g2item[c.idx][1], c.ftype, name=c.name))


def _remap_final(expr, gmap):
    return expr.transform_columns(
        lambda c: Column(gmap(c.idx), c.ftype, name=c.name))


def pull_proj_through_semi(plan):
    """Projection(pure columns) under a semi/anti join's PROBE side pulls
    above the join (the join's output IS its left schema, so the pull is
    a pure rotation). Join reorder inserts such projections to restore
    column order; leaving one between the aggregate and the join blocks
    the fused device fragment (collect_tree sees ProjectionExec), while
    above the join it inlines into the aggregate
    (_inline_agg_projection)."""
    for i, c in enumerate(plan.children):
        plan.children[i] = pull_proj_through_semi(c)
    if (isinstance(plan, Join) and plan.kind in ("semi", "anti")
            and isinstance(plan.left, Projection)
            and all(isinstance(e, Column) for e in plan.left.exprs)):
        proj = plan.left
        nl, gap = len(proj.exprs), len(proj.child.schema) - len(proj.exprs)
        plan.children[0] = proj.child
        plan.left_keys = [
            e.transform_columns(lambda c: proj.exprs[c.idx])
            for e in plan.left_keys]
        # residuals index [probe | build]: the probe half reads through
        # the projection, the build half moves past the wider probe
        plan.other_conds = [
            e.transform_columns(
                lambda c: proj.exprs[c.idx] if c.idx < nl
                else Column(c.idx + gap, c.ftype, name=c.name))
            for e in plan.other_conds]
        plan.schema = proj.child.schema
        proj.children[0] = plan
        return proj
    return plan


# ---------------------------------------------------------------------------
# column pruning (reference: rule_column_pruning.go)
# ---------------------------------------------------------------------------

def prune_columns(plan):
    new_plan, _mapping = _prune(plan, set(range(len(plan.schema))))
    return new_plan


def _prune(plan, needed):
    """Returns (new_plan, mapping old_idx -> new_idx). `needed` may not cover
    all outputs; nodes narrow their schemas accordingly."""
    if isinstance(plan, DataSource):
        used = set(needed)
        for c in plan.pushed_conds:
            c.columns_used(used)
        keep = sorted(used) if used else [0] if plan.schema.refs else []
        if not keep and plan.col_infos:
            keep = [0]  # scans need at least one column for row count
        mapping = {old: i for i, old in enumerate(keep)}
        plan.col_infos = [plan.col_infos[i] for i in keep]
        plan.schema = Schema([plan.schema.refs[i] for i in keep])
        plan.pushed_conds = [_remap_cols(c, mapping) for c in plan.pushed_conds]
        return plan, mapping
    if isinstance(plan, MemSource) or isinstance(plan, Dual):
        return plan, {i: i for i in range(len(plan.schema))}
    if isinstance(plan, Selection):
        child_needed = set(needed)
        for c in plan.conds:
            c.columns_used(child_needed)
        plan.children[0], mapping = _prune(plan.child, child_needed)
        plan.conds = [_remap_cols(c, mapping) for c in plan.conds]
        plan.schema = plan.child.schema
        return plan, mapping
    if isinstance(plan, Projection):
        keep = sorted(needed)
        child_needed = set()
        kept_exprs = [plan.exprs[i] for i in keep]
        for e in kept_exprs:
            e.columns_used(child_needed)
        plan.children[0], cmap = _prune(plan.child, child_needed)
        plan.exprs = [_remap_cols(e, cmap) for e in kept_exprs]
        plan.schema = Schema([plan.schema.refs[i] for i in keep])
        return plan, {old: i for i, old in enumerate(keep)}
    if isinstance(plan, Aggregation):
        n_group = len(plan.group_exprs)
        keep_aggs = [i for i in range(len(plan.aggs))
                     if (n_group + i) in needed]
        child_needed = set()
        for e in plan.group_exprs:
            e.columns_used(child_needed)
        kept_descs = [plan.aggs[i] for i in keep_aggs]
        for d in kept_descs:
            for a in d.args:
                a.columns_used(child_needed)
        plan.children[0], cmap = _prune(plan.child, child_needed)
        plan.group_exprs = [_remap_cols(e, cmap) for e in plan.group_exprs]
        for d in kept_descs:
            d.args = [_remap_cols(a, cmap) for a in d.args]
        plan.aggs = kept_descs
        keep = list(range(n_group)) + [n_group + i for i in keep_aggs]
        plan.schema = Schema([plan.schema.refs[i] for i in keep])
        return plan, {old: i for i, old in enumerate(keep)}
    if isinstance(plan, Join):
        nl = len(plan.left.schema)
        child_needed = set(needed)
        for e in plan.other_conds:
            e.columns_used(child_needed)
        lneed = {i for i in child_needed if i < nl}
        rneed = {i - nl for i in child_needed if i >= nl}
        for e in plan.left_keys:
            e.columns_used(lneed)
        for e in plan.right_keys:
            e.columns_used(rneed)
        plan.children[0], lmap = _prune(plan.left, lneed)
        plan.children[1], rmap = _prune(plan.right, rneed)
        new_nl = len(plan.left.schema)
        mapping = {}
        for old, new in lmap.items():
            mapping[old] = new
        for old, new in rmap.items():
            mapping[old + nl] = new + new_nl
        plan.left_keys = [_remap_cols(e, lmap) for e in plan.left_keys]
        plan.right_keys = [_remap_cols(e, rmap) for e in plan.right_keys]
        plan.other_conds = [_remap_cols(e, mapping) for e in plan.other_conds]
        if plan.kind in ("semi", "anti"):
            # the output is the probe's rows alone: a join above reads
            # its right side's columns right after them
            plan.schema = plan.left.schema
            return plan, lmap
        plan.schema = plan.left.schema.concat(plan.right.schema)
        return plan, mapping
    if isinstance(plan, (Sort, TopN)):
        child_needed = set(needed)
        for e, _d in plan.by:
            e.columns_used(child_needed)
        plan.children[0], mapping = _prune(plan.child, child_needed)
        plan.by = [(_remap_cols(e, mapping), d) for e, d in plan.by]
        plan.schema = plan.child.schema
        return plan, mapping
    if isinstance(plan, Limit):
        plan.children[0], mapping = _prune(plan.child, needed)
        plan.schema = plan.child.schema
        return plan, mapping
    if isinstance(plan, SetOp):
        # children must keep identical layouts: prune nothing
        new_children = []
        for c in plan.children:
            nc, _m = _prune(c, set(range(len(c.schema))))
            new_children.append(nc)
        plan.children = new_children
        return plan, {i: i for i in range(len(plan.schema))}
    # unknown: no pruning
    plan.children = [(_prune(c, set(range(len(c.schema))))[0]) for c in plan.children]
    return plan, {i: i for i in range(len(plan.schema))}


def _remap_cols(expr, mapping):
    return expr.transform_columns(
        lambda c: Column(mapping[c.idx], c.ftype, name=c.name))
