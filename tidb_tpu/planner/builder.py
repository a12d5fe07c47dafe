"""AST → logical plan (reference: planner/core/logical_plan_builder.go +
planbuilder.go; aggregate extraction mirrors buildAggregation, star expansion
mirrors unfoldWildStar, order-by alias rules mirror resolveByItems)."""

from __future__ import annotations

from ..errors import ColumnError, SchemaError, TiDBError, ErrCode
from ..expression import (
    AggFuncDesc, Column, ColumnRef, Constant, ExprBuilder, Schema, unify_types,
)
from ..expression.core import ScalarFunc
from ..parser import ast
from ..sqltypes import TYPE_LONGLONG, FieldType
from .logical import (
    Aggregation, DataSource, Dual, Join, Limit, LogicalPlan, MemSource,
    Projection, Selection, SetOp, Sort, TopN, Window,
)

_BOOL_FT = FieldType(tp=TYPE_LONGLONG)


class _ViewCtx:
    """Planner ctx proxy for view expansion: unqualified names inside the
    view body resolve against the view's creation-time database. Everything
    else delegates to the real session ctx; `_base_ctx` lets nested views
    share one recursion-guard stack."""

    def __init__(self, base, db):
        self._base_ctx = base
        self._db = db

    def current_db(self):
        return self._db

    def __getattr__(self, name):
        return getattr(self._base_ctx, name)


def split_cnf(expr):
    """Split a built expression on AND (reference: expression.SplitCNFItems)."""
    if isinstance(expr, ScalarFunc) and expr.op == "and":
        return split_cnf(expr.args[0]) + split_cnf(expr.args[1])
    return [expr]


def collect_aggs(node, out):
    """Collect AggregateFunc AST nodes (deduplicated by restore text)."""
    if node is None:
        return
    if isinstance(node, ast.AggregateFunc):
        key = node.restore()
        if key not in out:
            out[key] = node
        return  # nested aggs are invalid anyway
    for child in _ast_children(node):
        collect_aggs(child, out)


def _window_ftype(name, args):
    """Output type per window function (reference:
    expression/aggregation/window_func.go)."""
    from ..sqltypes import TYPE_DOUBLE
    if name in ("row_number", "rank", "dense_rank", "ntile", "count"):
        return FieldType(tp=TYPE_LONGLONG)
    if name in ("percent_rank", "cume_dist", "avg"):
        return FieldType(tp=TYPE_DOUBLE)
    if name in ("lead", "lag", "first_value", "last_value", "nth_value",
                "min", "max"):
        if not args:
            raise TiDBError(f"window function {name} requires an argument")
        return args[0].ftype
    if name == "sum":
        return AggFuncDesc("sum", [args[0]]).ftype
    raise TiDBError(f"unsupported window function {name}")


_RANKERS = {"row_number", "rank", "dense_rank", "percent_rank", "cume_dist",
            "ntile", "lead", "lag"}


def _normalize_frame(frame, name):
    """Validate an explicit frame clause. Default frame → None; explicit
    ROWS frames are executed; RANGE frames with offsets are rejected rather
    than silently computed with default-frame semantics."""
    if frame is None or name in _RANKERS:  # rankers ignore frames (SQL std)
        return None
    unit, lo, hi = frame
    if (unit == "range"
            and (lo, hi) == (("unbounded_preceding", 0), ("current", 0))):
        return None  # exactly the default frame (peer-aware); the ROWS
        # spelling is NOT equivalent when order keys tie — keep it explicit
    if unit == "range":
        if (lo, hi) == (("unbounded_preceding", 0),
                        ("unbounded_following", 0)):
            return ("rows", lo, hi)  # whole partition: unit-independent
        raise TiDBError("RANGE frames with offsets are not supported yet")
    if name in ("min", "max"):
        raise TiDBError(f"{name} with an explicit frame is not supported yet")
    return ("rows", lo, hi)


def collect_windows(node, out):
    """Collect WindowFunc AST nodes (deduplicated by restore text)."""
    if node is None:
        return
    if isinstance(node, ast.WindowFunc):
        key = node.restore()
        if key not in out:
            out[key] = node
        return
    for child in _ast_children(node):
        collect_windows(child, out)


def _ast_children(node):
    if isinstance(node, ast.BinaryOp):
        return [node.left, node.right]
    if isinstance(node, ast.UnaryOp):
        return [node.operand]
    if isinstance(node, (ast.IsNullExpr, ast.IsTruthExpr)):
        return [node.expr]
    if isinstance(node, ast.BetweenExpr):
        return [node.expr, node.low, node.high]
    if isinstance(node, ast.InExpr):
        return [node.expr] + [i for i in node.items if isinstance(i, ast.ExprNode)]
    if isinstance(node, (ast.LikeExpr, ast.RegexpExpr)):
        return [node.expr, node.pattern]
    if isinstance(node, ast.CaseExpr):
        out = []
        if node.operand:
            out.append(node.operand)
        for c, r in node.whens:
            out += [c, r]
        if node.else_:
            out.append(node.else_)
        return out
    if isinstance(node, (ast.FuncCall, ast.AggregateFunc)):
        return list(node.args)
    if isinstance(node, ast.CastExpr):
        return [node.expr]
    if isinstance(node, ast.IntervalExpr):
        return [node.value]
    if isinstance(node, ast.RowExpr):
        return list(node.items)
    return []


def _subst_select(sel, ctes):
    """Inline WITH ctes (reference: non-recursive CTEs; parser.y WithClause):
    every reference to a CTE name becomes a derived table over a deep copy
    of its body. Inner WITH lists shadow outer ones; each body sees the
    CTEs defined before it."""
    import copy as _copy

    if isinstance(sel, ast.SetOprStmt):
        scope = dict(ctes)
        first = sel.selects[0] if sel.selects else None
        if first is not None and getattr(first, "with_ctes", None):
            rec_flag = getattr(first, "with_recursive", False)
            for name, cols, stmt in first.with_ctes:
                body_scope = dict(scope)
                if rec_flag:
                    body_scope[name.lower()] = _RECURSIVE
                _subst_select(stmt, body_scope)
                if rec_flag and _references_cte(stmt, name):
                    scope[name.lower()] = _RecursiveDef(cols, stmt)
                else:
                    scope[name.lower()] = (cols, stmt)
            first.with_ctes = []
        for s in sel.selects:
            _subst_select(s, scope)
        return
    scope = dict(ctes)
    rec_flag = getattr(sel, "with_recursive", False)
    for name, cols, stmt in getattr(sel, "with_ctes", []) or []:
        body_scope = dict(scope)
        if rec_flag:
            # only WITH RECURSIVE makes the name visible to its own body;
            # otherwise a self-name refers to the outer scope / real table
            body_scope[name.lower()] = _RECURSIVE
        _subst_select(stmt, body_scope)
        if rec_flag and _references_cte(stmt, name):
            scope[name.lower()] = _RecursiveDef(cols, stmt)
        else:
            scope[name.lower()] = (cols, stmt)
    sel.with_ctes = []
    if not scope:
        return
    if sel.from_ is not None:
        sel.from_ = _subst_from(sel.from_, scope, _copy)
    for f in sel.fields:
        if not isinstance(f.expr, ast.StarExpr):
            _subst_expr(f.expr, scope)
    _subst_expr(sel.where, scope)
    _subst_expr(sel.having, scope)
    for bi in list(sel.group_by) + list(sel.order_by):
        _subst_expr(bi.expr, scope)


_RECURSIVE = object()  # sentinel: a CTE body referencing its own name


class _RecursiveDef:
    """A CTE whose body references its own name: kept whole; each outer
    reference becomes a RecursiveCTETable for fixpoint evaluation."""

    __slots__ = ("cols", "stmt")

    def __init__(self, cols, stmt):
        self.cols = cols
        self.stmt = stmt


def _references_cte(stmt, name: str) -> bool:
    """Does the (already-substituted) body still reference `name` in a
    FROM position? Self-references were left as bare TableNames."""
    from ..priv_check import _collect_tables
    tabs = []
    _collect_tables(stmt, tabs)
    return any(not t.schema and t.name.lower() == name.lower()
               for t in tabs)


def _subst_from(node, ctes, _copy):
    if isinstance(node, ast.TableName):
        if not node.schema and node.name.lower() in ctes:
            entry = ctes[node.name.lower()]
            if entry is _RECURSIVE:
                # a self-reference inside the CTE's own body: left intact;
                # the fixpoint executor binds it per iteration
                return node
            if isinstance(entry, _RecursiveDef):
                body = _copy.deepcopy(entry.stmt)
                if not isinstance(body, ast.SetOprStmt):
                    raise TiDBError(
                        f"Recursive CTE '{node.name}' must be a UNION of a "
                        f"seed part and a recursive part")
                return ast.RecursiveCTETable(
                    name=node.name.lower(), cols=list(entry.cols),
                    query=body, as_name=node.as_name or node.name)
            cols, stmt = entry
            body = _copy.deepcopy(stmt)
            return ast.SubqueryTable(query=body,
                                     as_name=node.as_name or node.name,
                                     col_names=list(cols))
        return node
    if isinstance(node, ast.Join):
        node.left = _subst_from(node.left, ctes, _copy)
        node.right = _subst_from(node.right, ctes, _copy)
        _subst_expr(node.on, ctes)
        return node
    if isinstance(node, ast.SubqueryTable):
        _subst_select(node.query, ctes)
        return node
    return node


def _subst_expr(node, ctes):
    if node is None or not ctes:
        return
    if isinstance(node, ast.SubqueryExpr):
        _subst_select(node.query, ctes)
        return
    if isinstance(node, ast.ExistsExpr):
        _subst_select(node.query.query, ctes)
        return
    if isinstance(node, ast.CompareSubquery):
        _subst_expr(node.expr, ctes)
        _subst_select(node.query.query, ctes)
        return
    for c in _ast_children(node):
        _subst_expr(c, ctes)


class AggExprBuilder(ExprBuilder):
    """Resolves expressions over an Aggregation's output: group exprs and agg
    funcs map to output columns; bare columns not in GROUP BY become implicit
    first_row aggregates (MySQL non-ONLY_FULL_GROUP_BY behavior)."""

    def __init__(self, agg: Aggregation, child_schema: Schema, expr_map, ctx,
                 outer=None):
        super().__init__(agg.schema, ctx, outer=outer)
        self.agg = agg
        self.child_schema = child_schema
        self.expr_map = expr_map  # restore text -> output idx

    def build(self, node):
        key = node.restore() if isinstance(node, ast.ExprNode) else None
        if key is not None and key in self.expr_map:
            idx = self.expr_map[key]
            return Column(idx, self.agg.schema.refs[idx].ftype,
                          name=self.agg.schema.refs[idx].name)
        return super().build(node)

    def _b_ColumnName(self, node):
        idx = self.schema.find(node)
        if idx is not None:
            r = self.schema.refs[idx]
            return Column(idx, r.ftype, name=r.name)
        # implicit first_row over a non-grouped column
        cidx = self.child_schema.find(node)
        if cidx is None:
            if self.outer is not None:
                e = self.outer.resolve(node)
                if e is not None:
                    return e
            raise ColumnError(f"Unknown column '{node.name}' in 'field list'")
        cref = self.child_schema.refs[cidx]
        arg = Column(cidx, cref.ftype, name=cref.name)
        desc = AggFuncDesc("first_row", [arg])
        self.agg.aggs.append(desc)
        self.agg.schema.refs.append(
            ColumnRef(cref.name, cref.table, cref.db, desc.ftype))
        idx = len(self.agg.schema.refs) - 1
        self.expr_map[node.restore()] = idx
        return Column(idx, desc.ftype, name=cref.name)

    def _b_AggregateFunc(self, node):
        raise TiDBError("aggregate not extracted — nested aggregates are invalid",
                        code=ErrCode.InvalidGroupFuncUse)


class PlanBuilder:
    """ctx provides: infoschema(), current_db(), eval_subquery(sel, limit_one),
    get_sysvar/set_uservar/get_uservar, mem_table_rows(db, name)."""

    def __init__(self, ctx, outer=None):
        self.ctx = ctx
        self.outer = outer  # OuterScope of the enclosing SELECT (subqueries)
        self._sub_memo = None  # decorrelation-analysis cache (build_select)
        self.ctes = {}      # WITH name -> SelectStmt AST

    # -- entry points -------------------------------------------------------

    def build(self, stmt):
        if isinstance(stmt, ast.SelectStmt):
            if stmt.with_ctes:
                _subst_select(stmt, {})
            return self.build_select(stmt)
        if isinstance(stmt, ast.SetOprStmt):
            _subst_select(stmt, {})
            return self.build_set_op(stmt)
        raise TiDBError(f"cannot plan {type(stmt).__name__}")

    def build_set_op(self, stmt: ast.SetOprStmt):
        children = [self.build_select(s) for s in stmt.selects]
        ncols = len(children[0].schema)
        for c in children[1:]:
            if len(c.schema) != ncols:
                raise TiDBError(
                    "The used SELECT statements have a different number of columns",
                    code=ErrCode.WrongNumberOfColumnsInSelect)
        # unify column types; names come from the first select
        refs = []
        for i in range(ncols):
            ft = unify_types([c.schema.refs[i].ftype for c in children])
            r0 = children[0].schema.refs[i]
            refs.append(ColumnRef(r0.name, "", "", ft))
        schema = Schema(refs)
        plan = children[0]
        kinds = {"union all": "union_all", "union": "union",
                 "intersect": "intersect", "except": "except",
                 "intersect all": "intersect", "except all": "except"}
        for op, nxt in zip(stmt.ops, children[1:]):
            plan = SetOp([plan, nxt], kinds[op], schema)
        if stmt.order_by or stmt.limit:
            plan = self._apply_order_limit(plan, stmt.order_by, stmt.limit,
                                           ExprBuilder(plan.schema, self.ctx, outer=self.outer), [])
        return plan

    # -- FROM ---------------------------------------------------------------

    def build_from(self, node):
        if node is None:
            return Dual()
        if isinstance(node, ast.TableName):
            return self._build_table(node)
        if isinstance(node, ast.SubqueryTable):
            sub = self.build(node.query)
            alias = node.as_name or ""
            renames = node.col_names
            if renames and len(renames) != len(sub.schema.refs):
                raise TiDBError(
                    "In definition of view, derived table or common table "
                    "expression, SELECT list and column names list have "
                    "different column counts", code=ErrCode.ViewWrongList)
            refs = []
            for i, r in enumerate(sub.schema.refs):
                name = renames[i] if i < len(renames) else r.name
                refs.append(ColumnRef(name, alias, "", r.ftype))
            sub2 = Projection(sub, [Column(i, r.ftype, name=r.name)
                                    for i, r in enumerate(sub.schema.refs)],
                              Schema(refs))
            return sub2
        if isinstance(node, ast.Join):
            return self._build_join(node)
        if isinstance(node, ast.RecursiveCTETable):
            return self._build_recursive_cte(node)
        raise TiDBError(f"unsupported FROM item {type(node).__name__}")

    def _build_recursive_cte(self, node: ast.RecursiveCTETable):
        """Fixpoint evaluation of WITH RECURSIVE (reference:
        executor/cte.go:60 — seed into the result table, iterate the
        recursive part against the previous iteration until empty, dedup
        for UNION DISTINCT, bounded by cte_max_recursion_depth)."""
        body = node.query
        ctx = self.ctx
        if not hasattr(ctx, "eval_subquery"):
            raise TiDBError("recursive CTE not available in this context")
        # one materialization per (name, body) per statement: further
        # references reuse it (reference: cteutil shared working table)
        cache = getattr(ctx, "cte_results", None)
        if cache is None:
            cache = ctx.cte_results = {}
        cache_key = (node.name, body.restore())
        hit = cache.get(cache_key)
        if hit is not None:
            names, fts, result = hit
            alias = node.as_name or node.name
            refs = [ColumnRef(n, alias, "", ft)
                    for n, ft in zip(names, fts)]
            return MemSource("", node.name, Schema(refs), lambda: result)
        if any(op not in ("union", "union all") for op in body.ops):
            raise TiDBError("recursive CTE supports UNION [ALL] only")
        if body.order_by:
            raise TiDBError(
                "ORDER BY inside a recursive CTE body is not supported")
        cap = None
        if body.limit is not None:
            off, cnt = self._limit_values(body.limit)
            if cnt is not None:
                cap = (off or 0) + cnt  # LIMIT terminates the iteration
        seeds, recs = [], []
        for s in body.selects:
            (recs if _references_cte(s, node.name) else seeds).append(s)
        if not seeds:
            raise TiDBError(f"Recursive CTE '{node.name}' has no "
                            f"non-recursive seed part")
        distinct = any(op == "union" for op in body.ops)
        rows, fts = [], None
        names = list(node.cols)
        for s in seeds:
            r, f = ctx.eval_subquery(s)
            rows.extend(r)
            fts = fts or f
            if not names:
                names = [fld.as_name or _derive_name(fld.expr)
                         for fld in s.fields]
        if names and fts is not None and len(names) != len(fts):
            raise TiDBError(
                "In definition of view, derived table or common table "
                "expression, SELECT list and column names list have "
                "different column counts")
        seen = set(map(tuple, rows)) if distinct else None
        if distinct:
            rows = list(dict.fromkeys(map(tuple, rows)))
        try:
            limit = int(ctx.get_sysvar("cte_max_recursion_depth", "session"))
        except Exception:
            limit = 1000
        bindings = getattr(ctx, "cte_bindings", None)
        if bindings is None:
            bindings = ctx.cte_bindings = {}
        key = node.name.lower()
        prev = bindings.get(key)
        work = list(rows)
        if cap is not None and len(rows) >= cap:
            rows, work = rows[:cap], []
        it = 0
        try:
            while work:
                bindings[key] = (names, fts, work)
                new_rows = []
                for s in recs:
                    r, _f = ctx.eval_subquery(s)
                    new_rows.extend(r)
                if distinct:
                    fresh = []
                    for r in map(tuple, new_rows):
                        if r not in seen:
                            seen.add(r)
                            fresh.append(r)
                    new_rows = fresh
                if not new_rows:
                    break
                # only a PRODUCTIVE iteration counts against the depth
                # limit (an exhausted-but-empty final step is termination)
                it += 1
                if it > limit:
                    raise TiDBError(
                        f"Recursive query aborted after {limit} iterations."
                        f" Try increasing @@cte_max_recursion_depth")
                rows.extend(new_rows)
                work = new_rows
                if cap is not None and len(rows) >= cap:
                    rows = rows[:cap]
                    break
        finally:
            if prev is None:
                bindings.pop(key, None)
            else:
                bindings[key] = prev
        alias = node.as_name or node.name
        refs = [ColumnRef(n, alias, "", ft) for n, ft in zip(names, fts)]
        result = [tuple(r) for r in rows]
        cache[cache_key] = (names, fts, result)
        return MemSource("", node.name, Schema(refs), lambda: result)

    def _build_table(self, tn: ast.TableName):
        if tn.as_of is not None:
            # stale read: pin the statement's read view at that instant
            # (reference: sessiontxn/interface.go:48 staleness providers)
            sess = getattr(self.ctx, "session", None)
            if sess is None or not hasattr(sess, "set_stmt_as_of"):
                raise TiDBError(
                    "AS OF TIMESTAMP is not available in this context")
            sess.set_stmt_as_of(tn.as_of)
        # an in-flight recursive CTE iteration binds its name to the
        # previous iteration's rows (reference: cteutil working table)
        bindings = getattr(self.ctx, "cte_bindings", None)
        if bindings and not tn.schema:
            bound = bindings.get(tn.name.lower())
            if bound is not None:
                names, fts, rows = bound
                alias = tn.as_name or tn.name
                refs = [ColumnRef(n, alias, "", ft)
                        for n, ft in zip(names, fts)]
                frozen = [tuple(r) for r in rows]
                return MemSource("", tn.name, Schema(refs), lambda: frozen)
        db = tn.schema or self.ctx.current_db()
        if not db:
            raise SchemaError("No database selected", code=ErrCode.BadDB)
        alias = tn.as_name or tn.name
        if db.lower() in ("information_schema", "performance_schema", "metrics_schema"):
            cols, rows_fn = self.ctx.mem_table(db.lower(), tn.name.lower())
            refs = [ColumnRef(name, alias, db, ft) for name, ft in cols]
            return MemSource(db, tn.name.lower(), Schema(refs), rows_fn)
        info = self.ctx.infoschema().table_by_name(db, tn.name)
        if info.is_view:
            return self._expand_view(db, info, alias)
        if info.is_sequence:
            raise TiDBError(
                f"'{db}.{tn.name}' is a SEQUENCE; use NEXTVAL/LASTVAL",
                code=ErrCode.WrongObjectSequence)
        cols = info.public_columns()
        refs = [ColumnRef(c.name, alias, db, c.ftype, origin=info.name)
                for c in cols]
        ds = DataSource(db, info, cols, Schema(refs), alias=alias)
        ds.index_hints = list(tn.index_hints)
        if tn.partition_names:
            if info.partition is None:
                raise TiDBError(
                    f"PARTITION () clause on non partitioned table",
                    code=ErrCode.PartitionMgmtOnNonpartitioned)
            sel = []
            for pn in tn.partition_names:
                d = info.partition.find_def(pn)
                if d is None:
                    raise TiDBError(
                        f"Unknown partition '{pn}' in table '{info.name}'",
                        code=ErrCode.UnknownPartition)
                sel.append(d)
            ds.partitions = sel
        return ds

    def _expand_view(self, db, info, alias):
        """Inline a view's defining select as a subquery and rename its
        output columns to the view's column list (reference: planbuilder.go
        BuildDataSourceFromView)."""
        from ..parser import parse
        base = getattr(self.ctx, "_base_ctx", self.ctx)
        stack = getattr(base, "_view_stack", None)
        if stack is None:
            stack = set()
            try:
                base._view_stack = stack
            except AttributeError:
                pass
        if info.id in stack:
            raise TiDBError(
                f"`{db}`.`{info.name}` contains view recursion",
                code=ErrCode.ViewRecursive)
        stack.add(info.id)
        try:
            sel = parse(info.view["select"])[0]
            # resolve against the view's creation-time db with no access to
            # the enclosing query's scope (a view body never correlates)
            vctx = _ViewCtx(base, info.view.get("db") or db)
            sub = PlanBuilder(vctx, outer=None).build(sel)
        except TiDBError as e:
            if getattr(e, "code", None) == ErrCode.ViewRecursive:
                raise
            raise TiDBError(
                f"View '{db}.{info.name}' references invalid table(s) or "
                f"column(s): {e}", code=ErrCode.ViewInvalid)
        finally:
            stack.discard(info.id)
        names = info.view["cols"]
        if len(names) != len(sub.schema):
            raise TiDBError(
                f"View '{db}.{info.name}' is invalid (column count changed)",
                code=ErrCode.ViewInvalid)
        exprs = [Column(i, r.ftype, name=nm)
                 for i, (r, nm) in enumerate(zip(sub.schema.refs, names))]
        refs = [ColumnRef(nm, alias, db, r.ftype)
                for r, nm in zip(sub.schema.refs, names)]
        return Projection(sub, exprs, Schema(refs))

    def _build_join(self, jn: ast.Join):
        left = self.build_from(jn.left)
        right = self.build_from(jn.right)
        kind = jn.kind
        if kind == "right":
            left, right = right, left
            kind = "left"
        schema = left.schema.concat(right.schema)
        join = Join(left, right, "inner" if kind == "cross" else kind, schema)
        conds = []
        if jn.on is not None:
            b = ExprBuilder(schema, self.ctx, outer=self.outer)
            conds = split_cnf(b.build(jn.on))
        elif jn.using:
            names = jn.using
            if names == ["*natural*"]:
                lnames = {r.name for r in left.schema.refs}
                names = [r.name for r in right.schema.refs if r.name in lnames]
            b = ExprBuilder(schema, self.ctx, outer=self.outer)
            for name in names:
                conds.append(b.build(ast.BinaryOp(
                    op="=",
                    left=ast.ColumnName(name=name, table=_schema_table(left.schema, name)),
                    right=ast.ColumnName(name=name, table=_schema_table(right.schema, name)))))
        self._attach_join_conds(join, conds)
        return join

    def _attach_join_conds(self, join: Join, conds):
        nl = len(join.left.schema)
        for cond in conds:
            used = set()
            cond.columns_used(used)
            left_only = all(i < nl for i in used)
            right_only = all(i >= nl for i in used)
            if (isinstance(cond, ScalarFunc) and cond.op == "eq"
                    and not left_only and not right_only):
                lhs, rhs = cond.args
                lu, ru = set(), set()
                lhs.columns_used(lu)
                rhs.columns_used(ru)
                if all(i < nl for i in lu) and all(i >= nl for i in ru):
                    join.left_keys.append(lhs)
                    join.right_keys.append(_shift(rhs, -nl))
                    continue
                if all(i < nl for i in ru) and all(i >= nl for i in lu):
                    join.left_keys.append(rhs)
                    join.right_keys.append(_shift(lhs, -nl))
                    continue
            if join.kind == "inner" and left_only:
                join.children[0] = Selection(join.left, [cond])
            elif join.kind in ("inner", "left") and right_only:
                # a LEFT join's inner-side-only ON cond restricts which
                # rows can MATCH — pushing it into the inner child is
                # equivalent (unmatched probe rows still null-extend);
                # a left-only ON cond is NOT pushable for outer joins
                join.children[1] = Selection(join.right, [_shift(cond, -nl)])
            else:
                join.other_conds.append(cond)

    # -- SELECT -------------------------------------------------------------

    def _try_decorrelate(self, conj, from_schema):
        """Correlated EXISTS / [NOT] IN conjunct → decorrelated join spec
        (kind, right_child_plan, left_keys, right_keys, other_conds), or
        None to take the normal expression path.

        The subquery is analyzed once with outer refs surfacing as OuterRef
        markers; the rewrite accepts the canonical shape — [Sort] [Limit≥1,
        EXISTS only] [Projection] Selection(from-tree) — where every
        OuterRef sits in a top-Selection conjunct and at least one of them
        has the form eq(OuterRef, inner_expr): those are the join keys,
        every other correlated conjunct (TPC-H Q21's `l2.l_suppkey <>
        l1.l_suppkey`) the join's other condition, over the joined schema
        (reference: TiDB keeps it as the semi join's OtherConditions).
        Anything else (correlation under an aggregate, a correlation with
        no equality, nested Apply) bails to the SubqueryApply fallback.
        NOT IN compiles to a NULL-AWARE anti join:
        the membership key matches when equal OR either side is NULL
        (reference: null-aware anti join, planner/core/
        expression_rewriter.go handleInSubquery)."""
        from ..expression.builder import OuterScope
        from ..expression.core import OuterRef
        from ..expression import phys_kind
        if self.outer is not None:
            # nested scopes would mix marked and NULL-constant analysis
            return None
        negate = False
        while (isinstance(conj, ast.UnaryOp) and conj.op == "not"
               and isinstance(conj.operand, (ast.ExistsExpr, ast.UnaryOp))):
            negate = not negate
            conj = conj.operand
        if isinstance(conj, ast.ExistsExpr):
            sub_ast = conj.query.query
            kind = "anti" if (conj.negated ^ negate) else "semi"
            target_ast = None
        elif negate:
            return None
        elif (isinstance(conj, ast.InExpr) and len(conj.items) == 1
                and isinstance(conj.items[0], ast.SubqueryExpr)):
            sub_ast = conj.items[0].query
            kind = "anti" if conj.negated else "semi"
            target_ast = conj.expr
        elif (isinstance(conj, ast.BinaryOp)
                and conj.op in ("=", "!=", "<", "<=", ">", ">=")
                and (isinstance(conj.left, ast.SubqueryExpr)
                     != isinstance(conj.right, ast.SubqueryExpr))):
            # expr <op> (correlated scalar-aggregate subquery) — the TPC-H
            # Q17/Q20 shape — rewrites to a semi join against the subquery
            # re-grouped by its correlation keys
            return self._try_decorrelate_scalar_cmp(conj, from_schema)
        else:
            return None
        scope = OuterScope(from_schema, mark=True)
        try:
            subplan = self.ctx.analyze_subquery(sub_ast, scope)
        except Exception:
            return None
        if self._sub_memo is not None:
            # a bail below must not re-analyze (analysis executes eager
            # nested subqueries); the ExprBuilder fallback reuses this
            self._sub_memo[id(sub_ast)] = (scope, subplan)
        if not scope.used:
            # UNCORRELATED positive IN → semi join (reference:
            # tidb_opt_insubq_to_join_and_agg, expression_rewriter.go
            # handleInSubquery): the subquery becomes a plan child
            # executed at RUN time — the in-set path materializes it at
            # expression-build time, so even EXPLAIN executed it. NOT IN
            # stays on build_in_set (its three-valued NULL semantics need
            # the set form without correlation keys to hang them on).
            if (target_ast is None or kind != "semi"):
                return None
            try:
                on = self.ctx.get_sysvar(
                    "tidb_opt_insubq_to_join_and_agg", "session")
            except Exception:
                on = "ON"
            if str(on).upper() not in ("ON", "1"):
                return None
            return self._uncorrelated_in_semi(subplan, target_ast,
                                              from_schema)

        node = subplan
        if isinstance(node, Sort):
            node = node.child  # ORDER BY cannot affect existence/membership
        if isinstance(node, (Limit, TopN)):
            if target_ast is not None:
                return None  # LIMIT changes the membership set
            if not node.count or (node.offset or 0) > 0:
                return None
            node = node.child
            if isinstance(node, Sort):
                node = node.child
        proj = None
        if isinstance(node, Projection):
            proj = node
            node = node.child
        if not isinstance(node, Selection):
            return None
        sel_node = node
        base = sel_node.child

        # every correlated expression must be a top-Selection conjunct
        for nd in _walk_plan(subplan, []):
            if nd is sel_node:
                continue
            for e in _node_exprs(nd):
                acc = []
                _collect_outer_refs(e, acc)
                if acc:
                    return None

        residual, lkeys, rkeys, oconds = [], [], [], []
        nl = len(from_schema)
        for c in sel_node.conds:
            acc = []
            _collect_outer_refs(c, acc)
            if not acc:
                residual.append(c)
                continue
            if not all(isinstance(r, OuterRef) for r in acc):
                return None  # a nested Apply pins the conjunct to Apply
            key = _outer_eq_key(c)
            if key is None:
                # any other correlated conjunct (Q21's l2.l_suppkey <>
                # l1.l_suppkey) tests each matched pair: the join's
                # other condition over [outer | inner]
                oconds.append(bind_outer_refs(c, nl))
                continue
            lkeys.append(key[0])
            rkeys.append(key[1])
        if not lkeys:
            return None  # no equi keys: a cartesian semi join would be
            #              worse than the memoized Apply

        if target_ast is not None:
            out_len = len(proj.exprs) if proj else len(base.schema)
            if out_len != 1:
                raise TiDBError("Operand should contain 1 column(s)",
                                code=ErrCode.OperandColumns)
            y = proj.exprs[0] if proj else Column(
                0, base.schema.refs[0].ftype)
            b = ExprBuilder(from_schema, self.ctx, outer=self.outer)
            x = b.build(target_ast)
            x_acc = []
            _collect_outer_refs(x, x_acc)
            if x_acc or phys_kind(x.ftype) != phys_kind(y.ftype):
                return None
            if kind == "semi":
                # IN match: plain equality (NULLs never match — correct in
                # WHERE context, where NULL filters like FALSE)
                lkeys.append(x)
                rkeys.append(y)
            else:
                # NOT IN: null-aware residual — a build row "blocks" the
                # probe row when the values match OR either side is NULL
                ys = _shift(y, nl)
                oconds.append(ScalarFunc("or", [
                    ScalarFunc("or", [
                        ScalarFunc("eq", [x, ys], _BOOL_FT.clone()),
                        ScalarFunc("isnull", [ys], _BOOL_FT.clone()),
                    ], _BOOL_FT.clone()),
                    ScalarFunc("isnull", [x], _BOOL_FT.clone()),
                ], _BOOL_FT.clone()))
        right_child = Selection(base, residual) if residual else base
        return kind, right_child, lkeys, rkeys, oconds

    def _uncorrelated_in_semi(self, subplan, target_ast, from_schema):
        """`x IN (SELECT e FROM ...)` (uncorrelated) → semi join with the
        subquery plan as the build child. The subquery keeps its whole
        shape (DISTINCT/LIMIT/aggregates included — they restrict the
        membership set and must survive)."""
        from ..expression import phys_kind
        proj = subplan if isinstance(subplan, Projection) else None
        if proj is not None and len(proj.exprs) == 1:
            right_child = proj.child
            y = proj.exprs[0]
        else:
            if len(subplan.schema) != 1:
                raise TiDBError("Operand should contain 1 column(s)",
                                code=ErrCode.OperandColumns)
            right_child = subplan
            y = Column(0, subplan.schema.refs[0].ftype)
        b = ExprBuilder(from_schema, self.ctx, outer=self.outer)
        b.sub_memo = self._sub_memo
        x = b.build(target_ast)
        acc = []
        _collect_outer_refs(x, acc)
        if acc or phys_kind(x.ftype) != phys_kind(y.ftype):
            return None
        return "semi", right_child, [x], [y], []

    _MIRROR_OP = {"=": "=", "!=": "!=", "<": ">", "<=": ">=", ">": "<",
                  ">=": "<="}

    def _try_decorrelate_scalar_cmp(self, conj, from_schema):
        """`x <op> (SELECT f(agg) FROM s WHERE s.k = x.k ...)` → semi join
        against `SELECT k, f(agg) FROM s ... GROUP BY k` with the
        comparison as the join residual (reference: the aggregate
        decorrelation in planner/core/rule_decorrelate.go pulls the
        correlated filter above the agg by injecting its columns into
        GROUP BY). Grouping by k yields exactly one row per key, so the
        semi-join residual equals the scalar comparison; a missing group
        means the scalar is NULL and the comparison filters the row —
        which the semi join's no-match case reproduces. COUNT bails: its
        empty-group scalar is 0, not NULL, and a semi join would wrongly
        drop the row."""
        from ..expression.builder import OuterScope, _OP_MAP
        if isinstance(conj.left, ast.SubqueryExpr):
            sub_ast, target_ast = conj.left.query, conj.right
            op = self._MIRROR_OP[conj.op]
        else:
            sub_ast, target_ast = conj.right.query, conj.left
            op = conj.op
        scope = OuterScope(from_schema, mark=True)
        try:
            subplan = self.ctx.analyze_subquery(sub_ast, scope)
        except Exception:
            return None
        if self._sub_memo is not None:
            self._sub_memo[id(sub_ast)] = (scope, subplan)
        if not scope.used:
            return None

        node = subplan
        proj = None
        if isinstance(node, Projection):
            proj = node
            node = node.child
        if not (isinstance(node, Aggregation) and not node.group_exprs):
            return None
        agg = node
        if any(d.name not in ("sum", "avg", "min", "max") or d.distinct
               for d in agg.aggs):
            return None
        if not isinstance(agg.child, Selection):
            return None
        sel_node = agg.child
        base = sel_node.child
        if proj is not None and len(proj.exprs) != 1:
            raise TiDBError("Operand should contain 1 column(s)",
                            code=ErrCode.OperandColumns)

        for nd in _walk_plan(subplan, []):
            if nd is sel_node:
                continue
            for e in _node_exprs(nd):
                acc = []
                _collect_outer_refs(e, acc)
                if acc:
                    return None

        residual, lkeys, ikeys = [], [], []
        for c in sel_node.conds:
            acc = []
            _collect_outer_refs(c, acc)
            if not acc:
                residual.append(c)
                continue
            key = _outer_eq_key(c)
            if key is None:
                return None
            lkeys.append(key[0])
            ikeys.append(key[1])
        if not lkeys:
            return None

        # regroup the aggregate by its correlation keys: output schema is
        # [keys..., original agg outputs...] (group keys lead — executor
        # contract), so the projection's column refs shift by len(keys)
        nk = len(lkeys)
        child = Selection(base, residual) if residual else base
        key_refs = [ColumnRef(getattr(e, "name", "") or f"dk{i}", "", "",
                              e.ftype)
                    for i, e in enumerate(ikeys)]
        new_agg = Aggregation(child, ikeys, agg.aggs,
                              Schema(key_refs + list(agg.schema.refs)))
        scalar = (proj.exprs[0] if proj is not None
                  else Column(0, agg.schema.refs[0].ftype))
        scalar = _shift(scalar, nk)

        b = ExprBuilder(from_schema, self.ctx, outer=self.outer)
        x = b.build(target_ast)
        acc = []
        _collect_outer_refs(x, acc)
        if acc:
            return None
        nl = len(from_schema)
        cmp_cond = ScalarFunc(_OP_MAP[op], [x, _shift(scalar, nl)],
                              _BOOL_FT.clone())
        rkeys = [Column(i, e.ftype) for i, e in enumerate(ikeys)]
        return "semi", new_agg, lkeys, rkeys, [cmp_cond]

    def build_select(self, sel: ast.SelectStmt) -> LogicalPlan:
        plan = self.build_from(sel.from_)
        from_schema = plan.schema
        if sel.hints:
            # optimizer hints ride on the query block's plan subtree; the
            # optimizer collects them tree-wide (reference: hint scopes,
            # planner/core/logical_plan_builder.go hint tables)
            plan.sql_hints = list(sel.hints)

        if sel.where is not None:
            # decorrelation first (reference: optimizer.go:73-91 decorrelate
            # + expression_rewriter.go): correlated EXISTS/IN conjuncts whose
            # correlation is equality-only become semi/anti joins — they hit
            # the (device-capable) join executors instead of the per-outer-
            # row Apply re-execution
            conjuncts = []
            _split_ast_and(sel.where, conjuncts)
            plain_ast, joins = [], []
            self._sub_memo = {}  # decorrelation-analysis reuse on bail
            for c in conjuncts:
                spec = self._try_decorrelate(c, from_schema)
                if spec is None:
                    plain_ast.append(c)
                else:
                    joins.append(spec)
            if plain_ast:
                b = ExprBuilder(from_schema, self.ctx, outer=self.outer)
                b.sub_memo = self._sub_memo
                conds = []
                for c in plain_ast:
                    conds.extend(split_cnf(b.build(c)))
                plan = Selection(plan, conds)
            self._sub_memo = None
            for kind, right_child, lkeys, rkeys, oconds in joins:
                j = Join(plan, right_child, kind, plan.schema)
                j.left_keys = lkeys
                j.right_keys = rkeys
                j.other_conds = oconds
                plan = j

        # -- aggregate detection
        agg_map = {}
        for f in sel.fields:
            if not isinstance(f.expr, ast.StarExpr):
                collect_aggs(f.expr, agg_map)
        collect_aggs(sel.having, agg_map)
        for bi in sel.order_by:
            collect_aggs(bi.expr, agg_map)
        has_agg = bool(agg_map) or bool(sel.group_by)

        alias_map = {}  # select alias -> field index (after building)
        hidden = 0

        if has_agg:
            plan, expr_builder = self._build_aggregation(plan, sel, agg_map)
        else:
            expr_builder = ExprBuilder(plan.schema, self.ctx, outer=self.outer)

        # -- window functions: evaluate over the post-agg/post-having rows
        # (reference: planner/core/logical_plan_builder.go buildWindowFunctions)
        win_map = {}
        for f in sel.fields:
            if not isinstance(f.expr, ast.StarExpr):
                collect_windows(f.expr, win_map)
        for bi in sel.order_by:
            collect_windows(bi.expr, win_map)
        having_applied = False
        if win_map:
            if sel.having is not None:
                # HAVING filters before windows compute (SQL eval order);
                # bare-alias refs are resolved later in the normal path and
                # cannot be supported here
                cond = expr_builder.build(sel.having)
                plan = Selection(plan, split_cnf(cond))
                having_applied = True
            plan, expr_builder = self._build_window(plan, expr_builder,
                                                    win_map)

        # -- star expansion + select expr building
        fields = []
        for f in sel.fields:
            if isinstance(f.expr, ast.StarExpr):
                if has_agg:
                    raise TiDBError("SELECT * with GROUP BY is not supported")
                for i, r in enumerate(expr_builder.schema.refs):
                    if f.expr.table and r.table != f.expr.table.lower():
                        continue
                    fields.append((Column(i, r.ftype, name=r.name), r.name))
                continue
            e = expr_builder.build(f.expr)
            name = f.as_name or _derive_name(f.expr)
            fields.append((e, name))

        for i, (_, name) in enumerate(fields):
            alias_map.setdefault(name.lower(), i)

        # -- having (after select aliases are known; may reference them)
        if sel.having is not None and not having_applied:
            cond = self._build_having(sel.having, expr_builder, fields, alias_map)
            plan = Selection(plan, split_cnf(cond))

        proj_exprs = [e for e, _ in fields]
        proj_names = [n for _, n in fields]
        visible = len(proj_exprs)

        # -- order by: resolve against output aliases/positions, else add
        # hidden columns computed from the pre-projection schema
        sort_items = []
        for bi in sel.order_by:
            idx = self._resolve_by_item(bi.expr, fields, alias_map, expr_builder)
            if idx is not None:
                sort_items.append((idx, bi.desc))
            else:
                e = expr_builder.build(bi.expr)
                match = None
                for i, pe in enumerate(proj_exprs):
                    if repr(pe) == repr(e):
                        match = i
                        break
                if match is None:
                    proj_exprs.append(e)
                    proj_names.append(f"__sort_{len(proj_exprs)}")
                    match = len(proj_exprs) - 1
                sort_items.append((match, bi.desc))

        refs = [ColumnRef(n, "", "", e.ftype) for e, n in zip(proj_exprs, proj_names)]
        plan = Projection(plan, proj_exprs, Schema(refs))

        if sel.distinct:
            plan = self._build_distinct(plan, visible)

        by = [(Column(i, plan.schema.refs[i].ftype), d) for i, d in sort_items]
        plan = self._apply_order_limit_built(plan, by, sel.limit)

        if len(proj_exprs) > visible:
            trim_refs = plan.schema.refs[:visible]
            plan = Projection(plan, [Column(i, r.ftype, name=r.name)
                                     for i, r in enumerate(trim_refs)],
                              Schema(list(trim_refs)))
        return plan

    def _build_aggregation(self, plan, sel, agg_map):
        child_schema = plan.schema
        b = ExprBuilder(child_schema, self.ctx, outer=self.outer)
        group_exprs = []
        expr_map = {}
        refs = []
        for bi in sel.group_by:
            node = bi.expr
            # positional GROUP BY 2 and alias refs
            if isinstance(node, ast.Literal) and node.kind == "int":
                pos = int(node.val) - 1
                if pos < 0 or pos >= len(sel.fields):
                    raise TiDBError(f"Unknown column '{node.val}' in 'group statement'")
                node = sel.fields[pos].expr
            elif isinstance(node, ast.ColumnName) and not node.table:
                if child_schema.find(node) is None:
                    for f in sel.fields:
                        if f.as_name and f.as_name.lower() == node.name.lower():
                            node = f.expr
                            break
            e = b.build(node)
            group_exprs.append(e)
            key = node.restore()
            expr_map[key] = len(refs)
            if isinstance(e, Column):
                r = child_schema.refs[e.idx]
                refs.append(ColumnRef(r.name, r.table, r.db, r.ftype))
            else:
                refs.append(ColumnRef(key, "", "", e.ftype))
        aggs = []
        for key, node in agg_map.items():
            args = [b.build(a) for a in node.args]
            name = node.name
            if name == "count" and not args:
                args = [Constant(1, FieldType(tp=TYPE_LONGLONG))]
            if name in ("std", "stddev"):
                name = "stddev_pop"
            if name == "variance":
                name = "var_pop"
            desc = AggFuncDesc(name, args, distinct=node.distinct)
            expr_map[key] = len(refs)
            aggs.append(desc)
            refs.append(ColumnRef(key, "", "", desc.ftype))
        agg = Aggregation(plan, group_exprs, aggs, Schema(refs))
        return agg, AggExprBuilder(agg, child_schema, expr_map, self.ctx,
                                   outer=self.outer)

    def _build_window(self, plan, b, win_map):
        """Group the collected OVER() expressions by (partition, order)
        spec; one Window node per spec, stacked. The builder `b` gains a
        window_map so select-field building resolves each WindowFunc to its
        appended output column (reference: logical_plan_builder.go
        groupWindowFuncs)."""
        from .logical import WinFuncDesc, Window
        groups = {}
        for key, node in win_map.items():
            spec = (tuple(e.restore() for e in node.partition_by),
                    tuple((bi.expr.restore(), bi.desc)
                          for bi in node.order_by))
            groups.setdefault(spec, []).append((key, node))
        if not hasattr(b, "window_map"):
            b.window_map = {}
        for _spec, items in groups.items():
            part = [b.build(e) for e in items[0][1].partition_by]
            order = [(b.build(bi.expr), bi.desc)
                     for bi in items[0][1].order_by]
            funcs = []
            refs = list(plan.schema.refs)
            for key, node in items:
                args = [b.build(a) for a in node.args]
                name = node.name.lower()
                if name == "count" and not args:  # count(*) over (...)
                    args = [Constant(1, FieldType(tp=TYPE_LONGLONG))]
                ft = _window_ftype(name, args)
                frame = _normalize_frame(node.frame, name)
                b.window_map[key] = Column(len(refs), ft, name=key)
                funcs.append(WinFuncDesc(name, args, ft, frame))
                refs.append(ColumnRef(key, "", "", ft))
            plan = Window(plan, funcs, part, order, Schema(refs))
        return plan, b

    def _build_having(self, having, expr_builder, fields, alias_map):
        # rewrite bare alias references to the built select expressions
        if isinstance(having, ast.ColumnName) and not having.table:
            i = alias_map.get(having.name.lower())
            if i is not None and expr_builder.schema.find(having) is None:
                return fields[i][0]
        try:
            return expr_builder.build(having)
        except ColumnError:
            rewritten = _substitute_aliases(having, alias_map, fields)
            if rewritten is not None:
                return rewritten
            raise

    def _build_distinct(self, plan, visible):
        group = [Column(i, r.ftype) for i, r in enumerate(plan.schema.refs)]
        aggs = []
        refs = [ColumnRef(r.name, r.table, r.db, r.ftype) for r in plan.schema.refs]
        return Aggregation(plan, group, aggs, Schema(refs))

    def _resolve_by_item(self, node, fields, alias_map, expr_builder):
        if isinstance(node, ast.Literal) and node.kind == "int":
            pos = int(node.val) - 1
            if pos < 0 or pos >= len(fields):
                raise TiDBError(f"Unknown column '{node.val}' in 'order clause'")
            return pos
        if isinstance(node, ast.ColumnName) and not node.table:
            # output alias wins only if not resolvable in the source schema?
            # MySQL: ORDER BY prefers select aliases for bare names.
            i = alias_map.get(node.name.lower())
            if i is not None:
                return i
        return None

    def _apply_order_limit_built(self, plan, by, limit):
        offset, count = self._limit_values(limit)
        if by:
            if count is not None:
                return TopN(plan, by, offset or 0, count)
            return Sort(plan, by)
        if count is not None:
            return Limit(plan, offset or 0, count)
        return plan

    def _apply_order_limit(self, plan, order_by, limit, b, _fields):
        by = []
        for bi in order_by:
            node = bi.expr
            if isinstance(node, ast.Literal) and node.kind == "int":
                pos = int(node.val) - 1
                by.append((Column(pos, plan.schema.refs[pos].ftype), bi.desc))
            else:
                by.append((b.build(node), bi.desc))
        return self._apply_order_limit_built(plan, by, limit)

    def _limit_values(self, limit):
        if limit is None:
            return None, None
        b = ExprBuilder(Schema([]), self.ctx, outer=self.outer)
        count = b.build(limit.count).eval_scalar() if limit.count is not None else None
        offset = b.build(limit.offset).eval_scalar() if limit.offset is not None else 0
        return int(offset or 0), (int(count) if count is not None else None)


def _shift(expr, delta):
    return expr.transform_columns(
        lambda c: Column(c.idx + delta, c.ftype, name=c.name))


def _outer_eq_key(c):
    """(outer key Column, inner key expr) of a correlated conjunct of the
    form eq(OuterRef, inner) with both sides of one physical kind; None
    for any other shape."""
    from ..expression import phys_kind
    from ..expression.core import OuterRef
    if not (isinstance(c, ScalarFunc) and c.op == "eq"
            and len(c.args) == 2):
        return None
    for outer_ref, inner in (c.args, c.args[::-1]):
        acc = []
        _collect_outer_refs(inner, acc)
        if (isinstance(outer_ref, OuterRef) and not acc
                and phys_kind(outer_ref.ftype) == phys_kind(inner.ftype)):
            return (Column(outer_ref.idx, outer_ref.ftype,
                           name=outer_ref.name), inner)
    return None


def bind_outer_refs(e, nl):
    """A correlated conjunct over a semi / anti join's joined schema
    [outer | inner]: an OuterRef reads the outer column at its index, an
    inner column shifts past the `nl` outer ones.  (benchmark/queries/
    q21.py asks for this name to tell a planner that keeps such a
    conjunct as the join's residual from one that leaves it to Apply.)"""
    from ..expression.core import OuterRef
    if isinstance(e, OuterRef):
        return Column(e.idx, e.ftype, name=e.name)
    if isinstance(e, Column):
        return Column(e.idx + nl, e.ftype, name=e.name)
    if isinstance(e, ScalarFunc):
        return ScalarFunc(e.op, [bind_outer_refs(a, nl) for a in e.args],
                          e.ftype, e.extra)
    return e


def _split_ast_and(e, out):
    if isinstance(e, ast.BinaryOp) and e.op == "and":
        _split_ast_and(e.left, out)
        _split_ast_and(e.right, out)
    else:
        out.append(e)


def _collect_outer_refs(e, acc):
    """OuterRef markers (and nested Apply expressions, which also pin the
    conjunct to the fallback path) anywhere under `e`."""
    from ..expression.core import OuterRef, SubqueryApply
    if isinstance(e, (OuterRef, SubqueryApply)):
        acc.append(e)
        return
    for a in getattr(e, "args", None) or ():
        _collect_outer_refs(a, acc)


def _node_exprs(p):
    if isinstance(p, Selection):
        return list(p.conds)
    if isinstance(p, Projection):
        return list(p.exprs)
    if isinstance(p, Join):
        return list(p.left_keys) + list(p.right_keys) + list(p.other_conds)
    if isinstance(p, Aggregation):
        return list(p.group_exprs) + [a for d in p.aggs for a in d.args]
    if isinstance(p, (Sort, TopN)):
        return [e for e, _d in p.by]
    if isinstance(p, Window):
        return (list(p.partition_exprs) + [e for e, _d in p.order_by]
                + [a for f in p.funcs for a in f.args])
    if isinstance(p, DataSource):
        return list(p.pushed_conds)
    return []


def _walk_plan(p, out):
    out.append(p)
    for c in p.children:
        _walk_plan(c, out)
    return out


def _schema_table(schema: Schema, colname: str):
    for r in schema.refs:
        if r.name == colname.lower():
            return r.table
    return ""


def _derive_name(node) -> str:
    if isinstance(node, ast.ColumnName):
        return node.name
    r = node.restore()
    return r if len(r) <= 64 else r[:64]


def _substitute_aliases(node, alias_map, fields):
    """HAVING alias substitution fallback — only simple comparisons."""
    if isinstance(node, ast.BinaryOp):
        for side in ("left", "right"):
            sub = getattr(node, side)
            if isinstance(sub, ast.ColumnName) and not sub.table:
                i = alias_map.get(sub.name.lower())
                if i is not None:
                    pass
    return None
