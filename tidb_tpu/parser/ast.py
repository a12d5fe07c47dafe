"""AST node definitions with SQL restore (reference: parser/ast/ — dml.go,
ddl.go, expressions.go; Node.Restore). Nodes are plain dataclasses; the
visitor of the reference becomes ad-hoc traversal in the planner."""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional

from ..sqltypes import FieldType


class Node:
    def restore(self) -> str:
        raise NotImplementedError(type(self).__name__)

    def __repr__(self):
        try:
            return f"<{type(self).__name__} {self.restore()}>"
        except Exception:
            return f"<{type(self).__name__}>"


# ---------------------------------------------------------------------------
# Expressions
# ---------------------------------------------------------------------------

class ExprNode(Node):
    pass


@dataclass(repr=False)
class Literal(ExprNode):
    """Constant literal. kind: int|dec|float|str|null|bool|date|time|hex.
    `val` keeps the lexical value (dec keeps text to preserve scale)."""
    kind: str
    val: object

    def restore(self):
        if self.kind == "null":
            return "NULL"
        if self.kind == "str":
            return "'" + str(self.val).replace("\\", "\\\\").replace("'", "\\'") + "'"
        if self.kind == "bool":
            return "TRUE" if self.val else "FALSE"
        if self.kind in ("date", "time", "datetime"):
            kw = {"date": "DATE", "time": "TIME", "datetime": "TIMESTAMP"}[self.kind]
            return f"{kw} '{self.val}'"
        return str(self.val)


@dataclass(repr=False)
class ColumnName(ExprNode):
    name: str
    table: str = ""
    schema: str = ""

    def restore(self):
        parts = [p for p in (self.schema, self.table, self.name) if p]
        return ".".join(f"`{p}`" for p in parts)


@dataclass(repr=False)
class ParamMarker(ExprNode):
    index: int = 0

    def restore(self):
        return "?"


@dataclass(repr=False)
class VariableExpr(ExprNode):
    name: str
    is_system: bool = False
    scope: str = ""  # "", "global", "session"
    value: Optional[ExprNode] = None  # for @v := expr

    def restore(self):
        if self.is_system:
            pre = f"@@{self.scope}." if self.scope else "@@"
            return pre + self.name
        return "@" + self.name


@dataclass(repr=False)
class BinaryOp(ExprNode):
    op: str  # lowercase: and or xor + - * / div mod % = <=> < > <= >= != like & | ^ << >>
    left: ExprNode
    right: ExprNode

    def restore(self):
        return f"({self.left.restore()} {self.op.upper()} {self.right.restore()})"


@dataclass(repr=False)
class UnaryOp(ExprNode):
    op: str  # - not ~ !
    operand: ExprNode

    def restore(self):
        return f"({self.op.upper()} {self.operand.restore()})"


@dataclass(repr=False)
class IsNullExpr(ExprNode):
    expr: ExprNode
    negated: bool = False

    def restore(self):
        return f"({self.expr.restore()} IS {'NOT ' if self.negated else ''}NULL)"


@dataclass(repr=False)
class IsTruthExpr(ExprNode):
    expr: ExprNode
    truth: bool = True
    negated: bool = False

    def restore(self):
        return f"({self.expr.restore()} IS {'NOT ' if self.negated else ''}{'TRUE' if self.truth else 'FALSE'})"


@dataclass(repr=False)
class BetweenExpr(ExprNode):
    expr: ExprNode
    low: ExprNode
    high: ExprNode
    negated: bool = False

    def restore(self):
        return (f"({self.expr.restore()} {'NOT ' if self.negated else ''}BETWEEN "
                f"{self.low.restore()} AND {self.high.restore()})")


@dataclass(repr=False)
class InExpr(ExprNode):
    expr: ExprNode
    items: list = field(default_factory=list)  # list[ExprNode] OR [SubqueryExpr]
    negated: bool = False

    def restore(self):
        inner = ", ".join(e.restore() for e in self.items)
        return f"({self.expr.restore()} {'NOT ' if self.negated else ''}IN ({inner}))"


@dataclass(repr=False)
class LikeExpr(ExprNode):
    expr: ExprNode
    pattern: ExprNode
    negated: bool = False
    escape: str = "\\"

    def restore(self):
        return f"({self.expr.restore()} {'NOT ' if self.negated else ''}LIKE {self.pattern.restore()})"


@dataclass(repr=False)
class RegexpExpr(ExprNode):
    expr: ExprNode
    pattern: ExprNode
    negated: bool = False

    def restore(self):
        return f"({self.expr.restore()} {'NOT ' if self.negated else ''}REGEXP {self.pattern.restore()})"


@dataclass(repr=False)
class CaseExpr(ExprNode):
    operand: Optional[ExprNode]
    whens: list = field(default_factory=list)  # [(cond, result)]
    else_: Optional[ExprNode] = None

    def restore(self):
        s = "CASE"
        if self.operand:
            s += " " + self.operand.restore()
        for c, r in self.whens:
            s += f" WHEN {c.restore()} THEN {r.restore()}"
        if self.else_:
            s += " ELSE " + self.else_.restore()
        return s + " END"


@dataclass(repr=False)
class FuncCall(ExprNode):
    name: str  # lowercase
    args: list = field(default_factory=list)

    def restore(self):
        return f"{self.name.upper()}({', '.join(a.restore() for a in self.args)})"


@dataclass(repr=False)
class AggregateFunc(ExprNode):
    name: str  # count sum avg min max group_concat bit_or bit_and var_pop stddev_pop
    args: list = field(default_factory=list)
    distinct: bool = False

    def restore(self):
        inner = "*" if not self.args else ", ".join(a.restore() for a in self.args)
        return f"{self.name.upper()}({'DISTINCT ' if self.distinct else ''}{inner})"


@dataclass(repr=False)
class WindowFunc(ExprNode):
    name: str
    args: list = field(default_factory=list)
    partition_by: list = field(default_factory=list)
    order_by: list = field(default_factory=list)  # [ByItem]
    frame: object = None

    def restore(self):
        s = f"{self.name.upper()}({', '.join(a.restore() for a in self.args)}) OVER ("
        if self.partition_by:
            s += "PARTITION BY " + ", ".join(e.restore() for e in self.partition_by)
        if self.order_by:
            s += " ORDER BY " + ", ".join(b.restore() for b in self.order_by)
        if self.frame is not None:
            # frame participates in dedup: same func text with different
            # frames must NOT share one window output column
            unit, lo, hi = self.frame
            def bnd(b):
                kind, n = b
                return {"unbounded_preceding": "UNBOUNDED PRECEDING",
                        "unbounded_following": "UNBOUNDED FOLLOWING",
                        "current": "CURRENT ROW",
                        "preceding": f"{n} PRECEDING",
                        "following": f"{n} FOLLOWING"}[kind]
            s += f" {unit.upper()} BETWEEN {bnd(lo)} AND {bnd(hi)}"
        return s + ")"


@dataclass(repr=False)
class SubqueryExpr(ExprNode):
    query: "SelectStmt"

    def restore(self):
        return f"({self.query.restore()})"


@dataclass(repr=False)
class ExistsExpr(ExprNode):
    query: SubqueryExpr
    negated: bool = False

    def restore(self):
        return f"({'NOT ' if self.negated else ''}EXISTS {self.query.restore()})"


@dataclass(repr=False)
class CompareSubquery(ExprNode):
    """expr op ANY/ALL (subquery)"""
    op: str
    expr: ExprNode
    query: SubqueryExpr
    quantifier: str = "any"  # any | all

    def restore(self):
        return f"({self.expr.restore()} {self.op.upper()} {self.quantifier.upper()} {self.query.restore()})"


@dataclass(repr=False)
class RowExpr(ExprNode):
    items: list = field(default_factory=list)

    def restore(self):
        return "(" + ", ".join(e.restore() for e in self.items) + ")"


@dataclass(repr=False)
class CastExpr(ExprNode):
    expr: ExprNode
    ftype: FieldType

    def restore(self):
        return f"CAST({self.expr.restore()} AS {self.ftype.sql_string()})"


@dataclass(repr=False)
class IntervalExpr(ExprNode):
    value: ExprNode
    unit: str  # day month year hour minute second week quarter microsecond

    def restore(self):
        return f"INTERVAL {self.value.restore()} {self.unit.upper()}"


@dataclass(repr=False)
class DefaultExpr(ExprNode):
    col: Optional[ColumnName] = None

    def restore(self):
        return "DEFAULT"


@dataclass(repr=False)
class StarExpr(ExprNode):
    table: str = ""
    schema: str = ""

    def restore(self):
        pre = ".".join(f"`{p}`" for p in (self.schema, self.table) if p)
        return (pre + "." if pre else "") + "*"


# ---------------------------------------------------------------------------
# Table references
# ---------------------------------------------------------------------------

@dataclass(repr=False)
class TableName(Node):
    name: str
    schema: str = ""
    as_name: str = ""
    index_hints: list = field(default_factory=list)
    partition_names: list = field(default_factory=list)
    as_of: object = None  # AS OF TIMESTAMP expr (stale read)

    def restore(self):
        s = (f"`{self.schema}`." if self.schema else "") + f"`{self.name}`"
        if self.as_of is not None:
            s += f" AS OF TIMESTAMP {self.as_of.restore()}"
        if self.partition_names:
            s += " PARTITION (" + ", ".join(
                f"`{p}`" for p in self.partition_names) + ")"
        if self.as_name:
            s += f" AS `{self.as_name}`"
        for verb, names in self.index_hints:
            s += (f" {verb.upper()} INDEX ("
                  + ", ".join(f"`{n}`" for n in names) + ")")
        return s


@dataclass(repr=False)
class SubqueryTable(Node):
    query: "SelectStmt"
    as_name: str = ""
    #: the alias's column list, `(select ...) as t (a, b)`; a CTE's too
    col_names: list = field(default_factory=list)

    def restore(self):
        s = f"({self.query.restore()}) AS `{self.as_name}`"
        if self.col_names:
            s += " (" + ", ".join(f"`{c}`" for c in self.col_names) + ")"
        return s


@dataclass(repr=False)
class RecursiveCTETable(Node):
    """A FROM reference to a recursive CTE: body is the full UNION whose
    self-referencing branches iterate (reference: executor/cte.go)."""
    name: str
    cols: list = field(default_factory=list)
    query: "SetOprStmt" = None
    as_name: str = ""

    def restore(self):
        return f"`{self.name}`" + (f" AS `{self.as_name}`"
                                   if self.as_name else "")


@dataclass(repr=False)
class Join(Node):
    left: Node
    right: Node
    kind: str = "inner"  # inner | left | right | cross
    on: Optional[ExprNode] = None
    using: list = field(default_factory=list)

    def restore(self):
        k = {"inner": "JOIN", "cross": "CROSS JOIN",
             "left": "LEFT JOIN", "right": "RIGHT JOIN"}[self.kind]
        s = f"{self.left.restore()} {k} {self.right.restore()}"
        if self.on is not None:
            s += f" ON {self.on.restore()}"
        elif self.using:
            s += " USING (" + ", ".join(f"`{c}`" for c in self.using) + ")"
        return s


# ---------------------------------------------------------------------------
# Statements
# ---------------------------------------------------------------------------

class StmtNode(Node):
    pass


@dataclass(repr=False)
class ByItem(Node):
    expr: ExprNode
    desc: bool = False

    def restore(self):
        return self.expr.restore() + (" DESC" if self.desc else "")


@dataclass(repr=False)
class Limit(Node):
    count: Optional[ExprNode] = None
    offset: Optional[ExprNode] = None

    def restore(self):
        s = "LIMIT "
        if self.offset is not None:
            s += f"{self.offset.restore()}, "
        return s + self.count.restore()


@dataclass(repr=False)
class SelectField(Node):
    expr: ExprNode
    as_name: str = ""

    def restore(self):
        s = self.expr.restore()
        if self.as_name:
            s += f" AS `{self.as_name}`"
        return s


@dataclass(repr=False)
class SelectStmt(StmtNode):
    fields: list = field(default_factory=list)       # [SelectField]
    from_: Optional[Node] = None
    where: Optional[ExprNode] = None
    group_by: list = field(default_factory=list)     # [ByItem]
    having: Optional[ExprNode] = None
    order_by: list = field(default_factory=list)     # [ByItem]
    limit: Optional[Limit] = None
    distinct: bool = False
    for_update: bool = False
    lock_in_share_mode: bool = False
    with_ctes: list = field(default_factory=list)    # [(name, [cols], stmt)]
    with_recursive: bool = False
    hints: list = field(default_factory=list)        # [(name, [args])] from /*+ */

    def restore(self):
        s = ""
        if self.with_ctes:
            parts = []
            for name, cols, stmt in self.with_ctes:
                c = f" ({', '.join(cols)})" if cols else ""
                parts.append(f"`{name}`{c} AS ({stmt.restore()})")
            s += ("WITH RECURSIVE " if self.with_recursive else "WITH ") \
                + ", ".join(parts) + " "
        s += "SELECT "
        if self.hints:
            def arg(a):  # bracket groups re-render as parens to reparse
                return a.replace("[", "(").replace("]", ")")
            rendered = " ".join(
                f"{name.upper()}({', '.join(arg(a) for a in args)})"
                if args else f"{name.upper()}()"
                for name, args in self.hints)
            s += f"/*+ {rendered} */ "
        s += "DISTINCT " if self.distinct else ""
        s += ", ".join(f.restore() for f in self.fields)
        if self.from_ is not None:
            s += " FROM " + self.from_.restore()
        if self.where is not None:
            s += " WHERE " + self.where.restore()
        if self.group_by:
            s += " GROUP BY " + ", ".join(b.restore() for b in self.group_by)
        if self.having is not None:
            s += " HAVING " + self.having.restore()
        if self.order_by:
            s += " ORDER BY " + ", ".join(b.restore() for b in self.order_by)
        if self.limit is not None:
            s += " " + self.limit.restore()
        if self.for_update:
            s += " FOR UPDATE"
        return s


@dataclass(repr=False)
class SetOprStmt(StmtNode):
    """UNION / UNION ALL / INTERSECT / EXCEPT chain."""
    selects: list = field(default_factory=list)   # [SelectStmt]
    ops: list = field(default_factory=list)       # ["union"|"union all"|...] len-1
    order_by: list = field(default_factory=list)
    limit: Optional[Limit] = None

    def restore(self):
        parts = [self.selects[0].restore()]
        for op, sel in zip(self.ops, self.selects[1:]):
            parts.append(op.upper())
            parts.append(sel.restore())
        s = " ".join(parts)
        if self.order_by:
            s += " ORDER BY " + ", ".join(b.restore() for b in self.order_by)
        if self.limit:
            s += " " + self.limit.restore()
        return s


@dataclass(repr=False)
class InsertStmt(StmtNode):
    table: TableName = None
    columns: list = field(default_factory=list)       # [str]
    values: list = field(default_factory=list)        # [[ExprNode]]
    select: Optional[SelectStmt] = None
    is_replace: bool = False
    ignore: bool = False
    on_duplicate: list = field(default_factory=list)  # [(ColumnName, ExprNode)]

    def restore(self):
        verb = "REPLACE" if self.is_replace else "INSERT"
        s = f"{verb} {'IGNORE ' if self.ignore else ''}INTO {self.table.restore()}"
        if self.columns:
            s += " (" + ", ".join(f"`{c}`" for c in self.columns) + ")"
        if self.select is not None:
            s += " " + self.select.restore()
        else:
            rows = ", ".join("(" + ", ".join(e.restore() for e in row) + ")"
                             for row in self.values)
            s += " VALUES " + rows
        if self.on_duplicate:
            s += " ON DUPLICATE KEY UPDATE " + ", ".join(
                f"{c.restore()}={e.restore()}" for c, e in self.on_duplicate)
        return s


@dataclass(repr=False)
class UpdateStmt(StmtNode):
    table: Node = None
    assignments: list = field(default_factory=list)  # [(ColumnName, ExprNode)]
    where: Optional[ExprNode] = None
    order_by: list = field(default_factory=list)
    limit: Optional[Limit] = None

    def restore(self):
        s = f"UPDATE {self.table.restore()} SET "
        s += ", ".join(f"{c.restore()}={e.restore()}" for c, e in self.assignments)
        if self.where is not None:
            s += " WHERE " + self.where.restore()
        if self.order_by:
            s += " ORDER BY " + ", ".join(b.restore() for b in self.order_by)
        if self.limit:
            s += " " + self.limit.restore()
        return s


@dataclass(repr=False)
class DeleteStmt(StmtNode):
    table: Node = None
    where: Optional[ExprNode] = None
    order_by: list = field(default_factory=list)
    limit: Optional[Limit] = None
    targets: list = field(default_factory=list)  # multi-table: [TableName]

    def restore(self):
        if self.targets:
            s = ("DELETE " + ", ".join(t.restore() for t in self.targets)
                 + f" FROM {self.table.restore()}")
            if self.where is not None:
                s += " WHERE " + self.where.restore()
            return s
        s = f"DELETE FROM {self.table.restore()}"
        if self.where is not None:
            s += " WHERE " + self.where.restore()
        if self.order_by:
            s += " ORDER BY " + ", ".join(b.restore() for b in self.order_by)
        if self.limit:
            s += " " + self.limit.restore()
        return s


# -- DDL --------------------------------------------------------------------

@dataclass(repr=False)
class ColumnDef(Node):
    name: str
    ftype: FieldType = None
    options: dict = field(default_factory=dict)
    # options keys: not_null, null, primary, unique, auto_increment,
    #               default (ExprNode), comment (str), on_update (ExprNode)

    def restore(self):
        s = f"`{self.name}` {self.ftype.sql_string()}"
        if self.options.get("not_null"):
            s += " NOT NULL"
        if self.options.get("auto_increment"):
            s += " AUTO_INCREMENT"
        if "default" in self.options:
            s += f" DEFAULT {self.options['default'].restore()}"
        if self.options.get("primary"):
            s += " PRIMARY KEY"
        if self.options.get("unique"):
            s += " UNIQUE"
        return s


@dataclass(repr=False)
class Constraint(Node):
    kind: str  # primary | unique | index | fulltext | foreign
    name: str = ""
    columns: list = field(default_factory=list)  # [(colname, length|None)]
    ref: object = None

    def restore(self):
        cols = ", ".join(f"`{c}`" for c, _ in self.columns)
        if self.kind == "primary":
            return f"PRIMARY KEY ({cols})"
        if self.kind == "unique":
            return f"UNIQUE KEY `{self.name}` ({cols})"
        return f"KEY `{self.name}` ({cols})"


@dataclass(repr=False)
class PartitionOpt(Node):
    """PARTITION BY clause (reference: parser/ast/ddl.go PartitionOptions).
    defs: [(name, kind, values)] where kind is "less_than" (values a 1-list
    of ExprNode or the string MAXVALUE) or "in" (values a list of ExprNode)."""
    type: str = "range"            # range | hash | list
    expr: "ExprNode" = None
    num: int = 0                   # HASH ... PARTITIONS n
    defs: list = field(default_factory=list)

    def restore(self):
        s = f"PARTITION BY {self.type.upper()} ({self.expr.restore()})"
        if self.type == "hash":
            return s + f" PARTITIONS {self.num}"
        parts = []
        for name, kind, values in self.defs:
            if kind == "less_than":
                v = values[0]
                vs = v if isinstance(v, str) else f"({v.restore()})"
                parts.append(f"PARTITION `{name}` VALUES LESS THAN {vs}")
            else:
                vs = ", ".join("NULL" if v is None else v.restore()
                               for v in values)
                parts.append(f"PARTITION `{name}` VALUES IN ({vs})")
        return s + " (" + ", ".join(parts) + ")"


@dataclass(repr=False)
class CreateTableStmt(StmtNode):
    table: TableName = None
    columns: list = field(default_factory=list)      # [ColumnDef]
    constraints: list = field(default_factory=list)  # [Constraint]
    if_not_exists: bool = False
    options: dict = field(default_factory=dict)      # engine, charset, auto_increment, comment
    like: Optional[TableName] = None
    select: Optional[SelectStmt] = None
    partition: Optional[PartitionOpt] = None
    temporary: bool = False

    def restore(self):
        s = ("CREATE TEMPORARY TABLE " if self.temporary
             else "CREATE TABLE ")
        if self.if_not_exists:
            s += "IF NOT EXISTS "
        s += self.table.restore()
        if self.like is not None:
            return s + f" LIKE {self.like.restore()}"
        items = [c.restore() for c in self.columns] + [c.restore() for c in self.constraints]
        s += " (" + ", ".join(items) + ")"
        if self.partition is not None:
            s += " " + self.partition.restore()
        return s


@dataclass(repr=False)
class CreateViewStmt(StmtNode):
    """CREATE [OR REPLACE] VIEW name [(cols)] AS select
    (reference: parser/ast/ddl.go CreateViewStmt)."""
    view: TableName = None
    cols: list = field(default_factory=list)
    select: object = None       # SelectStmt | SetOprStmt
    or_replace: bool = False
    definer: str = ""

    def restore(self):
        s = "CREATE "
        if self.or_replace:
            s += "OR REPLACE "
        s += "VIEW " + self.view.restore()
        if self.cols:
            s += " (" + ", ".join(f"`{c}`" for c in self.cols) + ")"
        return s + " AS " + self.select.restore()


@dataclass(repr=False)
class CreateBindingStmt(StmtNode):
    """CREATE [GLOBAL|SESSION] BINDING FOR stmt USING hinted_stmt
    (reference: parser/ast/misc.go CreateBindingStmt)."""
    original: object = None
    hinted: object = None
    is_global: bool = False

    def restore(self):
        scope = "GLOBAL" if self.is_global else "SESSION"
        return (f"CREATE {scope} BINDING FOR {self.original.restore()} "
                f"USING {self.hinted.restore()}")


@dataclass(repr=False)
class CreatePlacementPolicyStmt(StmtNode):
    name: str = ""
    if_not_exists: bool = False
    options: dict = field(default_factory=dict)
    or_alter: bool = False  # ALTER PLACEMENT POLICY reuses the node

    def restore(self):
        opts = " ".join(f"{k.upper()}={v!r}" for k, v in
                        self.options.items())
        verb = "ALTER" if self.or_alter else "CREATE"
        return f"{verb} PLACEMENT POLICY `{self.name}` {opts}"


@dataclass(repr=False)
class DropPlacementPolicyStmt(StmtNode):
    name: str = ""
    if_exists: bool = False

    def restore(self):
        return f"DROP PLACEMENT POLICY `{self.name}`"


@dataclass(repr=False)
class DropBindingStmt(StmtNode):
    original: object = None
    is_global: bool = False

    def restore(self):
        scope = "GLOBAL" if self.is_global else "SESSION"
        return f"DROP {scope} BINDING FOR {self.original.restore()}"


@dataclass(repr=False)
class RecoverTableStmt(StmtNode):
    """RECOVER TABLE t / FLASHBACK TABLE t [TO new] (reference:
    ddl/ddl_api.go RecoverTable + FlashbackTable over delayed
    delete-ranges)."""
    table: TableName = None
    new_name: str = ""
    flashback: bool = False

    def restore(self):
        kw = "FLASHBACK" if self.flashback else "RECOVER"
        s = f"{kw} TABLE {self.table.restore()}"
        if self.new_name:
            s += f" TO `{self.new_name}`"
        return s


@dataclass(repr=False)
class LockTablesStmt(StmtNode):
    """LOCK TABLES t READ|WRITE, ... (reference: ddl/table_lock.go)."""
    items: list = field(default_factory=list)  # [(TableName, "read"|"write")]

    def restore(self):
        return "LOCK TABLES " + ", ".join(
            f"{tn.restore()} {m.upper()}" for tn, m in self.items)


@dataclass(repr=False)
class UnlockTablesStmt(StmtNode):
    def restore(self):
        return "UNLOCK TABLES"


@dataclass(repr=False)
class CreateSequenceStmt(StmtNode):
    """reference: parser/ast/ddl.go CreateSequenceStmt + ddl/sequence.go."""
    name: TableName = None
    if_not_exists: bool = False
    options: dict = field(default_factory=dict)  # start/increment/min/max/cache/cycle

    def restore(self):
        s = "CREATE SEQUENCE "
        if self.if_not_exists:
            s += "IF NOT EXISTS "
        s += self.name.restore()
        o = self.options
        if "start" in o:
            s += f" START WITH {o['start']}"
        if "increment" in o:
            s += f" INCREMENT BY {o['increment']}"
        if "min" in o:
            s += f" MINVALUE {o['min']}"
        if "max" in o:
            s += f" MAXVALUE {o['max']}"
        if "cache" in o:
            s += f" CACHE {o['cache']}" if o["cache"] else " NOCACHE"
        if o.get("cycle"):
            s += " CYCLE"
        return s


@dataclass(repr=False)
class DropSequenceStmt(StmtNode):
    sequences: list = field(default_factory=list)
    if_exists: bool = False

    def restore(self):
        return ("DROP SEQUENCE " + ("IF EXISTS " if self.if_exists else "")
                + ", ".join(t.restore() for t in self.sequences))


@dataclass(repr=False)
class DropTableStmt(StmtNode):
    tables: list = field(default_factory=list)
    if_exists: bool = False
    is_view: bool = False
    temporary: bool = False

    def restore(self):
        return (f"DROP {'VIEW' if self.is_view else 'TABLE'} "
                + ("IF EXISTS " if self.if_exists else "")
                + ", ".join(t.restore() for t in self.tables))


@dataclass(repr=False)
class TruncateTableStmt(StmtNode):
    table: TableName = None

    def restore(self):
        return f"TRUNCATE TABLE {self.table.restore()}"


@dataclass(repr=False)
class CreateDatabaseStmt(StmtNode):
    name: str = ""
    if_not_exists: bool = False

    def restore(self):
        return "CREATE DATABASE " + ("IF NOT EXISTS " if self.if_not_exists else "") + f"`{self.name}`"


@dataclass(repr=False)
class DropDatabaseStmt(StmtNode):
    name: str = ""
    if_exists: bool = False

    def restore(self):
        return "DROP DATABASE " + ("IF EXISTS " if self.if_exists else "") + f"`{self.name}`"


@dataclass(repr=False)
class CreateIndexStmt(StmtNode):
    index_name: str = ""
    table: TableName = None
    columns: list = field(default_factory=list)
    unique: bool = False
    if_not_exists: bool = False

    def restore(self):
        return (f"CREATE {'UNIQUE ' if self.unique else ''}INDEX `{self.index_name}` "
                f"ON {self.table.restore()} ("
                + ", ".join(f"`{c}`" for c, _ in self.columns) + ")")


@dataclass(repr=False)
class DropIndexStmt(StmtNode):
    index_name: str = ""
    table: TableName = None
    if_exists: bool = False

    def restore(self):
        return f"DROP INDEX `{self.index_name}` ON {self.table.restore()}"


@dataclass(repr=False)
class AlterTableStmt(StmtNode):
    table: TableName = None
    specs: list = field(default_factory=list)
    # spec: ("add_column", ColumnDef, pos) | ("drop_column", name)
    #     | ("add_index", Constraint) | ("drop_index", name)
    #     | ("modify_column", ColumnDef) | ("change_column", old, ColumnDef)
    #     | ("rename", TableName) | ("add_primary", Constraint) | ("drop_primary",)
    #     | ("auto_increment", int)

    def restore(self):
        return f"ALTER TABLE {self.table.restore()} ..."


@dataclass(repr=False)
class RenameTableStmt(StmtNode):
    pairs: list = field(default_factory=list)  # [(TableName, TableName)]

    def restore(self):
        return "RENAME TABLE " + ", ".join(
            f"{a.restore()} TO {b.restore()}" for a, b in self.pairs)


# -- simple statements ------------------------------------------------------

@dataclass(repr=False)
class UseStmt(StmtNode):
    db: str = ""

    def restore(self):
        return f"USE `{self.db}`"


@dataclass(repr=False)
class SetStmt(StmtNode):
    # items: [(scope, name, ExprNode)] scope in {"session","global","user"}
    items: list = field(default_factory=list)

    def restore(self):
        return "SET " + ", ".join(f"{s + '.' if s not in ('', 'user') else ''}{n}={e.restore()}"
                                  for s, n, e in self.items)


@dataclass(repr=False)
class BRIEStmt(StmtNode):
    """BACKUP DATABASE x TO 'dir' / RESTORE DATABASE x FROM 'dir'
    (reference: executor/brie.go BRIE statements)."""
    kind: str = ""      # backup | restore
    db: str = ""
    path: str = ""
    mode: str = ""      # '' (logical default) | physical | logical

    def restore(self):
        prep = "TO" if self.kind == "backup" else "FROM"
        s = f"{self.kind.upper()} DATABASE `{self.db}` {prep} '{self.path}'"
        if self.mode:
            s += f" MODE {self.mode.upper()}"
        return s


@dataclass(repr=False)
class CreateUserStmt(StmtNode):
    users: list = field(default_factory=list)  # [(user, host, pw, plugin)]
    if_not_exists: bool = False

    def restore(self):
        return "CREATE USER " + ", ".join(
            f"'{u[0]}'@'{u[1]}'" for u in self.users)


@dataclass(repr=False)
class DropUserStmt(StmtNode):
    users: list = field(default_factory=list)  # [(user, host)]
    if_exists: bool = False

    def restore(self):
        return "DROP USER " + ", ".join(
            f"'{u}'@'{h}'" for u, h in self.users)


@dataclass(repr=False)
class AlterUserStmt(StmtNode):
    users: list = field(default_factory=list)  # [(user, host, password)]
    if_exists: bool = False

    def restore(self):
        return "ALTER USER"


@dataclass(repr=False)
class GrantStmt(StmtNode):
    privs: list = field(default_factory=list)   # ["select", ...] or ["all"]
    db: str = ""                                # "*" = global
    table: str = ""                             # "*" = whole db
    users: list = field(default_factory=list)   # [(user, host, pw, plugin)]
    with_grant: bool = False

    def restore(self):
        return (f"GRANT {', '.join(p.upper() for p in self.privs)} "
                f"ON {self.db}.{self.table} TO " + ", ".join(
                    f"'{u[0]}'@'{u[1]}'" for u in self.users))


@dataclass(repr=False)
class RevokeStmt(StmtNode):
    privs: list = field(default_factory=list)
    db: str = ""
    table: str = ""
    users: list = field(default_factory=list)   # [(user, host)]

    def restore(self):
        return (f"REVOKE {', '.join(p.upper() for p in self.privs)} "
                f"ON {self.db}.{self.table} FROM " + ", ".join(
                    f"'{u}'@'{h}'" for u, h in self.users))


@dataclass(repr=False)
class ShowStmt(StmtNode):
    kind: str = ""   # databases|tables|columns|create_table|variables|index|processlist|status|engines|charset|collation|warnings|schemas|table_status
    target: object = None
    db: str = ""
    like: Optional[ExprNode] = None
    where: Optional[ExprNode] = None
    full: bool = False
    global_scope: bool = False

    def restore(self):
        return f"SHOW {self.kind.upper()}"


@dataclass(repr=False)
class ExplainStmt(StmtNode):
    stmt: StmtNode = None
    analyze: bool = False
    format: str = "row"

    def restore(self):
        return f"EXPLAIN {'ANALYZE ' if self.analyze else ''}{self.stmt.restore()}"


@dataclass(repr=False)
class BeginStmt(StmtNode):
    pessimistic: bool = None  # None = session default
    read_only: bool = False
    as_of: object = None  # AS OF TIMESTAMP expr (stale-read txn)

    def restore(self):
        s = "START TRANSACTION"
        if self.read_only:
            s += " READ ONLY"
        if self.as_of is not None:
            s += f" AS OF TIMESTAMP {self.as_of.restore()}"
        return s


@dataclass(repr=False)
class CommitStmt(StmtNode):
    def restore(self):
        return "COMMIT"


@dataclass(repr=False)
class RollbackStmt(StmtNode):
    def restore(self):
        return "ROLLBACK"


@dataclass(repr=False)
class AnalyzeTableStmt(StmtNode):
    tables: list = field(default_factory=list)

    def restore(self):
        return "ANALYZE TABLE " + ", ".join(t.restore() for t in self.tables)


@dataclass(repr=False)
class PrepareStmt(StmtNode):
    name: str = ""
    sql: object = None  # str literal or user variable name

    def restore(self):
        return f"PREPARE `{self.name}` FROM ..."


@dataclass(repr=False)
class ExecuteStmt(StmtNode):
    name: str = ""
    using: list = field(default_factory=list)  # [user var names]

    def restore(self):
        return f"EXECUTE `{self.name}`"


@dataclass(repr=False)
class DeallocateStmt(StmtNode):
    name: str = ""

    def restore(self):
        return f"DEALLOCATE PREPARE `{self.name}`"


@dataclass(repr=False)
class AdminStmt(StmtNode):
    kind: str = ""  # check_table | check_index | show_ddl | show_ddl_jobs | cancel_ddl_jobs
    tables: list = field(default_factory=list)
    job_ids: list = field(default_factory=list)
    index_name: str = ""

    def restore(self):
        return f"ADMIN {self.kind.upper()}"


@dataclass(repr=False)
class FlushStmt(StmtNode):
    kind: str = ""

    def restore(self):
        return f"FLUSH {self.kind.upper()}"


@dataclass(repr=False)
class KillStmt(StmtNode):
    conn_id: int = 0
    query_only: bool = False

    def restore(self):
        return f"KILL {'QUERY ' if self.query_only else ''}{self.conn_id}"


@dataclass(repr=False)
class TraceStmt(StmtNode):
    stmt: StmtNode = None
    format: str = "row"   # row (span tree) | opt (optimizer rule trace)

    def restore(self):
        f = f" FORMAT='{self.format}'" if self.format != "row" else ""
        return f"TRACE{f} {self.stmt.restore()}"


@dataclass(repr=False)
class PlanReplayerStmt(StmtNode):
    """PLAN REPLAYER DUMP EXPLAIN <stmt> (reference:
    executor/plan_replayer.go — capture schema+stats+config+explain into
    a zip for offline reproduction)."""
    stmt: StmtNode = None

    def restore(self):
        return f"PLAN REPLAYER DUMP EXPLAIN {self.stmt.restore()}"
