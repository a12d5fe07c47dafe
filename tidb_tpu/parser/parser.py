"""Recursive-descent / Pratt parser for the MySQL dialect
(reference: parser/parser.y — 13.8k-line LALR grammar; same surface, curated
subset, grown as the engine needs it)."""

from __future__ import annotations

from ..errors import ParseError
from ..sqltypes import (
    FieldType, FLAG_UNSIGNED, FLAG_NOT_NULL, TYPE_BIT, TYPE_BLOB, TYPE_DATE,
    TYPE_DATETIME, TYPE_DOUBLE, TYPE_DURATION, TYPE_ENUM, TYPE_FLOAT,
    TYPE_INT24, TYPE_JSON, TYPE_LONG, TYPE_LONGLONG, TYPE_NEWDECIMAL,
    TYPE_SET, TYPE_SHORT, TYPE_STRING, TYPE_TIMESTAMP, TYPE_TINY,
    TYPE_VARCHAR, TYPE_YEAR, UNSPECIFIED_LENGTH,
)
from . import ast
from .lexer import (
    EOF, HINT, IDENT, NUM_DEC, NUM_FLOAT, NUM_INT, OP, PARAM, QIDENT,
    STRING, SYSVAR, USERVAR, Token, tokenize,
)

AGG_FUNCS = {
    "count", "sum", "avg", "min", "max", "group_concat", "bit_and", "bit_or",
    "bit_xor", "std", "stddev", "stddev_pop", "stddev_samp", "var_pop",
    "var_samp", "variance", "approx_count_distinct", "json_arrayagg",
    "json_objectagg",
}

WINDOW_FUNCS = {
    "row_number", "rank", "dense_rank", "ntile", "lead", "lag",
    "first_value", "last_value", "nth_value", "percent_rank", "cume_dist",
}

NO_PAREN_FUNCS = {
    "current_date", "current_time", "current_timestamp", "current_user",
    "localtime", "localtimestamp", "utc_timestamp", "utc_date", "utc_time",
}

TIME_UNITS = {
    "microsecond", "second", "minute", "hour", "day", "week", "month",
    "quarter", "year", "second_microsecond", "minute_second", "hour_minute",
    "day_hour", "year_month",
}

# words that terminate an expression / cannot start an operand
RESERVED_STOP = {
    "from", "where", "group", "having", "order", "limit", "union", "on",
    "join", "inner", "left", "right", "cross", "straight_join", "as", "asc",
    "desc", "and", "or", "xor", "not", "between", "in", "like", "is", "then",
    "when", "else", "end", "for", "into", "values", "set", "using", "intersect",
    "except", "lock", "offset", "separator", "div", "mod", "regexp", "rlike",
    "collate", "interval", "exists", "select", "by", "with", "window", "over",
    "duplicate", "partition", "use", "force", "ignore",
}



def _parse_hint_text(text: str):
    """/*+ ... */ body -> [(name_lower, [arg strings])] (reference:
    parser/hintparser.y — a separate grammar there; a hand parser over
    the main lexer here). Args keep bracket groups intact:
    READ_FROM_STORAGE(TPU[t1, t2]) -> ("read_from_storage", ["tpu[t1,t2]"]).
    Malformed hint text degrades to no hints — hints must never break a
    statement that would otherwise parse."""
    try:
        toks = tokenize(text)
    except Exception:
        return []
    out = []
    i = 0

    def word(j):
        t = toks[j]
        if t.kind in (IDENT, QIDENT):
            return str(t.val).lower()
        if t.kind in (NUM_INT, NUM_DEC, NUM_FLOAT):
            return str(t.val)
        return None

    n = len(toks)
    while i < n and toks[i].kind != EOF:
        name = word(i)
        if name is None:
            i += 1
            continue
        i += 1
        args = []
        if i < n and toks[i].kind == OP and toks[i].val == "(":
            i += 1
            depth = 1
            cur = []
            while i < n and toks[i].kind != EOF:
                t = toks[i]
                if t.kind == OP and t.val == "(":
                    depth += 1
                    cur.append("[")
                elif t.kind == OP and t.val == ")":
                    depth -= 1
                    if depth == 0:
                        i += 1
                        break
                    cur.append("]")
                elif t.kind == OP and t.val == "," and depth == 1:
                    if cur:
                        args.append("".join(cur))
                    cur = []
                else:
                    w = word(i)
                    cur.append(w if w is not None else str(t.val))
                i += 1
            if cur:
                args.append("".join(cur))
        out.append((name, args))
    return out


class Parser:
    """reference: parser/yy_parser.go Parser.Parse."""

    def __init__(self):
        self.toks: list[Token] = []
        self.pos = 0
        self.param_count = 0

    # -- token helpers ------------------------------------------------------

    def _cur(self) -> Token:
        return self.toks[self.pos]

    def _peek_kw(self, k: str) -> bool:
        t = self._cur()
        return t.kind == IDENT and t.val.lower() == k

    def _peek_kws(self, *ks) -> bool:
        for i, k in enumerate(ks):
            t = self.toks[self.pos + i] if self.pos + i < len(self.toks) else None
            if t is None or t.kind != IDENT or t.val.lower() != k:
                return False
        return True

    def _peek_op(self, op: str) -> bool:
        t = self._cur()
        return t.kind == OP and t.val == op

    def _accept_kw(self, k: str) -> bool:
        if self._peek_kw(k):
            self.pos += 1
            return True
        return False

    def _accept_op(self, op: str) -> bool:
        if self._peek_op(op):
            self.pos += 1
            return True
        return False

    def _expect_kw(self, k: str):
        if not self._accept_kw(k):
            raise ParseError(f"expected {k.upper()} near {self._near()}")

    def _expect_op(self, op: str):
        if not self._accept_op(op):
            raise ParseError(f"expected '{op}' near {self._near()}")

    def _near(self) -> str:
        t = self._cur()
        return repr(t.val) if t.kind != EOF else "end of statement"

    def _ident(self) -> str:
        t = self._cur()
        if t.kind in (IDENT, QIDENT):
            self.pos += 1
            return t.val
        raise ParseError(f"expected identifier near {self._near()}")

    # -- entry --------------------------------------------------------------

    def parse(self, sql: str) -> list[ast.StmtNode]:
        toks = tokenize(sql)
        # hint comments only bind directly after SELECT (reference: the
        # hint grammar hangs off specific statement heads); anywhere else
        # they behave like plain comments — drop them so expression/DDL
        # paths never see the token kind
        self.toks = [t for i, t in enumerate(toks)
                     if t.kind != HINT
                     or (i > 0 and toks[i - 1].kind == IDENT
                         and toks[i - 1].val.lower() == "select")]
        self.pos = 0
        self.param_count = 0
        stmts = []
        while True:
            while self._accept_op(";"):
                pass
            if self._cur().kind == EOF:
                break
            stmts.append(self._parse_statement())
            if self._cur().kind != EOF and not self._peek_op(";"):
                raise ParseError(f"unexpected input near {self._near()}")
        return stmts

    # -- statements ---------------------------------------------------------

    def _parse_statement(self) -> ast.StmtNode:
        t = self._cur()
        if t.kind == OP and t.val == "(":
            return self._parse_select_or_union()
        if t.kind != IDENT:
            raise ParseError(f"unexpected {self._near()}")
        kw = t.val.lower()
        if kw in ("select", "with"):
            return self._parse_select_or_union()
        if kw == "insert" or kw == "replace":
            return self._parse_insert()
        if kw == "update":
            return self._parse_update()
        if kw == "delete":
            return self._parse_delete()
        if kw == "create":
            return self._parse_create()
        if kw == "drop":
            return self._parse_drop()
        if kw == "alter":
            return self._parse_alter()
        if kw == "truncate":
            self.pos += 1
            self._accept_kw("table")
            return ast.TruncateTableStmt(table=self._parse_table_name())
        if kw in ("recover", "flashback"):
            self.pos += 1
            self._expect_kw("table")
            tn = self._parse_table_name()
            new_name = ""
            if kw == "flashback" and self._accept_kw("to"):
                new_name = self._ident()
            return ast.RecoverTableStmt(table=tn, new_name=new_name,
                                        flashback=(kw == "flashback"))
        if kw == "lock":
            self.pos += 1
            if not (self._accept_kw("tables") or self._accept_kw("table")):
                raise ParseError("expected TABLES after LOCK")
            items = []
            while True:
                tn = self._parse_table_name()
                if self._accept_kw("write"):
                    mode = "write"
                else:
                    self._expect_kw("read")
                    self._accept_kw("local")
                    mode = "read"
                items.append((tn, mode))
                if not self._accept_op(","):
                    break
            return ast.LockTablesStmt(items=items)
        if kw == "unlock":
            self.pos += 1
            if not (self._accept_kw("tables") or self._accept_kw("table")):
                raise ParseError("expected TABLES after UNLOCK")
            return ast.UnlockTablesStmt()
        if kw == "rename":
            self.pos += 1
            self._expect_kw("table")
            pairs = []
            while True:
                a = self._parse_table_name()
                self._expect_kw("to")
                b = self._parse_table_name()
                pairs.append((a, b))
                if not self._accept_op(","):
                    break
            return ast.RenameTableStmt(pairs=pairs)
        if kw == "use":
            self.pos += 1
            return ast.UseStmt(db=self._ident())
        if kw == "set":
            return self._parse_set()
        if kw == "show":
            return self._parse_show()
        if kw in ("explain", "desc", "describe"):
            return self._parse_explain()
        if kw == "begin":
            self.pos += 1
            return ast.BeginStmt()
        if kw == "start":
            self.pos += 1
            self._expect_kw("transaction")
            read_only = False
            as_of = None
            if self._accept_kw("read"):
                if not self._accept_kw("only"):
                    self._expect_kw("write")
                else:
                    read_only = True
            if read_only and self._accept_kw("as"):
                # START TRANSACTION READ ONLY AS OF TIMESTAMP expr
                # (reference: sessiontxn/interface.go:48 stale-read
                # providers; parser ast.StartTSBound)
                self._expect_kw("of")
                self._expect_kw("timestamp")
                as_of = self._parse_expr(0)
            return ast.BeginStmt(read_only=read_only, as_of=as_of)
        if kw == "commit":
            self.pos += 1
            return ast.CommitStmt()
        if kw == "rollback":
            self.pos += 1
            return ast.RollbackStmt()
        if kw == "analyze":
            self.pos += 1
            self._expect_kw("table")
            tables = [self._parse_table_name()]
            while self._accept_op(","):
                tables.append(self._parse_table_name())
            return ast.AnalyzeTableStmt(tables=tables)
        if kw == "admin":
            return self._parse_admin()
        if kw == "grant":
            return self._parse_grant()
        if kw == "revoke":
            return self._parse_revoke()
        if kw in ("backup", "restore"):
            self.pos += 1
            self._expect_kw("database")
            db = self._ident()
            self._expect_kw("to" if kw == "backup" else "from")
            t = self._cur()
            if t.kind != STRING:
                raise ParseError(f"expected path string near {self._near()}")
            self.pos += 1
            path = t.val.decode() if isinstance(t.val, bytes) else t.val
            mode = ""
            if self._accept_kw("mode"):
                if self._peek_op("="):
                    self.pos += 1
                mode = self._ident().lower()
                if mode not in ("physical", "logical"):
                    raise ParseError(
                        f"BACKUP/RESTORE MODE must be PHYSICAL or "
                        f"LOGICAL, got '{mode}'")
            return ast.BRIEStmt(kind=kw, db=db, path=path, mode=mode)
        if kw == "prepare":
            self.pos += 1
            name = self._ident()
            self._expect_kw("from")
            t = self._cur()
            if t.kind == STRING:
                self.pos += 1
                return ast.PrepareStmt(name=name, sql=t.val)
            if t.kind == USERVAR:
                self.pos += 1
                return ast.PrepareStmt(name=name, sql=ast.VariableExpr(t.val))
            raise ParseError("expected string or @var after PREPARE ... FROM")
        if kw == "execute":
            self.pos += 1
            name = self._ident()
            using = []
            if self._accept_kw("using"):
                while True:
                    tv = self._cur()
                    if tv.kind != USERVAR:
                        raise ParseError("expected @var in EXECUTE ... USING")
                    using.append(tv.val)
                    self.pos += 1
                    if not self._accept_op(","):
                        break
            return ast.ExecuteStmt(name=name, using=using)
        if kw == "deallocate":
            self.pos += 1
            self._expect_kw("prepare")
            return ast.DeallocateStmt(name=self._ident())
        if kw == "flush":
            self.pos += 1
            k = self._ident().lower()
            return ast.FlushStmt(kind=k)
        if kw == "kill":
            self.pos += 1
            query_only = self._accept_kw("query")
            self._accept_kw("tidb")
            t = self._cur()
            if t.kind != NUM_INT:
                raise ParseError("expected connection id after KILL")
            self.pos += 1
            return ast.KillStmt(conn_id=t.val, query_only=query_only)
        if kw == "trace":
            self.pos += 1
            fmt = "row"
            if self._accept_kw("format"):
                self._expect_op("=")
                ft = self._cur()
                fmt = str(ft.val).strip("'\"").lower()
                self.pos += 1
            return ast.TraceStmt(stmt=self._parse_statement(), format=fmt)
        if kw == "plan":
            # PLAN REPLAYER DUMP EXPLAIN <stmt>
            # (reference: executor/plan_replayer.go)
            self.pos += 1
            self._expect_kw("replayer")
            self._expect_kw("dump")
            self._expect_kw("explain")
            return ast.PlanReplayerStmt(stmt=self._parse_statement())
        raise ParseError(f"unsupported statement starting with {t.val!r}")

    # -- SELECT -------------------------------------------------------------

    def _parse_select_or_union(self) -> ast.StmtNode:
        first = self._parse_select_core()
        ops = []
        selects = [first]
        while True:
            low = None
            if self._peek_kw("union"):
                low = "union"
            elif self._peek_kw("intersect"):
                low = "intersect"
            elif self._peek_kw("except"):
                low = "except"
            if low is None:
                break
            self.pos += 1
            if self._accept_kw("all"):
                low += " all"
            else:
                self._accept_kw("distinct")
            selects.append(self._parse_select_core())
            ops.append(low)
        if not ops:
            return first
        stmt = ast.SetOprStmt(selects=selects, ops=ops)
        # trailing ORDER BY / LIMIT bind to the whole set operation
        last = selects[-1]
        if last.order_by or last.limit:
            stmt.order_by, last.order_by = last.order_by, []
            stmt.limit, last.limit = last.limit, None
        return stmt

    def _parse_select_core(self) -> ast.SelectStmt:
        ctes = []
        recursive = False
        if self._peek_kw("with"):
            # common table expressions (reference: parser.y WithClause);
            # RECURSIVE gates fixpoint evaluation — without it a CTE body
            # naming itself refers to the outer scope / real table
            self.pos += 1
            recursive = self._accept_kw("recursive")
            while True:
                name = self._ident()
                cols = []
                if self._accept_op("("):
                    while True:
                        cols.append(self._ident())
                        if not self._accept_op(","):
                            break
                    self._expect_op(")")
                self._expect_kw("as")
                self._expect_op("(")
                stmt = self._parse_select_or_union()
                self._expect_op(")")
                ctes.append((name, cols, stmt))
                if not self._accept_op(","):
                    break
        if self._accept_op("("):
            sel = self._parse_select_or_union()
            self._expect_op(")")
            if isinstance(sel, ast.SetOprStmt):
                raise ParseError("nested set operations in parentheses unsupported")
            # allow trailing order by / limit after parens
            if self._peek_kw("order"):
                self.pos += 1
                self._expect_kw("by")
                sel.order_by = self._parse_by_items()
            if self._peek_kw("limit"):
                sel.limit = self._parse_limit()
            if ctes:
                sel.with_ctes = ctes + sel.with_ctes
                sel.with_recursive = sel.with_recursive or recursive
            return sel
        self._expect_kw("select")
        sel = ast.SelectStmt()
        if self._cur().kind == HINT:
            sel.hints = _parse_hint_text(self._cur().val)
            self.pos += 1
        sel.with_ctes = ctes
        sel.with_recursive = recursive
        # modifiers
        while True:
            if self._accept_kw("distinct") or self._accept_kw("distinctrow"):
                sel.distinct = True
            elif self._accept_kw("all") or self._accept_kw("sql_no_cache") or self._accept_kw("sql_calc_found_rows") or self._accept_kw("straight_join"):
                pass
            else:
                break
        # fields
        while True:
            sel.fields.append(self._parse_select_field())
            if not self._accept_op(","):
                break
        if self._accept_kw("from"):
            sel.from_ = self._parse_table_refs()
        if self._accept_kw("where"):
            sel.where = self._parse_expr()
        if self._accept_kw("group"):
            self._expect_kw("by")
            sel.group_by = self._parse_by_items()
            self._accept_kw("with")  # WITH ROLLUP — parsed, ignored for now
        if self._peek_kw("rollup"):
            self.pos += 1
        if self._accept_kw("having"):
            sel.having = self._parse_expr()
        if self._accept_kw("order"):
            self._expect_kw("by")
            sel.order_by = self._parse_by_items()
        if self._peek_kw("limit"):
            sel.limit = self._parse_limit()
        if self._accept_kw("for"):
            self._expect_kw("update")
            sel.for_update = True
        elif self._accept_kw("lock"):
            self._expect_kw("in")
            self._expect_kw("share")
            self._expect_kw("mode")
            sel.lock_in_share_mode = True
        return sel

    def _parse_select_field(self) -> ast.SelectField:
        if self._peek_op("*"):
            self.pos += 1
            return ast.SelectField(expr=ast.StarExpr())
        # tbl.* / db.tbl.*
        save = self.pos
        t = self._cur()
        if t.kind in (IDENT, QIDENT):
            parts = [t.val]
            p = self.pos + 1
            while (self.toks[p].kind == OP and self.toks[p].val == "."
                   and self.toks[p + 1].kind in (IDENT, QIDENT, OP)):
                if self.toks[p + 1].kind == OP:
                    if self.toks[p + 1].val == "*" and len(parts) <= 2:
                        self.pos = p + 2
                        if len(parts) == 1:
                            return ast.SelectField(expr=ast.StarExpr(table=parts[0]))
                        return ast.SelectField(expr=ast.StarExpr(schema=parts[0], table=parts[1]))
                    break
                parts.append(self.toks[p + 1].val)
                p += 2
            self.pos = save
        expr = self._parse_expr()
        as_name = ""
        if self._accept_kw("as"):
            t = self._cur()
            if t.kind in (IDENT, QIDENT, STRING):
                as_name = t.val
                self.pos += 1
            else:
                raise ParseError("expected alias after AS")
        else:
            t = self._cur()
            if (t.kind == QIDENT or t.kind == STRING
                    or (t.kind == IDENT and t.val.lower() not in RESERVED_STOP)):
                as_name = t.val
                self.pos += 1
        return ast.SelectField(expr=expr, as_name=as_name)

    def _parse_by_items(self) -> list:
        items = []
        while True:
            e = self._parse_expr()
            desc = False
            if self._accept_kw("desc"):
                desc = True
            else:
                self._accept_kw("asc")
            items.append(ast.ByItem(expr=e, desc=desc))
            if not self._accept_op(","):
                break
        return items

    def _parse_limit(self) -> ast.Limit:
        self._expect_kw("limit")
        first = self._parse_expr(5)
        if self._accept_op(","):
            return ast.Limit(count=self._parse_expr(5), offset=first)
        if self._accept_kw("offset"):
            return ast.Limit(count=first, offset=self._parse_expr(5))
        return ast.Limit(count=first)

    # -- table refs ---------------------------------------------------------

    def _parse_table_refs(self):
        left = self._parse_table_factor()
        while True:
            if self._accept_op(","):
                right = self._parse_table_factor()
                left = ast.Join(left=left, right=right, kind="cross")
                continue
            kind = None
            natural = False
            if self._peek_kw("natural"):
                self.pos += 1
                natural = True
            if self._peek_kw("join") or self._peek_kw("inner") or self._peek_kw("straight_join"):
                if not self._accept_kw("join"):
                    self.pos += 1
                    self._accept_kw("join")
                kind = "inner"
            elif self._peek_kw("cross"):
                self.pos += 1
                self._expect_kw("join")
                kind = "cross"
            elif self._peek_kw("left"):
                self.pos += 1
                self._accept_kw("outer")
                self._expect_kw("join")
                kind = "left"
            elif self._peek_kw("right"):
                self.pos += 1
                self._accept_kw("outer")
                self._expect_kw("join")
                kind = "right"
            elif natural:
                raise ParseError("expected JOIN after NATURAL")
            if kind is None:
                return left
            right = self._parse_table_factor()
            join = ast.Join(left=left, right=right, kind=kind)
            if natural:
                join.using = ["*natural*"]
            elif self._accept_kw("on"):
                join.on = self._parse_expr()
            elif self._accept_kw("using"):
                self._expect_op("(")
                join.using.append(self._ident())
                while self._accept_op(","):
                    join.using.append(self._ident())
                self._expect_op(")")
            left = join

    def _parse_table_factor(self):
        if self._accept_op("("):
            if (self._peek_kw("select") or self._peek_kw("with")
                    or self._peek_op("(")):
                sub = self._parse_select_or_union()
                self._expect_op(")")
                as_name = ""
                self._accept_kw("as")
                t = self._cur()
                if t.kind in (IDENT, QIDENT) and (t.kind == QIDENT or t.val.lower() not in RESERVED_STOP):
                    as_name = t.val
                    self.pos += 1
                st = ast.SubqueryTable(query=sub, as_name=as_name)
                # the alias's column list: (select ...) as t (a, b)
                if as_name and self._accept_op("("):
                    st.col_names.append(self._ident())
                    while self._accept_op(","):
                        st.col_names.append(self._ident())
                    self._expect_op(")")
                return st
            refs = self._parse_table_refs()
            self._expect_op(")")
            return refs
        return self._parse_table_name(allow_alias=True)

    def _parse_table_name(self, allow_alias=False) -> ast.TableName:
        name = self._ident()
        schema = ""
        if self._accept_op("."):
            schema, name = name, self._ident()
        tn = ast.TableName(name=name, schema=schema)
        # explicit partition selection: t PARTITION (p0, p1)
        if (self._peek_kw("partition")
                and self.toks[self.pos + 1].kind == OP
                and self.toks[self.pos + 1].val == "("):
            self.pos += 1
            self._expect_op("(")
            tn.partition_names.append(self._ident())
            while self._accept_op(","):
                tn.partition_names.append(self._ident())
            self._expect_op(")")
        if allow_alias:
            # t AS OF TIMESTAMP expr (stale read, reference:
            # sessiontxn/interface.go:48) — disambiguated from `AS alias`
            # by the OF keyword
            if self._peek_kws("as", "of"):
                self.pos += 2
                self._expect_kw("timestamp")
                # full expression: NOW() - INTERVAL n SECOND is the
                # idiomatic stale-read bound; a following alias identifier
                # is not an operator, so bp 0 cannot swallow it
                tn.as_of = self._parse_expr(0)
            if self._accept_kw("as"):
                tn.as_name = self._ident()
            else:
                t = self._cur()
                if t.kind == QIDENT or (t.kind == IDENT and t.val.lower() not in RESERVED_STOP):
                    tn.as_name = t.val
                    self.pos += 1
            # index hints: USE/FORCE/IGNORE INDEX (i1, i2)
            while self._peek_kw("use") or self._peek_kw("force") or self._peek_kw("ignore"):
                verb = self._cur().val.lower()
                self.pos += 1
                if not (self._accept_kw("index") or self._accept_kw("key")):
                    self.pos -= 1
                    break
                self._expect_op("(")
                names = []
                if not self._peek_op(")"):
                    names.append(self._ident())
                    while self._accept_op(","):
                        names.append(self._ident())
                self._expect_op(")")
                tn.index_hints.append((verb, names))
        return tn

    # -- expressions (Pratt) ------------------------------------------------

    def _parse_expr(self, min_bp: int = 0) -> ast.ExprNode:
        lhs = self._parse_prefix(min_bp)
        while True:
            t = self._cur()
            if t.kind == OP:
                op = t.val
                if op in ("||", ):
                    bp = 1
                elif op == "&&":
                    bp = 3
                elif op in ("=", "<=>", "<", ">", "<=", ">=", "!=", "<>", ":="):
                    bp = 5
                elif op == "|":
                    bp = 6
                elif op == "&":
                    bp = 7
                elif op in ("<<", ">>"):
                    bp = 8
                elif op in ("+", "-"):
                    bp = 9
                elif op in ("*", "/", "%"):
                    bp = 10
                elif op == "^":
                    bp = 11
                elif op in ("->", "->>"):
                    # JSON path extraction sugar (reference: parser.y
                    # juxtaposed JSONExtract): col->'$.p' / col->>'$.p'
                    bp = 12
                else:
                    return lhs
                if bp <= min_bp:
                    return lhs
                self.pos += 1
                if op == ":=":
                    if not isinstance(lhs, ast.VariableExpr):
                        raise ParseError(":= requires a user variable on the left")
                    lhs.value = self._parse_expr(bp - 1)
                    continue
                norm = {"<>": "!=", "||": "or", "&&": "and"}.get(op, op)
                if norm in ("=", "<", ">", "<=", ">=", "!=", "<=>") and (
                        self._peek_kw("any") or self._peek_kw("all") or self._peek_kw("some")):
                    quant = "all" if self._peek_kw("all") else "any"
                    self.pos += 1
                    self._expect_op("(")
                    sub = self._parse_select_or_union()
                    self._expect_op(")")
                    lhs = ast.CompareSubquery(op=norm, expr=lhs,
                                              query=ast.SubqueryExpr(sub), quantifier=quant)
                    continue
                if op in ("->", "->>"):
                    rhs = self._parse_expr(bp)
                    lhs = ast.FuncCall(name="json_extract", args=[lhs, rhs])
                    if op == "->>":
                        lhs = ast.FuncCall(name="json_unquote", args=[lhs])
                    continue
                rhs = self._parse_expr(bp)
                lhs = ast.BinaryOp(op=norm, left=lhs, right=rhs)
                continue
            if t.kind == IDENT:
                kw = t.val.lower()
                if kw == "or":
                    bp = 1
                elif kw == "xor":
                    bp = 2
                elif kw == "and":
                    bp = 3
                elif kw in ("is", "like", "rlike", "regexp", "in", "between", "not",
                            "sounds", "collate", "member"):
                    bp = 5
                elif kw in ("div", "mod"):
                    bp = 10
                else:
                    return lhs
                if bp <= min_bp:
                    return lhs
                if kw in ("or", "xor", "and", "div", "mod"):
                    self.pos += 1
                    rhs = self._parse_expr(bp)
                    lhs = ast.BinaryOp(op=kw, left=lhs, right=rhs)
                    continue
                if kw == "collate":
                    self.pos += 1
                    self._ident()  # collation name — recorded nowhere yet
                    continue
                lhs = self._parse_predicate(lhs)
                continue
            return lhs

    def _parse_predicate(self, lhs: ast.ExprNode) -> ast.ExprNode:
        negated = False
        if self._accept_kw("not"):
            negated = True
        if self._accept_kw("is"):
            if negated:
                raise ParseError("NOT IS is invalid")
            neg = self._accept_kw("not")
            if self._accept_kw("null"):
                return ast.IsNullExpr(expr=lhs, negated=neg)
            if self._accept_kw("true"):
                return ast.IsTruthExpr(expr=lhs, truth=True, negated=neg)
            if self._accept_kw("false"):
                return ast.IsTruthExpr(expr=lhs, truth=False, negated=neg)
            raise ParseError("expected NULL/TRUE/FALSE after IS")
        if self._accept_kw("in"):
            self._expect_op("(")
            if self._peek_kw("select") or self._peek_kw("with"):
                sub = self._parse_select_or_union()
                self._expect_op(")")
                return ast.InExpr(expr=lhs, items=[ast.SubqueryExpr(sub)], negated=negated)
            items = [self._parse_expr()]
            while self._accept_op(","):
                items.append(self._parse_expr())
            self._expect_op(")")
            return ast.InExpr(expr=lhs, items=items, negated=negated)
        if self._accept_kw("between"):
            low = self._parse_expr(5)
            self._expect_kw("and")
            high = self._parse_expr(5)
            return ast.BetweenExpr(expr=lhs, low=low, high=high, negated=negated)
        if self._accept_kw("like"):
            pat = self._parse_expr(10)
            esc = "\\"
            if self._accept_kw("escape"):
                t = self._cur()
                if t.kind != STRING:
                    raise ParseError("expected string after ESCAPE")
                esc = t.val
                self.pos += 1
            return ast.LikeExpr(expr=lhs, pattern=pat, negated=negated, escape=esc)
        if self._accept_kw("regexp") or self._accept_kw("rlike"):
            pat = self._parse_expr(10)
            return ast.RegexpExpr(expr=lhs, pattern=pat, negated=negated)
        raise ParseError(f"unexpected token near {self._near()}")

    def _parse_prefix(self, min_bp: int = 0) -> ast.ExprNode:
        t = self._cur()
        if t.kind == OP:
            if t.val == "(":
                self.pos += 1
                if self._peek_kw("select") or self._peek_kw("with"):
                    sub = self._parse_select_or_union()
                    self._expect_op(")")
                    return ast.SubqueryExpr(sub)
                items = [self._parse_expr()]
                while self._accept_op(","):
                    items.append(self._parse_expr())
                self._expect_op(")")
                if len(items) == 1:
                    return items[0]
                return ast.RowExpr(items=items)
            if t.val == "-":
                self.pos += 1
                operand = self._parse_prefix(min_bp)
                if isinstance(operand, ast.Literal) and operand.kind in ("int", "float"):
                    operand.val = -operand.val
                    return operand
                if isinstance(operand, ast.Literal) and operand.kind == "dec":
                    operand.val = "-" + operand.val
                    return operand
                return ast.UnaryOp(op="-", operand=operand)
            if t.val == "+":
                self.pos += 1
                return self._parse_prefix(min_bp)
            if t.val == "~":
                self.pos += 1
                return ast.UnaryOp(op="~", operand=self._parse_prefix(min_bp))
            if t.val == "!":
                self.pos += 1
                return ast.UnaryOp(op="not", operand=self._parse_prefix(min_bp))
            if t.val == "*":
                # bare * only valid in COUNT(*) — handled there; else error
                raise ParseError("unexpected '*'")
        if t.kind == NUM_INT:
            self.pos += 1
            return ast.Literal("int", t.val)
        if t.kind == NUM_FLOAT:
            self.pos += 1
            return ast.Literal("float", t.val)
        if t.kind == NUM_DEC:
            self.pos += 1
            return ast.Literal("dec", t.val)
        if t.kind == STRING:
            self.pos += 1
            return ast.Literal("str", t.val)
        if t.kind == PARAM:
            self.pos += 1
            self.param_count += 1
            return ast.ParamMarker(index=self.param_count - 1)
        if t.kind == SYSVAR:
            self.pos += 1
            name = t.val
            scope = ""
            if "." in name:
                scope, name = name.split(".", 1)
                scope = scope.lower()
            return ast.VariableExpr(name=name.lower(), is_system=True, scope=scope)
        if t.kind == USERVAR:
            self.pos += 1
            return ast.VariableExpr(name=t.val.lower())
        if t.kind == QIDENT:
            return self._parse_name_expr()
        if t.kind == IDENT:
            kw = t.val.lower()
            if kw == "null":
                self.pos += 1
                return ast.Literal("null", None)
            if kw == "true":
                self.pos += 1
                return ast.Literal("int", 1)
            if kw == "false":
                self.pos += 1
                return ast.Literal("int", 0)
            if kw == "not":
                self.pos += 1
                return ast.UnaryOp(op="not", operand=self._parse_expr(4))
            if kw == "binary":
                self.pos += 1
                return self._parse_prefix(min_bp)  # BINARY collate-cast: pass through
            if kw == "case":
                return self._parse_case()
            if kw == "cast":
                self.pos += 1
                self._expect_op("(")
                e = self._parse_expr()
                self._expect_kw("as")
                ft = self._parse_cast_type()
                self._expect_op(")")
                return ast.CastExpr(expr=e, ftype=ft)
            if kw == "convert":
                self.pos += 1
                self._expect_op("(")
                e = self._parse_expr()
                if self._accept_kw("using"):
                    self._ident()
                    self._expect_op(")")
                    return e
                self._expect_op(",")
                ft = self._parse_cast_type()
                self._expect_op(")")
                return ast.CastExpr(expr=e, ftype=ft)
            if kw == "exists":
                self.pos += 1
                self._expect_op("(")
                sub = self._parse_select_or_union()
                self._expect_op(")")
                return ast.ExistsExpr(query=ast.SubqueryExpr(sub))
            if kw == "interval":
                self.pos += 1
                v = self._parse_expr(9)
                unit = self._ident().lower()
                if unit not in TIME_UNITS:
                    raise ParseError(f"unknown INTERVAL unit {unit}")
                return ast.IntervalExpr(value=v, unit=unit)
            if kw == "default":
                self.pos += 1
                if self._accept_op("("):
                    col = self._parse_name_expr()
                    self._expect_op(")")
                    return ast.DefaultExpr(col=col)
                return ast.DefaultExpr()
            if kw in ("date", "time", "timestamp") and self.toks[self.pos + 1].kind == STRING:
                self.pos += 1
                s = self._cur().val
                self.pos += 1
                return ast.Literal({"date": "date", "time": "time", "timestamp": "datetime"}[kw], s)
            if kw in NO_PAREN_FUNCS and not (self.toks[self.pos + 1].kind == OP and self.toks[self.pos + 1].val == "("):
                self.pos += 1
                return ast.FuncCall(name={"localtime": "now", "localtimestamp": "now",
                                          "current_timestamp": "now"}.get(kw, kw), args=[])
            # generic identifier: column ref or function call
            return self._parse_name_expr()
        raise ParseError(f"unexpected token near {self._near()}")

    def _parse_name_expr(self) -> ast.ExprNode:
        name = self._ident()
        if self._peek_op("("):
            return self._parse_func_call(name)
        parts = [name]
        while self._peek_op(".") and self.toks[self.pos + 1].kind in (IDENT, QIDENT):
            self.pos += 2
            parts.append(self.toks[self.pos - 1].val)
        if len(parts) == 1:
            return ast.ColumnName(name=parts[0])
        if len(parts) == 2:
            return ast.ColumnName(table=parts[0], name=parts[1])
        if len(parts) == 3:
            return ast.ColumnName(schema=parts[0], table=parts[1], name=parts[2])
        raise ParseError("too many name parts")

    def _parse_case(self) -> ast.CaseExpr:
        self._expect_kw("case")
        operand = None
        if not self._peek_kw("when"):
            operand = self._parse_expr()
        whens = []
        while self._accept_kw("when"):
            c = self._parse_expr()
            self._expect_kw("then")
            r = self._parse_expr()
            whens.append((c, r))
        else_ = None
        if self._accept_kw("else"):
            else_ = self._parse_expr()
        self._expect_kw("end")
        return ast.CaseExpr(operand=operand, whens=whens, else_=else_)

    def _parse_func_call(self, name: str) -> ast.ExprNode:
        fname = name.lower()
        self._expect_op("(")
        # COUNT(*) / COUNT(DISTINCT ...)
        if fname in AGG_FUNCS:
            distinct = self._accept_kw("distinct")
            args = []
            if self._peek_op("*"):
                self.pos += 1
            elif not self._peek_op(")"):
                args.append(self._parse_expr())
                while self._accept_op(","):
                    args.append(self._parse_expr())
            sep = None
            if fname == "group_concat" and self._accept_kw("separator"):
                t = self._cur()
                if t.kind != STRING:
                    raise ParseError("expected string after SEPARATOR")
                sep = t.val
                self.pos += 1
            self._expect_op(")")
            agg = ast.AggregateFunc(name=fname, args=args, distinct=distinct)
            if sep is not None:
                agg.args.append(ast.Literal("str", sep))
            if self._peek_kw("over"):
                return self._parse_over(ast.WindowFunc(name=fname, args=args))
            return agg
        if fname in WINDOW_FUNCS:
            args = []
            if not self._peek_op(")"):
                args.append(self._parse_expr())
                while self._accept_op(","):
                    args.append(self._parse_expr())
            self._expect_op(")")
            return self._parse_over(ast.WindowFunc(name=fname, args=args))
        # special argument syntaxes
        if fname == "get_format":
            kind = self._ident().lower()
            self._expect_op(",")
            r = self._parse_expr()
            self._expect_op(")")
            return ast.FuncCall(name="get_format",
                                args=[ast.Literal("str", kind), r])
        if fname in ("timestampdiff", "timestampadd"):
            unit = self._ident().lower()
            self._expect_op(",")
            a = self._parse_expr()
            self._expect_op(",")
            b = self._parse_expr()
            self._expect_op(")")
            return ast.FuncCall(name=fname,
                                args=[ast.Literal("str", unit), a, b])
        if fname == "extract":
            unit = self._ident().lower()
            self._expect_kw("from")
            e = self._parse_expr()
            self._expect_op(")")
            return ast.FuncCall(name="extract", args=[ast.Literal("str", unit), e])
        if fname in ("substring", "substr") and True:
            e = self._parse_expr()
            if self._accept_kw("from"):
                a = self._parse_expr()
                args = [e, a]
                if self._accept_kw("for"):
                    args.append(self._parse_expr())
            else:
                args = [e]
                while self._accept_op(","):
                    args.append(self._parse_expr())
            self._expect_op(")")
            return ast.FuncCall(name="substring", args=args)
        if fname == "trim":
            direction = "both"
            rem = None
            if self._peek_kw("leading") or self._peek_kw("trailing") or self._peek_kw("both"):
                direction = self._cur().val.lower()
                self.pos += 1
                if not self._peek_kw("from"):
                    rem = self._parse_expr()
                self._expect_kw("from")
                s = self._parse_expr()
            else:
                first = self._parse_expr()
                if self._accept_kw("from"):
                    rem, s = first, self._parse_expr()
                else:
                    s = first
            self._expect_op(")")
            args = [s, ast.Literal("str", direction)]
            if rem is not None:
                args.append(rem)
            return ast.FuncCall(name="trim", args=args)
        if fname == "position":
            sub = self._parse_expr(5)
            self._expect_kw("in")
            s = self._parse_expr()
            self._expect_op(")")
            return ast.FuncCall(name="locate", args=[sub, s])
        # generic call (includes date_add/date_sub whose 2nd arg is INTERVAL)
        args = []
        if not self._peek_op(")"):
            args.append(self._parse_expr())
            while self._accept_op(","):
                args.append(self._parse_expr())
        self._expect_op(")")
        fc = ast.FuncCall(name=fname, args=args)
        if self._peek_kw("over"):
            return self._parse_over(ast.WindowFunc(name=fname, args=args))
        return fc

    def _parse_over(self, wf: ast.WindowFunc) -> ast.WindowFunc:
        self._expect_kw("over")
        self._expect_op("(")
        if self._accept_kw("partition"):
            self._expect_kw("by")
            wf.partition_by.append(self._parse_expr())
            while self._accept_op(","):
                wf.partition_by.append(self._parse_expr())
        if self._accept_kw("order"):
            self._expect_kw("by")
            wf.order_by = self._parse_by_items()
        if self._peek_kw("rows") or self._peek_kw("range"):
            unit = self._cur().val.lower()
            self.pos += 1
            if self._accept_kw("between"):
                lo = self._parse_frame_bound()
                self._expect_kw("and")
                hi = self._parse_frame_bound()
            else:
                lo = self._parse_frame_bound()
                hi = ("current", 0)
            wf.frame = (unit, lo, hi)
        self._expect_op(")")
        return wf

    def _parse_frame_bound(self):
        """-> (kind, n): unbounded_preceding | preceding | current |
        following | unbounded_following."""
        if self._accept_kw("unbounded"):
            if self._accept_kw("preceding"):
                return ("unbounded_preceding", 0)
            self._expect_kw("following")
            return ("unbounded_following", 0)
        if self._accept_kw("current"):
            self._expect_kw("row")
            return ("current", 0)
        n = self._int_lit()
        if self._accept_kw("preceding"):
            return ("preceding", n)
        self._expect_kw("following")
        return ("following", n)

    def _parse_cast_type(self) -> FieldType:
        name = self._ident().lower()
        ft = FieldType()
        if name in ("signed", "integer", "int"):
            self._accept_kw("integer")
            ft.tp = TYPE_LONGLONG
        elif name == "unsigned":
            self._accept_kw("integer")
            ft.tp = TYPE_LONGLONG
            ft.flag |= FLAG_UNSIGNED
        elif name == "char":
            ft.tp = TYPE_VARCHAR
            if self._accept_op("("):
                ft.flen = self._int_lit()
                self._expect_op(")")
        elif name == "binary":
            ft.tp = TYPE_VARCHAR
            if self._accept_op("("):
                ft.flen = self._int_lit()
                self._expect_op(")")
        elif name == "decimal":
            ft.tp = TYPE_NEWDECIMAL
            ft.flen, ft.decimal = 10, 0
            if self._accept_op("("):
                ft.flen = self._int_lit()
                if self._accept_op(","):
                    ft.decimal = self._int_lit()
                self._expect_op(")")
        elif name == "date":
            ft.tp = TYPE_DATE
        elif name == "datetime":
            ft.tp = TYPE_DATETIME
            ft.decimal = 0
            if self._accept_op("("):
                ft.decimal = self._int_lit()
                self._expect_op(")")
        elif name == "time":
            ft.tp = TYPE_DURATION
            ft.decimal = 0
            if self._accept_op("("):
                ft.decimal = self._int_lit()
                self._expect_op(")")
        elif name == "double":
            ft.tp = TYPE_DOUBLE
        elif name == "float":
            ft.tp = TYPE_FLOAT
        elif name == "json":
            ft.tp = TYPE_JSON
        else:
            raise ParseError(f"unsupported CAST type {name}")
        return ft

    def _int_lit(self) -> int:
        t = self._cur()
        if t.kind != NUM_INT:
            raise ParseError("expected integer")
        self.pos += 1
        return t.val

    def _signed_int_lit(self) -> int:
        neg = self._accept_op("-")
        v = self._int_lit()
        return -v if neg else v

    # -- INSERT / UPDATE / DELETE ------------------------------------------

    def _parse_insert(self) -> ast.InsertStmt:
        is_replace = self._accept_kw("replace")
        if not is_replace:
            self._expect_kw("insert")
        ignore = self._accept_kw("ignore")
        self._accept_kw("into")
        stmt = ast.InsertStmt(is_replace=is_replace, ignore=ignore)
        stmt.table = self._parse_table_name()
        if self._peek_op("("):
            # could be column list or (SELECT...)
            save = self.pos
            self.pos += 1
            if self._peek_kw("select"):
                self.pos = save
            else:
                cols = [self._ident()]
                while self._accept_op(","):
                    cols.append(self._ident())
                self._expect_op(")")
                stmt.columns = cols
        if self._accept_kw("values") or self._accept_kw("value"):
            while True:
                self._expect_op("(")
                row = []
                if not self._peek_op(")"):
                    row.append(self._parse_expr())
                    while self._accept_op(","):
                        row.append(self._parse_expr())
                self._expect_op(")")
                stmt.values.append(row)
                if not self._accept_op(","):
                    break
        elif self._accept_kw("set"):
            # INSERT ... SET a=1, b=2
            cols, vals = [], []
            while True:
                cols.append(self._ident())
                self._expect_op("=")
                vals.append(self._parse_expr())
                if not self._accept_op(","):
                    break
            stmt.columns = cols
            stmt.values = [vals]
        else:
            stmt.select = self._parse_select_or_union()
        if self._accept_kw("on"):
            self._expect_kw("duplicate")
            self._expect_kw("key")
            self._expect_kw("update")
            while True:
                col = self._parse_name_expr()
                if not isinstance(col, ast.ColumnName):
                    raise ParseError("expected column in ON DUPLICATE KEY UPDATE")
                self._expect_op("=")
                stmt.on_duplicate.append((col, self._parse_expr()))
                if not self._accept_op(","):
                    break
        return stmt

    def _parse_update(self) -> ast.UpdateStmt:
        self._expect_kw("update")
        stmt = ast.UpdateStmt()
        stmt.table = self._parse_table_refs()
        self._expect_kw("set")
        while True:
            col = self._parse_name_expr()
            if not isinstance(col, ast.ColumnName):
                raise ParseError("expected column in UPDATE SET")
            self._expect_op("=")
            stmt.assignments.append((col, self._parse_expr()))
            if not self._accept_op(","):
                break
        if self._accept_kw("where"):
            stmt.where = self._parse_expr()
        if self._accept_kw("order"):
            self._expect_kw("by")
            stmt.order_by = self._parse_by_items()
        if self._peek_kw("limit"):
            stmt.limit = self._parse_limit()
        return stmt

    def _parse_delete(self) -> ast.DeleteStmt:
        self._expect_kw("delete")
        stmt = ast.DeleteStmt()
        if not self._peek_kw("from"):
            # DELETE t1, t2 FROM <joins> ... (multi-table, targets first)
            stmt.targets = [self._parse_table_name()]
            while self._accept_op(","):
                stmt.targets.append(self._parse_table_name())
            self._expect_kw("from")
            stmt.table = self._parse_table_refs()
            if self._accept_kw("where"):
                stmt.where = self._parse_expr()
            return stmt
        self._expect_kw("from")
        first = self._parse_table_name(allow_alias=True)
        if self._peek_op(",") or self._peek_kw("using"):
            # DELETE FROM t1[, t2] USING <joins> ...
            stmt.targets = [first]
            while self._accept_op(","):
                stmt.targets.append(self._parse_table_name())
            self._expect_kw("using")
            stmt.table = self._parse_table_refs()
            if self._accept_kw("where"):
                stmt.where = self._parse_expr()
            return stmt
        stmt.table = first
        if self._accept_kw("where"):
            stmt.where = self._parse_expr()
        if self._accept_kw("order"):
            self._expect_kw("by")
            stmt.order_by = self._parse_by_items()
        if self._peek_kw("limit"):
            stmt.limit = self._parse_limit()
        return stmt

    # -- DDL ----------------------------------------------------------------

    def _parse_user_spec(self):
        """'u'@'h' | 'u' | u@h | CURRENT_USER() → (user, host)."""
        t = self._cur()
        if t.kind in (STRING, IDENT, QIDENT):
            user = t.val
            self.pos += 1
        else:
            raise ParseError(f"expected user near {self._near()}")
        host = "%"
        t = self._cur()
        if t.kind == USERVAR:
            self.pos += 1
            if t.val:
                host = t.val
            else:
                h = self._cur()
                if h.kind in (STRING, IDENT, QIDENT):
                    host = h.val
                    self.pos += 1
        return user, host

    def _parse_user_with_auth(self):
        """→ (user, host, password|None, plugin|None). IDENTIFIED WITH
        names the auth plugin (mysql_native_password default,
        caching_sha2_password supported — reference: server/conn.go:810)."""
        user, host = self._parse_user_spec()
        pw = None
        plugin = None
        if self._accept_kw("identified"):
            if self._accept_kw("with"):
                t = self._cur()
                if t.kind == STRING:
                    plugin = t.val.decode() if isinstance(t.val, bytes) \
                        else t.val
                    self.pos += 1
                else:
                    plugin = self._ident()
                if not self._peek_kw("by") and not self._peek_kw("as"):
                    return user, host, pw, plugin
            hashed = False
            if self._accept_kw("by"):
                pass
            elif self._accept_kw("as"):
                hashed = True  # AS carries the stored auth string verbatim
            t = self._cur()
            if t.kind == STRING:
                pw = t.val.decode() if isinstance(t.val, bytes) else t.val
                self.pos += 1
                if hashed:
                    pw = ("hash", pw)
        return user, host, pw, plugin

    _PRIV_WORDS = {"select", "insert", "update", "delete", "create", "drop",
                   "index", "alter", "super", "grant", "references",
                   "execute", "process", "reload", "trigger", "usage"}

    def _parse_priv_list(self):
        privs = []
        if self._accept_kw("all"):
            self._accept_kw("privileges")
            return ["all"]
        while True:
            w = self._ident().lower()
            if w not in self._PRIV_WORDS:
                raise ParseError(f"unknown privilege '{w}'")
            if w == "grant":
                self._expect_kw("option")
            privs.append(w)
            if not self._accept_op(","):
                break
        return privs

    def _parse_grant_target(self):
        """ON *.* | db.* | db.tbl | tbl → (db, table)."""
        if self._accept_op("*"):
            self._expect_op(".")
            self._expect_op("*")
            return "*", "*"
        name = self._ident()
        if self._accept_op("."):
            if self._accept_op("*"):
                return name, "*"
            return name, self._ident()
        return "", name  # current db

    def _parse_grant(self):
        self._expect_kw("grant")
        privs = self._parse_priv_list()
        self._expect_kw("on")
        self._accept_kw("table")
        db, table = self._parse_grant_target()
        self._expect_kw("to")
        users = [self._parse_user_with_auth()]
        while self._accept_op(","):
            users.append(self._parse_user_with_auth())
        with_grant = False
        if self._accept_kw("with"):
            self._expect_kw("grant")
            self._expect_kw("option")
            with_grant = True
        return ast.GrantStmt(privs=privs, db=db, table=table, users=users,
                             with_grant=with_grant)

    def _parse_revoke(self):
        self._expect_kw("revoke")
        privs = self._parse_priv_list()
        self._expect_kw("on")
        self._accept_kw("table")
        db, table = self._parse_grant_target()
        self._expect_kw("from")
        users = [self._parse_user_spec()]
        while self._accept_op(","):
            users.append(self._parse_user_spec())
        return ast.RevokeStmt(privs=privs, db=db, table=table, users=users)

    def _parse_create(self):
        self._expect_kw("create")
        if self._peek_kws("placement", "policy"):
            self.pos += 2
            ine = False
            if self._accept_kw("if"):
                self._expect_kw("not")
                self._expect_kw("exists")
                ine = True
            name = self._ident()
            return ast.CreatePlacementPolicyStmt(
                name=name, if_not_exists=ine,
                options=self._parse_placement_options())
        if (self._peek_kw("binding")
                or self._peek_kws("global", "binding")
                or self._peek_kws("session", "binding")):
            is_global = self._accept_kw("global")
            self._accept_kw("session")
            self._expect_kw("binding")
            self._expect_kw("for")
            orig = self._parse_select_or_union()
            self._expect_kw("using")
            hinted = self._parse_select_or_union()
            return ast.CreateBindingStmt(original=orig, hinted=hinted,
                                         is_global=is_global)
        or_replace = False
        if self._accept_kw("or"):
            self._expect_kw("replace")
            or_replace = True
        definer = ""
        while True:
            # swallow ALGORITHM=... / DEFINER=... / SQL SECURITY ... prefixes
            if self._accept_kw("algorithm"):
                self._accept_op("=")
                self.pos += 1
            elif self._accept_kw("definer"):
                self._accept_op("=")
                u, h = self._parse_user_spec()
                definer = f"{u}@{h}"
            elif self._peek_kws("sql", "security"):
                self.pos += 2
                self.pos += 1  # DEFINER | INVOKER
            else:
                break
        if self._accept_kw("view"):
            vn = self._parse_table_name()
            cols = []
            if self._accept_op("("):
                cols.append(self._ident())
                while self._accept_op(","):
                    cols.append(self._ident())
                self._expect_op(")")
            self._expect_kw("as")
            sel = self._parse_select_or_union()
            # swallow WITH [CASCADED|LOCAL] CHECK OPTION
            if self._accept_kw("with"):
                self._accept_kw("cascaded")
                self._accept_kw("local")
                self._expect_kw("check")
                self._expect_kw("option")
            return ast.CreateViewStmt(view=vn, cols=cols, select=sel,
                                      or_replace=or_replace, definer=definer)
        if or_replace or definer:
            raise ParseError("expected VIEW after CREATE OR REPLACE/DEFINER")
        if self._accept_kw("user"):
            ine = False
            if self._accept_kw("if"):
                self._expect_kw("not")
                self._expect_kw("exists")
                ine = True
            users = [self._parse_user_with_auth()]
            while self._accept_op(","):
                users.append(self._parse_user_with_auth())
            return ast.CreateUserStmt(users=users, if_not_exists=ine)
        if self._accept_kw("database") or self._accept_kw("schema"):
            ine = False
            if self._accept_kw("if"):
                self._expect_kw("not")
                self._expect_kw("exists")
                ine = True
            name = self._ident()
            # swallow charset options
            while self._cur().kind == IDENT and not self._peek_op(";"):
                if self._cur().kind == EOF:
                    break
                self.pos += 1
                if self._accept_op("="):
                    self.pos += 1
            return ast.CreateDatabaseStmt(name=name, if_not_exists=ine)
        unique = self._accept_kw("unique")
        if self._accept_kw("index") or self._accept_kw("key"):
            ine = False
            if self._accept_kw("if"):
                self._expect_kw("not")
                self._expect_kw("exists")
                ine = True
            iname = self._ident()
            self._expect_kw("on")
            table = self._parse_table_name()
            self._expect_op("(")
            cols = [self._parse_index_col()]
            while self._accept_op(","):
                cols.append(self._parse_index_col())
            self._expect_op(")")
            return ast.CreateIndexStmt(index_name=iname, table=table,
                                       columns=cols, unique=unique, if_not_exists=ine)
        if unique:
            raise ParseError("expected INDEX after CREATE UNIQUE")
        if self._accept_kw("sequence"):
            ine = False
            if self._accept_kw("if"):
                self._expect_kw("not")
                self._expect_kw("exists")
                ine = True
            seq = ast.CreateSequenceStmt(name=self._parse_table_name(),
                                         if_not_exists=ine)
            while True:
                if self._accept_kw("start"):
                    self._accept_kw("with")
                    self._accept_op("=")
                    seq.options["start"] = self._signed_int_lit()
                elif self._accept_kw("increment"):
                    self._accept_kw("by")
                    self._accept_op("=")
                    seq.options["increment"] = self._signed_int_lit()
                elif self._accept_kw("minvalue"):
                    self._accept_op("=")
                    seq.options["min"] = self._signed_int_lit()
                elif self._accept_kw("maxvalue"):
                    self._accept_op("=")
                    seq.options["max"] = self._signed_int_lit()
                elif self._accept_kw("cache"):
                    self._accept_op("=")
                    seq.options["cache"] = self._signed_int_lit()
                elif self._accept_kw("nocache"):
                    seq.options["cache"] = 0
                elif self._accept_kw("cycle"):
                    seq.options["cycle"] = 1
                elif self._accept_kw("nocycle"):
                    seq.options["cycle"] = 0
                elif self._accept_kw("no"):
                    if self._accept_kw("cache"):
                        seq.options["cache"] = 0
                    elif self._accept_kw("cycle"):
                        seq.options["cycle"] = 0
                    elif (self._accept_kw("minvalue")
                          or self._accept_kw("maxvalue")):
                        pass  # keep the range defaults
                    else:
                        raise ParseError(
                            "expected MINVALUE, MAXVALUE, CACHE or CYCLE "
                            "after NO")
                else:
                    break
            return seq
        temporary = self._accept_kw("temporary")
        self._expect_kw("table")
        ine = False
        if self._accept_kw("if"):
            self._expect_kw("not")
            self._expect_kw("exists")
            ine = True
        stmt = ast.CreateTableStmt(if_not_exists=ine, temporary=temporary)
        stmt.table = self._parse_table_name()
        if self._accept_kw("like"):
            stmt.like = self._parse_table_name()
            return stmt
        self._expect_op("(")
        while True:
            item = self._parse_table_item()
            if isinstance(item, ast.ColumnDef):
                stmt.columns.append(item)
            else:
                stmt.constraints.append(item)
            if not self._accept_op(","):
                break
        self._expect_op(")")
        # table options
        while self._cur().kind == IDENT:
            opt = self._cur().val.lower()
            if opt in ("engine", "charset", "collate", "comment", "auto_increment", "row_format"):
                self.pos += 1
                self._accept_op("=")
                v = self._cur()
                self.pos += 1
                val = v.val
                # hyphenated option values (ENGINE=tpu-htap) lex as
                # ident '-' ident — stitch them back together
                while (self._peek_op("-") and v.kind == IDENT
                       and self.toks[self.pos + 1].kind == IDENT):
                    self.pos += 1
                    val = f"{val}-{self._ident()}"
                stmt.options[opt] = val
            elif opt == "default":
                self.pos += 1
            elif opt == "character":
                self.pos += 1
                self._expect_kw("set")
                self._accept_op("=")
                stmt.options["charset"] = self._ident()
            else:
                break
        if self._peek_kw("partition"):
            stmt.partition = self._parse_partition_opt()
        if self._accept_kw("as") or self._peek_kw("select"):
            stmt.select = self._parse_select_or_union()
        return stmt

    def _parse_partition_opt(self) -> ast.PartitionOpt:
        """PARTITION BY RANGE|HASH|LIST [COLUMNS] (expr) ... (reference:
        parser/parser.y PartitionOpt)."""
        self._expect_kw("partition")
        self._expect_kw("by")
        popt = ast.PartitionOpt()
        if self._accept_kw("range"):
            popt.type = "range"
        elif self._accept_kw("hash"):
            popt.type = "hash"
        elif self._accept_kw("list"):
            popt.type = "list"
        else:
            raise ParseError("expected RANGE, HASH or LIST after PARTITION BY")
        self._accept_kw("columns")  # COLUMNS(col) ≡ bare-column expr here
        self._expect_op("(")
        popt.expr = self._parse_expr()
        self._expect_op(")")
        if popt.type == "hash":
            if self._accept_kw("partitions"):
                popt.num = self._int_lit()
            else:
                popt.num = 1
            return popt
        self._expect_op("(")
        while True:
            popt.defs.append(self._parse_partition_def(popt.type))
            if not self._accept_op(","):
                break
        self._expect_op(")")
        return popt

    def _parse_partition_def_any(self):
        """Partition def in ALTER (type unknown until execution): peek at
        VALUES LESS THAN vs VALUES IN."""
        save = self.pos
        self._expect_kw("partition")
        self._ident()
        self._expect_kw("values")
        is_range = self._peek_kw("less")
        self.pos = save
        return self._parse_partition_def("range" if is_range else "list")

    def _parse_partition_def(self, ptype):
        self._expect_kw("partition")
        name = self._ident()
        self._expect_kw("values")
        if ptype == "range":
            self._expect_kw("less")
            self._expect_kw("than")
            if self._accept_kw("maxvalue"):
                return (name, "less_than", ["MAXVALUE"])
            self._expect_op("(")
            if self._accept_kw("maxvalue"):
                self._expect_op(")")
                return (name, "less_than", ["MAXVALUE"])
            v = self._parse_expr()
            self._expect_op(")")
            return (name, "less_than", [v])
        self._expect_kw("in")
        self._expect_op("(")
        values = []
        while True:
            if self._accept_kw("null"):
                values.append(None)
            else:
                values.append(self._parse_expr())
            if not self._accept_op(","):
                break
        self._expect_op(")")
        return (name, "in", values)

    def _parse_index_col(self):
        name = self._ident()
        length = None
        if self._accept_op("("):
            length = self._int_lit()
            self._expect_op(")")
        self._accept_kw("asc")
        self._accept_kw("desc")
        return (name, length)

    def _parse_table_item(self):
        t = self._cur()
        kw = t.val.lower() if t.kind == IDENT else ""
        if kw == "primary":
            self.pos += 1
            self._expect_kw("key")
            self._expect_op("(")
            cols = [self._parse_index_col()]
            while self._accept_op(","):
                cols.append(self._parse_index_col())
            self._expect_op(")")
            return ast.Constraint(kind="primary", columns=cols)
        if kw in ("unique", "key", "index", "fulltext", "constraint"):
            conname = ""
            if kw == "constraint":
                self.pos += 1
                if not (self._peek_kw("unique") or self._peek_kw("primary") or self._peek_kw("foreign")):
                    conname = self._ident()
                return self._parse_named_constraint(conname)
            unique = kw == "unique"
            self.pos += 1
            if unique:
                if not (self._accept_kw("key") or self._accept_kw("index")):
                    pass
            iname = ""
            if self._cur().kind in (IDENT, QIDENT) and not self._peek_op("("):
                iname = self._ident()
            self._expect_op("(")
            cols = [self._parse_index_col()]
            while self._accept_op(","):
                cols.append(self._parse_index_col())
            self._expect_op(")")
            return ast.Constraint(kind="unique" if unique else "index",
                                  name=iname, columns=cols)
        if kw == "foreign":
            return self._parse_named_constraint("")
        # column definition
        name = self._ident()
        ftype = self._parse_data_type()
        col = ast.ColumnDef(name=name, ftype=ftype)
        while True:
            t = self._cur()
            if t.kind != IDENT:
                break
            o = t.val.lower()
            if o == "not":
                self.pos += 1
                self._expect_kw("null")
                col.options["not_null"] = True
                col.ftype.flag |= FLAG_NOT_NULL
            elif o == "null":
                self.pos += 1
                col.options["null"] = True
            elif o == "default":
                self.pos += 1
                col.options["default"] = self._parse_expr(5)
            elif o == "auto_increment":
                self.pos += 1
                col.options["auto_increment"] = True
            elif o == "auto_random":
                self.pos += 1
                bits = 5
                if self._accept_op("("):
                    bits = self._int_lit()
                    self._expect_op(")")
                col.options["auto_random"] = bits
            elif o == "primary":
                self.pos += 1
                self._expect_kw("key")
                col.options["primary"] = True
            elif o == "key" or o == "unique":
                self.pos += 1
                self._accept_kw("key")
                col.options["unique" if o == "unique" else "key"] = True
            elif o == "comment":
                self.pos += 1
                c = self._cur()
                self.pos += 1
                col.options["comment"] = c.val
            elif o == "on":
                self.pos += 1
                self._expect_kw("update")
                col.options["on_update"] = self._parse_expr(5)
            elif o in ("collate", "character", "charset"):
                self.pos += 1
                if o == "character":
                    self._expect_kw("set")
                self._accept_op("=")
                ident = self._ident()
                if o == "collate":
                    col.options["collate"] = ident.lower()
            elif o == "references":
                self.pos += 1
                self._parse_table_name()
                if self._accept_op("("):
                    self._ident()
                    while self._accept_op(","):
                        self._ident()
                    self._expect_op(")")
            else:
                break
        return col

    def _parse_named_constraint(self, name: str):
        if self._accept_kw("unique"):
            self._accept_kw("key")
            self._accept_kw("index")
            iname = name
            if self._cur().kind in (IDENT, QIDENT) and not self._peek_op("("):
                iname = self._ident()
            self._expect_op("(")
            cols = [self._parse_index_col()]
            while self._accept_op(","):
                cols.append(self._parse_index_col())
            self._expect_op(")")
            return ast.Constraint(kind="unique", name=iname, columns=cols)
        if self._accept_kw("primary"):
            self._expect_kw("key")
            self._expect_op("(")
            cols = [self._parse_index_col()]
            while self._accept_op(","):
                cols.append(self._parse_index_col())
            self._expect_op(")")
            return ast.Constraint(kind="primary", columns=cols)
        if self._accept_kw("foreign"):
            self._expect_kw("key")
            if self._cur().kind in (IDENT, QIDENT) and not self._peek_op("("):
                self._ident()
            self._expect_op("(")
            cols = [self._parse_index_col()]
            while self._accept_op(","):
                cols.append(self._parse_index_col())
            self._expect_op(")")
            self._expect_kw("references")
            ref_table = self._parse_table_name()
            self._expect_op("(")
            ref_cols = [self._ident()]
            while self._accept_op(","):
                ref_cols.append(self._ident())
            self._expect_op(")")
            actions = {}
            while self._accept_kw("on"):
                which = self._ident().lower()  # update | delete
                if self._accept_kw("set"):
                    act = "set " + self._ident().lower()  # null | default
                elif self._accept_kw("no"):
                    self._expect_kw("action")
                    act = "no action"
                else:
                    act = self._ident().lower()  # cascade | restrict
                actions[which] = act
            return ast.Constraint(
                kind="foreign", name=name, columns=cols,
                ref={"table": ref_table, "columns": ref_cols,
                     "on_delete": actions.get("delete", ""),
                     "on_update": actions.get("update", "")})
        raise ParseError(f"unsupported constraint near {self._near()}")

    def _parse_data_type(self) -> FieldType:
        name = self._ident().lower()
        ft = FieldType()
        ints = {"tinyint": TYPE_TINY, "smallint": TYPE_SHORT, "mediumint": TYPE_INT24,
                "int": TYPE_LONG, "integer": TYPE_LONG, "bigint": TYPE_LONGLONG,
                "year": TYPE_YEAR, "serial": TYPE_LONGLONG, "bool": TYPE_TINY,
                "boolean": TYPE_TINY, "bit": TYPE_BIT}
        if name in ints:
            ft.tp = ints[name]
            if self._accept_op("("):
                ft.flen = self._int_lit()
                self._expect_op(")")
            while True:
                if self._accept_kw("unsigned"):
                    ft.flag |= FLAG_UNSIGNED
                elif self._accept_kw("signed") or self._accept_kw("zerofill"):
                    pass
                else:
                    break
            return ft
        if name in ("decimal", "numeric", "dec", "fixed"):
            ft.tp = TYPE_NEWDECIMAL
            ft.flen, ft.decimal = 10, 0
            if self._accept_op("("):
                ft.flen = self._int_lit()
                if self._accept_op(","):
                    ft.decimal = self._int_lit()
                self._expect_op(")")
            if self._accept_kw("unsigned"):
                ft.flag |= FLAG_UNSIGNED
            return ft
        if name in ("float", "double", "real"):
            ft.tp = TYPE_FLOAT if name == "float" else TYPE_DOUBLE
            if self._accept_op("("):
                self._int_lit()
                if self._accept_op(","):
                    self._int_lit()
                self._expect_op(")")
            self._accept_kw("unsigned")
            if self._accept_kw("precision"):  # DOUBLE PRECISION
                pass
            return ft
        if name in ("varchar", "varbinary", "char", "binary", "nvarchar", "nchar"):
            ft.tp = TYPE_VARCHAR if name.startswith(("var", "nvar")) else TYPE_STRING
            if self._accept_op("("):
                ft.flen = self._int_lit()
                self._expect_op(")")
            elif name in ("char", "binary", "nchar"):
                ft.flen = 1
            while self._peek_kw("character") or self._peek_kw("charset") or self._peek_kw("collate") or self._peek_kw("binary"):
                w = self._cur().val.lower()
                self.pos += 1
                if w == "character":
                    self._expect_kw("set")
                    self._ident()
                elif w == "collate":
                    ft.collate = self._ident().lower()
                elif w == "charset":
                    self._ident()
            return ft
        if name in ("text", "tinytext", "mediumtext", "longtext", "blob",
                    "tinyblob", "mediumblob", "longblob"):
            ft.tp = TYPE_BLOB
            if self._accept_op("("):
                self._int_lit()
                self._expect_op(")")
            while self._peek_kw("character") or self._peek_kw("charset") or self._peek_kw("collate"):
                w = self._cur().val.lower()
                self.pos += 1
                if w == "character":
                    self._expect_kw("set")
                ident = self._ident()
                if w == "collate":
                    ft.collate = ident.lower()
            return ft
        if name == "date":
            ft.tp = TYPE_DATE
            return ft
        if name in ("datetime", "timestamp"):
            ft.tp = TYPE_DATETIME if name == "datetime" else TYPE_TIMESTAMP
            ft.decimal = 0
            if self._accept_op("("):
                ft.decimal = self._int_lit()
                self._expect_op(")")
            return ft
        if name == "time":
            ft.tp = TYPE_DURATION
            ft.decimal = 0
            if self._accept_op("("):
                ft.decimal = self._int_lit()
                self._expect_op(")")
            return ft
        if name == "json":
            ft.tp = TYPE_JSON
            return ft
        if name in ("enum", "set"):
            ft.tp = TYPE_ENUM if name == "enum" else TYPE_SET
            self._expect_op("(")
            elems = []
            while True:
                t = self._cur()
                if t.kind != STRING:
                    raise ParseError("expected string in ENUM/SET")
                elems.append(t.val)
                self.pos += 1
                if not self._accept_op(","):
                    break
            self._expect_op(")")
            ft.elems = tuple(elems)
            while self._peek_kw("character") or self._peek_kw("charset") or self._peek_kw("collate"):
                w = self._cur().val.lower()
                self.pos += 1
                if w == "character":
                    self._expect_kw("set")
                ident = self._ident()
                if w == "collate":
                    ft.collate = ident.lower()
            return ft
        raise ParseError(f"unsupported data type {name!r}")

    def _parse_placement_options(self) -> dict:
        """PRIMARY_REGION/REGIONS/FOLLOWERS/LEARNERS/SCHEDULE/CONSTRAINTS
        ... = value pairs (reference: parser placement options grammar)."""
        opts = {}
        int_keys = {"followers", "learners", "voters"}
        str_keys = {"primary_region", "regions", "schedule", "constraints",
                    "leader_constraints", "follower_constraints",
                    "learner_constraints"}
        while True:
            t = self._cur()
            if t.kind != IDENT or t.val.lower() not in (int_keys | str_keys):
                break
            key = t.val.lower()
            self.pos += 1
            self._accept_op("=")
            v = self._cur()
            if key in int_keys:
                if v.kind != NUM_INT:
                    raise ParseError(
                        f"placement option {key.upper()} requires an "
                        f"integer value")
                opts[key] = int(v.val)
            elif v.kind == STRING:
                opts[key] = v.val.decode() if isinstance(v.val, bytes) \
                    else str(v.val)
            else:
                raise ParseError(f"bad placement option value near {v.val}")
            self.pos += 1
            self._accept_op(",")
        if not opts:
            # a bare ALTER would otherwise silently wipe every setting
            raise ParseError(
                "placement policy requires at least one placement option")
        return opts

    def _parse_drop(self):
        self._expect_kw("drop")
        if self._peek_kws("placement", "policy"):
            self.pos += 2
            ie = False
            if self._accept_kw("if"):
                self._expect_kw("exists")
                ie = True
            return ast.DropPlacementPolicyStmt(name=self._ident(),
                                               if_exists=ie)
        if (self._peek_kw("binding")
                or self._peek_kws("global", "binding")
                or self._peek_kws("session", "binding")):
            is_global = self._accept_kw("global")
            self._accept_kw("session")
            self._expect_kw("binding")
            self._expect_kw("for")
            orig = self._parse_select_or_union()
            return ast.DropBindingStmt(original=orig, is_global=is_global)
        if self._accept_kw("user"):
            ie = False
            if self._accept_kw("if"):
                self._expect_kw("exists")
                ie = True
            users = [self._parse_user_spec()]
            while self._accept_op(","):
                users.append(self._parse_user_spec())
            return ast.DropUserStmt(users=users, if_exists=ie)
        if self._accept_kw("database") or self._accept_kw("schema"):
            ie = False
            if self._accept_kw("if"):
                self._expect_kw("exists")
                ie = True
            return ast.DropDatabaseStmt(name=self._ident(), if_exists=ie)
        if self._accept_kw("index") or self._accept_kw("key"):
            ie = False
            if self._accept_kw("if"):
                self._expect_kw("exists")
                ie = True
            iname = self._ident()
            self._expect_kw("on")
            return ast.DropIndexStmt(index_name=iname, table=self._parse_table_name(), if_exists=ie)
        if self._accept_kw("sequence"):
            ie = False
            if self._accept_kw("if"):
                self._expect_kw("exists")
                ie = True
            seqs = [self._parse_table_name()]
            while self._accept_op(","):
                seqs.append(self._parse_table_name())
            return ast.DropSequenceStmt(sequences=seqs, if_exists=ie)
        is_view = self._accept_kw("view")
        temporary = self._accept_kw("temporary")
        if not is_view:
            self._expect_kw("table")
        ie = False
        if self._accept_kw("if"):
            self._expect_kw("exists")
            ie = True
        tables = [self._parse_table_name()]
        while self._accept_op(","):
            tables.append(self._parse_table_name())
        return ast.DropTableStmt(tables=tables, if_exists=ie, is_view=is_view,
                                 temporary=temporary)

    def _parse_alter(self):
        self._expect_kw("alter")
        if self._peek_kws("placement", "policy"):
            self.pos += 2
            name = self._ident()
            return ast.CreatePlacementPolicyStmt(
                name=name, or_alter=True,
                options=self._parse_placement_options())
        if self._accept_kw("user"):
            ie = False
            if self._accept_kw("if"):
                self._expect_kw("exists")
                ie = True
            users = [self._parse_user_with_auth()]
            while self._accept_op(","):
                users.append(self._parse_user_with_auth())
            return ast.AlterUserStmt(users=users, if_exists=ie)
        self._expect_kw("table")
        stmt = ast.AlterTableStmt(table=self._parse_table_name())
        while True:
            if self._accept_kw("add"):
                if self._accept_kw("partition"):
                    self._expect_op("(")
                    defs = []
                    while True:
                        # partition type resolved at execution from the table
                        defs.append(self._parse_partition_def_any())
                        if not self._accept_op(","):
                            break
                    self._expect_op(")")
                    stmt.specs.append(("add_partition", defs))
                elif self._accept_kw("column"):
                    if self._accept_op("("):
                        while True:
                            cd = self._parse_table_item()
                            stmt.specs.append(("add_column", cd, None))
                            if not self._accept_op(","):
                                break
                        self._expect_op(")")
                    else:
                        cd = self._parse_table_item()
                        pos = self._parse_col_position()
                        stmt.specs.append(("add_column", cd, pos))
                elif self._peek_kw("primary"):
                    con = self._parse_table_item()
                    stmt.specs.append(("add_primary", con))
                elif (self._peek_kw("index") or self._peek_kw("key")
                      or self._peek_kw("unique") or self._peek_kw("constraint")
                      or self._peek_kw("fulltext") or self._peek_kw("foreign")):
                    con = self._parse_table_item()
                    stmt.specs.append(("add_index", con))
                else:
                    cd = self._parse_table_item()
                    pos = self._parse_col_position()
                    stmt.specs.append(("add_column", cd, pos))
            elif self._accept_kw("drop"):
                if self._accept_kw("partition"):
                    names = [self._ident()]
                    while self._accept_op(","):
                        names.append(self._ident())
                    stmt.specs.append(("drop_partition", names))
                elif self._accept_kw("column"):
                    stmt.specs.append(("drop_column", self._ident()))
                elif self._accept_kw("index") or self._accept_kw("key"):
                    stmt.specs.append(("drop_index", self._ident()))
                elif self._accept_kw("primary"):
                    self._expect_kw("key")
                    stmt.specs.append(("drop_primary",))
                elif self._accept_kw("foreign"):
                    self._expect_kw("key")
                    self._ident()
                else:
                    stmt.specs.append(("drop_column", self._ident()))
            elif self._accept_kw("modify"):
                self._accept_kw("column")
                cd = self._parse_table_item()
                self._parse_col_position()
                stmt.specs.append(("modify_column", cd))
            elif self._accept_kw("change"):
                self._accept_kw("column")
                old = self._ident()
                cd = self._parse_table_item()
                self._parse_col_position()
                stmt.specs.append(("change_column", old, cd))
            elif self._accept_kw("rename"):
                if self._accept_kw("index") or self._accept_kw("key"):
                    old = self._ident()
                    self._expect_kw("to")
                    stmt.specs.append(("rename_index", old, self._ident()))
                else:
                    self._accept_kw("to")
                    self._accept_kw("as")
                    stmt.specs.append(("rename", self._parse_table_name()))
            elif self._accept_kw("exchange"):
                self._expect_kw("partition")
                pname = self._ident()
                self._expect_kw("with")
                self._expect_kw("table")
                other = self._parse_table_name()
                validate = True
                if self._accept_kw("without"):
                    self._expect_kw("validation")
                    validate = False
                elif self._accept_kw("with"):
                    self._expect_kw("validation")
                stmt.specs.append(("exchange_partition", pname, other,
                                   validate))
            elif self._accept_kw("cache"):
                stmt.specs.append(("cache", True))
            elif self._accept_kw("nocache"):
                stmt.specs.append(("cache", False))
            elif self._accept_kw("truncate"):
                self._expect_kw("partition")
                names = [self._ident()]
                while self._accept_op(","):
                    names.append(self._ident())
                stmt.specs.append(("truncate_partition", names))
            elif self._accept_kw("auto_increment"):
                self._accept_op("=")
                stmt.specs.append(("auto_increment", self._int_lit()))
            elif self._accept_kw("alter"):
                self._accept_kw("column")
                col = self._ident()
                if self._accept_kw("set"):
                    self._expect_kw("default")
                    stmt.specs.append(("set_default", col, self._parse_expr(5)))
                else:
                    self._expect_kw("drop")
                    self._expect_kw("default")
                    stmt.specs.append(("drop_default", col))
            else:
                break
            if not self._accept_op(","):
                break
        return stmt

    def _parse_col_position(self):
        if self._accept_kw("first"):
            return ("first",)
        if self._accept_kw("after"):
            return ("after", self._ident())
        return None

    # -- SET / SHOW / EXPLAIN / ADMIN --------------------------------------

    def _parse_set(self):
        self._expect_kw("set")
        if self._accept_kw("names"):
            t = self._cur()
            self.pos += 1
            items = [("session", "names", ast.Literal("str", str(t.val)))]
            self._accept_kw("collate")
            return ast.SetStmt(items=items)
        if self._peek_kws("session", "transaction") or self._peek_kws("global", "transaction") or self._peek_kw("transaction"):
            # SET [SESSION|GLOBAL] TRANSACTION ISOLATION LEVEL ...
            scope = "session"
            if self._accept_kw("global"):
                scope = "global"
            else:
                self._accept_kw("session")
            self._expect_kw("transaction")
            if self._accept_kw("isolation"):
                self._expect_kw("level")
                level = self._ident()
                while self._cur().kind == IDENT and not self._peek_op(";") and not self._peek_op(","):
                    level += " " + self._ident()
                return ast.SetStmt(items=[(scope, "transaction_isolation",
                                           ast.Literal("str", level.lower().replace(" ", "-")))])
            self._accept_kw("read")
            mode = self._ident()
            return ast.SetStmt(items=[(scope, "transaction_read_only",
                                       ast.Literal("int", 1 if mode.lower() == "only" else 0))])
        items = []
        while True:
            scope = "session"
            t = self._cur()
            if t.kind == USERVAR:
                self.pos += 1
                name = t.val.lower()
                scope = "user"
            elif t.kind == SYSVAR:
                self.pos += 1
                name = t.val.lower()
                if "." in name:
                    scope, name = name.split(".", 1)
            else:
                if self._accept_kw("global"):
                    scope = "global"
                elif self._accept_kw("session") or self._accept_kw("local"):
                    scope = "session"
                name = self._ident().lower()
            if not (self._accept_op("=") or self._accept_op(":=")):
                raise ParseError("expected = in SET")
            if self._peek_kw("on") :
                self.pos += 1
                val = ast.Literal("str", "ON")
            elif self._peek_kw("off"):
                self.pos += 1
                val = ast.Literal("str", "OFF")
            elif self._peek_kw("default"):
                self.pos += 1
                val = ast.DefaultExpr()
            else:
                val = self._parse_expr()
            items.append((scope, name, val))
            if not self._accept_op(","):
                break
        return ast.SetStmt(items=items)

    def _parse_show(self):
        self._expect_kw("show")
        full = self._accept_kw("full")
        glob = self._accept_kw("global")
        self._accept_kw("session")
        stmt = ast.ShowStmt(full=full, global_scope=glob)
        if self._accept_kw("bindings"):
            stmt.kind = "bindings"
        elif self._accept_kw("plugins"):
            stmt.kind = "plugins"
        elif self._accept_kw("databases") or self._accept_kw("schemas"):
            stmt.kind = "databases"
        elif self._accept_kw("tables"):
            stmt.kind = "tables"
            if self._accept_kw("from") or self._accept_kw("in"):
                stmt.db = self._ident()
        elif self._accept_kw("table"):
            self._expect_kw("status")
            stmt.kind = "table_status"
            if self._accept_kw("from") or self._accept_kw("in"):
                stmt.db = self._ident()
        elif self._accept_kw("columns") or self._accept_kw("fields"):
            stmt.kind = "columns"
            if self._accept_kw("from") or self._accept_kw("in"):
                stmt.target = self._parse_table_name()
            if self._accept_kw("from") or self._accept_kw("in"):
                stmt.db = self._ident()
        elif self._accept_kw("index") or self._accept_kw("indexes") or self._accept_kw("keys"):
            stmt.kind = "index"
            if self._accept_kw("from") or self._accept_kw("in"):
                stmt.target = self._parse_table_name()
        elif self._accept_kw("create"):
            if (self._accept_kw("table") or self._accept_kw("view")
                    or self._accept_kw("sequence")):
                # views/sequences render their own DDL from the same
                # handler (reference: ShowCreateView/ShowCreateSequence)
                stmt.kind = "create_table"
                stmt.target = self._parse_table_name()
            elif self._accept_kw("database"):
                stmt.kind = "create_database"
                stmt.db = self._ident()
            else:
                raise ParseError("unsupported SHOW CREATE")
        elif self._accept_kw("variables"):
            stmt.kind = "variables"
        elif self._accept_kw("status"):
            stmt.kind = "status"
        elif self._accept_kw("processlist"):
            stmt.kind = "processlist"
        elif self._accept_kw("engines"):
            stmt.kind = "engines"
        elif self._accept_kw("warnings"):
            stmt.kind = "warnings"
        elif self._accept_kw("errors"):
            stmt.kind = "errors"
        elif self._accept_kw("collation"):
            stmt.kind = "collation"
        elif self._accept_kw("charset") or self._peek_kws("character", "set"):
            if not stmt.kind:
                if self._accept_kw("character"):
                    self._expect_kw("set")
            stmt.kind = "charset"
        elif self._accept_kw("grants"):
            stmt.kind = "grants"
            if self._accept_kw("for"):
                stmt.target = self._parse_user_spec()
        else:
            raise ParseError(f"unsupported SHOW near {self._near()}")
        if self._accept_kw("like"):
            stmt.like = self._parse_expr(10)
        elif self._accept_kw("where"):
            stmt.where = self._parse_expr()
        return stmt

    def _parse_explain(self):
        self.pos += 1  # explain|desc|describe
        analyze = self._accept_kw("analyze")
        fmt = "row"
        if self._accept_kw("format"):
            self._expect_op("=")
            t = self._cur()
            fmt = str(t.val).lower()
            self.pos += 1
        # DESC table shorthand
        if not analyze and self._cur().kind in (IDENT, QIDENT):
            kw = self._cur().val.lower()
            if kw not in ("select", "insert", "update", "delete", "replace", "with"):
                tn = self._parse_table_name()
                return ast.ShowStmt(kind="columns", target=tn)
        return ast.ExplainStmt(stmt=self._parse_statement(), analyze=analyze, format=fmt)

    def _parse_admin(self):
        self._expect_kw("admin")
        if self._accept_kw("check"):
            if self._accept_kw("index"):
                tn = self._parse_table_name()
                idx_name = self._ident()
                return ast.AdminStmt(kind="check_index", tables=[tn],
                                     index_name=idx_name)
            self._expect_kw("table")
            tables = [self._parse_table_name()]
            while self._accept_op(","):
                tables.append(self._parse_table_name())
            return ast.AdminStmt(kind="check_table", tables=tables)
        if self._accept_kw("show"):
            if self._accept_kw("telemetry"):
                return ast.AdminStmt(kind="show_telemetry")
            self._expect_kw("ddl")
            if self._accept_kw("jobs"):
                return ast.AdminStmt(kind="show_ddl_jobs")
            return ast.AdminStmt(kind="show_ddl")
        if self._accept_kw("checksum"):
            self._expect_kw("table")
            tables = [self._parse_table_name()]
            while self._accept_op(","):
                tables.append(self._parse_table_name())
            return ast.AdminStmt(kind="checksum_table", tables=tables)
        if self._accept_kw("cancel"):
            self._expect_kw("ddl")
            self._expect_kw("jobs")
            ids = [self._int_lit()]
            while self._accept_op(","):
                ids.append(self._int_lit())
            return ast.AdminStmt(kind="cancel_ddl_jobs", job_ids=ids)
        if self._accept_kw("compile"):
            # ADMIN COMPILE: prewarm the compile service's bucket ladder
            # for every hot fragment recipe (executor/compile_service.py)
            return ast.AdminStmt(kind="compile")
        raise ParseError("unsupported ADMIN statement")


def parse(sql: str) -> list[ast.StmtNode]:
    return Parser().parse(sql)


def parse_one(sql: str) -> ast.StmtNode:
    stmts = parse(sql)
    if len(stmts) != 1:
        raise ParseError(f"expected a single statement, got {len(stmts)}")
    return stmts[0]
