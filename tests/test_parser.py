"""Parser tests (reference test model: parser/parser_test.go)."""

import pytest

from tidb_tpu.errors import ParseError
from tidb_tpu.parser import ast, digest, normalize, parse, parse_one


def test_simple_select():
    s = parse_one("SELECT a, b+1 AS c FROM t WHERE a > 10 ORDER BY b DESC LIMIT 5")
    assert isinstance(s, ast.SelectStmt)
    assert len(s.fields) == 2
    assert s.fields[1].as_name == "c"
    assert isinstance(s.where, ast.BinaryOp) and s.where.op == ">"
    assert s.order_by[0].desc
    assert s.limit.count.val == 5


def test_select_star_and_qualified():
    s = parse_one("select *, t.*, db.t.* from db.t")
    assert isinstance(s.fields[0].expr, ast.StarExpr)
    assert s.fields[1].expr.table == "t"
    assert s.fields[2].expr.schema == "db"


def test_operator_precedence():
    s = parse_one("select 1 + 2 * 3 = 7 and 2 < 3 or not 1")
    e = s.fields[0].expr
    assert isinstance(e, ast.BinaryOp) and e.op == "or"
    land = e.left
    assert land.op == "and"
    eq = land.left
    assert eq.op == "="
    assert eq.left.op == "+"
    assert eq.left.right.op == "*"


def test_predicates():
    s = parse_one("select * from t where a between 1 and 10 and b not in (1,2,3) "
                  "and c like 'x%' and d is not null and e in (select f from u)")
    w = s.where
    # and-chain; just check restore round-trips through parse again
    parse_one(s.restore())


def test_joins():
    s = parse_one("select * from a join b on a.x=b.x left join c on b.y=c.y, d")
    f = s.from_
    assert isinstance(f, ast.Join) and f.kind == "cross"
    lj = f.left
    assert lj.kind == "left"
    assert lj.left.kind == "inner"


def test_join_using():
    s = parse_one("select * from a join b using (x, y)")
    assert s.from_.using == ["x", "y"]


def test_subquery_table():
    s = parse_one("select * from (select a from t) s where s.a > 1")
    assert isinstance(s.from_, ast.SubqueryTable)
    assert s.from_.as_name == "s"


def test_union():
    s = parse_one("select a from t union all select b from u union select c from v "
                  "order by 1 limit 10")
    assert isinstance(s, ast.SetOprStmt)
    assert s.ops == ["union all", "union"]
    assert s.limit.count.val == 10
    assert len(s.order_by) == 1


def test_aggregates():
    s = parse_one("select count(*), count(distinct a), sum(b*c), avg(d), "
                  "group_concat(e separator ',') from t group by f having count(*) > 1")
    assert s.fields[0].expr.name == "count" and not s.fields[0].expr.args
    assert s.fields[1].expr.distinct
    assert isinstance(s.having, ast.BinaryOp)


def test_case_when():
    s = parse_one("select case when a=1 then 'x' else 'y' end, "
                  "case a when 1 then 2 when 3 then 4 end from t")
    c0 = s.fields[0].expr
    assert isinstance(c0, ast.CaseExpr) and c0.operand is None and c0.else_ is not None
    c1 = s.fields[1].expr
    assert c1.operand is not None and len(c1.whens) == 2


def test_funcs_special():
    parse_one("select extract(year from d), substring(s, 1, 3), substring(s from 2 for 4), "
              "trim(leading 'x' from s), position('a' in s), cast(a as signed), "
              "cast(b as decimal(10,2)), convert(c, char(5)) from t")


def test_date_literals_and_interval():
    s = parse_one("select date '1995-01-01', date_add(d, interval 3 month) from t")
    lit = s.fields[0].expr
    assert lit.kind == "date"
    fc = s.fields[1].expr
    assert isinstance(fc.args[1], ast.IntervalExpr) and fc.args[1].unit == "month"


def test_exists_and_scalar_subquery():
    parse_one("select (select max(a) from t) from u where exists (select 1 from v) "
              "and x > all (select y from w)")


def test_insert():
    s = parse_one("insert into t (a, b) values (1, 'x'), (2, 'y')")
    assert s.columns == ["a", "b"]
    assert len(s.values) == 2
    s2 = parse_one("insert into t select * from u")
    assert s2.select is not None
    s3 = parse_one("replace into t values (1)")
    assert s3.is_replace
    s4 = parse_one("insert into t set a=1, b=2")
    assert s4.columns == ["a", "b"]
    s5 = parse_one("insert into t values (1) on duplicate key update a=a+1")
    assert len(s5.on_duplicate) == 1


def test_update_delete():
    s = parse_one("update t set a=1, b=b+1 where c=2 limit 3")
    assert len(s.assignments) == 2
    assert s.limit.count.val == 3
    d = parse_one("delete from t where a=1")
    assert d.where is not None


def test_create_table():
    s = parse_one("""
        CREATE TABLE IF NOT EXISTS t (
            id BIGINT NOT NULL AUTO_INCREMENT,
            name VARCHAR(64) DEFAULT 'x',
            price DECIMAL(15,2) NOT NULL,
            d DATE,
            ts DATETIME(6),
            PRIMARY KEY (id),
            UNIQUE KEY uk (name),
            KEY idx_price (price, d)
        ) ENGINE=InnoDB CHARSET=utf8mb4
    """)
    assert isinstance(s, ast.CreateTableStmt)
    assert s.if_not_exists
    assert len(s.columns) == 5
    assert s.columns[0].options.get("auto_increment")
    assert s.columns[1].options["default"].val == "x"
    assert len(s.constraints) == 3
    assert s.constraints[0].kind == "primary"
    assert s.constraints[1].kind == "unique"


def test_ddl_misc():
    parse_one("create database if not exists db1")
    parse_one("drop database if exists db1")
    parse_one("drop table if exists a, b")
    parse_one("create unique index i on t (a, b(10))")
    parse_one("drop index i on t")
    parse_one("truncate table t")
    parse_one("rename table a to b")
    a = parse_one("alter table t add column c int not null default 0 after b, drop column d")
    assert a.specs[0][0] == "add_column"
    assert a.specs[1][0] == "drop_column"
    a2 = parse_one("alter table t add index idx (a), add unique key uk (b), modify column c bigint")
    assert [sp[0] for sp in a2.specs] == ["add_index", "add_index", "modify_column"]


def test_simple_stmts():
    parse_one("use test")
    s = parse_one("set @@session.sql_mode='', global max_connections=100, @u=5")
    assert [i[0] for i in s.items] == ["session", "global", "user"]
    parse_one("set names utf8mb4")
    parse_one("show databases")
    parse_one("show tables from db like 't%'")
    parse_one("show create table t")
    parse_one("show variables like 'a%'")
    parse_one("begin")
    parse_one("start transaction")
    parse_one("commit")
    parse_one("rollback")
    parse_one("analyze table t")
    e = parse_one("explain analyze select 1")
    assert e.analyze
    d = parse_one("desc t")
    assert isinstance(d, ast.ShowStmt) and d.kind == "columns"
    parse_one("admin show ddl jobs")
    parse_one("kill 42")


def test_multi_statement():
    stmts = parse("select 1; select 2;")
    assert len(stmts) == 2


def test_parse_errors():
    with pytest.raises(ParseError):
        parse_one("select from where")
    with pytest.raises(ParseError):
        parse_one("create table t")
    with pytest.raises(ParseError):
        parse_one("select * from t limit")


def test_string_escapes():
    s = parse_one(r"select 'a\'b', 'c''d', 'x' 'y'")
    assert s.fields[0].expr.val == "a'b"
    assert s.fields[1].expr.val == "c'd"
    assert s.fields[2].expr.val == "xy"


def test_comments():
    s = parse_one("select 1 -- comment\n + 2 /* inline */ , 3 # end\n from t")
    assert len(s.fields) == 2


def test_normalize_digest():
    n1 = normalize("SELECT * FROM t WHERE a = 10 AND b IN (1, 2, 3)")
    n2 = normalize("select * from t where a = 99 and b in (4,5)")
    assert n1 == n2
    assert digest(n1) == digest(n2)


TPCH_Q1 = """
select
    l_returnflag, l_linestatus,
    sum(l_quantity) as sum_qty,
    sum(l_extendedprice) as sum_base_price,
    sum(l_extendedprice * (1 - l_discount)) as sum_disc_price,
    sum(l_extendedprice * (1 - l_discount) * (1 + l_tax)) as sum_charge,
    avg(l_quantity) as avg_qty,
    avg(l_extendedprice) as avg_price,
    avg(l_discount) as avg_disc,
    count(*) as count_order
from lineitem
where l_shipdate <= date '1998-12-01' - interval 90 day
group by l_returnflag, l_linestatus
order by l_returnflag, l_linestatus
"""

TPCH_Q3 = """
select
    l_orderkey,
    sum(l_extendedprice * (1 - l_discount)) as revenue,
    o_orderdate, o_shippriority
from customer, orders, lineitem
where c_mktsegment = 'BUILDING'
  and c_custkey = o_custkey
  and l_orderkey = o_orderkey
  and o_orderdate < date '1995-03-15'
  and l_shipdate > date '1995-03-15'
group by l_orderkey, o_orderdate, o_shippriority
order by revenue desc, o_orderdate
limit 10
"""

TPCH_Q5 = """
select n_name, sum(l_extendedprice * (1 - l_discount)) as revenue
from customer, orders, lineitem, supplier, nation, region
where c_custkey = o_custkey and l_orderkey = o_orderkey
  and l_suppkey = s_suppkey and c_nationkey = s_nationkey
  and s_nationkey = n_nationkey and n_regionkey = r_regionkey
  and r_name = 'ASIA'
  and o_orderdate >= date '1994-01-01'
  and o_orderdate < date '1994-01-01' + interval '1' year
group by n_name
order by revenue desc
"""

TPCH_Q18 = """
select c_name, c_custkey, o_orderkey, o_orderdate, o_totalprice, sum(l_quantity)
from customer, orders, lineitem
where o_orderkey in (
        select l_orderkey
        from lineitem
        group by l_orderkey
        having sum(l_quantity) > 300)
  and c_custkey = o_custkey
  and o_orderkey = l_orderkey
group by c_name, c_custkey, o_orderkey, o_orderdate, o_totalprice
order by o_totalprice desc, o_orderdate
limit 100
"""


@pytest.mark.parametrize("q", [TPCH_Q1, TPCH_Q3, TPCH_Q5, TPCH_Q18],
                         ids=["q1", "q3", "q5", "q18"])
def test_tpch_queries_parse(q):
    s = parse_one(q)
    assert isinstance(s, ast.SelectStmt)
    # restore must itself re-parse to the same restored text (fixpoint)
    r1 = s.restore()
    assert parse_one(r1).restore() == r1


# -- a derived table's alias with a column list (ISSUE 37) --------------------

@pytest.mark.parametrize("sql", [
    "select a from (select 1, 2) as t (a, b)",
    "select a from (select 1, 2) t (a, b)",
    "select a from (select 1, 2) AS `t` (`a`, `b`)",
])
def test_derived_table_column_list(sql):
    s = parse_one(sql)
    assert isinstance(s.from_, ast.SubqueryTable)
    assert s.from_.as_name == "t" and s.from_.col_names == ["a", "b"]


def test_derived_table_column_list_round_trips():
    s = parse_one("select c_count, count(*) from (select k, count(v) from u "
                  "group by k) as c_orders (c_custkey, c_count) "
                  "group by c_count")
    text = s.restore()
    assert "AS `c_orders` (`c_custkey`, `c_count`)" in text
    again = parse_one(text)
    assert again.from_.col_names == ["c_custkey", "c_count"]
    assert again.restore() == text
    # without a list, restore() prints what it printed before
    bare = parse_one("select a from (select 1 as a) as t")
    assert bare.from_.col_names == [] and bare.restore().endswith("AS `t`")


def test_derived_table_column_list_inside_a_join():
    s = parse_one("select * from t1 join (select 1, 2) d (x, y) on t1.a = d.x "
                  "left join (select 3) as e (z) on e.z = d.y")
    assert s.from_.right.col_names == ["z"]
    assert s.from_.left.right.col_names == ["x", "y"]


@pytest.mark.parametrize("sql", [
    "select a from (select 1) as t ()",
    "select a from (select 1) as t (a,)",
    "select a from (select 1) as t (a b)",
    "select a from (select 1) (a)",      # a column list needs an alias
])
def test_derived_table_column_list_errors(sql):
    with pytest.raises(ParseError):
        parse(sql)
