"""Correlated subqueries, quantified comparisons, and CTE edge cases
(code-review round 2 regressions)."""

import pytest

from tidb_tpu.testkit import TestKit


@pytest.fixture(scope="module")
def tk():
    tk = TestKit()
    tk.must_exec("create database subq")
    tk.must_exec("use subq")
    tk.must_exec("create table t1 (a bigint, k bigint)")
    tk.must_exec("create table t2 (b decimal(10,2), k bigint)")
    tk.must_exec("insert into t1 values (1, 1), (2, 1), (3, 2)")
    tk.must_exec("insert into t2 values (1.00, 1), (2.50, 1), (3.00, 2)")
    tk.must_exec("create table emp (id bigint, dept bigint, sal bigint)")
    tk.must_exec("insert into emp values (1,10,100),(2,10,200),(3,20,50)")
    return tk


def test_correlated_in_decimal_vs_int(tk):
    # scaled-decimal internals must unify with the int target (1 = 1.00)
    r = tk.must_query(
        "select a from t1 where a in (select b from t2 where t2.k = t1.k) "
        "order by a")
    r.check([("1",), ("3",)])


def test_correlated_any_all(tk):
    r = tk.must_query(
        "select a from t1 where a > any (select b from t2 where t2.k = t1.k) "
        "order by a")
    r.check([("2",)])
    r = tk.must_query(
        "select a from t1 where a >= all (select b from t2 where t2.k = t1.k) "
        "order by a")
    r.check([("3",)])


def test_correlated_in_agg_select_list(tk):
    # outer ref inside the subquery's aggregated SELECT list / HAVING
    r = tk.must_query(
        "select id from emp e where exists (select count(*) from t1 "
        "having count(*) > e.dept - 10) order by id")
    r.check([("1",), ("2",)])  # count=3 > 0 for dept 10; 3 > 10 false for 20
    r = tk.must_query(
        "select (select max(b) + t1.a from t2) from t1 where a = 1")
    r.check([("4.00",)])


def test_recursive_cte_supported(tk):
    # round-1 rejected these; they now evaluate by fixpoint
    # (tests/test_recursive_cte.py covers the full matrix)
    tk.must_query(
        "with recursive r as (select 1 as n union all "
        "select n + 1 from r where n < 3) select * from r order by n"
    ).check([("1",), ("2",), ("3",)])


def test_cte_column_count_mismatch(tk):
    e = tk.exec_error("with c (x, y) as (select 1) select x from c")
    assert "different column counts" in str(e)


def test_with_in_derived_table(tk):
    r = tk.must_query(
        "select * from (with x as (select 1 as a) select * from x) d")
    r.check([("1",)])


def test_uncorrelated_still_works(tk):
    r = tk.must_query(
        "select a from t1 where a in (select b from t2) order by a")
    r.check([("1",), ("3",)])
    r = tk.must_query(
        "select a from t1 where exists (select * from t2 where b > 2.9) "
        "order by a")
    r.check([("1",), ("2",), ("3",)])


# -- a derived table named with a column list (ISSUE 37) ----------------------

def test_derived_table_column_list_names_the_columns(tk):
    tk.must_query(
        "select k2, n from (select k, count(a) from t1 group by k) "
        "as d (k2, n) order by k2").check([("1", "2"), ("2", "1")])
    # without AS, and the names reach the result set's header
    r = tk.must_query("select * from (select a, k from t1) d (x, y) "
                      "where x = 3")
    r.check([("3", "2")])
    assert list(r.result.names) == ["x", "y"]


def test_derived_table_column_list_shadows_select_aliases(tk):
    # the list wins over the select list's own aliases: `n` is gone
    tk.must_query("select m from (select a as n from t1) as d (m) "
                  "order by m").check([("1",), ("2",), ("3",)])
    e = tk.exec_error("select n from (select a as n from t1) as d (m)")
    assert e.code == 1054 and "n" in str(e)
    tk.must_query("select d.m from (select a as n from t1) as d (m) "
                  "where d.m = 2").check([("2",)])


def test_derived_table_column_list_arity(tk):
    for sql in ("select * from (select a, k from t1) as d (x)",
                "select * from (select a from t1) as d (x, y)"):
        e = tk.exec_error(sql)
        assert e.code == 1353, sql
        assert "different column counts" in str(e)
    # the CTE form of the same refusal carries the same code now
    assert tk.exec_error(
        "with c (x, y) as (select 1) select x from c").code == 1353


@pytest.mark.parametrize("engine", ["host", "tpu"])
def test_derived_table_column_list_inside_a_join(tk, engine):
    tk.must_exec(f"set tidb_executor_engine = '{engine}'")
    try:
        tk.must_query(
            "select t1.a, d.n from t1 join (select k, count(*) from t2 "
            "group by k) as d (kk, n) on t1.k = d.kk order by t1.a"
        ).check([("1", "2"), ("2", "2"), ("3", "1")])
        tk.must_query(
            "select n, count(*) from (select t1.k, count(b) from t1 "
            "left join t2 on t1.a = t2.k group by t1.k) as d (kk, n) "
            "group by n order by n").check([("0", "1"), ("3", "1")])
    finally:
        tk.must_exec("set tidb_executor_engine = 'auto'")
