"""TPC-H Q13 and Q4 in the specification's own text (ISSUE 37): the plain
numpy reference, the host engine and the `tpu` engine give the same rows
on two seeds; Q13 (a left outer join over a non-unique build that
CSR-expands and null-extends, ``count()`` over the NULL-extended column,
a derived table named with a column list) is ONE fused `engine:tpu`
fragment with a host aggregate over its 3,000 derived rows, Q4 (a semi
join at the root against a filtered non-unique build) ONE; the join
kinds and the expansion are counted, printed by ``EXPLAIN ANALYZE`` and
the host aggregate has a span.  The benchmark's cell `tpch-sf1.q13q4`
runs both at SF1 on the chip; this file holds them at a size XLA:CPU
takes in seconds."""

import json
import pathlib
import re
import sys

import numpy as np
import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from benchmark.datasets import tpch_text  # noqa: E402
from benchmark.queries import q4, q13  # noqa: E402
from tidb_tpu.executor import device_exec  # noqa: E402
from tidb_tpu.executor import device_join as dj  # noqa: E402
from tidb_tpu.ops import device as dev  # noqa: E402
from tidb_tpu.session import tracing  # noqa: E402
from tidb_tpu.testkit import TestKit  # noqa: E402

MODS = {"q13": q13, "q4": q4}
SF = 0.02
SEEDS = (7, 3700000011)
N_CUSTOMERS = int(SF * 150_000)
PATTERN = re.compile(rb"special.*requests", re.S)


def _load(seed):
    want = {t: list(cols) for t, cols in tpch_text.SCHEMA.items()}
    tables = tpch_text.generate(seed, SF, want)
    tk = TestKit()
    tpch_text.load(tk, tables, want, False, f"test_tpch_q13q4/{seed}")
    tk.must_exec("set tidb_device_dispatch_rows = 1")
    tk.must_exec("set tidb_result_cache = 'OFF'")
    return tables, tk


_LOADED = {}


def _loaded(seed):
    """(tables, TestKit) of one seed, made once a module."""
    if seed not in _LOADED:
        _LOADED[seed] = _load(seed)
    return _LOADED[seed]


def _rows(tk, engine, sql):
    tk.must_exec(f"set tidb_executor_engine = '{engine}'")
    return [tuple(r) for r in tk.must_query(sql).rows]


def _notes(tk, sql):
    plan = tk.must_query("explain analyze " + sql).rows
    return [part for row in plan for part in (row[2] or "").split(", ")]


def _pipelines(tk):
    return json.loads(tk.must_query("DIAG STATUS").rows[0][0])[
        "device_pipelines"]


def _grew(before, after, *keys):
    return [after[k] - before[k] for k in keys]


def _kept_orders(tables):
    """(orders the ON clause keeps, customers none of them belongs to)."""
    o = tables["orders"]
    codes, words = o["o_comment"]
    matched = np.array([PATTERN.search(w) is not None for w in words])[codes]
    with_order = np.unique(o["o_custkey"][~matched])
    return int((~matched).sum()), N_CUSTOMERS - len(with_order)


# -- reference == host == tpu, in the specification's text ----------------------

def test_the_templates_are_the_specifications_text():
    assert "customer left outer join orders" in q13.SQL
    assert "and o_comment not like '%special%requests%'" in q13.SQL
    assert "as c_orders (c_custkey, c_count)" in q13.SQL
    assert "count(o_orderkey)" in q13.SQL
    assert "order by custdist desc, c_count desc" in q13.SQL
    assert "exists (select * from lineitem" in q4.SQL
    assert "l_commitdate < l_receiptdate" in q4.SQL
    assert "date '1993-07-01' + interval '3' month" in q4.SQL


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("template", list(MODS))
def test_reference_host_and_tpu_agree(template, seed):
    mod = MODS[template]
    tables, tk = _loaded(seed)
    want = mod.reference(tables)
    assert want, "an empty answer proves nothing"
    assert _rows(tk, "host", mod.SQL) == want
    assert _rows(tk, "tpu", mod.SQL) == want


@pytest.mark.parametrize("seed", SEEDS)
def test_q13s_zero_group_holds_the_customers_without_an_order(seed):
    tables, tk = _loaded(seed)
    rows = dict((int(c), int(n)) for c, n in _rows(tk, "tpu", q13.SQL))
    _kept, without = _kept_orders(tables)
    # o_custkey is never a multiple of 3 (cl. 4.2.3): every such customer
    # is answered by the null extension alone, count(o_orderkey) = 0
    assert rows[0] == without >= N_CUSTOMERS // 3
    assert (tables["orders"]["o_custkey"] % 3 != 0).all()
    assert sum(rows.values()) == N_CUSTOMERS
    assert sum(c * n for c, n in rows.items()) == _kept
    # the largest group first
    first = _rows(tk, "tpu", q13.SQL)[0]
    assert first == ("0", str(without))


@pytest.mark.parametrize("seed", SEEDS)
def test_q4_counts_an_order_once_however_many_lines_are_late(seed):
    tables, tk = _loaded(seed)
    got = _rows(tk, "tpu", q4.SQL)
    assert [r[0] for r in got] == [
        "1-URGENT", "2-HIGH", "3-MEDIUM", "4-NOT SPECIFIED", "5-LOW"]
    li = tables["lineitem"]
    late_lines = int((li["l_commitdate"] < li["l_receiptdate"]).sum())
    in_quarter = _rows(tk, "host", "select count(*) from orders where "
                       "o_orderdate >= '1993-07-01' and o_orderdate < "
                       "'1993-10-01'")[0][0]
    # an existence count: fewer than the quarter's orders, far fewer than
    # the late lines an inner join would emit
    assert 0 < sum(int(r[1]) for r in got) < int(in_quarter) < late_lines


# -- one fragment each, counted and printed -------------------------------------

@pytest.mark.parametrize("seed", SEEDS)
def test_q13_is_one_fragment_that_expands_and_null_extends(seed):
    tables, tk = _loaded(seed)
    _rows(tk, "tpu", q13.SQL)              # capacities learned
    before = _pipelines(tk)
    _rows(tk, "tpu", q13.SQL)
    after = _pipelines(tk)
    assert _grew(before, after, "join_direct", "join_search", "join_left",
                 "join_semi", "join_anti", "join_expand") == [1, 0, 1, 0, 0, 1]
    assert _grew(before, after, "unsupported", "capacity_reruns",
                 "compiles") == [0, 0, 0]
    kept, without = _kept_orders(tables)
    rows, slots = _grew(before, after, "join_expand_rows",
                        "join_expand_capacity")
    # a row a kept order, one null-extended row a customer without one
    assert rows == kept + without
    assert rows <= slots == dev.next_pow2(rows)
    notes = _notes(tk, q13.SQL)
    assert [n for n in notes if n.startswith("engine:")] == ["engine:tpu"]
    assert "join:direct x1 (left x1" in notes
    assert "expand x1 one-pass)" in notes
    assert "agg:sort" in notes and "probe:resident" in notes
    assert notes.count("fused:into tpu fragment") == 3
    assert not [n for n in notes if n.startswith("device_unsupported:")]


@pytest.mark.parametrize("seed", SEEDS)
def test_q4_is_one_fragment_with_a_semi_join_at_its_root(seed):
    _tables, tk = _loaded(seed)
    _rows(tk, "tpu", q4.SQL)
    before = _pipelines(tk)
    _rows(tk, "tpu", q4.SQL)
    after = _pipelines(tk)
    # an existence count over a non-unique build is not an expansion
    assert _grew(before, after, "join_direct", "join_left", "join_semi",
                 "join_anti", "join_expand", "join_expand_rows",
                 "join_expand_capacity") == [1, 0, 1, 0, 0, 0, 0]
    assert _grew(before, after, "unsupported", "capacity_reruns",
                 "compiles") == [0, 0, 0]
    notes = _notes(tk, q4.SQL)
    assert [n for n in notes if n.startswith("engine:")] == ["engine:tpu"]
    assert "join:direct x1 (semi x1)" in notes
    assert notes.count("fused:into tpu fragment") == 3
    assert not [n for n in notes if n.startswith("device_unsupported:")]


def test_inner_unique_joins_print_what_they_printed():
    _tables, tk = _loaded(SEEDS[0])
    tk.must_exec("set tidb_executor_engine = 'tpu'")
    sql = ("select o_orderpriority, count(*) from orders, customer "
           "where o_custkey = c_custkey group by o_orderpriority")
    before = _pipelines(tk)
    notes = _notes(tk, sql)
    after = _pipelines(tk)
    assert "join:direct x1" in notes
    assert _grew(before, after, "join_left", "join_semi", "join_anti",
                 "join_expand", "join_expand_rows") == [0, 0, 0, 0, 0]


def test_an_anti_join_counts_under_its_kind():
    _tables, tk = _loaded(SEEDS[0])
    sql = ("select o_orderpriority, count(*) from orders where not exists "
           "(select * from lineitem where l_orderkey = o_orderkey and "
           "l_commitdate < l_receiptdate) group by o_orderpriority "
           "order by o_orderpriority")
    want = _rows(tk, "host", sql)
    before = _pipelines(tk)
    assert _rows(tk, "tpu", sql) == want and len(want) == 5
    after = _pipelines(tk)
    assert _grew(before, after, "join_anti", "join_semi", "join_expand",
                 "unsupported") == [1, 0, 0, 0]
    assert "join:direct x1 (anti x1)" in _notes(tk, sql)


def test_the_expansion_counts_once_across_a_capacity_rerun():
    tables, tk = _loaded(SEEDS[1])
    dj._CAP_STORE.clear()
    device_exec._PIPE_CACHE.clear()
    before = _pipelines(tk)
    assert _rows(tk, "tpu", q13.SQL) == q13.reference(tables)
    after = _pipelines(tk)
    # the first run's capacities are estimates: the loop goes round again
    assert after["capacity_reruns"] - before["capacity_reruns"] >= 1
    assert _grew(before, after, "join_direct", "join_left",
                 "join_expand") == [1, 1, 1]
    kept, without = _kept_orders(tables)
    rows, slots = _grew(before, after, "join_expand_rows",
                        "join_expand_capacity")
    assert rows == kept + without and rows <= slots
    assert slots & (slots - 1) == 0        # ONE program's capacity


# -- the aggregate over the derived table: the host's, with a span --------------

def _find(node, name):
    out = [node] if node["name"] == name else []
    for c in node.get("children", ()):
        out += _find(c, name)
    return out


@pytest.mark.parametrize("engine", ["tpu", "host"])
def test_the_derived_tables_aggregate_has_a_span(engine):
    tables, tk = _loaded(SEEDS[0])
    tk.must_exec(f"set tidb_executor_engine = '{engine}'")
    tk.must_exec("set tidb_trace_sampling_rate = 1")
    rows = tk.must_query(q13.SQL).rows
    tree = tracing.last_trace().to_dict()["root"]
    tk.must_query(q4.SQL)
    q4_tree = tracing.last_trace().to_dict()["root"]
    tk.must_exec("set tidb_trace_sampling_rate = 0")
    (sp,) = _find(tree, "derived.aggregate")
    assert sp["tags"] == {"rows_in": N_CUSTOMERS, "groups": len(rows)}
    # the derived table's own fragment ran before the span opened
    assert not _find(sp, "device.dispatch")
    assert len(_find(tree, "device.dispatch")) == (engine == "tpu")
    # an aggregate over a scan or a join fragment opens none
    assert not _find(q4_tree, "derived.aggregate")


def test_no_span_is_opened_without_a_trace():
    _tables, tk = _loaded(SEEDS[0])
    started = tracing.STATS["started"]
    _rows(tk, "tpu", q13.SQL)
    assert tracing.STATS["started"] == started


# -- a pattern that matches nothing, a comment that is NULL ---------------------

@pytest.fixture(scope="module")
def small():
    """customer and orders of SF 0.002 through SQL (KV-backed, so they
    take an UPDATE), with Q13's three columns."""
    t = tpch_text.generate(SEEDS[0], 0.002, q13.READS)
    tk = TestKit()
    tk.must_exec("create table customer (c_custkey bigint primary key)")
    tk.must_exec("create table orders (o_orderkey bigint primary key, "
                 "o_custkey bigint, o_comment varchar(79))")
    tk.must_exec("insert into customer values " + ", ".join(
        f"({k})" for k in t["customer"]["c_custkey"]))
    codes, words = t["orders"]["o_comment"]
    o = t["orders"]
    for lo in range(0, len(codes), 1000):
        tk.must_exec("insert into orders values " + ", ".join(
            "({}, {}, '{}')".format(
                k, c, words[w].decode().replace("'", "''")) for k, c, w in zip(
                o["o_orderkey"][lo:lo + 1000], o["o_custkey"][lo:lo + 1000],
                codes[lo:lo + 1000])))
    tk.must_exec("set tidb_device_dispatch_rows = 1")
    tk.must_exec("set tidb_result_cache = 'OFF'")
    return t, tk


def _orders_counted(rows):
    return sum(int(c) * int(n) for c, n in rows)


def test_sql_loaded_tables_give_the_references_rows(small):
    t, tk = small
    want = q13.reference(t)
    assert _rows(tk, "host", q13.SQL) == want
    assert _rows(tk, "tpu", q13.SQL) == want
    assert [n for n in _notes(tk, q13.SQL) if n.startswith("engine:")] \
        == ["engine:tpu"]


def test_a_null_comment_is_not_kept(small):
    t, tk = small
    counted = _orders_counted(q13.reference(t))
    kept = tk.must_query(
        "select o_orderkey from orders where o_comment not like "
        "'%special%requests%' order by o_orderkey limit 1").rows[0][0]
    tk.must_exec(f"update orders set o_comment = null where o_orderkey = "
                 f"{kept}")
    # NULL not like ... is NULL: the ON clause drops the order, and its
    # customer keeps a row (null-extended if it was the only one)
    host = _rows(tk, "host", q13.SQL)
    assert _orders_counted(host) == counted - 1
    assert sum(int(n) for _c, n in host) == len(t["customer"]["c_custkey"])
    assert _rows(tk, "tpu", q13.SQL) == host
    assert [n for n in _notes(tk, q13.SQL) if n.startswith("engine:")] \
        == ["engine:tpu"]


def test_a_pattern_that_matches_no_order_keeps_every_order(small):
    t, tk = small
    matched = int(tk.must_query(
        "select count(*) from orders where o_comment like "
        "'%special%requests%'").rows[0][0])
    assert matched > 0
    tk.must_exec("update orders set o_comment = 'no such words' where "
                 "o_comment like '%special%requests%'")
    host = _rows(tk, "host", q13.SQL)
    # every order but the NULL one of the test above
    n_null = int(tk.must_query("select count(*) from orders where "
                               "o_comment is null").rows[0][0])
    assert _orders_counted(host) == len(t["orders"]["o_custkey"]) - n_null
    assert _rows(tk, "tpu", q13.SQL) == host
    # and one that matches every order: all customers in the 0 group
    everything = q13.SQL.replace("%special%requests%", "%")
    host = _rows(tk, "host", everything)
    assert host == [("0", str(len(t["customer"]["c_custkey"])))]
    assert _rows(tk, "tpu", everything) == host
