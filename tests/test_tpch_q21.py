"""TPC-H Q21 in the specification's own text (ISSUE 39): the plain numpy
reference, the host engine and the `tpu` engine give the same rows on two
seeds; the correlated EXISTS and NOT EXISTS, each on the order by ``=``
and on the supplier by ``<>``, plan as a semi and an anti join whose
``<>`` is a residual, and under the `tpu` engine the whole query is ONE
fused `engine:tpu` fragment: three unique gathers over l1, then two
existence tests that CSR-expand l1's live rows into pairs, test the
build leaf's filter and the residual on each pair and reduce the pairs
back to their probe row.  Both tests read ONE cached index of
``l_orderkey`` (unfiltered).  The kinds, the residuals and the pairs are
counted and printed by ``EXPLAIN ANALYZE``.  The benchmark's cell
`tpch-sf1.q21` runs it at SF1 on the chip; this file holds it at a size
XLA:CPU takes in seconds."""

import json
import pathlib
import re
import sys

import numpy as np
import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from benchmark.datasets import tpch  # noqa: E402
from benchmark.queries import q21  # noqa: E402
from tidb_tpu.executor import device_exec  # noqa: E402
from tidb_tpu.executor import device_join as dj  # noqa: E402
from tidb_tpu.ops import device as dev  # noqa: E402
from tidb_tpu.testkit import TestKit  # noqa: E402

SF = 0.02
SEEDS = (7, 2100000001)


def _load(seed, sf=SF):
    want = {t: list(cols) for t, cols in tpch.SCHEMA.items()}
    tables = tpch.generate(seed, sf, want)
    tk = TestKit()
    tpch.load(tk, tables, want, False, f"test_tpch_q21/{seed}/{sf}")
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


def _pairs(t):
    """(pairs the EXISTS test holds, pairs the NOT EXISTS test holds):
    every line of the order of every l1 row live at that test, the
    order's own line and its early lines included (both tests read the
    unfiltered index of l_orderkey)."""
    s, li, o, n = t["supplier"], t["lineitem"], t["orders"], t["nation"]
    ok, sk = li["l_orderkey"], li["l_suppkey"]
    late = li["l_receiptdate"] > li["l_commitdate"]
    saudi = n["n_nationkey"][tpch.values(n["n_name"]) == tpch.code_of(
        n["n_name"], b"SAUDI ARABIA")]
    final = o["o_orderkey"][tpch.values(o["o_orderstatus"])
                            == tpch.code_of(o["o_orderstatus"], b"F")]
    live = late & np.isin(ok, final) & np.isin(
        sk, s["s_suppkey"][np.isin(s["s_nationkey"], saudi)])
    keys, inv, lines = np.unique(ok, return_inverse=True,
                                 return_counts=True)
    own = ok * (int(sk.max()) + 1) + sk
    _u, oinv, own_lines = np.unique(own, return_inverse=True,
                                    return_counts=True)
    others = lines[inv] > own_lines[oinv]
    return int(lines[inv][live].sum()), int(lines[inv][live & others].sum())


# -- reference == host == tpu, in the specification's text ----------------------

def _spec_text():
    src = (pathlib.Path(__file__).parent / "test_tpch.py").read_text()
    body = src.split("def test_q21(tk):")[1].split('"""')[1]
    return " ".join(body.split())


def test_the_template_is_the_specifications_text():
    assert " ".join(q21.SQL.split()) == _spec_text()
    assert "l2.l_suppkey <> l1.l_suppkey" in q21.SQL
    assert "and not exists (select * from lineitem l3" in q21.SQL
    assert "n_name = 'SAUDI ARABIA'" in q21.SQL and "limit 100" in q21.SQL


@pytest.mark.parametrize("seed", SEEDS)
def test_reference_host_and_tpu_agree(seed):
    tables, tk = _loaded(seed)
    want = q21.reference(tables)
    assert want, "an empty answer proves nothing"
    assert _rows(tk, "host", q21.SQL) == want
    assert _rows(tk, "tpu", q21.SQL) == want


def test_the_plan_has_no_apply():
    _tables, tk = _loaded(SEEDS[0])
    plan = "\n".join(r[0] + "|" + r[1] for r in
                     tk.must_query("explain " + q21.SQL).rows)
    assert "apply" not in plan.lower()
    assert re.search(r"semi, equal:.*other:ne\(", plan)
    assert re.search(r"anti, equal:.*other:ne\(", plan)


# -- one fragment, counted and printed ------------------------------------------

@pytest.mark.parametrize("seed", SEEDS)
def test_q21_is_one_fragment_with_two_residual_existence_tests(seed):
    tables, tk = _loaded(seed)
    _rows(tk, "tpu", q21.SQL)              # capacities learned
    before = _pipelines(tk)
    _rows(tk, "tpu", q21.SQL)
    after = _pipelines(tk)
    # supplier, orders and nation are unique gathers, l2 and l3 CSR
    assert _grew(before, after, "join_direct", "join_search", "join_left",
                 "join_semi", "join_anti", "join_residual",
                 "join_expand") == [5, 0, 0, 1, 1, 2, 0]
    assert _grew(before, after, "unsupported", "capacity_reruns",
                 "compiles", "join_index_builds") == [0, 0, 0, 0]
    semi, anti = _pairs(tables)
    rows, slots = _grew(before, after, "join_residual_rows",
                        "join_residual_capacity")
    assert rows == semi + anti
    assert slots == dev.next_pow2(semi) + dev.next_pow2(anti)
    notes = _notes(tk, q21.SQL)
    assert [n for n in notes if n.startswith("engine:")] == ["engine:tpu"]
    assert "join:direct x5 (semi x1, anti x1, residual x2)" in ", ".join(
        notes)
    assert "agg:sort" in notes and "probe:resident" in notes
    assert notes.count("fused:into tpu fragment") == 12
    assert not [n for n in notes if n.startswith("device_unsupported:")]


def test_both_existence_tests_share_one_unfiltered_index():
    """l2 (no filter) and l3 (late lines) index lineitem.l_orderkey under
    one tag: a fresh load builds four indexes, not five, and a repeat
    none."""
    tables, tk = _load(SEEDS[1], sf=0.01)
    before = _pipelines(tk)
    assert _rows(tk, "tpu", q21.SQL) == q21.reference(tables)
    middle = _pipelines(tk)
    assert _rows(tk, "tpu", q21.SQL) == q21.reference(tables)
    after = _pipelines(tk)
    assert _grew(before, middle, "join_index_builds") == [4]
    assert _grew(middle, after, "join_index_builds",
                 "capacity_reruns") == [0, 0]


def test_expand_rows_is_asked_with_the_programs_own_shapes(monkeypatch):
    tables, tk = _loaded(SEEDS[0])
    dj._CAP_STORE.clear()
    _rows(tk, "tpu", q21.SQL)              # capacities learned
    asked = []
    orig = dj.expand_one_pass

    def spy(cap, n_probe):
        asked.append((int(cap), int(n_probe)))
        return orig(cap, n_probe)
    monkeypatch.setattr(dj, "expand_one_pass", spy)
    device_exec._PIPE_CACHE.clear()
    before = _pipelines(tk)
    assert _rows(tk, "tpu", q21.SQL) == q21.reference(tables)
    after = _pipelines(tk)
    device_exec._PIPE_CACHE.clear()
    semi, anti = _pairs(tables)
    n_lines = len(tables["lineitem"]["l_orderkey"])
    # one trace: the EXISTS test, then the NOT EXISTS test, both over
    # l1's live rows as the cuts past its filter and the inner gathers
    # left them; the capacities are the ones the counter holds
    assert [cap for cap, _n in asked] == [dev.next_pow2(semi),
                                         dev.next_pow2(anti)]
    (n_probe,) = {n for _cap, n in asked}
    live = {k[1][1]: v for k, v in dj._CAP_STORE.items()
            if isinstance(k[1], tuple) and k[1][0] == "live"}
    n = dev.bucket_rows(n_lines)
    for pos in (-1, 0, 1, 2):                  # the leaf, three gathers
        n = dj.compact_to(live[pos], n) or n
    assert n_probe == n < n_lines
    assert _grew(before, after, "join_residual_capacity") == [
        sum(cap for cap, _n in asked)]


def test_the_pairs_count_once_across_a_capacity_rerun():
    tables, tk = _loaded(SEEDS[1])
    dj._CAP_STORE.clear()
    device_exec._PIPE_CACHE.clear()
    before = _pipelines(tk)
    assert _rows(tk, "tpu", q21.SQL) == q21.reference(tables)
    after = _pipelines(tk)
    # the first capacities are estimates (the probe's bucket): the loop
    # shrinks them to the pairs it counted
    assert after["capacity_reruns"] - before["capacity_reruns"] >= 1
    semi, anti = _pairs(tables)
    assert _grew(before, after, "join_residual", "join_residual_rows",
                 "join_residual_capacity") == [
        2, semi + anti, dev.next_pow2(semi) + dev.next_pow2(anti)]


# -- by hand: what EXISTS and NOT EXISTS answer ---------------------------------

#: (l_orderkey, l_suppkey, late) of the hand-made lineitem
_LINES = [
    (10, 1, True), (10, 1, True),                  # one supplier: no EXISTS
    (20, 1, True), (20, 1, False), (20, 2, False),  # 1 waits, alone late
    (30, 1, True), (30, 2, True),                  # both late: none waits
    (40, 2, True), (40, 2, True), (40, 3, False),  # 2's own late lines only
    (50, 1, True), (50, 3, False),                 # status O
    (60, 1, True), (60, 2, False),                 # no such order
    (70, 1, True), (70, None, False),              # <> NULL is no match
    (80, 3, True), (80, 1, False),                 # 3 is not Saudi
]


@pytest.fixture(scope="module")
def hand():
    tk = TestKit()
    tk.must_exec("create table nation (n_nationkey bigint primary key, "
                 "n_name varchar(25))")
    tk.must_exec("create table supplier (s_suppkey bigint primary key, "
                 "s_name varchar(25), s_nationkey bigint)")
    tk.must_exec("create table orders (o_orderkey bigint primary key, "
                 "o_orderstatus varchar(1))")
    tk.must_exec("create table lineitem (l_orderkey bigint, l_suppkey "
                 "bigint, l_commitdate date, l_receiptdate date)")
    tk.must_exec("insert into nation values (20, 'SAUDI ARABIA'), "
                 "(1, 'ARGENTINA')")
    tk.must_exec("insert into supplier values (1, 'Supplier#1', 20), "
                 "(2, 'Supplier#2', 20), (3, 'Supplier#3', 1)")
    tk.must_exec("insert into orders values (10, 'F'), (20, 'F'), "
                 "(30, 'F'), (40, 'F'), (50, 'O'), (70, 'F'), (80, 'F')")
    tk.must_exec("insert into lineitem values " + ", ".join(
        f"({o}, {'null' if s is None else s}, '1995-03-10', "
        f"'1995-03-{20 if late else 5}')" for o, s, late in _LINES))
    tk.must_exec("set tidb_device_dispatch_rows = 1")
    tk.must_exec("set tidb_result_cache = 'OFF'")
    return tk


def test_by_hand_exists_and_not_exists(hand):
    # order 20: supplier 1's late line; order 40: supplier 2's two
    want = [("Supplier#2", "2"), ("Supplier#1", "1")]
    assert _rows(hand, "host", q21.SQL) == want
    assert _rows(hand, "tpu", q21.SQL) == want
    notes = _notes(hand, q21.SQL)
    assert [n for n in notes if n.startswith("engine:")] == ["engine:tpu"]
    assert "(semi x1, anti x1, residual x2)" in ", ".join(notes)


@pytest.mark.parametrize("exists", ["exists", "not exists"])
def test_a_residual_over_a_unique_build(exists):
    """The build's key is unique: the residual is tested on the one row
    the probe gathers, the build leaf's filter beside it (the index is
    the unfiltered one)."""
    tk = TestKit()
    tk.must_exec("create table t (k bigint, v bigint)")
    tk.must_exec("create table u (k bigint primary key, w bigint)")
    rng = np.random.default_rng(39)
    tk.must_exec("insert into t values " + ", ".join(
        f"({int(rng.integers(0, 60))}, {int(rng.integers(0, 5))})"
        for _ in range(300)))
    tk.must_exec("insert into u values " + ", ".join(
        f"({k}, {'null' if k % 11 == 0 else int(rng.integers(-2, 5))})"
        for k in range(0, 50)))
    tk.must_exec("set tidb_device_dispatch_rows = 1")
    tk.must_exec("set tidb_result_cache = 'OFF'")
    sql = (f"select v, count(*) from t where {exists} (select * from u "
           "where u.k = t.k and u.w <> t.v and u.w > 0) "
           "group by v order by v")
    want = _rows(tk, "host", sql)
    assert want
    before = _pipelines(tk)
    assert _rows(tk, "tpu", sql) == want
    after = _pipelines(tk)
    kind = "anti" if exists == "not exists" else "semi"
    assert _grew(before, after, "join_" + kind, "join_residual",
                 "join_residual_rows", "unsupported") == [1, 1, 0, 0]
    notes = _notes(tk, sql)
    assert [n for n in notes if n.startswith("engine:")] == ["engine:tpu"]
    assert f"join:direct x1 ({kind} x1, residual x1)" in ", ".join(notes)


@pytest.mark.parametrize("exists", ["exists", "not exists"])
def test_a_residual_over_a_leaf_probe(exists):
    """One existence test at the root over a plain scan (Q4's place),
    with a residual: the same rows as the host."""
    _tables, tk = _loaded(SEEDS[0])
    sql = (f"select o_orderpriority, count(*) from orders where {exists} "
           "(select * from lineitem where l_orderkey = o_orderkey and "
           "l_suppkey * 15 > o_custkey and l_commitdate < "
           "l_receiptdate) group by o_orderpriority order by "
           "o_orderpriority")
    want = _rows(tk, "host", sql)
    assert len(want) == 5
    assert _rows(tk, "tpu", sql) == want
    kind = "anti" if exists == "not exists" else "semi"
    assert f"join:direct x1 ({kind} x1, residual x1)" in ", ".join(
        _notes(tk, sql))
