"""Join probes look a key up by direct address (ISSUE 28).

A host-built join index is ``dense`` (a table over the packed key span,
read by address: a slot table of row ids for a unique build side, CSR for
the rest) whenever that table's bytes are affordable, and ``sorted``
(binary-searched) only when they are not.  Here: the rule, on TPC-H's
spec-shaped keys and by arithmetic at the sizes of SF10; Q3 and Q5
against the host engine; every join kind over a slot table with gaps,
NULL keys, keys outside the pack range and a filtered build side; and the
counters that say which layout a dispatched fragment probed.
"""

import json
import pathlib
import sys

import numpy as np
import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from benchmark.datasets import tpch  # noqa: E402
from benchmark.queries import q3, q5  # noqa: E402
import tidb_tpu.executor.device_join as dj  # noqa: E402
from tidb_tpu.executor import join_index  # noqa: E402
from tidb_tpu.executor.join_index import (  # noqa: E402
    _quantize_range, build_join_index, direct_table_fits)
from tidb_tpu.ops import residency  # noqa: E402
from tidb_tpu.sqltypes import FieldType, TYPE_LONGLONG  # noqa: E402
from tidb_tpu.testkit import TestKit  # noqa: E402
from tidb_tpu.utils.chunk import Column  # noqa: E402

SEED = 2860486313


def _col(vals, nulls=None):
    a = np.asarray(vals, dtype=np.int64)
    return Column(FieldType(tp=TYPE_LONGLONG), a,
                  np.zeros(len(a), dtype=bool) if nulls is None
                  else np.asarray(nulls, dtype=bool))


def _span(lo, hi):
    mn, mx = _quantize_range(lo, hi)
    return mx - mn + 1


# -- (a) the rule --------------------------------------------------------------

@pytest.fixture(scope="module")
def tables():
    return tpch.generate(SEED, 0.01, {
        "orders": ["o_orderkey"], "customer": ["c_custkey", "c_nationkey"],
        "partsupp": ["ps_partkey", "ps_suppkey"]})


def test_spec_shaped_orderkey_is_addressed_directly(tables):
    keys = tables["orders"]["o_orderkey"]
    idx = build_join_index((_col(keys),))
    # 8 of every 32 values: the span is over four times the rows, which
    # the rule this replaces (span <= 4 x rows) refused
    assert idx.span > 4 * len(keys)
    assert idx.kind == "dense" and idx.unique
    assert idx.slots.shape == (idx.span,) and idx.slots.dtype == np.int32
    assert np.array_equal(idx.slots[keys - idx.packs[0][0]],
                          np.arange(len(keys)))
    assert np.count_nonzero(idx.slots >= 0) == len(keys)


def test_nation_custkey_pair_is_addressed_directly(tables):
    c = tables["customer"]
    idx = build_join_index((_col(c["c_nationkey"]), _col(c["c_custkey"])))
    assert idx.span > 20 * len(c["c_custkey"])
    assert idx.kind == "dense" and idx.unique and idx.slots is not None


@pytest.mark.parametrize("name,slots", [
    # orders: 15M rows, keys to 4n - 24; customer: 25 nations x 1.5M keys
    ("sf10.o_orderkey", _span(1, 4 * 15_000_000 - 24)),
    ("sf10.nation_x_custkey", _span(0, 24) * _span(1, 1_500_000)),
    ("sf1.o_orderkey", _span(1, 4 * 1_500_000 - 24)),
    ("sf1.nation_x_custkey", _span(0, 24) * _span(1, 150_000)),
])
def test_sf10_sizes_fit_by_arithmetic(name, slots):
    assert direct_table_fits(4 * (slots + 1)), name


@pytest.mark.parametrize("name,slots", [
    ("sf1.partkey_x_suppkey", _span(1, 200_000) * _span(1, 10_000)),
    ("sf10.partkey_x_suppkey", _span(1, 2_000_000) * _span(1, 100_000)),
])
def test_partsupp_composite_does_not_fit(name, slots):
    assert slots >= 2_000_000_000
    assert not direct_table_fits(4 * (slots + 1)), name


def test_partsupp_composite_stays_sorted_under_a_budget(tables):
    """At SF0.01 the composite's table is 900 KB and fits; under a device
    budget it does not fit, the same data takes the searched layout."""
    ps = tables["partsupp"]
    cols = (_col(ps["ps_partkey"]), _col(ps["ps_suppkey"]))
    assert build_join_index(cols).kind == "dense"
    residency.set_budget(200_000)
    try:
        idx = build_join_index(
            (_col(ps["ps_partkey"]), _col(ps["ps_suppkey"])))
    finally:
        residency.set_budget(0)
    assert idx.kind == "sorted" and idx.unique and idx.slots is None
    assert idx.sorted_keys is not None and idx.rows is not None


@pytest.mark.parametrize("budget", [0, 1 << 20, 1 << 28, 16 << 30])
def test_the_choice_is_monotone_in_bytes(budget):
    residency.set_budget(budget)
    try:
        fits = [direct_table_fits(1 << b) for b in range(8, 40)]
        cap = residency.resident_scan_bytes(budget)
        edge = min(join_index._DIRECT_MAX_BYTES, cap or (1 << 62))
        at_edge = direct_table_fits(edge), direct_table_fits(edge + 1)
    finally:
        residency.set_budget(0)
    assert fits[0] and not fits[-1]
    assert fits == sorted(fits, reverse=True), "one threshold, no island"
    assert at_edge == (True, False)


def test_a_filtered_slot_table_is_another_program():
    keys = list(range(0, 4000, 4))
    plain = build_join_index((_col(keys),))
    kept = np.arange(1000) % 2 == 0
    under = build_join_index((_col(keys),), mask_fn=lambda: kept,
                             cache_tag="t")
    assert (plain.filtered, under.filtered) == (False, True)
    assert under.n_valid == 500 and plain.packs == under.packs
    assert np.array_equal(under.slots >= 0, (plain.slots >= 0)
                          & (plain.slots % 2 == 0))
    assert plain.sig() != under.sig()


def test_force_sorted_still_searches():
    idx = build_join_index((_col(range(1, 65)),), force_sorted=True)
    assert idx.kind == "sorted" and idx.slots is None


def test_index_arrays_enter_the_residency_ledger():
    idx = build_join_index((_col(range(0, 4000, 4)),))
    before = residency.STATS["upload_bytes"]
    a0, a1, nv = idx.device_arrays()
    assert a1 is None and int(nv) == 1000
    assert residency.STATS["upload_bytes"] - before == idx.slots.nbytes
    hits = residency.STATS["hits"]
    assert idx.device_arrays()[0] is a0       # cached, not sent again
    assert residency.STATS["hits"] == hits + 1
    residency.bump_epoch("test")              # a fence drops the upload
    assert idx.device_arrays()[0] is not a0


# -- (b) Q3 and Q5 -------------------------------------------------------------

def _annotations(tk, sql, prefix):
    plan = tk.must_query("explain analyze " + sql).rows
    return [part for row in plan for part in row[2].split(", ")
            if part.startswith(prefix)]


def _device_pipelines(tk):
    rows = tk.must_query("DIAG STATUS").rows
    return json.loads(rows[0][0])["device_pipelines"]


@pytest.fixture(scope="module")
def tpch_tk():
    want = {t: list(tpch.SCHEMA[t]) for t in tpch.SCHEMA}
    tk = TestKit()
    tpch.load(tk, tpch.generate(SEED, 0.01, want), want, False,
              f"test_join_direct/{SEED}")
    tk.must_exec("set tidb_device_dispatch_rows = 1")
    tk.must_exec("set tidb_result_cache = 'OFF'")
    return tk


@pytest.mark.parametrize("name,sql,n_joins", [
    ("q3", q3.SQL, 2), ("q5", q5.SQL, 5)])
def test_q3_q5_equal_the_host_engine_and_probe_by_address(
        tpch_tk, name, sql, n_joins):
    tk = tpch_tk
    tk.must_exec("set tidb_executor_engine = 'tpu'")
    dev_rows = tk.must_query(sql).rows
    engines = _annotations(tk, sql, "engine:")
    joins = _annotations(tk, sql, "join:")
    tk.must_exec("set tidb_executor_engine = 'host'")
    assert dev_rows == tk.must_query(sql).rows and dev_rows
    assert engines == ["engine:tpu"]
    assert joins == [f"join:direct x{n_joins}"]


# -- (c) every join kind over a slot table -------------------------------------

@pytest.fixture(scope="module")
def gaps_tk():
    """d: unique keys 10, 14, .. 806 (gaps of 4), two NULL keys, half the
    rows removed by `flag = 1`; f: probe keys over the gaps, below and far
    above the pack range, and NULL."""
    tk = TestKit()
    tk.must_exec("use test")
    tk.must_exec("create table d (id int primary key, k bigint, grp int, "
                 "amt int, flag int)")
    tk.must_exec("create table m (id int primary key, k bigint, w int)")
    tk.must_exec("create table f (id int primary key, k bigint, v int)")
    rows = [f"({i}, {10 + 4 * i}, {i % 5}, {i * 7 % 31}, {i % 2})"
            for i in range(200)]
    rows += ["(200, null, 1, 3, 1)", "(201, null, 2, 4, 0)"]
    tk.must_exec("insert into d values " + ",".join(rows))
    # m: the same key space, three rows a key on every other key
    tk.must_exec("insert into m values " + ",".join(
        f"({i}, {10 + 8 * (i // 3)}, {i % 11})" for i in range(300)))
    rng = np.random.default_rng(28)
    probe = []
    for i in range(2000):
        r = rng.random()
        if r < 0.05:
            k = "null"
        elif r < 0.10:
            k = str(int(rng.integers(-50, 10)))         # below the range
        elif r < 0.15:
            k = str(int(rng.integers(10**6, 10**9)))    # far above it
        else:
            k = str(int(rng.integers(10, 900)))         # keys, gaps, slack
        probe.append(f"({i}, {k}, {int(rng.integers(0, 100))})")
    tk.must_exec("insert into f values " + ",".join(probe))
    for t in ("d", "m", "f"):
        tk.must_exec(f"analyze table {t}")
    tk.must_exec("set tidb_device_dispatch_rows = 1")
    tk.must_exec("set tidb_result_cache = 'OFF'")
    return tk


def _both(tk, sql):
    """Rows of `sql` on the device engine (checked against the host
    engine), and the (join kind, strategy, index) triples it compiled."""
    seen = []
    orig = dj.compile_fragment

    def spy(root, leaves, joins, *a, **k):
        seen.append([(jn.kind,) + tuple(jn.strategy) for jn in joins])
        return orig(root, leaves, joins, *a, **k)

    dj.compile_fragment = spy
    dj._CAP_STORE.clear()
    from tidb_tpu.executor import device_exec
    device_exec._PIPE_CACHE.clear()
    try:
        tk.must_exec("set tidb_executor_engine = 'tpu'")
        dev_rows = tk.must_query(sql).rows
        engines = _annotations(tk, sql, "engine:")
    finally:
        dj.compile_fragment = orig
    tk.must_exec("set tidb_executor_engine = 'host'")
    assert dev_rows == tk.must_query(sql).rows, sql
    assert engines == ["engine:tpu"], engines
    assert seen, "no join fragment compiled"
    return dev_rows, seen[-1]


@pytest.mark.parametrize("kind,sql", [
    ("inner", "select d.grp, count(*), sum(f.v + d.amt) from f join d "
              "on f.k = d.k where d.flag = 1 group by d.grp order by d.grp"),
    ("inner", "select d.grp, count(*), sum(f.v + d.amt) from f join d "
              "on f.k = d.k group by d.grp order by d.grp"),
    ("left", "select d.grp, count(*), count(d.k), sum(f.v) from f "
             "left join d on f.k = d.k and d.flag = 1 "
             "group by d.grp order by d.grp"),
    ("semi", "select f.v, count(*) from f where exists (select 1 from d "
             "where d.k = f.k and d.flag = 1) group by f.v order by f.v"),
    ("anti", "select f.v, count(*) from f where not exists (select 1 "
             "from d where d.k = f.k and d.flag = 1) "
             "group by f.v order by f.v"),
])
def test_join_kinds_over_a_slot_table(gaps_tk, kind, sql):
    rows, joins = _both(gaps_tk, sql)
    assert rows
    (jkind, strategy, side, idx), = joins
    assert jkind == kind and strategy == "uniq"
    assert idx.kind == "dense" and idx.unique and idx.slots is not None
    # gaps, NULL keys, filtered rows and the quantized slack read -1
    assert np.count_nonzero(idx.slots >= 0) == idx.n_valid < idx.span
    # built under the leaf's whole filter: the program reads no build mask
    assert idx.filtered == ("flag = 1" in sql)
    assert idx.n_valid == (100 if idx.filtered else 200)


def test_non_unique_dense_build_expands_through_csr(gaps_tk):
    rows, joins = _both(gaps_tk, (
        "select m.w, count(*), sum(f.v) from f join m on f.k = m.k "
        "group by m.w order by m.w"))
    assert rows
    (jkind, strategy, side, idx), = joins
    assert (jkind, strategy) == ("inner", "expand")
    assert idx.kind == "dense" and not idx.unique and idx.slots is None
    assert idx.starts.shape == (idx.span + 1,) and idx.max_cnt == 3


# -- (d) the counters ----------------------------------------------------------

def _counts(tk):
    st = _device_pipelines(tk)
    return st["join_direct"], st["join_search"]


def test_one_count_per_indexed_join_per_fragment(tpch_tk):
    tk = tpch_tk
    tk.must_exec("set tidb_executor_engine = 'tpu'")
    tk.must_query(q5.SQL)                      # warm: no retry below
    d0, s0 = _counts(tk)
    tk.must_query(q5.SQL)
    tk.must_query(q3.SQL)
    d1, s1 = _counts(tk)
    assert (d1 - d0, s1 - s0) == (5 + 2, 0)


def test_a_capacity_retry_counts_once(gaps_tk):
    """The expansion join's first execution learns its capacities and
    runs the fragment more than once; the joins count once."""
    tk = gaps_tk
    sql = ("select m.w, count(*), sum(f.v) from f join m on f.k = m.k "
           "where f.v < 97 group by m.w order by m.w")
    from tidb_tpu.executor import device_exec
    dj._CAP_STORE.clear()
    device_exec._PIPE_CACHE.clear()
    tk.must_exec("set tidb_executor_engine = 'tpu'")
    d0, s0 = _counts(tk)
    t0 = device_exec.pipe_cache_stats()["misses"]
    tk.must_query(sql)
    assert device_exec.pipe_cache_stats()["misses"] - t0 >= 2, \
        "expected a capacity retry on the first execution"
    assert _counts(tk) == (d0 + 1, s0)


def test_a_sorted_index_counts_as_search(gaps_tk, monkeypatch):
    tk = gaps_tk
    sql = ("select d.grp, count(*), sum(f.v + d.amt) from f join d "
           "on f.k = d.k where d.flag = 0 group by d.grp order by d.grp")
    monkeypatch.setattr(join_index, "_DIRECT_MAX_BYTES", 64)
    tk.must_exec("set tidb_executor_engine = 'tpu'")
    d0, s0 = _counts(tk)
    rows = tk.must_query(sql).rows
    assert _counts(tk) == (d0, s0 + 1)
    assert _annotations(tk, sql, "join:") == ["join:search x1"]
    tk.must_exec("set tidb_executor_engine = 'host'")
    assert rows == tk.must_query(sql).rows and rows
