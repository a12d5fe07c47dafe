"""Generator properties at SF0.01 (TPC-H cl. 4.2.3) over two seeds."""

import hashlib
import subprocess
import sys

import numpy as np
import pytest

from benchmark.datasets import tpch as g

SF = 0.01
SEEDS = (1, 2)


@pytest.fixture(scope="module", params=SEEDS)
def tables(request):
    return g.generate(request.param, SF)


def test_lines_per_order_uniform_1_to_7(tables):
    o, li = tables["orders"], tables["lineitem"]
    pos = np.searchsorted(o["o_orderkey"], li["l_orderkey"])
    assert (o["o_orderkey"][pos] == li["l_orderkey"]).all()
    per_order = np.bincount(pos, minlength=len(o["o_orderkey"]))
    assert per_order.min() == 1 and per_order.max() == 7
    share = np.bincount(per_order)[1:] / len(per_order)
    assert np.abs(share - 1 / 7).max() < 0.02      # 15,000 orders
    firsts = np.cumsum(per_order) - per_order
    assert (li["l_linenumber"][firsts] == 1).all()


def test_dates_follow_the_order(tables):
    o, li = tables["orders"], tables["lineitem"]
    odate = o["o_orderdate"][np.searchsorted(o["o_orderkey"],
                                             li["l_orderkey"])]
    ship = li["l_shipdate"] - odate
    assert ship.min() >= 1 and ship.max() <= 121
    commit = li["l_commitdate"] - odate
    assert commit.min() >= 30 and commit.max() <= 90
    receipt = li["l_receiptdate"] - li["l_shipdate"]
    assert receipt.min() >= 1 and receipt.max() <= 30
    assert o["o_orderdate"].min() >= g.days("1992-01-01")
    assert o["o_orderdate"].max() <= g.days("1998-08-02")


def test_order_keys_sparse_and_custkeys_skip_thirds(tables):
    o = tables["orders"]
    key = o["o_orderkey"]
    assert ((key - 1) % 32 < 8).all()
    assert (np.diff(key) > 0).all()
    assert key[8] == 33
    assert (o["o_custkey"] % 3 != 0).all()
    assert o["o_custkey"].min() >= 1
    assert o["o_custkey"].max() <= g.sizes(SF)["customer"]


def test_exactly_four_q1_groups(tables):
    li = tables["lineitem"]
    flag, status = li["l_returnflag"], li["l_linestatus"]
    groups = {(flag[1][f], status[1][s]) for f, s in
              zip(*np.unique(np.stack([g.values(flag), g.values(status)]),
                             axis=1))}
    assert groups == {(b"A", b"F"), (b"N", b"F"), (b"N", b"O"),
                      (b"R", b"F")}
    # N/F is the tiny one: shipped by 1995-06-17, received after it
    nf = (g.values(flag) == 1) & (g.values(status) == 0)
    assert 0 < nf.mean() < 0.01


def test_prices_and_suppliers(tables):
    li, ps, o = tables["lineitem"], tables["partsupp"], tables["orders"]
    assert (li["l_extendedprice"] == li["l_quantity"] // 100
            * g.retailprice(li["l_partkey"])).all()
    n_supp = g.sizes(SF)["supplier"]
    pairs = set(zip(ps["ps_partkey"].tolist(), ps["ps_suppkey"].tolist()))
    assert len(pairs) == len(ps["ps_partkey"])      # four DISTINCT each
    assert ps["ps_suppkey"].min() >= 1 and ps["ps_suppkey"].max() <= n_supp
    assert set(zip(li["l_partkey"].tolist(),
                   li["l_suppkey"].tolist())) <= pairs
    # o_totalprice from the order's lines
    line = li["l_extendedprice"] * (100 + li["l_tax"]) \
        * (100 - li["l_discount"])
    pos = np.searchsorted(o["o_orderkey"], li["l_orderkey"])
    total = np.zeros(len(o["o_orderkey"]), dtype=np.int64)
    np.add.at(total, pos, line)
    assert (o["o_totalprice"] == (total + 5000) // 10000).all()


def test_fixed_vocabularies(tables):
    assert len(tables["customer"]["c_mktsegment"][1]) == 5
    assert len(tables["nation"]["n_nationkey"]) == 25
    assert len(tables["region"]["r_regionkey"]) == 5
    words = {w for name in tables["part"]["p_name"][1]
             for w in name.decode().split()}
    assert words <= set(g.COLORS) and len(g.COLORS) == 92


def _digest(tables) -> str:
    h = hashlib.sha256()
    for t in sorted(tables):
        for c in sorted(tables[t]):
            h.update(np.ascontiguousarray(g.values(tables[t][c])).tobytes())
    return h.hexdigest()


def test_same_seed_same_arrays_other_seed_other_arrays():
    want = {"lineitem": ["l_orderkey", "l_shipdate", "l_extendedprice"],
            "orders": ["o_orderkey", "o_custkey"]}
    a, b, c = (g.generate(s, SF, want) for s in (5, 5, 6))
    assert _digest(a) == _digest(b)
    assert _digest(a) != _digest(c)
    # a column's values do not depend on which columns were asked for
    full = g.generate(5, SF)
    assert (full["lineitem"]["l_shipdate"]
            == a["lineitem"]["l_shipdate"]).all()


def test_same_seed_in_another_process():
    code = ("import sys; sys.path.insert(0, %r);"
            "from benchmark.tests.test_tpch import _digest, g;"
            "print(_digest(g.generate(5, 0.01, {'lineitem': "
            "['l_orderkey', 'l_shipdate'], 'orders': ['o_custkey']})))"
            % sys.path[0])
    outs = {subprocess.run([sys.executable, "-c", code], check=True,
                           capture_output=True, text=True).stdout
            for _ in range(2)}
    assert len(outs) == 1 and len(outs.pop().strip()) == 64


def test_unknown_column_is_an_error():
    with pytest.raises(KeyError, match="l_comment"):
        g.generate(1, SF, {"lineitem": ["l_comment"]})
