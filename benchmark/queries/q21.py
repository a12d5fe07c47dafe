"""TPC-H Q21, suppliers who kept orders waiting (cl. 2.4.21), with the
validation parameter of cl. 2.4.21.4: NATION SAUDI ARABIA.  The text is
the specification's: ``exists`` and ``not exists`` over lineitem, each
correlated on the order by ``=`` and on the supplier by ``<>``, the first
100 rows."""

import numpy as np

from benchmark.datasets.tpch import code_of, column_bytes, values


def _planner_keeps_the_residual() -> bool:
    """Does the program's planner keep a correlated conjunct that is not
    a key (``l2.l_suppkey <> l1.l_suppkey``) as a semi / anti join's
    residual (``planner.builder.bind_outer_refs``, PR 39)?  One that does
    not runs Q21 through the per-row Apply: 158 s a request at SF0.01 on
    XLA:CPU, hours at SF1, past the harness's 600 s wire timeout.  Only
    the planner is imported: no device is touched in this process."""
    from tidb_tpu.planner import builder
    return hasattr(builder, "bind_outer_refs")


if not _planner_keeps_the_residual():
    raise NotImplementedError(
        "q21: this program leaves a <> correlation to the per-row Apply "
        "(hours a request at SF1); the cell cannot be measured on it")

SQL = """
select s_name, count(*) as numwait
from supplier, lineitem l1, orders, nation
where s_suppkey = l1.l_suppkey
  and o_orderkey = l1.l_orderkey
  and o_orderstatus = 'F'
  and l1.l_receiptdate > l1.l_commitdate
  and exists (select * from lineitem l2
              where l2.l_orderkey = l1.l_orderkey
                and l2.l_suppkey <> l1.l_suppkey)
  and not exists (select * from lineitem l3
                  where l3.l_orderkey = l1.l_orderkey
                    and l3.l_suppkey <> l1.l_suppkey
                    and l3.l_receiptdate > l3.l_commitdate)
  and s_nationkey = n_nationkey
  and n_name = 'SAUDI ARABIA'
group by s_name
order by numwait desc, s_name
limit 100
"""

READS = {"supplier": ["s_suppkey", "s_name", "s_nationkey"],
         "lineitem": ["l_orderkey", "l_suppkey", "l_commitdate",
                      "l_receiptdate"],
         "orders": ["o_orderkey", "o_orderstatus"],
         "nation": ["n_nationkey", "n_name"]}

#: what the two existence tests read of lineitem (l2, l3)
EXISTS_READS = {"lineitem": READS["lineitem"]}


def min_bytes(rows: dict) -> int:
    """Bytes one execution must read: every column in READS, once."""
    return column_bytes(READS, rows)


def exists_min_bytes(rows: dict) -> int:
    """Bytes the two existence tests must read: the order key, the
    supplier and the two dates of lineitem, each once, at its row count
    (kernel.join_exists_roofline)."""
    return column_bytes(EXISTS_READS, rows)


def _counts(key):
    """For every row, how many rows share its `key`."""
    _u, inv, n = np.unique(key, return_inverse=True, return_counts=True)
    return n[inv]


def reference(t) -> list:
    s, li, o, n = t["supplier"], t["lineitem"], t["orders"], t["nation"]
    ok, sk = li["l_orderkey"], li["l_suppkey"]
    own = ok * (int(sk.max()) + 1) + sk        # (order, supplier)
    late = li["l_receiptdate"] > li["l_commitdate"]
    # EXISTS l2: the order has a line from another supplier, i.e. more
    # lines than it has from this one
    others = _counts(ok) > _counts(own)
    # NOT EXISTS l3: every late line of the order is this supplier's
    alone = np.zeros(len(ok), dtype=bool)
    alone[late] = _counts(ok[late]) == _counts(own[late])
    saudi = n["n_nationkey"][values(n["n_name"])
                             == code_of(n["n_name"], b"SAUDI ARABIA")]
    supp_ok = np.isin(s["s_nationkey"], saudi)
    final = o["o_orderkey"][values(o["o_orderstatus"])
                            == code_of(o["o_orderstatus"], b"F")]
    sel = late & others & alone & np.isin(ok, final) \
        & np.isin(sk, s["s_suppkey"][supp_ok])
    supp_row = np.full(int(s["s_suppkey"].max()) + 1, -1, dtype=np.int64)
    supp_row[s["s_suppkey"]] = np.arange(len(s["s_suppkey"]))
    numwait = np.bincount(supp_row[sk[sel]], minlength=len(s["s_suppkey"]))
    codes, words = s["s_name"]
    names = [words[c].decode() for c in codes]
    rows = sorted(((-int(w), names[i]) for i, w in enumerate(numwait) if w))
    return [(name, str(-neg)) for neg, name in rows[:100]]
