"""TPC-H Q3, shipping priority (cl. 2.4.3), with the validation
parameters of cl. 2.4.3.4: SEGMENT BUILDING, DATE 1995-03-15."""

import numpy as np

from benchmark.harness import fmt
from benchmark.datasets.tpch import column_bytes, code_of, date_str, days, values

SQL = """
select l_orderkey, sum(l_extendedprice * (1 - l_discount)) as revenue,
       o_orderdate, o_shippriority
from customer, orders, lineitem
where c_mktsegment = 'BUILDING' and c_custkey = o_custkey
  and l_orderkey = o_orderkey and o_orderdate < date '1995-03-15'
  and l_shipdate > date '1995-03-15'
group by l_orderkey, o_orderdate, o_shippriority
order by revenue desc, o_orderdate
limit 10
"""

READS = {"customer": ["c_custkey", "c_mktsegment"],
         "orders": ["o_orderkey", "o_custkey", "o_orderdate",
                    "o_shippriority"],
         "lineitem": ["l_orderkey", "l_extendedprice", "l_discount",
                      "l_shipdate"]}


def min_bytes(rows: dict) -> int:
    """Bytes one execution must read: every column in READS, once."""
    return column_bytes(READS, rows)


def reference(t) -> list:
    c, o, li = t["customer"], t["orders"], t["lineitem"]
    day = days("1995-03-15")
    building = np.zeros(int(c["c_custkey"].max()) + 1, dtype=bool)
    seg = c["c_mktsegment"]
    building[c["c_custkey"][values(seg) == code_of(seg, b"BUILDING")]] = True
    osel = building[o["o_custkey"]] & (o["o_orderdate"] < day)
    okeys = o["o_orderkey"][osel]             # ascending by construction
    lsel = li["l_shipdate"] > day
    lkey = li["l_orderkey"][lsel]
    pos = np.searchsorted(okeys, lkey)
    pos[pos == len(okeys)] = 0
    hit = okeys[pos] == lkey
    rev = (li["l_extendedprice"][lsel] * (100 - li["l_discount"][lsel]))[hit]
    revenue = np.zeros(len(okeys), dtype=np.int64)
    np.add.at(revenue, pos[hit], rev)
    has = np.bincount(pos[hit], minlength=len(okeys)) > 0
    odate = o["o_orderdate"][osel]
    prio = o["o_shippriority"][osel]
    idx = np.nonzero(has)[0]
    # revenue desc, o_orderdate asc; the order key breaks no tie: a tie
    # on both inside the first ten would make the answer ambiguous
    order = idx[np.lexsort((odate[idx], -revenue[idx]))][:10]
    return [(str(int(okeys[i])), fmt.dec(int(revenue[i]), 4),
             date_str(odate[i]), str(int(prio[i]))) for i in order]
