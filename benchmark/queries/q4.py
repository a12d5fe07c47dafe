"""TPC-H Q4, order priority checking (cl. 2.4.4), with the validation
parameter of cl. 2.4.4.4: DATE 1993-07-01.  The text is the
specification's: the correlated ``exists (select * ...)`` and the
``interval '3' month``."""

import numpy as np

from benchmark.datasets.tpch_text import column_bytes, days, values

SQL = """
select o_orderpriority, count(*) as order_count
from orders
where o_orderdate >= date '1993-07-01'
  and o_orderdate < date '1993-07-01' + interval '3' month
  and exists (select * from lineitem
              where l_orderkey = o_orderkey
                and l_commitdate < l_receiptdate)
group by o_orderpriority
order by o_orderpriority
"""

READS = {"orders": ["o_orderkey", "o_orderdate", "o_orderpriority"],
         "lineitem": ["l_orderkey", "l_commitdate", "l_receiptdate"]}


def min_bytes(rows: dict) -> int:
    """Bytes one execution must read: every column in READS, once."""
    return column_bytes(READS, rows)


def reference(t) -> list:
    o, li = t["orders"], t["lineitem"]
    late = np.unique(li["l_orderkey"][li["l_commitdate"]
                                      < li["l_receiptdate"]])
    quarter = (o["o_orderdate"] >= days("1993-07-01")) \
        & (o["o_orderdate"] < days("1993-10-01"))
    sel = quarter & np.isin(o["o_orderkey"], late)
    prio = o["o_orderpriority"]
    counts = np.bincount(values(prio)[sel], minlength=len(prio[1]))
    rows = [(prio[1][code].decode(), str(int(n)))
            for code, n in enumerate(counts) if n]
    return sorted(rows)
