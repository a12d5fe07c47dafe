"""TPC-H Q6, forecasting revenue change (cl. 2.4.6), with the validation
parameters of cl. 2.4.6.4: DATE 1994-01-01, DISCOUNT 0.06, QUANTITY 24."""

import numpy as np

from benchmark.harness import fmt
from benchmark.datasets.tpch import column_bytes, days

SQL = """
select sum(l_extendedprice * l_discount) as revenue
from lineitem
where l_shipdate >= date '1994-01-01'
  and l_shipdate < date '1994-01-01' + interval '1' year
  and l_discount between 0.06 - 0.01 and 0.06 + 0.01
  and l_quantity < 24
"""

READS = {"lineitem": ["l_shipdate", "l_discount", "l_quantity",
                      "l_extendedprice"]}


def min_bytes(rows: dict) -> int:
    """Bytes one execution must read: every column in READS, once."""
    return column_bytes(READS, rows)


def reference(t) -> list:
    li = t["lineitem"]
    m = ((li["l_shipdate"] >= days("1994-01-01"))
         & (li["l_shipdate"] < days("1995-01-01"))
         & (li["l_discount"] >= 5) & (li["l_discount"] <= 7)
         & (li["l_quantity"] < 2400))
    total = int(np.sum(li["l_extendedprice"][m] * li["l_discount"][m]))
    return [(fmt.dec(total, 4),)]
