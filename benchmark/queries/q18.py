"""TPC-H Q18, large volume customer (cl. 2.4.18), with the validation
parameter of cl. 2.4.18.4: QUANTITY 300.  The text is the specification's:
the ``in`` subquery with its ``having``, the first 100 rows."""

import numpy as np

from benchmark.harness import fmt
from benchmark.datasets.tpch import column_bytes, date_str

SQL = """
select c_name, c_custkey, o_orderkey, o_orderdate, o_totalprice,
       sum(l_quantity)
from customer, orders, lineitem
where o_orderkey in (select l_orderkey from lineitem
                     group by l_orderkey
                     having sum(l_quantity) > 300)
  and c_custkey = o_custkey and o_orderkey = l_orderkey
group by c_name, c_custkey, o_orderkey, o_orderdate, o_totalprice
order by o_totalprice desc, o_orderdate
limit 100
"""

READS = {"customer": ["c_custkey", "c_name"],
         "orders": ["o_orderkey", "o_custkey", "o_orderdate",
                    "o_totalprice"],
         "lineitem": ["l_orderkey", "l_quantity"]}


def min_bytes(rows: dict) -> int:
    """Bytes one execution must read: every column in READS once, and
    lineitem's twice (the subquery scans it, the join probes it)."""
    return column_bytes(READS, rows) \
        + column_bytes({"lineitem": READS["lineitem"]}, rows)


def reference(t) -> list:
    c, o, li = t["customer"], t["orders"], t["lineitem"]
    okeys = o["o_orderkey"]                    # ascending by construction
    pos = np.searchsorted(okeys, li["l_orderkey"])
    pos[pos == len(okeys)] = 0
    hit = okeys[pos] == li["l_orderkey"]
    quantity = np.zeros(len(okeys), dtype=np.int64)
    np.add.at(quantity, pos[hit], li["l_quantity"][hit])
    cust = np.full(int(max(c["c_custkey"].max(), o["o_custkey"].max())) + 1,
                   -1, dtype=np.int64)
    cust[c["c_custkey"]] = np.arange(len(c["c_custkey"]))
    # decimal(15,2) x 100; an order joins its customer or gives no row
    large = np.nonzero((quantity > 300 * 100) & (cust[o["o_custkey"]] >= 0))[0]
    total, odate = o["o_totalprice"][large], o["o_orderdate"][large]
    order = np.lexsort((odate, -total))
    # o_totalprice desc, o_orderdate asc: a tie on both that reaches into
    # the first hundred leaves the answer to the engine's choice
    head = order[:101]
    if ((np.diff(total[head]) == 0) & (np.diff(odate[head]) == 0)).any():
        raise ValueError("q18: a tie on (o_totalprice, o_orderdate) inside "
                         "the first 100 rows makes the answer ambiguous")
    codes, words = c["c_name"]
    return [(words[codes[cust[o["o_custkey"][i]]]].decode(),
             str(int(o["o_custkey"][i])), str(int(okeys[i])),
             date_str(odate_i), fmt.dec(int(o["o_totalprice"][i]), 2),
             fmt.dec(int(quantity[i]), 2))
            for i, odate_i in zip(large[order[:100]], odate[order[:100]])]
