"""TPC-H Q13, customer distribution (cl. 2.4.13), with the validation
parameters of cl. 2.4.13.4: WORD1 special, WORD2 requests.  The text is
the specification's: the left outer join with the ``not like`` in its ON
clause, ``count(o_orderkey)`` over the NULL-extended column, the derived
table named with a column list."""

import re

import numpy as np

from benchmark.datasets.tpch_text import column_bytes

SQL = """
select c_count, count(*) as custdist
from (select c_custkey, count(o_orderkey)
      from customer left outer join orders
        on c_custkey = o_custkey
        and o_comment not like '%special%requests%'
      group by c_custkey) as c_orders (c_custkey, c_count)
group by c_count
order by custdist desc, c_count desc
"""

READS = {"customer": ["c_custkey"],
         "orders": ["o_orderkey", "o_custkey", "o_comment"]}


def min_bytes(rows: dict) -> int:
    """Bytes one execution must read: every column in READS, once
    (o_comment as its 4-byte codes)."""
    return column_bytes(READS, rows)


def reference(t) -> list:
    c, o = t["customer"], t["orders"]
    codes, words = o["o_comment"]
    # like '%special%requests%': '%' matches any run of characters
    pattern = re.compile(rb"special.*requests", re.S)
    matched = np.fromiter((pattern.search(w) is not None for w in words),
                          dtype=bool, count=len(words))
    kept = ~matched[codes]
    # orders of every customer key, the customers without one included
    per_key = np.bincount(o["o_custkey"][kept],
                          minlength=int(c["c_custkey"].max()) + 1)
    c_count = per_key[c["c_custkey"]]
    custdist = np.bincount(c_count)
    counts = np.nonzero(custdist)[0]
    order = np.lexsort((-counts, -custdist[counts]))
    return [(str(int(counts[i])), str(int(custdist[counts[i]])))
            for i in order]
