"""TPC-H Q1, pricing summary report (cl. 2.4.1), with the validation
parameter of cl. 2.4.1.4: DELTA 90."""

import numpy as np

from benchmark.harness import fmt
from benchmark.datasets.tpch import column_bytes, days, values

SQL = """
select l_returnflag, l_linestatus,
       sum(l_quantity) as sum_qty,
       sum(l_extendedprice) as sum_base_price,
       sum(l_extendedprice * (1 - l_discount)) as sum_disc_price,
       sum(l_extendedprice * (1 - l_discount) * (1 + l_tax)) as sum_charge,
       avg(l_quantity) as avg_qty,
       avg(l_extendedprice) as avg_price,
       avg(l_discount) as avg_disc,
       count(*) as count_order
from lineitem
where l_shipdate <= date '1998-12-01' - interval '90' day
group by l_returnflag, l_linestatus
order by l_returnflag, l_linestatus
"""

READS = {"lineitem": ["l_returnflag", "l_linestatus", "l_quantity",
                      "l_extendedprice", "l_discount", "l_tax",
                      "l_shipdate"]}


def min_bytes(rows: dict) -> int:
    """Bytes one execution must read: every column in READS, once."""
    return column_bytes(READS, rows)


def reference(t) -> list:
    li = t["lineitem"]
    m = li["l_shipdate"] <= days("1998-12-01") - 90
    flag, status = li["l_returnflag"], li["l_linestatus"]
    fcode, scode = values(flag)[m], values(status)[m]
    qty, price = li["l_quantity"][m], li["l_extendedprice"][m]
    disc, tax = li["l_discount"][m], li["l_tax"][m]
    disc_price = price * (100 - disc)
    charge = disc_price * (100 + tax)
    group = fcode.astype(np.int64) * len(status[1]) + scode
    rows = []
    for g in np.unique(group):
        sel = group == g
        n = int(sel.sum())

        def total(a):
            return int(a[sel].sum())

        rows.append((
            flag[1][g // len(status[1])].decode(),
            status[1][g % len(status[1])].decode(),
            fmt.dec(total(qty), 2), fmt.dec(total(price), 2),
            fmt.dec(total(disc_price), 4), fmt.dec(total(charge), 6),
            fmt.avg(total(qty), n, 2), fmt.avg(total(price), n, 2),
            fmt.avg(total(disc), n, 2), str(n)))
    return sorted(rows)
