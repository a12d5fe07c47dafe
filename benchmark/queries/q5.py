"""TPC-H Q5, local supplier volume (cl. 2.4.5), with the validation
parameters of cl. 2.4.5.4: REGION ASIA, DATE 1994-01-01."""

import numpy as np

from benchmark.harness import fmt
from benchmark.datasets.tpch import column_bytes, code_of, days, values

SQL = """
select n_name, sum(l_extendedprice * (1 - l_discount)) as revenue
from customer, orders, lineitem, supplier, nation, region
where c_custkey = o_custkey and l_orderkey = o_orderkey
  and l_suppkey = s_suppkey and c_nationkey = s_nationkey
  and s_nationkey = n_nationkey and n_regionkey = r_regionkey
  and r_name = 'ASIA' and o_orderdate >= date '1994-01-01'
  and o_orderdate < date '1994-01-01' + interval '1' year
group by n_name
order by revenue desc
"""

READS = {"customer": ["c_custkey", "c_nationkey"],
         "orders": ["o_orderkey", "o_custkey", "o_orderdate"],
         "lineitem": ["l_orderkey", "l_suppkey", "l_extendedprice",
                      "l_discount"],
         "supplier": ["s_suppkey", "s_nationkey"],
         "nation": ["n_nationkey", "n_name", "n_regionkey"],
         "region": ["r_regionkey", "r_name"]}


def min_bytes(rows: dict) -> int:
    """Bytes one execution must read: every column in READS, once."""
    return column_bytes(READS, rows)


def reference(t) -> list:
    c, o, li, s = t["customer"], t["orders"], t["lineitem"], t["supplier"]
    n, r = t["nation"], t["region"]
    asia = r["r_regionkey"][values(r["r_name"])
                            == code_of(r["r_name"], b"ASIA")]
    in_asia = np.isin(n["n_regionkey"], asia)
    cust_nation = np.full(int(c["c_custkey"].max()) + 1, -1, dtype=np.int64)
    cust_nation[c["c_custkey"]] = c["c_nationkey"]
    supp_nation = np.full(int(s["s_suppkey"].max()) + 1, -1, dtype=np.int64)
    supp_nation[s["s_suppkey"]] = s["s_nationkey"]
    osel = ((o["o_orderdate"] >= days("1994-01-01"))
            & (o["o_orderdate"] < days("1995-01-01")))
    okeys = o["o_orderkey"][osel]
    onation = cust_nation[o["o_custkey"][osel]]
    pos = np.searchsorted(okeys, li["l_orderkey"])
    pos[pos == len(okeys)] = 0
    hit = okeys[pos] == li["l_orderkey"]
    lnation = supp_nation[li["l_suppkey"]]
    keep = hit & (lnation == onation[pos]) & in_asia[np.maximum(lnation, 0)]
    rev = li["l_extendedprice"][keep] * (100 - li["l_discount"][keep])
    revenue = np.zeros(len(n["n_nationkey"]), dtype=np.int64)
    np.add.at(revenue, lnation[keep], rev)
    names = n["n_name"]
    rows = [(names[1][values(names)[k]].decode(), int(revenue[k]))
            for k in np.nonzero(np.bincount(lnation[keep], minlength=25))[0]]
    rows.sort(key=lambda x: -x[1])
    return [(name, fmt.dec(v, 4)) for name, v in rows]
