"""TPC-H Q9, product type profit measure (cl. 2.4.9), with the validation
parameter of cl. 2.4.9.4: COLOR green.  The text is the specification's:
the derived table ``profit`` and ``extract(year from o_orderdate)``."""

import numpy as np

from benchmark.harness import fmt
from benchmark.datasets.tpch import column_bytes, values

SQL = """
select nation, o_year, sum(amount) as sum_profit
from (select n_name as nation, extract(year from o_orderdate) as o_year,
             l_extendedprice * (1 - l_discount)
             - ps_supplycost * l_quantity as amount
      from part, supplier, lineitem, partsupp, orders, nation
      where s_suppkey = l_suppkey and ps_suppkey = l_suppkey
        and ps_partkey = l_partkey and p_partkey = l_partkey
        and o_orderkey = l_orderkey and s_nationkey = n_nationkey
        and p_name like '%green%'
     ) as profit
group by nation, o_year
order by nation, o_year desc
"""

READS = {"part": ["p_partkey", "p_name"],
         "supplier": ["s_suppkey", "s_nationkey"],
         "lineitem": ["l_orderkey", "l_partkey", "l_suppkey", "l_quantity",
                      "l_extendedprice", "l_discount"],
         "partsupp": ["ps_partkey", "ps_suppkey", "ps_supplycost"],
         "orders": ["o_orderkey", "o_orderdate"],
         "nation": ["n_nationkey", "n_name"]}


def min_bytes(rows: dict) -> int:
    """Bytes one execution must read: every column in READS, once."""
    return column_bytes(READS, rows)


def _lookup(keys, probe):
    """(position of each `probe` value in the ascending `keys`, found)."""
    pos = np.searchsorted(keys, probe)
    pos[pos == len(keys)] = 0
    return pos, keys[pos] == probe


def reference(t) -> list:
    p, s, li, ps = t["part"], t["supplier"], t["lineitem"], t["partsupp"]
    o, n = t["orders"], t["nation"]
    # LIKE '%green%' on the dictionary's strings, then by code
    codes, words = p["p_name"]
    green = np.array([b"green" in w for w in words], dtype=bool)[codes]
    is_green = np.zeros(int(p["p_partkey"].max()) + 1, dtype=bool)
    is_green[p["p_partkey"][green]] = True
    sel = is_green[li["l_partkey"]]
    lpart, lsupp = li["l_partkey"][sel], li["l_suppkey"][sel]
    # partsupp by its composite key (ps_partkey, ps_suppkey)
    width = int(max(ps["ps_suppkey"].max(), lsupp.max(initial=0))) + 1
    pskey = ps["ps_partkey"] * width + ps["ps_suppkey"]
    by_key = np.argsort(pskey, kind="stable")
    pos, hit = _lookup(pskey[by_key], lpart * width + lsupp)
    cost = ps["ps_supplycost"][by_key][pos]
    supp_nation = np.full(int(s["s_suppkey"].max()) + 1, -1, dtype=np.int64)
    supp_nation[s["s_suppkey"]] = s["s_nationkey"]
    nation = supp_nation[lsupp]
    opos, ohit = _lookup(o["o_orderkey"], li["l_orderkey"][sel])
    year = o["o_orderdate"][opos].astype("datetime64[D]") \
        .astype("datetime64[Y]").astype(np.int64) + 1970
    keep = hit & ohit & (nation >= 0)
    # exact integers: both products carry four decimals
    amount = (li["l_extendedprice"][sel] * (100 - li["l_discount"][sel])
              - cost * li["l_quantity"][sel])[keep]
    keys, inverse = np.unique(nation[keep] * 10000 + year[keep],
                              return_inverse=True)
    profit = np.zeros(len(keys), dtype=np.int64)
    np.add.at(profit, inverse, amount)
    names = dict(zip(n["n_nationkey"].tolist(),
                     (n["n_name"][1][c] for c in values(n["n_name"]))))
    rows = [(names[k // 10000].decode(), k % 10000, v)
            for k, v in zip(keys.tolist(), profit.tolist())]
    rows.sort(key=lambda r: (r[0], -r[1]))
    return [(name, str(y), fmt.dec(v, 4)) for name, y, v in rows]
