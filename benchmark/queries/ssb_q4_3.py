"""Star Schema Benchmark Q4.3, flight 4 (O'Neil et al., revision 3, section 3;
the paper's own literals, cited from memory): one nation's suppliers by city
and brand, one category."""

from benchmark.datasets.ssb import column_bytes, star, words_where

SQL = """
select d_year, s_city, p_brand1, sum(lo_revenue - lo_supplycost) as profit
from date, customer, supplier, part, lineorder
where lo_custkey = c_custkey
  and lo_suppkey = s_suppkey
  and lo_partkey = p_partkey
  and lo_orderdate = d_datekey
  and c_region = 'AMERICA'
  and s_nation = 'UNITED STATES'
  and (d_year = 1997 or d_year = 1998)
  and p_category = 'MFGR#14'
group by d_year, s_city, p_brand1
order by d_year, s_city, p_brand1
"""

READS = {"date": ["d_datekey", "d_year"],
         "customer": ["c_custkey", "c_region"],
         "supplier": ["s_suppkey", "s_nation", "s_city"],
         "part": ["p_partkey", "p_category", "p_brand1"],
         "lineorder": ["lo_custkey", "lo_suppkey", "lo_partkey",
                       "lo_orderdate", "lo_revenue", "lo_supplycost"]}


def min_bytes(rows: dict) -> int:
    """Bytes one execution must read: every column in READS, once."""
    return column_bytes(READS, rows)


def reference(t) -> list:
    lo, d, c, s, p = (t[k] for k in ("lineorder", "date", "customer",
                                     "supplier", "part"))
    return star(
        t, lo["lo_revenue"] - lo["lo_supplycost"],
        {"lo_orderdate": ("date", "d_datekey",
                          (d["d_year"] == 1997) | (d["d_year"] == 1998)),
         "lo_custkey": ("customer", "c_custkey",
                        words_where(c["c_region"],
                                    lambda w: w == b"AMERICA")),
         "lo_suppkey": ("supplier", "s_suppkey",
                        words_where(s["s_nation"],
                                    lambda w: w == b"UNITED STATES")),
         "lo_partkey": ("part", "p_partkey",
                        words_where(p["p_category"],
                                    lambda w: w == b"MFGR#14"))},
        group=[("lo_orderdate", "d_year"), ("lo_suppkey", "s_city"),
               ("lo_partkey", "p_brand1")])
