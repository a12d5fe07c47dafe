"""Star Schema Benchmark Q4.1, flight 4 (O'Neil et al., revision 3, section 3;
the paper's own literals, cited from memory): profit by year and customer
nation inside one region, for two manufacturers."""

from benchmark.datasets.ssb import column_bytes, star, words_where

SQL = """
select d_year, c_nation, sum(lo_revenue - lo_supplycost) as profit
from date, customer, supplier, part, lineorder
where lo_custkey = c_custkey
  and lo_suppkey = s_suppkey
  and lo_partkey = p_partkey
  and lo_orderdate = d_datekey
  and c_region = 'AMERICA'
  and s_region = 'AMERICA'
  and (p_mfgr = 'MFGR#1' or p_mfgr = 'MFGR#2')
group by d_year, c_nation
order by d_year, c_nation
"""

READS = {"date": ["d_datekey", "d_year"],
         "customer": ["c_custkey", "c_region", "c_nation"],
         "supplier": ["s_suppkey", "s_region"],
         "part": ["p_partkey", "p_mfgr"],
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
        {"lo_orderdate": ("date", "d_datekey", None),
         "lo_custkey": ("customer", "c_custkey",
                        words_where(c["c_region"],
                                    lambda w: w == b"AMERICA")),
         "lo_suppkey": ("supplier", "s_suppkey",
                        words_where(s["s_region"],
                                    lambda w: w == b"AMERICA")),
         "lo_partkey": ("part", "p_partkey",
                        words_where(p["p_mfgr"],
                                    lambda w: w in (b"MFGR#1", b"MFGR#2")))},
        group=[("lo_orderdate", "d_year"), ("lo_custkey", "c_nation")])
