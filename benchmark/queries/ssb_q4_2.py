"""Star Schema Benchmark Q4.2, flight 4 (O'Neil et al., revision 3, section 3;
the paper's own literals, cited from memory): the same two years by supplier
nation and part category."""

from benchmark.datasets.ssb import column_bytes, star, words_where

SQL = """
select d_year, s_nation, p_category, sum(lo_revenue - lo_supplycost) as profit
from date, customer, supplier, part, lineorder
where lo_custkey = c_custkey
  and lo_suppkey = s_suppkey
  and lo_partkey = p_partkey
  and lo_orderdate = d_datekey
  and c_region = 'AMERICA'
  and s_region = 'AMERICA'
  and (d_year = 1997 or d_year = 1998)
  and (p_mfgr = 'MFGR#1' or p_mfgr = 'MFGR#2')
group by d_year, s_nation, p_category
order by d_year, s_nation, p_category
"""

READS = {"date": ["d_datekey", "d_year"],
         "customer": ["c_custkey", "c_region"],
         "supplier": ["s_suppkey", "s_region", "s_nation"],
         "part": ["p_partkey", "p_mfgr", "p_category"],
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
                        words_where(s["s_region"],
                                    lambda w: w == b"AMERICA")),
         "lo_partkey": ("part", "p_partkey",
                        words_where(p["p_mfgr"],
                                    lambda w: w in (b"MFGR#1", b"MFGR#2")))},
        group=[("lo_orderdate", "d_year"), ("lo_suppkey", "s_nation"),
               ("lo_partkey", "p_category")])
