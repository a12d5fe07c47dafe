"""Star Schema Benchmark Q2.1, flight 2 (O'Neil et al., revision 3, section 3;
the paper's own literals, cited from memory): revenue by year and brand for
one part category and the suppliers of one region."""

from benchmark.datasets.ssb import column_bytes, star, words_where

SQL = """
select sum(lo_revenue), d_year, p_brand1
from lineorder, date, part, supplier
where lo_orderdate = d_datekey
  and lo_partkey = p_partkey
  and lo_suppkey = s_suppkey
  and p_category = 'MFGR#12'
  and s_region = 'AMERICA'
group by d_year, p_brand1
order by d_year, p_brand1
"""

READS = {"lineorder": ["lo_orderdate", "lo_partkey", "lo_suppkey",
                       "lo_revenue"],
         "date": ["d_datekey", "d_year"],
         "part": ["p_partkey", "p_category", "p_brand1"],
         "supplier": ["s_suppkey", "s_region"]}


def min_bytes(rows: dict) -> int:
    """Bytes one execution must read: every column in READS, once."""
    return column_bytes(READS, rows)


def reference(t) -> list:
    p, s = t["part"], t["supplier"]
    return star(
        t, t["lineorder"]["lo_revenue"],
        {"lo_orderdate": ("date", "d_datekey", None),
         "lo_partkey": ("part", "p_partkey",
                        words_where(p["p_category"],
                                    lambda w: w == b"MFGR#12")),
         "lo_suppkey": ("supplier", "s_suppkey",
                        words_where(s["s_region"],
                                    lambda w: w == b"AMERICA"))},
        group=[("lo_orderdate", "d_year"), ("lo_partkey", "p_brand1")],
        order=lambda rows: [(rev, y, b) for y, b, rev in sorted(rows)])
