"""Star Schema Benchmark Q3.1, flight 3 (O'Neil et al., revision 3, section 3;
the paper's own literals, cited from memory): revenue between the nations of
one region, by year."""

from benchmark.datasets.ssb import column_bytes, star, words_where

SQL = """
select c_nation, s_nation, d_year, sum(lo_revenue) as revenue
from customer, lineorder, supplier, date
where lo_custkey = c_custkey
  and lo_suppkey = s_suppkey
  and lo_orderdate = d_datekey
  and c_region = 'ASIA'
  and s_region = 'ASIA'
  and d_year >= 1992 and d_year <= 1997
group by c_nation, s_nation, d_year
order by d_year asc, revenue desc
"""

READS = {"customer": ["c_custkey", "c_region", "c_nation"],
         "lineorder": ["lo_custkey", "lo_suppkey", "lo_orderdate",
                       "lo_revenue"],
         "supplier": ["s_suppkey", "s_region", "s_nation"],
         "date": ["d_datekey", "d_year"]}


def min_bytes(rows: dict) -> int:
    """Bytes one execution must read: every column in READS, once."""
    return column_bytes(READS, rows)


def reference(t) -> list:
    c, s, d = t["customer"], t["supplier"], t["date"]
    here = (b"ASIA",)
    return star(
        t, t["lineorder"]["lo_revenue"],
        {"lo_custkey": ("customer", "c_custkey",
                        words_where(c["c_region"], lambda w: w in here)),
         "lo_suppkey": ("supplier", "s_suppkey",
                        words_where(s["s_region"], lambda w: w in here)),
         "lo_orderdate": ("date", "d_datekey",
                          (d["d_year"] >= 1992) & (d["d_year"] <= 1997))},
        group=[("lo_custkey", "c_nation"), ("lo_suppkey", "s_nation"),
               ("lo_orderdate", "d_year")],
        # d_year asc, revenue desc: no two groups of a year tie
        order=lambda rows: sorted(rows, key=lambda r: (r[2], -r[3])))
