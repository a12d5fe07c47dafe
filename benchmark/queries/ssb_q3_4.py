"""Star Schema Benchmark Q3.4, flight 3 (O'Neil et al., revision 3, section 3;
the paper's own literals, cited from memory): the same two cities in one
month, December 1997."""

from benchmark.datasets.ssb import column_bytes, star, words_where

SQL = """
select c_city, s_city, d_year, sum(lo_revenue) as revenue
from customer, lineorder, supplier, date
where lo_custkey = c_custkey
  and lo_suppkey = s_suppkey
  and lo_orderdate = d_datekey
  and (c_city = 'UNITED KI1' or c_city = 'UNITED KI5')
  and (s_city = 'UNITED KI1' or s_city = 'UNITED KI5')
  and d_yearmonth = 'Dec1997'
group by c_city, s_city, d_year
order by d_year asc, revenue desc
"""

READS = {"customer": ["c_custkey", "c_city"],
         "lineorder": ["lo_custkey", "lo_suppkey", "lo_orderdate",
                       "lo_revenue"],
         "supplier": ["s_suppkey", "s_city"],
         "date": ["d_datekey", "d_year", "d_yearmonth"]}


def min_bytes(rows: dict) -> int:
    """Bytes one execution must read: every column in READS, once."""
    return column_bytes(READS, rows)


def reference(t) -> list:
    c, s, d = t["customer"], t["supplier"], t["date"]
    here = (b"UNITED KI1", b"UNITED KI5")
    return star(
        t, t["lineorder"]["lo_revenue"],
        {"lo_custkey": ("customer", "c_custkey",
                        words_where(c["c_city"], lambda w: w in here)),
         "lo_suppkey": ("supplier", "s_suppkey",
                        words_where(s["s_city"], lambda w: w in here)),
         "lo_orderdate": ("date", "d_datekey",
                          words_where(d["d_yearmonth"],
                                      lambda w: w == b"Dec1997"))},
        group=[("lo_custkey", "c_city"), ("lo_suppkey", "s_city"),
               ("lo_orderdate", "d_year")],
        # d_year asc, revenue desc: no two groups of a year tie
        order=lambda rows: sorted(rows, key=lambda r: (r[2], -r[3])))
