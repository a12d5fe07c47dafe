"""Star Schema Benchmark Q1.2, flight 1 (O'Neil et al., revision 3, section 3;
the paper's own literals, cited from memory): the same question for one
month, January 1994."""

from benchmark.datasets.ssb import column_bytes, star

SQL = """
select sum(lo_extendedprice * lo_discount) as revenue
from lineorder, date
where lo_orderdate = d_datekey
  and d_yearmonthnum = 199401
  and lo_discount between 4 and 6
  and lo_quantity between 26 and 35
"""

READS = {"lineorder": ["lo_orderdate", "lo_extendedprice", "lo_discount",
                       "lo_quantity"],
         "date": ["d_datekey", "d_yearmonthnum"]}


def min_bytes(rows: dict) -> int:
    """Bytes one execution must read: every column in READS, once."""
    return column_bytes(READS, rows)


def reference(t) -> list:
    lo, d = t["lineorder"], t["date"]
    keep = ((lo["lo_discount"] >= 4) & (lo["lo_discount"] <= 6)
            & (lo["lo_quantity"] >= 26) & (lo["lo_quantity"] <= 35))
    return star(t, lo["lo_extendedprice"] * lo["lo_discount"] * keep,
                {"lo_orderdate": ("date", "d_datekey",
                                  d["d_yearmonthnum"] == 199401)})
