"""Star Schema Benchmark Q1.1, flight 1 (O'Neil et al., revision 3, section 3;
the paper's own literals, cited from memory): the revenue a 1-3% discount
gave up on small orders of 1993."""

from benchmark.datasets.ssb import column_bytes, star

SQL = """
select sum(lo_extendedprice * lo_discount) as revenue
from lineorder, date
where lo_orderdate = d_datekey
  and d_year = 1993
  and lo_discount between 1 and 3
  and lo_quantity < 25
"""

READS = {"lineorder": ["lo_orderdate", "lo_extendedprice", "lo_discount",
                       "lo_quantity"],
         "date": ["d_datekey", "d_year"]}


def min_bytes(rows: dict) -> int:
    """Bytes one execution must read: every column in READS, once."""
    return column_bytes(READS, rows)


def reference(t) -> list:
    lo, d = t["lineorder"], t["date"]
    keep = ((lo["lo_discount"] >= 1) & (lo["lo_discount"] <= 3)
            & (lo["lo_quantity"] < 25))
    return star(t, lo["lo_extendedprice"] * lo["lo_discount"] * keep,
                {"lo_orderdate": ("date", "d_datekey",
                                  d["d_year"] == 1993)})
