"""TPC-H Q17, small-quantity-order revenue (cl. 2.4.17), with the
validation parameters of cl. 2.4.17.4: BRAND Brand#23, CONTAINER MED BOX.
The text is the specification's: the correlated scalar subquery
``0.2 * avg(l_quantity)`` over the part's own lines."""

import numpy as np

from benchmark.harness import fmt
from benchmark.datasets.tpch import code_of, column_bytes, values


def _fragment_takes_a_derived_build() -> bool:
    """Does the program's join fragment take a build side that is another
    operator's result (``device_join._derived_leaf``)?  One that
    does not answers Q17 under the pinned ``tpu`` engine with the host's
    joins over all 6M lines, which the harness counts as a failure, not a
    result.  Importing the module touches no device."""
    from tidb_tpu.executor import device_join
    return hasattr(device_join, "_derived_leaf")


if not _fragment_takes_a_derived_build():
    raise NotImplementedError(
        "q17: this program's join fragment takes no derived build, so "
        "Q17 runs in the host engine under a configuration that pins tpu; "
        "the cell cannot be measured on it")

SQL = """
select sum(l_extendedprice) / 7.0 as avg_yearly
from lineitem, part
where p_partkey = l_partkey
  and p_brand = 'Brand#23'
  and p_container = 'MED BOX'
  and l_quantity < (select 0.2 * avg(l_quantity) from lineitem
                    where l_partkey = p_partkey)
"""

READS = {"lineitem": ["l_partkey", "l_quantity", "l_extendedprice"],
         "part": ["p_partkey", "p_brand", "p_container"]}


def min_bytes(rows: dict) -> int:
    """Bytes one execution must read: every column in READS once, and
    l_partkey and l_quantity twice (the subquery's aggregate scans them,
    the join probes them)."""
    return column_bytes(READS, rows) + column_bytes(
        {"lineitem": ["l_partkey", "l_quantity"]}, rows)


def _half_up(num, den):
    """num / den rounded half away from zero, num >= 0, den > 0."""
    return (2 * num + den) // (2 * den)


def counts(t) -> dict:
    """What the query reads on the way, for a report: the parts of the
    brand and container, their lines, and those under the threshold."""
    return _select(t)[1]


def _select(t):
    li, p = t["lineitem"], t["part"]
    key = li["l_partkey"]
    quantity = li["l_quantity"]            # decimal(15,2) x 100
    n = int(max(key.max(), p["p_partkey"].max())) + 1
    lines = np.bincount(key, minlength=n)
    total = np.zeros(n, dtype=np.int64)
    np.add.at(total, key, quantity)
    # avg(decimal(15,2)) at scale 2 + 4, rounded half up; 0.2 * avg at
    # scale 7 is twice it; l_quantity < that at scale 7
    has = lines > 0
    avg6 = np.zeros(n, dtype=np.int64)
    avg6[has] = _half_up(total[has] * 10 ** 4, lines[has])
    wanted = np.zeros(n, dtype=bool)
    wanted[p["p_partkey"][
        (values(p["p_brand"]) == code_of(p["p_brand"], b"Brand#23"))
        & (values(p["p_container"]) == code_of(p["p_container"],
                                               b"MED BOX"))]] = True
    live = wanted[key]
    small = live & (quantity * 10 ** 5 < 2 * avg6[key])
    return small, {"parts": int(wanted.sum()), "live_lines": int(live.sum()),
                   "lines_under": int(small.sum()),
                   "groups": int(has.sum())}


def reference(t) -> list:
    small, _counts = _select(t)
    if not small.any():
        return [(None,)]                   # sum over no row is NULL
    revenue = int(t["lineitem"]["l_extendedprice"][small].sum())
    # sum(decimal(15,2)) / 7.0 at scale 2 + 4, rounded half up
    return [(fmt.dec(_half_up(revenue * 10 ** 4, 7), 6),)]
