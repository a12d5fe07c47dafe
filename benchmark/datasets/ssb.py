"""The data set ``ssb``: the Star Schema Benchmark's five tables from a
seed, vectorised numpy (P. O'Neil, E. O'Neil, X. Chen, S. Revelle, "Star
Schema Benchmark", revision 3, June 2009: schema and scaling of its
section 2; cited from memory, there is no network here).  A configuration
names it under ``dataset``; the harness asks a data set for ``DB``,
``GEN_VERSION``, ``SCHEMA``, ``generate`` and ``load`` and nothing else.

Every column is drawn from a stream of its own,
``numpy.random.default_rng([seed, table, column])``, in one call over the
whole table: a column's values depend on the seed and the scale factor
alone, never on which columns a configuration asks for, and a column
nobody asks for is never drawn.  There is no loop over rows or pages.

Scaling (section 2): LINEORDER about SF x 6,000,000 (SF x 1,500,000
orders of 1..7 lines), CUSTOMER SF x 30,000, SUPPLIER SF x 2,000, PART
200,000 x floor(1 + log2 SF) (800,000 at SF10; 200,000 x SF below SF1,
which the paper does not define), DATE 2,556 days from 1992-01-01.
Distributions follow the paper where a query's selectivity depends on
them: uniform foreign keys, ``lo_quantity`` 1..50, ``lo_discount`` 0..10,
``lo_tax`` 0..8, ``lo_extendedprice`` = quantity x the part's price (TPC-H's
retail-price formula, in cents), ``lo_revenue`` = ``lo_extendedprice`` x
(100 - ``lo_discount``) / 100, ``lo_supplycost`` = 6/10 of the part's price,
order keys sparse as in TPC-H (8 of every 32), order dates uniform over
1992-01-01..1998-08-02 and commit dates 30..90 days later (both inside
the date dimension), 5 regions x 5 nations x 10 cities (a city is the
nation's first nine letters and a digit), ``p_mfgr`` 5 / ``p_category`` 25 /
``p_brand1`` 1,000 values.  It is NOT the paper's dbgen variant: the random
streams are numpy's, so row counts differ by seed (lineorder at SF10 is
60,000,000 +- ~11,000).  Free-text columns (``*_name``, ``*_address``,
``*_phone``) are not generated.

Representation: identifiers, integers, money (cents) and date keys
(yyyymmdd) int64, fixed-vocabulary strings as ``(int32 codes, [bytes,
...])``.
"""

import math

import numpy as np

from benchmark.datasets.tpch import (
    COLORS, CONTAINER_S1, CONTAINER_S2, MODES, NATIONS, PRIORITIES, REGIONS,
    SEGMENTS, TYPE_S1, TYPE_S2, TYPE_S3, retailprice, values)

#: bump when any column's values change for a given (seed, sf): every
#: cache keyed by the data (store directory, reference answers) carries it
GEN_VERSION = 1

#: the database the tables are created in
DB = "ssb"
#: no table is small enough to be worth 2,556 INSERT statements: all five
#: are bulk-installed
SQL_TABLES = ()

#: section 2's column types, free-text columns left out.  "pk" marks the
#: single-column integer primary keys the DDL declares.
SCHEMA = {
    "customer": {"c_custkey": "bigint pk", "c_city": "varchar(10)",
                 "c_nation": "varchar(15)", "c_region": "varchar(12)",
                 "c_mktsegment": "varchar(10)"},
    "supplier": {"s_suppkey": "bigint pk", "s_city": "varchar(10)",
                 "s_nation": "varchar(15)", "s_region": "varchar(12)"},
    "part": {"p_partkey": "bigint pk", "p_mfgr": "varchar(6)",
             "p_category": "varchar(7)", "p_brand1": "varchar(9)",
             "p_color": "varchar(11)", "p_type": "varchar(25)",
             "p_size": "bigint", "p_container": "varchar(10)"},
    "date": {"d_datekey": "bigint pk", "d_date": "varchar(18)",
             "d_dayofweek": "varchar(9)", "d_month": "varchar(9)",
             "d_year": "bigint", "d_yearmonthnum": "bigint",
             "d_yearmonth": "varchar(7)", "d_daynuminweek": "bigint",
             "d_daynuminmonth": "bigint", "d_daynuminyear": "bigint",
             "d_monthnuminyear": "bigint", "d_weeknuminyear": "bigint",
             "d_sellingseason": "varchar(12)",
             "d_lastdayinweekfl": "bigint", "d_lastdayinmonthfl": "bigint",
             "d_holidayfl": "bigint", "d_weekdayfl": "bigint"},
    "lineorder": {"lo_orderkey": "bigint", "lo_linenumber": "bigint",
                  "lo_custkey": "bigint", "lo_partkey": "bigint",
                  "lo_suppkey": "bigint", "lo_orderdate": "bigint",
                  "lo_orderpriority": "varchar(15)",
                  "lo_shippriority": "varchar(1)", "lo_quantity": "bigint",
                  "lo_extendedprice": "bigint", "lo_ordtotalprice": "bigint",
                  "lo_discount": "bigint", "lo_revenue": "bigint",
                  "lo_supplycost": "bigint", "lo_tax": "bigint",
                  "lo_commitdate": "bigint", "lo_shipmode": "varchar(10)"},
}

_TABLE_ID = {t: i for i, t in enumerate(SCHEMA)}
_COLUMN_ID = {t: {c: i for i, c in enumerate(cols)}
              for t, cols in SCHEMA.items()}

FIRST_DAY = np.datetime64("1992-01-01")
#: "7 years of days" (section 2)
N_DAYS = 2556
#: orders are dated up to 151 days before the end of 1998, as in TPC-H
N_ORDER_DAYS = int((np.datetime64("1998-12-31") - FIRST_DAY).astype(int)) - 150

_NATION_NAMES = [n.encode() for n, _r in NATIONS]
_REGION_NAMES = [r.encode() for r in REGIONS]
_NATION_REGION = np.array([r for _n, r in NATIONS], dtype=np.int32)
#: city = nation * 10 + digit: "UNITED KI1", "PERU     0"
_CITY_NAMES = [b"%-9s%d" % (n[:9], d) for n in _NATION_NAMES
               for d in range(10)]
_MFGRS = [b"MFGR#%d" % m for m in range(1, 6)]
_CATEGORIES = [b"MFGR#%d%d" % (m, c) for m in range(1, 6)
               for c in range(1, 6)]
#: brand = category * 40 + (1..40): "MFGR#2221" is category 22, brand 21
_BRANDS = [cat + b"%d" % b for cat in _CATEGORIES for b in range(1, 41)]
_TYPES = [b" ".join((a, b, c)) for a in TYPE_S1 for b in TYPE_S2
          for c in TYPE_S3]
_CONTAINERS = [b" ".join((a, b)) for a in CONTAINER_S1 for b in CONTAINER_S2]
_MONTHS = ["January", "February", "March", "April", "May", "June", "July",
           "August", "September", "October", "November", "December"]
_WEEKDAYS = ["Monday", "Tuesday", "Wednesday", "Thursday", "Friday",
             "Saturday", "Sunday"]
_SEASONS = [b"Christmas", b"Fall", b"Spring", b"Summer", b"Winter"]
#: month (0..11) -> season: Christmas in December, Winter January to March
_MONTH_SEASON = np.array([4, 4, 4, 2, 2, 3, 3, 3, 1, 1, 1, 0],
                         dtype=np.int32)


def sizes(sf: float) -> dict:
    """Row counts at `sf` (lineorder's is known only after generation)."""
    part = (200_000 * int(math.floor(1 + math.log2(sf))) if sf >= 1
            else int(200_000 * sf))
    return {"customer": max(int(30_000 * sf), 25),
            "supplier": max(int(2_000 * sf), 10),
            "part": max(part, 40),
            "orders": max(int(1_500_000 * sf), 2),
            "date": N_DAYS}


def _rng(seed: int, table: str, column: str):
    return np.random.default_rng([int(seed), _TABLE_ID[table],
                                  _COLUMN_ID[table][column]])


class _Lazy(dict):
    """{column: array}, each made on first use by `make(column)`."""

    def __init__(self, make):
        super().__init__()
        self._make = make

    def __missing__(self, col):
        self[col] = self._make(self, col)
        return self[col]


def _geography(seed, table, prefix, n):
    """`prefix`key 1..n, a uniform nation, its region, one of its ten
    cities."""
    def make(t, col):
        if col == prefix + "key":
            return np.arange(1, n + 1, dtype=np.int64)
        if col == "_nation":
            return _rng(seed, table, prefix[0] + "_nation") \
                .integers(0, 25, n).astype(np.int32)
        if col == "_city":
            return (t["_nation"] * 10 + _rng(
                seed, table, prefix[0] + "_city").integers(0, 10, n)
            ).astype(np.int32)
        if col == "_region":
            return _NATION_REGION[t["_nation"]]
        if col == "c_mktsegment":
            return _rng(seed, table, col).integers(0, 5, n).astype(np.int32)
        raise KeyError(col)
    return _Lazy(make)


def _part(seed, n):
    def make(t, col):
        if col == "p_partkey":
            return np.arange(1, n + 1, dtype=np.int64)
        if col == "p_brand1":       # carries category and manufacturer
            return _rng(seed, "part", col).integers(0, 1000, n) \
                .astype(np.int32)
        if col == "p_category":
            return t["p_brand1"] // 40
        if col == "p_mfgr":
            return t["p_brand1"] // 200
        hi = {"p_color": 92, "p_type": 150, "p_container": 40}.get(col)
        if hi is not None:
            return _rng(seed, "part", col).integers(0, hi, n) \
                .astype(np.int32)
        if col == "p_size":
            return _rng(seed, "part", col).integers(1, 51, n)
        raise KeyError(col)
    return _Lazy(make)


def _date():
    """The date dimension: a calendar, no random draw."""
    day = FIRST_DAY + np.arange(N_DAYS)
    year = day.astype("datetime64[Y]").astype(np.int64) + 1970
    month = day.astype("datetime64[M]").astype(np.int64) % 12      # 0..11
    dom = (day - day.astype("datetime64[M]")).astype(np.int64) + 1
    doy = (day - day.astype("datetime64[Y]")).astype(np.int64) + 1
    # 1970-01-01 was a Thursday: Monday = 0
    dow = (day.astype("datetime64[D]").astype(np.int64) + 3) % 7
    last_dom = (day + 1).astype("datetime64[M]") != day.astype(
        "datetime64[M]")
    holiday = ((month == 11) & (dom == 25)) | ((month == 0) & (dom == 1)) \
        | ((month == 6) & (dom == 4))
    ym = (year - 1992) * 12 + month
    return {
        "d_datekey": year * 10000 + (month + 1) * 100 + dom,
        "d_date": (np.arange(N_DAYS, dtype=np.int32),
                   [f"{_MONTHS[m]} {d}, {y}".encode()
                    for y, m, d in zip(year, month, dom)]),
        "d_dayofweek": (dow.astype(np.int32),
                        [w.encode() for w in _WEEKDAYS]),
        "d_month": (month.astype(np.int32), [m.encode() for m in _MONTHS]),
        "d_year": year,
        "d_yearmonthnum": year * 100 + month + 1,
        "d_yearmonth": (ym.astype(np.int32),
                        [f"{_MONTHS[m][:3]}{y}".encode()
                         for y in range(1992, 1999) for m in range(12)]),
        "d_daynuminweek": dow + 1,
        "d_daynuminmonth": dom,
        "d_daynuminyear": doy,
        "d_monthnuminyear": month + 1,
        "d_weeknuminyear": (doy - 1) // 7 + 1,
        "d_sellingseason": (_MONTH_SEASON[month], _SEASONS),
        "d_lastdayinweekfl": (dow == 6).astype(np.int64),
        "d_lastdayinmonthfl": last_dom.astype(np.int64),
        "d_holidayfl": holiday.astype(np.int64),
        "d_weekdayfl": (dow < 5).astype(np.int64),
    }


def _lineorder(seed, sz, datekeys):
    """Orders of 1..7 lines; a column of the order is repeated over its
    lines, a column of the line drawn at the fact's length."""
    m = sz["orders"]

    def draw(col, lo, hi, n):
        return _rng(seed, "lineorder", col).integers(lo, hi, n)

    def per_order(t, values):
        return np.repeat(values, t["_nlines"])

    def make(t, col):
        if col == "_nlines":        # lo_linenumber's stream
            return draw("lo_linenumber", 1, 8, m)
        if col == "_first":
            return np.cumsum(t["_nlines"]) - t["_nlines"]
        if col == "_n":
            return int(t["_nlines"].sum())
        if col == "lo_orderkey":
            idx = np.arange(m, dtype=np.int64)
            return per_order(t, (idx // 8) * 32 + idx % 8 + 1)
        if col == "lo_linenumber":
            return (np.arange(t["_n"], dtype=np.int64)
                    - per_order(t, t["_first"]) + 1)
        if col == "lo_custkey":
            return per_order(t, draw(col, 1, sz["customer"] + 1, m))
        if col == "lo_partkey":
            return draw(col, 1, sz["part"] + 1, t["_n"])
        if col == "lo_suppkey":
            return draw(col, 1, sz["supplier"] + 1, t["_n"])
        if col == "_orderday":
            return per_order(t, draw("lo_orderdate", 0, N_ORDER_DAYS, m))
        if col == "lo_orderdate":
            return datekeys[t["_orderday"]]
        if col == "lo_commitdate":
            return datekeys[t["_orderday"] + draw(col, 30, 91, t["_n"])]
        if col == "lo_orderpriority":
            return per_order(t, draw(col, 0, 5, m).astype(np.int32))
        if col == "lo_shippriority":
            return np.zeros(t["_n"], dtype=np.int32)
        if col == "lo_shipmode":
            return draw(col, 0, 7, t["_n"]).astype(np.int32)
        if col == "lo_quantity":
            return draw(col, 1, 51, t["_n"])
        if col == "lo_discount":
            return draw(col, 0, 11, t["_n"])
        if col == "lo_tax":
            return draw(col, 0, 9, t["_n"])
        if col == "_price":
            return retailprice(t["lo_partkey"])
        if col == "lo_extendedprice":
            return t["lo_quantity"] * t["_price"]
        if col == "lo_revenue":
            return t["lo_extendedprice"] * (100 - t["lo_discount"]) // 100
        if col == "lo_supplycost":
            return 6 * t["_price"] // 10
        if col == "lo_ordtotalprice":
            line = (t["lo_extendedprice"] * (100 + t["lo_tax"])
                    * (100 - t["lo_discount"]))
            return per_order(
                t, (np.add.reduceat(line, t["_first"]) + 5000) // 10000)
        raise KeyError(col)
    return _Lazy(make)


#: column -> (the raw column that holds its codes, its dictionary)
_DICTS = {
    "c_city": ("_city", _CITY_NAMES), "c_nation": ("_nation", _NATION_NAMES),
    "c_region": ("_region", _REGION_NAMES),
    "c_mktsegment": ("c_mktsegment", SEGMENTS),
    "s_city": ("_city", _CITY_NAMES), "s_nation": ("_nation", _NATION_NAMES),
    "s_region": ("_region", _REGION_NAMES),
    "p_mfgr": ("p_mfgr", _MFGRS), "p_category": ("p_category", _CATEGORIES),
    "p_brand1": ("p_brand1", _BRANDS),
    "p_color": ("p_color", [c.encode() for c in COLORS]),
    "p_type": ("p_type", _TYPES), "p_container": ("p_container", _CONTAINERS),
    "lo_orderpriority": ("lo_orderpriority", PRIORITIES),
    "lo_shippriority": ("lo_shippriority", [b"0"]),
    "lo_shipmode": ("lo_shipmode", MODES),
}


def generate(seed: int, sf: float, want: "dict | None" = None) -> dict:
    """{table: {column: array | (codes, dictionary)}} for the tables and
    columns in `want` ({table: [columns]}; default: every column)."""
    want = want or {t: list(cols) for t, cols in SCHEMA.items()}
    for t, cols in want.items():
        if t not in SCHEMA:
            raise KeyError(f"ssb: unknown table {t!r}")
        for c in cols:
            if c not in SCHEMA[t]:
                raise KeyError(f"ssb: table {t!r} has no column {c!r} "
                               "(free-text columns are not generated)")
    sz = sizes(sf)
    date = _date()
    raw = {"date": date,
           "customer": _geography(seed, "customer", "c_cust", sz["customer"]),
           "supplier": _geography(seed, "supplier", "s_supp", sz["supplier"]),
           "part": _part(seed, sz["part"]),
           "lineorder": _lineorder(seed, sz, date["d_datekey"])}
    out = {}
    for t, cols in want.items():
        out[t] = {}
        for c in cols:
            if c in _DICTS:
                src, words = _DICTS[c]
                out[t][c] = (raw[t][src], words)
            else:
                out[t][c] = raw[t][c]
    return out


def load(tk, tables: dict, want: dict, seeded: bool, tag: str) -> dict:
    """Install `tables` (generate's result for `want`) through `tk` (worker
    side); -> row counts."""
    from benchmark.harness import install
    return install.load(tk, DB, SCHEMA, SQL_TABLES, tables, want, seeded,
                        tag)


def column_bytes(reads: dict, rows: dict) -> int:
    """Bytes of the columns in `reads` ({table: [columns]}) at the widths
    of this representation: 8 for identifiers, integers, money and date
    keys, 4 for dictionary codes."""
    return sum(rows[table] * (4 if "char" in SCHEMA[table][c] else 8)
               for table, cols in reads.items() for c in cols)


# -- the plain reference of the thirteen queries -------------------------------

def words_where(col, keep) -> np.ndarray:
    """Per row of a dict-coded column: does `keep(word)` hold (decided
    once per dictionary word, in Python's own bytes order)."""
    codes, words = col
    return np.array([bool(keep(w)) for w in words], dtype=bool)[codes]


def star(t, value, dims, group=(), order=None) -> list:
    """One star query in plain numpy: the sum of `value` (an int64 array
    over lineorder's rows) over the rows whose every dimension row passes
    its filter, per combination of the `group` attributes.

    dims: {fact key column: (dimension table, its key column, bool mask
    over the dimension's rows or None)}.  A dimension is looked up through
    a dense array over its key span.
    group: [(fact key column, dimension attribute)]: the answer's leading
    columns, in this order; the sum comes last unless `order` moves it.
    order: a function rows -> rows over [(attribute values..., sum)];
    default: ascending by the attributes.
    -> rows as the MySQL wire carries them (strings)."""
    lo = t["lineorder"]
    n = len(value)
    sel = np.ones(n, dtype=bool)
    slot = {}
    for fk, (table, key, mask) in dims.items():
        keys = t[table][key]
        base = int(keys.min())
        row_of = np.full(int(keys.max()) - base + 2, -1, dtype=np.int32)
        live = np.arange(len(keys)) if mask is None else np.nonzero(mask)[0]
        row_of[keys[live] - base] = live
        slot[fk] = row_of[np.clip(lo[fk] - base, -1, len(row_of) - 1)]
        sel &= slot[fk] >= 0
    if not group:
        if not sel.any():
            return [(None,)]
        return [(str(int(value[sel].sum())),)]
    gid = np.zeros(int(sel.sum()), dtype=np.int64)
    parts = []
    for fk, attr in group:
        col = t[dims[fk][0]][attr]
        codes = values(col)[slot[fk][sel]]
        uniq, inv = np.unique(codes, return_inverse=True)
        parts.append((col, uniq, len(uniq)))
        gid = gid * len(uniq) + inv
    total = np.zeros(int(np.prod([p[2] for p in parts])), dtype=np.int64)
    np.add.at(total, gid, value[sel])
    seen = np.bincount(gid, minlength=len(total)) > 0
    rows = []
    for g in np.nonzero(seen)[0]:
        rest, vals = int(g), []
        for col, uniq, k in reversed(parts):
            code = uniq[rest % k]
            rest //= k
            vals.append(col[1][code] if isinstance(col, tuple)
                        else int(code))
        rows.append((*reversed(vals), int(total[g])))
    rows = sorted(rows) if order is None else order(rows)
    return [tuple(v.decode() if isinstance(v, bytes) else str(v)
                  for v in r) for r in rows]
