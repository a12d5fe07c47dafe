"""The data set ``tpch``: TPC-H data from a seed, vectorised numpy
(TPC-H v3 cl. 4.2.3).  A configuration names it under ``dataset``; the
harness asks a data set for ``DB``, ``GEN_VERSION``, ``generate`` and
``load`` and nothing else.

Every random draw comes from ``numpy.random.default_rng([seed, table,
page])`` and every page draws all of its columns in one fixed order, so
a column's values depend on the seed and the scale factor alone -- never
on which columns a configuration asks for.

The generator follows the specification wherever a query's selectivity
or cardinality depends on it: sparse ``o_orderkey`` (8 of every 32),
``o_custkey`` never a multiple of 3, ``o_orderdate`` uniform over
1992-01-01..1998-08-02, 1..7 lineitems per order, ``l_shipdate`` =
orderdate + 1..121, ``l_commitdate`` = orderdate + 30..90,
``l_receiptdate`` = shipdate + 1..30, ``l_returnflag`` /
``l_linestatus`` from the dates against 1995-06-17, ``l_extendedprice``
= quantity x ``p_retailprice(partkey)``, ``o_totalprice`` and
``o_orderstatus`` from the order's lines, the four-suppliers-per-part
formula for ``ps_suppkey`` and ``l_suppkey``.  It is NOT dbgen: the
random streams are numpy's, so row counts differ from dbgen's by seed
(lineitem at SF1 is 6,000,000 +- ~3,000).  Free-text columns
(``*_comment``, addresses, phones) are not generated.

Representation (cl. 1.4): identifiers and integers int64, decimal(15,2)
as int64 scaled by 100, dates int32 days since 1970-01-01,
fixed-vocabulary strings as ``(int32 codes, [bytes, ...])``.
"""

import numpy as np

#: bump when any column's values change for a given (seed, sf): every
#: cache keyed by the data (store directory, reference answers) carries it
GEN_VERSION = 1

_EPOCH = np.datetime64("1970-01-01")


def days(date_str: str) -> int:
    """Days since 1970-01-01 of 'YYYY-MM-DD'."""
    return int((np.datetime64(date_str) - _EPOCH).astype(int))


def date_str(day: int) -> str:
    return str(_EPOCH + np.timedelta64(int(day), "D"))


STARTDATE = days("1992-01-01")
CURRENTDATE = days("1995-06-17")
ENDDATE = days("1998-12-31")
#: last order date: ENDDATE - 151 days (cl. 4.2.3)
LAST_ORDERDATE = ENDDATE - 151

NATIONS = [
    ("ALGERIA", 0), ("ARGENTINA", 1), ("BRAZIL", 1), ("CANADA", 1),
    ("EGYPT", 4), ("ETHIOPIA", 0), ("FRANCE", 3), ("GERMANY", 3),
    ("INDIA", 2), ("INDONESIA", 2), ("IRAN", 4), ("IRAQ", 4),
    ("JAPAN", 2), ("JORDAN", 4), ("KENYA", 0), ("MOROCCO", 0),
    ("MOZAMBIQUE", 0), ("PERU", 1), ("CHINA", 2), ("ROMANIA", 3),
    ("SAUDI ARABIA", 4), ("VIETNAM", 2), ("RUSSIA", 3),
    ("UNITED KINGDOM", 3), ("UNITED STATES", 1),
]
REGIONS = ["AFRICA", "AMERICA", "ASIA", "EUROPE", "MIDDLE EAST"]
SEGMENTS = [b"AUTOMOBILE", b"BUILDING", b"FURNITURE", b"MACHINERY",
            b"HOUSEHOLD"]
PRIORITIES = [b"1-URGENT", b"2-HIGH", b"3-MEDIUM", b"4-NOT SPECIFIED",
              b"5-LOW"]
INSTRUCTIONS = [b"DELIVER IN PERSON", b"COLLECT COD", b"NONE",
                b"TAKE BACK RETURN"]
MODES = [b"REG AIR", b"AIR", b"RAIL", b"SHIP", b"TRUCK", b"MAIL", b"FOB"]
RETURNFLAGS = [b"A", b"N", b"R"]
LINESTATUS = [b"F", b"O"]
ORDERSTATUS = [b"F", b"O", b"P"]
TYPE_S1 = [b"STANDARD", b"SMALL", b"MEDIUM", b"LARGE", b"ECONOMY", b"PROMO"]
TYPE_S2 = [b"ANODIZED", b"BURNISHED", b"PLATED", b"POLISHED", b"BRUSHED"]
TYPE_S3 = [b"TIN", b"NICKEL", b"BRASS", b"STEEL", b"COPPER"]
CONTAINER_S1 = [b"SM", b"LG", b"MED", b"JUMBO", b"WRAP"]
CONTAINER_S2 = [b"CASE", b"BOX", b"BAG", b"JAR", b"PKG", b"PACK", b"CAN",
                b"DRUM"]
#: the 92 colour words of P_NAME (cl. 4.2.3)
COLORS = (
    "almond antique aquamarine azure beige bisque black blanched blue "
    "blush brown burlywood burnished chartreuse chiffon chocolate coral "
    "cornflower cornsilk cream cyan dark deep dim dodger drab firebrick "
    "floral forest frosted gainsboro ghost goldenrod green grey honeydew "
    "hot indian ivory khaki lace lavender lawn lemon light lime linen "
    "magenta maroon medium metallic midnight mint misty moccasin navajo "
    "navy olive orange orchid pale papaya peach peru pink plum powder "
    "puff purple red rose rosy royal saddle salmon sandy seashell sienna "
    "sky slate smoke snow spring steel tan thistle tomato turquoise "
    "violet wheat white yellow").split()

#: cl. 1.4 column types, free-text columns left out.  "pk" marks the
#: single-column integer primary keys the DDL declares.
SCHEMA = {
    "region": {"r_regionkey": "bigint pk", "r_name": "varchar(25)"},
    "nation": {"n_nationkey": "bigint pk", "n_name": "varchar(25)",
               "n_regionkey": "bigint"},
    "supplier": {"s_suppkey": "bigint pk", "s_name": "varchar(25)",
                 "s_nationkey": "bigint", "s_acctbal": "decimal(15,2)"},
    "customer": {"c_custkey": "bigint pk", "c_name": "varchar(25)",
                 "c_nationkey": "bigint", "c_acctbal": "decimal(15,2)",
                 "c_mktsegment": "varchar(10)"},
    "part": {"p_partkey": "bigint pk", "p_name": "varchar(55)",
             "p_mfgr": "varchar(25)", "p_brand": "varchar(10)",
             "p_type": "varchar(25)", "p_size": "bigint",
             "p_container": "varchar(10)",
             "p_retailprice": "decimal(15,2)"},
    "partsupp": {"ps_partkey": "bigint", "ps_suppkey": "bigint",
                 "ps_availqty": "bigint", "ps_supplycost": "decimal(15,2)"},
    "orders": {"o_orderkey": "bigint pk", "o_custkey": "bigint",
               "o_orderstatus": "varchar(1)",
               "o_totalprice": "decimal(15,2)", "o_orderdate": "date",
               "o_orderpriority": "varchar(15)", "o_clerk": "varchar(15)",
               "o_shippriority": "bigint"},
    "lineitem": {"l_orderkey": "bigint", "l_partkey": "bigint",
                 "l_suppkey": "bigint", "l_linenumber": "bigint",
                 "l_quantity": "decimal(15,2)",
                 "l_extendedprice": "decimal(15,2)",
                 "l_discount": "decimal(15,2)", "l_tax": "decimal(15,2)",
                 "l_returnflag": "varchar(1)", "l_linestatus": "varchar(1)",
                 "l_shipdate": "date", "l_commitdate": "date",
                 "l_receiptdate": "date", "l_shipinstruct": "varchar(25)",
                 "l_shipmode": "varchar(10)"},
}

#: the database the tables are created in
DB = "tpch"
#: tables small enough to INSERT row by row (KV-backed, replayed)
SQL_TABLES = ("nation", "region")

_TABLE_ID = {t: i for i, t in enumerate(SCHEMA)}
#: orders generated per page (a page is one rng stream)
PAGE_ORDERS = 1 << 18
#: rows per page of the smaller tables
PAGE_ROWS = 1 << 20


def sizes(sf: float) -> dict:
    """Row counts at `sf` (lineitem's is known only after generation)."""
    return {"supplier": max(int(10_000 * sf), 4),
            "customer": max(int(150_000 * sf), 3),
            "part": max(int(200_000 * sf), 4),
            "orders": max(int(1_500_000 * sf), 2),
            "nation": 25, "region": 5}


def _rng(seed: int, table: str, page: int):
    return np.random.default_rng([int(seed), _TABLE_ID[table], int(page)])


def retailprice(partkey):
    """p_retailprice in cents (cl. 4.2.3)."""
    partkey = np.asarray(partkey, dtype=np.int64)
    return 90000 + (partkey // 10) % 20001 + 100 * (partkey % 1000)


def part_supplier(partkey, i, n_supp: int):
    """The i-th (0..3) supplier of a part (cl. 4.2.3 PS_SUPPKEY)."""
    partkey = np.asarray(partkey, dtype=np.int64)
    return (partkey + i * (n_supp // 4 + (partkey - 1) // n_supp)) \
        % n_supp + 1


def _numbered(prefix: bytes, keys) -> tuple:
    """('Customer#000000001'-style names as a dict-coded column)."""
    words = [prefix + b"#%09d" % k for k in keys]
    return np.arange(len(words), dtype=np.int32), words


def _pages(n: int, page_rows: int):
    for page, lo in enumerate(range(0, n, page_rows)):
        yield page, lo, min(page_rows, n - lo)


def _cat(parts: list) -> dict:
    return {k: np.concatenate([p[k] for p in parts]) for k in parts[0]}


def _region_nation() -> dict:
    return {
        "region": {"r_regionkey": np.arange(5, dtype=np.int64),
                   "r_name": (np.arange(5, dtype=np.int32),
                              [r.encode() for r in REGIONS])},
        "nation": {"n_nationkey": np.arange(25, dtype=np.int64),
                   "n_name": (np.arange(25, dtype=np.int32),
                              [n.encode() for n, _r in NATIONS]),
                   "n_regionkey": np.array([r for _n, r in NATIONS],
                                           dtype=np.int64)},
    }


def _supplier(seed, n) -> dict:
    parts = []
    for page, lo, m in _pages(n, PAGE_ROWS):
        r = _rng(seed, "supplier", page)
        parts.append({
            "s_suppkey": np.arange(lo + 1, lo + m + 1, dtype=np.int64),
            "s_nationkey": r.integers(0, 25, m),
            "s_acctbal": r.integers(-99999, 1000000, m)})
    return _cat(parts)


def _customer(seed, n) -> dict:
    parts = []
    for page, lo, m in _pages(n, PAGE_ROWS):
        r = _rng(seed, "customer", page)
        parts.append({
            "c_custkey": np.arange(lo + 1, lo + m + 1, dtype=np.int64),
            "c_nationkey": r.integers(0, 25, m),
            "c_acctbal": r.integers(-99999, 1000000, m),
            "c_mktsegment": r.integers(0, 5, m).astype(np.int32)})
    return _cat(parts)


def _part(seed, n) -> dict:
    parts = []
    for page, lo, m in _pages(n, PAGE_ROWS):
        r = _rng(seed, "part", page)
        key = np.arange(lo + 1, lo + m + 1, dtype=np.int64)
        mfgr = r.integers(1, 6, m)
        parts.append({
            "p_partkey": key,
            # five distinct colour words per name, as indices
            "_p_name_words": np.argsort(r.random((m, 92)), axis=1)[:, :5]
            .astype(np.int32),
            "p_mfgr": (mfgr - 1).astype(np.int32),
            "p_brand": ((mfgr - 1) * 5 + r.integers(0, 5, m))
            .astype(np.int32),
            "p_type": r.integers(0, 150, m).astype(np.int32),
            "p_size": r.integers(1, 51, m),
            "p_container": r.integers(0, 40, m).astype(np.int32),
            "p_retailprice": retailprice(key)})
    return _cat(parts)


def _partsupp(seed, n_part, n_supp) -> dict:
    parts = []
    for page, lo, m in _pages(n_part, PAGE_ROWS // 4):
        r = _rng(seed, "partsupp", page)
        key = np.repeat(np.arange(lo + 1, lo + m + 1, dtype=np.int64), 4)
        parts.append({
            "ps_partkey": key,
            "ps_suppkey": part_supplier(key, np.tile(np.arange(4), m),
                                        n_supp),
            "ps_availqty": r.integers(1, 10000, 4 * m),
            "ps_supplycost": r.integers(100, 100001, 4 * m)})
    return _cat(parts)


def _orders_lineitem_page(seed, page, lo, m, n_cust, n_part, n_supp,
                          n_clerk):
    """One page of orders and the lineitems that belong to them."""
    r = _rng(seed, "orders", page)
    idx = np.arange(lo, lo + m, dtype=np.int64)
    # sparse keys: the first 8 of every 32 (cl. 4.2.3 O_ORDERKEY)
    orderkey = (idx // 8) * 32 + idx % 8 + 1
    # never a multiple of 3: draw over the n_cust - n_cust//3 valid keys
    valid = r.integers(0, n_cust - n_cust // 3, m)
    custkey = valid + valid // 2 + 1
    orderdate = r.integers(STARTDATE, LAST_ORDERDATE + 1, m)
    priority = r.integers(0, 5, m).astype(np.int32)
    clerk = r.integers(1, n_clerk + 1, m)
    nlines = r.integers(1, 8, m)

    rl = _rng(seed, "lineitem", page)
    n = int(nlines.sum())
    owner = np.repeat(np.arange(m), nlines)
    first = np.cumsum(nlines) - nlines
    linenumber = np.arange(n, dtype=np.int64) - first[owner] + 1
    partkey = rl.integers(1, n_part + 1, n)
    suppkey = part_supplier(partkey, rl.integers(0, 4, n), n_supp)
    quantity = rl.integers(1, 51, n)
    discount = rl.integers(0, 11, n)
    tax = rl.integers(0, 9, n)
    shipdate = orderdate[owner] + rl.integers(1, 122, n)
    commitdate = orderdate[owner] + rl.integers(30, 91, n)
    receiptdate = shipdate + rl.integers(1, 31, n)
    returned = rl.integers(0, 2, n)            # 0 -> 'A', 1 -> 'R'
    instruct = rl.integers(0, 4, n).astype(np.int32)
    mode = rl.integers(0, 7, n).astype(np.int32)
    extendedprice = quantity * retailprice(partkey)
    # RETURNFLAGS = A, N, R: received by CURRENTDATE -> A or R, else N
    returnflag = np.where(receiptdate <= CURRENTDATE, returned * 2, 1)
    linestatus = (shipdate > CURRENTDATE).astype(np.int32)  # F, O

    # o_totalprice = sum(extendedprice * (1+tax) * (1-discount)), cents
    line_total = extendedprice * (100 + tax) * (100 - discount)
    totalprice = (np.add.reduceat(line_total, first) + 5000) // 10000
    n_open = np.add.reduceat(linestatus.astype(np.int64), first)
    orderstatus = np.where(n_open == 0, 0,
                           np.where(n_open == nlines, 1, 2))  # F, O, P
    orders = {
        "o_orderkey": orderkey, "o_custkey": custkey,
        "o_orderstatus": orderstatus.astype(np.int32),
        "o_totalprice": totalprice,
        "o_orderdate": orderdate.astype(np.int32),
        "o_orderpriority": priority, "_o_clerk": clerk,
        "o_shippriority": np.zeros(m, dtype=np.int64)}
    lineitem = {
        "l_orderkey": orderkey[owner], "l_partkey": partkey,
        "l_suppkey": suppkey, "l_linenumber": linenumber,
        "l_quantity": quantity * 100, "l_extendedprice": extendedprice,
        "l_discount": discount, "l_tax": tax,
        "l_returnflag": returnflag.astype(np.int32),
        "l_linestatus": linestatus,
        "l_shipdate": shipdate.astype(np.int32),
        "l_commitdate": commitdate.astype(np.int32),
        "l_receiptdate": receiptdate.astype(np.int32),
        "l_shipinstruct": instruct, "l_shipmode": mode}
    return orders, lineitem


_DICTS = {
    "c_mktsegment": SEGMENTS, "o_orderstatus": ORDERSTATUS,
    "o_orderpriority": PRIORITIES, "l_returnflag": RETURNFLAGS,
    "l_linestatus": LINESTATUS, "l_shipinstruct": INSTRUCTIONS,
    "l_shipmode": MODES,
    "p_mfgr": [b"Manufacturer#%d" % i for i in range(1, 6)],
    "p_brand": [b"Brand#%d%d" % (i, j) for i in range(1, 6)
                for j in range(1, 6)],
    "p_type": [b" ".join((a, b, c)) for a in TYPE_S1 for b in TYPE_S2
               for c in TYPE_S3],
    "p_container": [b" ".join((a, b)) for a in CONTAINER_S1
                    for b in CONTAINER_S2],
}


def _finish(table: str, raw: dict, want) -> dict:
    """Select `want` columns; attach dictionaries; build the formatted
    name columns only when asked for (they cost a Python loop)."""
    out = {}
    for col in want:
        if col not in SCHEMA[table]:
            raise KeyError(f"tpch: table {table!r} has no column "
                           f"{col!r} (free-text columns are not generated)")
        if col in raw:
            v = raw[col]
            out[col] = (v, _DICTS[col]) if col in _DICTS else v
        elif col == "c_name":
            out[col] = _numbered(b"Customer", raw["c_custkey"])
        elif col == "s_name":
            out[col] = _numbered(b"Supplier", raw["s_suppkey"])
        elif col == "o_clerk":
            keys, codes = np.unique(raw["_o_clerk"], return_inverse=True)
            out[col] = (codes.astype(np.int32),
                        [b"Clerk#%09d" % k for k in keys])
        elif col == "p_name":
            words = raw["_p_name_words"]
            names = np.array([" ".join(COLORS[i] for i in row).encode()
                              for row in words], dtype=object)
            uniq, codes = np.unique(names, return_inverse=True)
            out[col] = (codes.astype(np.int32), list(uniq))
        else:
            raise KeyError(f"tpch: no generator for {table}.{col}")
    return out


def generate(seed: int, sf: float, want: "dict | None" = None) -> dict:
    """{table: {column: array | (codes, dictionary)}} for the tables and
    columns in `want` ({table: [columns]}; default: every column)."""
    want = want or {t: list(cols) for t, cols in SCHEMA.items()}
    for t in want:
        if t not in SCHEMA:
            raise KeyError(f"tpch: unknown table {t!r}")
    sz = sizes(sf)
    raw = {}
    if "region" in want or "nation" in want:
        raw.update(_region_nation())
    if "supplier" in want:
        raw["supplier"] = _supplier(seed, sz["supplier"])
    if "customer" in want:
        raw["customer"] = _customer(seed, sz["customer"])
    if "part" in want:
        raw["part"] = _part(seed, sz["part"])
    if "partsupp" in want:
        raw["partsupp"] = _partsupp(seed, sz["part"], sz["supplier"])
    if "orders" in want or "lineitem" in want:
        n_clerk = max(int(1000 * sf), 1)
        pages = [_orders_lineitem_page(seed, page, lo, m, sz["customer"],
                                       sz["part"], sz["supplier"], n_clerk)
                 for page, lo, m in _pages(sz["orders"], PAGE_ORDERS)]
        raw["orders"] = _cat([o for o, _l in pages])
        raw["lineitem"] = _cat([li for _o, li in pages])
    out = {}
    for t, cols in want.items():
        src = raw[t]
        if t in ("region", "nation"):
            out[t] = {c: src[c] for c in cols}
        else:
            out[t] = _finish(t, src, cols)
    return out


def load(tk, tables: dict, want: dict, seeded: bool, tag: str) -> dict:
    """Install `tables` (generate's result for `want`) through `tk` (worker
    side); -> row counts."""
    from benchmark.harness import install
    return install.load(tk, DB, SCHEMA, SQL_TABLES, tables, want, seeded,
                        tag)


def values(col):
    """The plain array of a column: codes for a dict-coded one."""
    return col[0] if isinstance(col, tuple) else col


def code_of(col, word: bytes) -> int:
    """The code of `word` in a dict-coded column's dictionary."""
    return col[1].index(word)


def column_bytes(reads: dict, rows: dict) -> int:
    """Bytes of the columns in `reads` ({table: [columns]}) at the widths
    of this representation: 8 for identifiers, integers and decimals, 4
    for dates and dictionary codes."""
    total = 0
    for table, cols in reads.items():
        for c in cols:
            tp = SCHEMA[table][c]
            total += rows[table] * (4 if tp == "date" or "char" in tp
                                    else 8)
    return total
