"""Driver benchmark: TPC-H north-star queries (Q1, Q3, Q5, Q9, Q18 — per
/root/repo/BASELINE.json and reference session/bench_test.go:117-361) through
the FULL SQL path — parse → plan → fused device kernels — on the real device,
vs the host (numpy) executor as the reference-CPU stand-in.

Prints ONE JSON line PER QUERY:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N, ...}

The first line, ``bench_backend``, names the device JAX found (platform,
``device_kind``, count).  A platform other than ``tpu`` exits non-zero —
the bench never selects the CPU after failing to find a chip.  The only
way to run it on XLA:CPU is for the CALLER to export ``JAX_PLATFORMS=cpu``
(tier-1's tests/test_bench_watchdog.py path); every line then says
``"platform": "cpu"``.  This process holds the chip from its first JAX
call; it spawns nothing that needs it.

Watchdog layering (innermost fires first; each outer layer covers the
failure mode the inner one cannot):
  1. device-runtime SUPERVISOR (tidb_tpu/executor/supervisor.py): each
     benchmarked query runs on a supervised worker thread under the
     BENCH_QUERY_TIMEOUT_S deadline — a backend hung inside a GIL-holding
     C call costs ONE query: the call is abandoned, an error JSON line is
     emitted, the backend is fenced, and the run continues on a fresh
     session.
  2. per-query SIGALRM (same budget + slack): catches a MAIN-thread stall
     outside the supervised body (datagen, host reference run) — only
     works while the GIL is droppable.
  3. global SIGALRM (BENCH_TIMEOUT_S): bounds the whole run.
"""

import json
import os
import signal
import sys
import threading
import time

import numpy as np

import tidb_tpu  # noqa: F401  (x64 on)

from tidb_tpu.testkit import TestKit
from tidb_tpu.utils.chunk import Column

_STAGE = ["start"]
_EMITTED = [0]
_COMPLETED = [0]


def _stage(msg: str) -> None:
    _STAGE[0] = msg
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


#: serializes JSONL emission AND the abandoned-flag handoff: an orphaned
#: supervised worker re-checks its query's abandoned flag under this lock
#: right before emitting, and the hang handler sets the flag under it —
#: so the stream can never carry both a hang record and a stale
#: provisional line for one query, nor interleaved partial lines
_EMIT_LOCK = threading.RLock()


def _emit(obj) -> None:
    with _EMIT_LOCK:
        _EMITTED[0] += 1
        print(json.dumps(obj), flush=True)


def _last_trace_text(conn_id=None, cap=4000) -> str:
    """The most recent finished query-lifecycle trace, rendered: a
    watchdog-skipped or failed query's error JSON line carries WHERE
    inside the query the time went (admission / compile / supervisor /
    backoff / dispatch).
    Empty when tracing was off (see BENCH_TRACE) or nothing finished."""
    from tidb_tpu.session import tracing
    return tracing.last_trace_text(conn_id, cap=cap)


# ---------------------------------------------------------------------------
# North-star queries (forms identical to the parity tests in test_tpch.py).

QUERIES = {
    "q1": """
select l_returnflag, l_linestatus,
       sum(l_quantity) as sum_qty,
       sum(l_extendedprice) as sum_base_price,
       sum(l_extendedprice * (1 - l_discount)) as sum_disc_price,
       sum(l_extendedprice * (1 - l_discount) * (1 + l_tax)) as sum_charge,
       avg(l_quantity) as avg_qty,
       avg(l_extendedprice) as avg_price,
       avg(l_discount) as avg_disc,
       count(1) as count_order
from lineitem
where l_shipdate <= '1998-09-02'
group by l_returnflag, l_linestatus
order by l_returnflag, l_linestatus
""",
    "q3": """
select l_orderkey, sum(l_extendedprice * (1 - l_discount)) as revenue,
       o_orderdate, o_shippriority
from customer, orders, lineitem
where c_mktsegment = 'BUILDING' and c_custkey = o_custkey
  and l_orderkey = o_orderkey and o_orderdate < '1995-03-15'
  and l_shipdate > '1995-03-15'
group by l_orderkey, o_orderdate, o_shippriority
order by revenue desc, o_orderdate limit 10
""",
    "q5": """
select n_name, sum(l_extendedprice * (1 - l_discount)) as revenue
from customer, orders, lineitem, supplier, nation, region
where c_custkey = o_custkey and l_orderkey = o_orderkey
  and l_suppkey = s_suppkey and c_nationkey = s_nationkey
  and s_nationkey = n_nationkey and n_regionkey = r_regionkey
  and r_name = 'ASIA' and o_orderdate >= '1994-01-01'
  and o_orderdate < '1995-01-01'
group by n_name order by revenue desc
""",
    "q9": """
select nationx, o_year, sum(amount) as sum_profit
from (select n_name as nationx, year(o_orderdate) as o_year,
             l_extendedprice * (1 - l_discount)
             - ps_supplycost * l_quantity as amount
      from part, supplier, lineitem, partsupp, orders, nation
      where s_suppkey = l_suppkey and ps_suppkey = l_suppkey
        and ps_partkey = l_partkey and p_partkey = l_partkey
        and o_orderkey = l_orderkey and s_nationkey = n_nationkey
        and p_name like '%green%'
     ) as profit
group by nationx, o_year order by nationx, o_year desc
""",
    "q18": """
select c_name, c_custkey, o_orderkey, o_orderdate, o_totalprice,
       sum(l_quantity)
from customer, orders, lineitem
where o_orderkey in (select l_orderkey from lineitem
                     group by l_orderkey
                     having sum(l_quantity) > 300)
  and c_custkey = o_custkey and o_orderkey = l_orderkey
group by c_name, c_custkey, o_orderkey, o_orderdate, o_totalprice
order by o_totalprice desc, o_orderdate limit 100
""",
}


class _PinnedTk:
    """TestKit view pinned to ONE session object.  The per-query closure
    runs on a supervised worker thread; when the supervisor abandons it,
    the loop swaps in a fresh session for the NEXT query — the orphan
    must keep talking to ITS session (via this pin), not race the new
    one through a live `tk.session` attribute read."""

    def __init__(self, tk):
        self.domain = tk.domain
        self.session = tk.session

    def must_exec(self, sql):
        results = self.session.execute(sql)
        return results[-1] if results else None

    def must_query(self, sql):
        from tidb_tpu.testkit import QueryResult
        return QueryResult(self.session.execute(sql)[-1])


class _QueryTimeout(Exception):
    """Raised by SIGALRM inside a query that exceeded its per-query
    budget — the bench SKIPS that query and continues, instead of the
    whole run dying and losing every query after it."""


#: per-query watchdog state shared with the SIGALRM handler:
#: _QUERY_GUARD flags that an alarm should raise (skip one query) rather
#: than emit-and-exit (global watchdog); _ALARM_READY gates arming on the
#: handler actually being installed (a test calling _bench_loop without
#: main()'s signal setup must not arm SIGALRM's default action).
_QUERY_GUARD = [False]
_ALARM_READY = [False]
_GLOBAL_DEADLINE = [0.0]


def _arm_query_alarm(budget_s: int):
    """Start the per-query deadline. Best effort: SIGALRM only interrupts
    Python-level waits — a backend call blocked inside C holding the GIL
    is the supervisor's (layer 1) to abandon."""
    if budget_s <= 0 or not _ALARM_READY[0]:
        return
    remaining = (_GLOBAL_DEADLINE[0] - time.time()
                 if _GLOBAL_DEADLINE[0] else budget_s)
    _QUERY_GUARD[0] = True
    signal.alarm(max(1, int(min(budget_s, max(remaining, 1)))))


def _disarm_query_alarm():
    if not _ALARM_READY[0]:
        return
    _QUERY_GUARD[0] = False
    if _GLOBAL_DEADLINE[0]:
        signal.alarm(max(1, int(_GLOBAL_DEADLINE[0] - time.time())))
    else:
        signal.alarm(0)


# ---------------------------------------------------------------------------
# Data generators: synthetic TPC-H-shaped data, bulk-installed through the
# Lightning-role columnar loader (no per-row encode). Shapes/distributions
# follow dbgen; keys are dense 1..N so every FK join finds its match.

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

_EPOCH = np.datetime64("1970-01-01")


def _days(date_str):
    return int((np.datetime64(date_str) - _EPOCH).astype(int))


def _dict_col(codes, dictionary, ft):
    """Dict-encoded string Column. set_dict requires sorted uniques."""
    n = len(codes)
    arr = np.asarray(dictionary, dtype=object)
    order = np.argsort(arr)
    remap = np.empty(len(arr), dtype=np.int64)
    remap[order] = np.arange(len(arr))
    c = Column(ft, arr[codes], np.zeros(n, dtype=bool))
    c.set_dict(remap[codes].astype(np.int32), arr[order])
    return c


def _install(tk, table, data, n):
    """data values: numeric np array, Column, or a (codes, dictionary)
    tuple for a dict-encoded string column. Installs via the bulk loader."""
    info = tk.domain.infoschema().table_by_name("tpch", table)
    cols = {c.name: c for c in info.public_columns()}
    z = np.zeros(n, dtype=bool)
    columns = {}
    for name, arr in data.items():
        c = cols[name]
        if isinstance(arr, Column):
            columns[c.id] = arr
        elif isinstance(arr, tuple):
            codes, dictionary = arr
            columns[c.id] = _dict_col(codes, dictionary, c.ftype)
        else:
            columns[c.id] = Column(c.ftype, arr, z)
    # the content tag makes the fixed-seeded generator's determinism an
    # EXPLICIT declaration: (table, row count, generator version) is the
    # content identity the fleet result cache keys bulk data under
    tk.domain.columnar_cache.install_bulk(
        info, columns, np.arange(1, n + 1, dtype=np.int64),
        content_tag=f"bench.gen_all/{table}/n{n}/v1")


def gen_all(tk, sf: float):
    """Generate the 8-table TPC-H-shaped dataset at scale factor `sf`."""
    rng = np.random.default_rng(42)
    n_line = int(6_001_215 * sf)
    n_orders = max(int(1_500_000 * sf), 2)
    n_cust = max(int(150_000 * sf), 2)
    n_supp = max(int(10_000 * sf), 4)
    n_part = max(int(200_000 * sf), 4)
    supp_stride = max(n_supp // 4, 1)

    tk.must_exec("create database if not exists tpch")
    tk.must_exec("use tpch")
    # a fleet worker over the durable shared store replays the seeding
    # worker's schema/stats/nation rows from the log (they are KV-backed)
    # and must only rebuild the PROCESS-LOCAL bulk columnar installs —
    # the generator is fixed-seeded, so every worker installs identical
    # columns (the content-hash dedup property)
    fresh = not tk.domain.infoschema().has_table("tpch", "lineitem")
    if fresh:
        tk.must_exec("""
        create table lineitem (
            l_orderkey bigint, l_partkey bigint, l_suppkey bigint,
            l_quantity decimal(15,2),
            l_extendedprice decimal(15,2), l_discount decimal(15,2),
            l_tax decimal(15,2), l_returnflag varchar(1),
            l_linestatus varchar(1), l_shipdate date)""")
        tk.must_exec("""
        create table orders (
            o_orderkey bigint primary key, o_custkey bigint,
            o_orderdate date,
            o_shippriority bigint, o_totalprice decimal(15,2))""")
        tk.must_exec("""
        create table customer (
            c_custkey bigint primary key, c_name varchar(25),
            c_mktsegment varchar(10), c_nationkey bigint)""")
        tk.must_exec("""
        create table supplier (
            s_suppkey bigint primary key, s_nationkey bigint)""")
        tk.must_exec("""
        create table part (
            p_partkey bigint primary key, p_name varchar(55))""")
        tk.must_exec("""
        create table partsupp (
            ps_partkey bigint, ps_suppkey bigint,
            ps_supplycost decimal(15,2))""")
        tk.must_exec("""
        create table nation (
            n_nationkey bigint primary key, n_name varchar(25),
            n_regionkey bigint)""")
        tk.must_exec("""
        create table region (
            r_regionkey bigint primary key, r_name varchar(25))""")

    # Paged generation (disk-backed memmap columns) for the big tables at
    # sf >= 5 or BENCH_PAGED=1: the generator writes page batches straight
    # to column files — neither datagen nor the scans ever hold a big
    # table's columns resident (SF100 lineitem is ~41GB of columns).
    paged = os.environ.get("BENCH_PAGED") == "1" or sf >= 5
    # one pdir for the paged column files AND the stats cache below — a
    # divergence would pair stats with the wrong dataset
    pdir = os.environ.get("BENCH_PAGED_DIR", os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "bench_paged"))

    def _paged_table(table, n_rows, dicts, gen_page):
        from tidb_tpu.storage.paged import (
            DEFAULT_PAGE_ROWS, PagedTableWriter, open_paged_columns)
        from tidb_tpu.storage.paged import LazyRangeHandles
        info = tk.domain.infoschema().table_by_name("tpch", table)
        root = os.path.join(pdir, f"sf{sf:g}", table)
        manifest = os.path.join(root, "MANIFEST.json")
        if os.path.exists(manifest):  # reuse across bench runs
            cols = open_paged_columns(root, info)
            if len(next(iter(cols.values()))) == n_rows:
                tk.domain.columnar_cache.install_bulk(
                    info, cols, LazyRangeHandles(n_rows),
                    content_tag=f"bench.gen_all/{table}/n{n_rows}/v1")
                return
            # stale cache: drop the manifest FIRST so a crash mid-rewrite
            # can't leave a valid manifest over truncated column files
            os.remove(manifest)
        w = PagedTableWriter(root, info)
        for name, d in dicts.items():
            w.set_dictionary(name, d)
        name2id = {c.name: c.id for c in info.public_columns()}
        for pi, lo in enumerate(range(0, n_rows, DEFAULT_PAGE_ROWS)):
            m = min(DEFAULT_PAGE_ROWS, n_rows - lo)
            w.append(gen_page(pi, lo, m))
        cols, handles = w.finalize()
        assert set(cols) <= set(name2id.values())
        tk.domain.columnar_cache.install_bulk(
            info, cols, handles,
            content_tag=f"bench.gen_all/{table}/n{n_rows}/v1")

    # --- lineitem -----------------------------------------------------
    _stage(f"generating lineitem ({n_line} rows, paged={paged})")

    def _line_page(pi, lo, m):
        prng = np.random.default_rng((42, pi))
        partkey = prng.integers(1, n_part + 1, m)
        supp_slot = prng.integers(0, 4, m)
        return {
            "l_orderkey": prng.integers(1, n_orders + 1, m),
            "l_partkey": partkey,
            "l_suppkey": (partkey - 1 + supp_slot * supp_stride) % n_supp + 1,
            "l_quantity": prng.integers(1, 51, m) * 100,
            "l_extendedprice": prng.integers(900_00, 105_000_00, m),
            "l_discount": prng.integers(0, 11, m),
            "l_tax": prng.integers(0, 9, m),
            "l_shipdate": prng.integers(_days("1992-01-01"),
                                        _days("1998-12-01"), m).astype(np.int32),
            "l_returnflag": prng.integers(0, 3, m).astype(np.int32),
            "l_linestatus": prng.integers(0, 2, m).astype(np.int32),
        }

    if paged:
        _paged_table("lineitem", n_line,
                     {"l_returnflag": [b"A", b"N", b"R"],
                      "l_linestatus": [b"F", b"O"]}, _line_page)
    else:
        orderkey = rng.integers(1, n_orders + 1, n_line)
        partkey = rng.integers(1, n_part + 1, n_line)
        # one of each part's 4 partsupp suppliers, so the Q9 join always hits
        supp_slot = rng.integers(0, 4, n_line)
        suppkey = (partkey - 1 + supp_slot * supp_stride) % n_supp + 1
        qty = rng.integers(1, 51, n_line) * 100              # 1.00-50.00
        price = rng.integers(900_00, 105_000_00, n_line)     # ~dbgen prices
        disc = rng.integers(0, 11, n_line)                   # 0.00-0.10
        tax = rng.integers(0, 9, n_line)                     # 0.00-0.08
        shipdate = rng.integers(_days("1992-01-01"), _days("1998-12-01"),
                                n_line).astype(np.int32)
        flag_codes = rng.integers(0, 3, n_line).astype(np.int32)
        status_codes = rng.integers(0, 2, n_line).astype(np.int32)
        _install(tk, "lineitem", {
            "l_orderkey": orderkey, "l_partkey": partkey,
            "l_suppkey": suppkey,
            "l_quantity": qty, "l_extendedprice": price, "l_discount": disc,
            "l_tax": tax, "l_shipdate": shipdate,
            "l_returnflag": (flag_codes, [b"A", b"N", b"R"]),
            "l_linestatus": (status_codes, [b"F", b"O"]),
        }, n_line)

    # --- orders / customer -------------------------------------------
    _stage(f"generating orders ({n_orders}) + customer ({n_cust})")
    rng2 = np.random.default_rng(7)

    def _orders_page(pi, lo, m):
        prng = np.random.default_rng((7, pi))
        return {
            "o_orderkey": np.arange(lo + 1, lo + m + 1, dtype=np.int64),
            "o_custkey": prng.integers(1, n_cust + 1, m),
            "o_orderdate": prng.integers(_days("1992-01-01"),
                                         _days("1998-08-02"), m).astype(np.int32),
            "o_shippriority": np.zeros(m, dtype=np.int64),
            "o_totalprice": prng.integers(1000_00, 400_000_00, m),
        }

    if paged:
        _paged_table("orders", n_orders, {}, _orders_page)
    else:
        _install(tk, "orders", {
            "o_orderkey": np.arange(1, n_orders + 1),
            "o_custkey": rng2.integers(1, n_cust + 1, n_orders),
            "o_orderdate": rng2.integers(_days("1992-01-01"),
                                         _days("1998-08-02"),
                                         n_orders).astype(np.int32),
            "o_shippriority": np.zeros(n_orders, dtype=np.int64),
            "o_totalprice": rng2.integers(1000_00, 400_000_00, n_orders),
        }, n_orders)

    cname = np.array([f"Customer#{i:09d}".encode() for i in
                      range(1, n_cust + 1)], dtype=object)
    _install(tk, "customer", {
        "c_custkey": np.arange(1, n_cust + 1),
        "c_name": (np.arange(n_cust, dtype=np.int32), list(cname)),
        "c_mktsegment": (rng2.integers(0, 5, n_cust).astype(np.int32),
                         SEGMENTS),
        "c_nationkey": rng2.integers(0, 25, n_cust),
    }, n_cust)

    # --- supplier / part / partsupp ----------------------------------
    _stage(f"generating supplier ({n_supp}) / part ({n_part}) / partsupp")
    _install(tk, "supplier", {
        "s_suppkey": np.arange(1, n_supp + 1),
        "s_nationkey": rng2.integers(0, 25, n_supp),
    }, n_supp)

    colors = [b"almond", b"green", b"blue", b"red", b"ivory", b"khaki",
              b"lemon", b"linen", b"navy", b"olive", b"orchid", b"peach",
              b"plum", b"puff", b"rose", b"salmon", b"sienna", b"snow"]
    pcodes = rng2.integers(0, len(colors), n_part).astype(np.int32)
    pdict = [c + b" anodized thing" for c in colors]
    _install(tk, "part", {
        "p_partkey": np.arange(1, n_part + 1),
        "p_name": (pcodes, pdict),
    }, n_part)

    n_ps = n_part * 4
    ps_part = np.repeat(np.arange(1, n_part + 1), 4)
    ps_slot = np.tile(np.arange(4), n_part)
    _install(tk, "partsupp", {
        "ps_partkey": ps_part,
        "ps_suppkey": (ps_part - 1 + ps_slot * supp_stride) % n_supp + 1,
        "ps_supplycost": rng2.integers(1_00, 1000_00, n_ps),
    }, n_ps)

    # --- nation / region (tiny: regular INSERT path — KV-backed, so a
    #     fleet replica replays them instead of re-inserting) ---------
    if fresh:
        for i, (nm, rk) in enumerate(NATIONS):
            tk.must_exec(f"insert into nation values ({i}, '{nm}', {rk})")
        for i, r in enumerate(REGIONS):
            tk.must_exec(f"insert into region values ({i}, '{r}')")

    # stats for the CBO: join order at SF>=1 must come from real NDVs,
    # not pseudo guesses (the reference benches against analyzed tables;
    # without this, Q5's greedy order starts from the nationkey join and
    # builds a >2x-lineitem intermediate)
    tables = ("lineitem", "orders", "customer", "supplier", "part",
              "partsupp", "nation", "region")
    if not fresh:
        # the seeding worker's ANALYZE wrote the stats blobs to meta —
        # replayed from the log; just warm this domain's stats dict
        tk.domain.load_stats()
        return n_line
    stats_cache = (os.path.join(pdir, f"sf{sf:g}", "_stats.json")
                   if paged else None)
    _STATS_CACHE_VERSION = 1  # bump when the analyze.py blob format moves
    saved = None
    if stats_cache and os.path.exists(stats_cache):
        with open(stats_cache) as f:
            saved = json.load(f)
        if (saved.get("_version") != _STATS_CACHE_VERSION
                or saved.get("_n_line") != n_line):
            saved = None  # format moved or dataset re-scaled: re-analyze
    if saved is not None:
        # block-sampled ANALYZE over the SF100 paged tables costs ~7min
        # per bench invocation and the data is deterministic per
        # (sf, seed) — install the saved stats instead (the same
        # mechanics as statistics/analyze.py's Meta.set_stats tail)
        _stage("installing cached table stats")
        from tidb_tpu.meta import Meta
        for t in tables:
            info = tk.domain.infoschema().table_by_name("tpch", t)
            st = saved["tables"].get(t)
            # catalog-id drift check: a bootstrap/DDL change can reassign
            # column ids, and silently mis-keyed stats would steer the
            # CBO into the bad join orders this ANALYZE step exists to
            # prevent
            if st is None or not set(st.get("columns", {})) <= {
                    str(c.id) for c in info.public_columns()}:
                tk.must_exec(f"analyze table {t}")
                continue
            txn = tk.session.store.begin()
            try:
                Meta(txn).set_stats(info.id, st)
                txn.commit()
            except Exception:
                txn.rollback()
                raise
            tk.domain.stats[info.id] = st
        tk.domain.stats_version += 1
    else:
        _stage("analyze tables")
        for t in tables:
            tk.must_exec(f"analyze table {t}")
        if stats_cache:
            blob = {"_version": _STATS_CACHE_VERSION, "_n_line": n_line,
                    "tables": {}}
            for t in tables:
                info = tk.domain.infoschema().table_by_name("tpch", t)
                st = tk.domain.stats.get(info.id)
                if st is not None:
                    blob["tables"][t] = st
            tmp = stats_cache + ".tmp"
            with open(tmp, "w") as f:
                json.dump(blob, f)
            os.replace(tmp, stats_cache)
    return n_line


def time_query(tk, sql, repeats=3):
    best = float("inf")
    rows = None
    for _ in range(repeats):
        t0 = time.perf_counter()
        rows = tk.must_query(sql).rows
        best = min(best, time.perf_counter() - t0)
    return best, rows


def _peak_rss_mb() -> int:
    import resource
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss // 1024


def main():
    watchdog_s = int(os.environ.get("BENCH_TIMEOUT_S", "2700"))

    def _on_alarm(signum, frame):
        global_up = (_GLOBAL_DEADLINE[0]
                     and time.time() >= _GLOBAL_DEADLINE[0] - 1)
        if _QUERY_GUARD[0] and not global_up:
            # per-query deadline: skip THIS query, keep the run alive.
            # The global deadline always wins — an expiry mid-query must
            # still emit the tpch_bench_watchdog line and exit, not be
            # laundered into an endless chain of per-query skips.
            _QUERY_GUARD[0] = False
            raise _QueryTimeout(
                f"per-query watchdog fired (stage: {_STAGE[0]})")
        _emit({"metric": "tpch_bench_watchdog", "value": _COMPLETED[0],
               "unit": "queries_completed", "vs_baseline": 0,
               "error": f"watchdog after {watchdog_s}s",
               "stage": _STAGE[0]})
        os._exit(1)

    signal.signal(signal.SIGALRM, _on_alarm)
    signal.alarm(watchdog_s)
    _ALARM_READY[0] = True
    _GLOBAL_DEADLINE[0] = time.time() + watchdog_s

    # take the device HERE, once, early — and say which one it is.  JAX
    # itself falls back to its CPU client when it finds no accelerator;
    # that is only acceptable when the caller asked for the CPU.
    from tidb_tpu.ops import residency
    _stage("initializing the JAX backend")
    t0 = time.perf_counter()
    backend = residency.backend_report()
    backend["init_s"] = round(time.perf_counter() - t0, 1)
    platform = backend["platform"]
    cpu_asked = os.environ.get("JAX_PLATFORMS") == "cpu"
    ok = platform == "tpu" or (platform == "cpu" and cpu_asked)
    _stage(f"backend: {platform} ({backend['device_kind']} x "
           f"{backend['device_count']})")
    _emit({"metric": "bench_backend", "value": int(platform == "tpu"),
           "unit": "device_up", "vs_baseline": int(platform == "tpu"),
           **backend})
    if not ok:
        signal.alarm(0)
        raise SystemExit(
            f"bench: JAX found platform {platform!r}, not 'tpu' — no "
            "accelerator is attached to this process (or another process "
            "holds it). Export JAX_PLATFORMS=cpu to run the CPU smoke "
            "deliberately.")

    # SF1 by default; per-query lines stream out as they complete. SF10 is
    # one BENCH_SF=10 away.
    sf = float(os.environ.get("BENCH_SF", "1"))
    qnames = [q.strip().lower() for q in os.environ.get(
        "BENCH_QUERIES", "q1,q3,q5,q9,q18").split(",") if q.strip()]
    unknown = [q for q in qnames if q not in QUERIES]
    if unknown:
        raise SystemExit(f"unknown BENCH_QUERIES entries: {unknown}; "
                         f"valid: {sorted(QUERIES)}")

    tk = TestKit()
    # the bench measures engine throughput, not quota governance: lift the
    # per-statement memory quota so the host-reference run at SF>=1 isn't
    # cancelled by the OOM action
    tk.must_exec("set tidb_mem_quota_query = 0")
    n = gen_all(tk, sf)

    meta = {"platform": platform, "device_kind": backend["device_kind"],
            "device_count": backend["device_count"], "sf": sf}
    # --mem-budget=BYTES (or BENCH_MEM_BUDGET): the memory-constrained
    # mode — cap tidb_device_mem_budget so oversized build sides go
    # through the hybrid hash join (radix spill + host/device
    # co-processing) instead of degrading the whole fragment to host;
    # per-query lines then carry the hj_* gauges
    mem_budget = 0
    for a in sys.argv[1:]:
        if a.startswith("--mem-budget="):
            mem_budget = int(float(a.split("=", 1)[1]))
    env_budget = os.environ.get("BENCH_MEM_BUDGET", "").strip()
    if env_budget:  # an exported-but-empty var must not discard the flag
        mem_budget = int(float(env_budget))
    if mem_budget > 0:
        tk.must_exec(f"set global tidb_device_mem_budget = {mem_budget}")
        meta["mem_budget"] = mem_budget
        _stage(f"memory-constrained mode: device budget {mem_budget} B")
    qbudget = int(os.environ.get("BENCH_QUERY_TIMEOUT_S", "900"))
    failures = _bench_loop(tk, qnames, sf, n, meta, query_budget_s=qbudget)

    signal.alarm(0)
    _ALARM_READY[0] = False
    if failures:
        sys.exit(1)


def _bench_loop(tk, qnames, sf, n, meta, query_budget_s=0) -> int:
    """Per-query benchmark loop with a per-QUERY watchdog: a hung
    backend call, a refused compile, or an injected failure
    (BENCH_FAIL_QUERY=q3 — the chaos hook) costs only that query — an
    error JSON line is emitted and the run continues with the next one,
    instead of one bad query losing everything after it. Returns the
    failure count.

    compile_s is MEASURED engine compile time (device_exec
    pipe_cache_stats: wall seconds of dispatches that triggered an XLA
    trace) during the warmup run, no longer the warmup-minus-steady
    difference; warm_compile_s is the same meter over the timed runs —
    ~0 when the compiled-fragment cache and shape buckets are doing
    their job."""
    from tidb_tpu.errors import DeviceHangError
    from tidb_tpu.executor import supervisor as _sup
    from tidb_tpu.executor.device_exec import pipe_cache_stats
    inject = set(q.strip().lower() for q in
                 os.environ.get("BENCH_FAIL_QUERY", "").split(",")
                 if q.strip())
    # span tracing OPT-IN (BENCH_TRACE=1): with it on, a failed/skipped
    # query's error line carries its full trace — set it on chip runs,
    # where the post-mortem matters.  Default OFF: sampling also wires a
    # per-operator runtime-stats collector through every traced query,
    # and vs_baseline must stay comparable with the pre-tracing rounds
    # (same rule as bench_serve.py's p99s)
    if os.environ.get("BENCH_TRACE", "") == "1":
        tk.must_exec("set tidb_trace_sampling_rate = 1")
    failures = 0
    for qname in qnames:
        sql = QUERIES[qname]
        _stage(f"{qname}: begin")
        r = {}
        qtk = _PinnedTk(tk)  # this query's session, pinned for its worker

        def _one(tk=qtk, qname=qname, sql=sql, r=r):
            """The whole per-query measurement, run on a SUPERVISED worker
            thread (layer 1) so a GIL-blocked backend call costs this one
            query. Results land in `r`; `r['host_skip']` replaces the old
            inline `continue`.  EVERY loop variable is pinned via default
            args (like the session): an abandoned worker that unblocks
            after the loop advanced must write its stale results into ITS
            OWN r/qname, never the next query's bindings."""
            def stage(msg):
                # an orphan's stage updates must not overwrite the LIVE
                # query's _STAGE — watchdog lines would blame the wrong
                # stage in exactly the triage path this stack serves
                if not r.get("abandoned"):
                    _stage(msg)
            if qname in inject:
                raise RuntimeError(
                    f"injected backend failure for {qname} "
                    "(BENCH_FAIL_QUERY)")
            from tidb_tpu.executor import hybrid_join as _hj0
            hj_runs0 = _hj0.STATS["hj_runs"]
            stage(f"{qname}: device warmup (compile + materialize)")
            tk.must_exec("set tidb_executor_engine = 'tpu'")
            st0 = pipe_cache_stats(thread_local=True)
            # process-wide snapshot for the per-query bg delta (the bg
            # meter lives on worker threads, so the thread-local view
            # above never sees it)
            bg0 = pipe_cache_stats()["bg_compile_s"]
            # two warmup runs, timed SEPARATELY: warm_t is the FIRST
            # (cold) run so warmup_minus_steady_s keeps its historical
            # meaning; the second run absorbs the learned-size
            # shrink-to-fit recompile (device_join _CAP_STORE) so the
            # timed window measures pure dispatch
            warm_t, _rows = time_query(tk, sql, repeats=1)
            time_query(tk, sql, repeats=1)
            st1 = pipe_cache_stats(thread_local=True)
            stage(f"{qname}: device timed runs")
            dev_t, dev_rows = time_query(tk, sql, repeats=2)
            st2 = pipe_cache_stats(thread_local=True)
            compile_cold = st1["compile_s"] - st0["compile_s"]
            compile_warm = st2["compile_s"] - st1["compile_s"]
            compile_info = {
                "compile_s": round(compile_cold, 4),
                "warm_compile_s": round(compile_warm, 4),
                "warmup_minus_steady_s": round(max(warm_t - dev_t, 0.0), 4),
                "xla_compiles": st2["compiles"] - st0["compiles"],
                # compile attribution split (executor/compile_service.py):
                # sync_compile_s is what THIS query's dispatches paid on
                # the query path (the thread-local meter above);
                # bg_compile_s is this query's window of the process-wide
                # background-worker meter — compile work the host-first
                # serving kept OFF the query path (wall-clock = execute +
                # sync_compile, with bg_compile overlapped).
                "sync_compile_s": round(compile_cold + compile_warm, 4),
                "bg_compile_s": round(
                    pipe_cache_stats()["bg_compile_s"] - bg0, 4),
            }
            # compile-service gauges: pending fragments / persistent-index
            # hits / prewarm counts once they fired — a bench line whose
            # first run was host-served says so
            from tidb_tpu.executor import compile_service as _csvc
            compile_info.update(_csvc.report_gauges())
            # HBM residency (ops/residency.py): cached-bytes ledger after
            # the timed runs; eviction/OOM counters only when they fired —
            # a bench line that ran under device-memory pressure says so
            from tidb_tpu.ops import residency as _res
            compile_info.update(_res.report_gauges())
            # MPP mesh gauges (executor/mpp_exec.py): placement-cache
            # bytes + fragment/retry counters once the mesh path has run
            # — a bench line that paid an exchange recompile says so
            from tidb_tpu.executor import mpp_exec as _mpp
            compile_info.update(_mpp.report_gauges())
            # hybrid hash join gauges (executor/hybrid_join.py): fanout /
            # spilled partitions / spill bytes / co-processed host rows —
            # only when THIS query's runs took the hybrid path (another
            # query's split on this line would misattribute the spill)
            from tidb_tpu.executor import hybrid_join as _hj
            if _hj.STATS["hj_runs"] > hj_runs0:
                compile_info.update(_hj.report_gauges())
            r["dev"] = (dev_t, dev_rows)
            r["compile_info"] = compile_info

            host_skip = (os.environ.get("BENCH_HOST_SKIP") == "1"
                         or sf >= 50)
            if sf >= 10 or host_skip:
                # the host (numpy) reference engine is the memory limiter
                # at this scale — its join intermediates can OOM-kill the
                # process (observed: Q9 SF10). Emit the measured device
                # number FIRST so a host-side death can't erase it. The
                # abandoned re-check happens INSIDE the emit lock (the
                # hang handler sets the flag under the same lock), so an
                # orphan can never race a stale provisional line past it.
                with _EMIT_LOCK:
                    if r.get("abandoned"):
                        return
                    _emit({
                        "metric":
                            f"tpch_{qname}_sf{sf:g}_device_provisional",
                        "value": round(n / dev_t),
                        "unit": "lineitem_rows/s", "vs_baseline": 0,
                        "device_s": round(dev_t, 4),
                        **compile_info,
                        "host_pending": True,
                        "peak_rss_mb": _peak_rss_mb(), **meta,
                    })

            if host_skip:
                # the single-threaded numpy reference cannot execute at
                # SF100 in any useful time; the provisional device line
                # above is the recorded number
                r["host_skip"] = True

        try:
            # SIGALRM (layer 2) arms with slack so the supervisor (layer
            # 1, able to interrupt even a GIL-blocked backend wait) fires
            # first; the alarm still covers main-thread stalls
            _arm_query_alarm(query_budget_s + 30 if query_budget_s else 0)
            if query_budget_s > 0:
                _sup.supervised_call(_one, deadline_s=query_budget_s,
                                     label=f"bench:{qname}")
            else:
                _one()
            if not r.get("host_skip"):
                # the host (numpy) reference runs on the MAIN thread,
                # outside the supervised body: a slow host run is a
                # SIGALRM _QueryTimeout skip (layer 2), never a false
                # "backend hang" that would fence a healthy device
                _stage(f"{qname}: host reference run")
                tk.must_exec("set tidb_executor_engine = 'host'")
                r["host"] = time_query(tk, sql, repeats=1)
        except DeviceHangError as exc:
            _disarm_query_alarm()
            with _EMIT_LOCK:
                r["abandoned"] = True  # gates the orphan's late _emit
            failures += 1
            _emit({"metric": f"tpch_{qname}_sf{sf:g}", "value": 0,
                   "unit": "rows/s", "vs_baseline": 0,
                   "error": f"{type(exc).__name__}: {exc}"[:300],
                   "skipped_by_watchdog": True, "watchdog": "supervisor",
                   "abandoned_calls": _sup.abandoned_calls(),
                   "trace": _last_trace_text(),
                   "stage": _STAGE[0], **meta})
            # the abandoned worker may still be executing against its
            # (pinned) session; kill the CONNECTION so its remaining
            # statements are refused, swap in a fresh session for later
            # queries
            try:
                from tidb_tpu.session import new_session
                tk.session.kill(query_only=False)
                tk.session = new_session(tk.domain)
                tk.must_exec("use tpch")
                tk.must_exec("set tidb_mem_quota_query = 0")
            except Exception as rexc:  # noqa: BLE001
                # recovery failed with the killed session still installed:
                # say so — otherwise every later query fails with refused
                # statements and no explanation (the exact silent-cascade
                # mode this watchdog exists to prevent)
                _stage(f"{qname}: session recovery after hang FAILED "
                       f"({type(rexc).__name__}: {rexc}); later queries "
                       "may be refused")
            continue
        except _QueryTimeout as exc:
            # also catches an alarm landing in the handler below or in
            # the post-try tail: wherever the one-shot SIGALRM fires, it
            # costs THIS query only
            _disarm_query_alarm()
            failures += 1
            _emit({"metric": f"tpch_{qname}_sf{sf:g}", "value": 0,
                   "unit": "rows/s", "vs_baseline": 0,
                   "error": f"{type(exc).__name__}: {exc}"[:300],
                   "skipped_by_watchdog": True,
                   "trace": _last_trace_text(),
                   "stage": _STAGE[0], **meta})
            continue
        except Exception as exc:
            # cancel the pending per-query alarm FIRST: it firing inside
            # this handler would escape the loop and lose every query
            # after this one (the exact failure the watchdog prevents)
            _disarm_query_alarm()
            failures += 1
            _emit({"metric": f"tpch_{qname}_sf{sf:g}", "value": 0,
                   "unit": "rows/s", "vs_baseline": 0,
                   "error": f"{type(exc).__name__}: {exc}"[:300],
                   "skipped_by_watchdog": False,
                   "trace": _last_trace_text(),
                   "stage": _STAGE[0], **meta})
            continue
        finally:
            _disarm_query_alarm()

        if r.get("host_skip"):
            _COMPLETED[0] += 1
            continue
        dev_t, dev_rows = r["dev"]
        host_t, host_rows = r["host"]
        compile_info = r["compile_info"]
        if dev_rows != host_rows:
            failures += 1
            _emit({"metric": f"tpch_{qname}_sf{sf:g}_parity", "value": 0,
                   "unit": "bool", "vs_baseline": 0, **meta})
            continue

        _COMPLETED[0] += 1
        _emit({
            "metric": f"tpch_{qname}_sf{sf:g}_device_rows_per_sec",
            "value": round(n / dev_t),
            "unit": "lineitem_rows/s",
            "vs_baseline": round(host_t / dev_t, 3),
            "device_s": round(dev_t, 4),
            "host_s": round(host_t, 4),
            # engine-measured compile seconds (cold vs warm) — the split
            # r03 lacked, which hid where the device seconds went
            **compile_info,
            "peak_rss_mb": _peak_rss_mb(),
            **meta,
        })
    return failures


if __name__ == "__main__":
    try:
        main()
    except SystemExit:
        raise
    except BaseException as exc:  # guarantee one JSON line, whatever happens
        _emit({"metric": "tpch_bench", "value": 0, "unit": "rows/s",
               "vs_baseline": 0, "error": f"{type(exc).__name__}: {exc}",
               "stage": _STAGE[0]})
        sys.exit(1)
