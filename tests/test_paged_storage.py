"""Disk-backed paged columns (storage/paged.py): the larger-than-memory
scan path (reference: cop paging kv/kv.go:349-350 + chunk spill
util/chunk/disk.go — here a memmap-backed columnar layer whose scans
stream fixed-size pages through the device pipeline)."""

import numpy as np
import pytest

from tidb_tpu.storage.paged import (
    PagedTableWriter, chunk_is_paged, open_paged_columns)
from tidb_tpu.testkit import TestKit
from tidb_tpu.utils.chunk import LazyDictColumn

N = 9_000
PAGE = 2_000


@pytest.fixture(scope="module")
def tk(tmp_path_factory):
    tk = TestKit()
    tk.must_exec("use test")
    tk.must_exec("create table pg (k bigint, grp bigint, amount bigint, "
                 "price decimal(10,2), tag varchar(8))")
    tk.must_exec("create table ref (k bigint, grp bigint, amount bigint, "
                 "price decimal(10,2), tag varchar(8))")

    rng = np.random.default_rng(3)
    k = np.arange(1, N + 1, dtype=np.int64)
    grp = rng.integers(0, 7, N)
    amount = rng.integers(-50, 500, N)
    price = rng.integers(0, 100000, N)  # cents
    tags = [b"alpha", b"beta", b"gamma"]
    tag_codes = rng.integers(0, 3, N).astype(np.int32)

    root = tmp_path_factory.mktemp("paged") / "pg"
    info = tk.domain.infoschema().table_by_name("test", "pg")
    w = PagedTableWriter(str(root), info)
    w.set_dictionary("tag", tags)
    for lo in range(0, N, PAGE):  # multiple append calls = multiple pages
        hi = min(lo + PAGE, N)
        w.append({"k": k[lo:hi], "grp": grp[lo:hi],
                  "amount": amount[lo:hi], "price": price[lo:hi],
                  "tag": tag_codes[lo:hi]})
    columns, handles = w.finalize()
    tk.domain.columnar_cache.install_bulk(info, columns, handles)

    # reference table through the ordinary SQL write path
    rows = []
    for i in range(N):
        rows.append(f"({k[i]}, {grp[i]}, {amount[i]}, "
                    f"{price[i] / 100:.2f}, '{tags[tag_codes[i]].decode()}')")
    for lo in range(0, N, 3000):
        tk.must_exec("insert into ref values " + ",".join(rows[lo:lo + 3000]))
    tk._paged_root = str(root)
    tk._paged_info = info
    return tk


AGG = ("select grp, tag, count(*), sum(amount), min(amount), max(price), "
       "avg(price) from {t} where amount > 0 group by grp, tag "
       "order by grp, tag")


class TestPagedStorage:
    def test_columns_are_memmap_backed(self, tk):
        cols = open_paged_columns(tk._paged_root, tk._paged_info)
        kinds = {type(c).__name__ for c in cols.values()}
        assert "LazyDictColumn" in kinds
        for c in cols.values():
            if isinstance(c, LazyDictColumn):
                codes, uniques = c.dict_encode()
                assert isinstance(codes, np.memmap)
                assert c._mat is None  # nothing materialized yet
            else:
                assert isinstance(c.data, np.memmap)

    def test_device_stream_parity_with_sql_loaded_table(self, tk):
        tk.must_exec("set tidb_executor_engine = 'tpu'")
        tk.must_exec(f"set tidb_device_stream_rows = {PAGE}")
        dev = tk.must_query(AGG.format(t="pg")).rows
        tk.must_exec("set tidb_device_stream_rows = 0")
        tk.must_exec("set tidb_executor_engine = 'host'")
        host = tk.must_query(AGG.format(t="ref")).rows
        assert dev == host

    def test_host_path_reads_paged_table(self, tk):
        tk.must_exec("set tidb_executor_engine = 'host'")
        a = tk.must_query(AGG.format(t="pg")).rows
        b = tk.must_query(AGG.format(t="ref")).rows
        assert a == b

    def test_point_lookups_and_strings(self, tk):
        tk.must_exec("set tidb_executor_engine = 'host'")
        r = tk.must_query(
            "select tag, amount from pg where k = 17").rows
        s = tk.must_query(
            "select tag, amount from ref where k = 17").rows
        assert r == s

    def test_streaming_does_not_materialize_string_column(self, tk):
        """The device scan must read dictionary CODES from the memmap, never
        the object view (materializing 600M python bytes at SF100 is the
        exact failure this layer exists to prevent)."""
        cols = open_paged_columns(tk._paged_root, tk._paged_info)
        info = tk._paged_info
        tk.domain.columnar_cache.install_bulk(
            info, cols, np.arange(1, N + 1, dtype=np.int64))
        tk.must_exec("set tidb_executor_engine = 'tpu'")
        tk.must_exec(f"set tidb_device_stream_rows = {PAGE}")
        tk.must_query(AGG.format(t="pg"))
        tk.must_exec("set tidb_device_stream_rows = 0")
        lazy = [c for c in cols.values() if isinstance(c, LazyDictColumn)]
        assert lazy and all(c._mat is None for c in lazy)

    def test_ci_collation_streams_without_materializing(self, tk):
        """_ci group keys on a paged table go through the per-page remap
        view, not a table-sized ci_codes array."""
        tk.must_exec("create table pgci (g bigint, s varchar(8) collate "
                     "utf8mb4_general_ci)")
        info = tk.domain.infoschema().table_by_name("test", "pgci")
        import tempfile
        root = tempfile.mkdtemp() + "/pgci"
        w = PagedTableWriter(root, info)
        w.set_dictionary("s", [b"AA", b"aa", b"bb"])
        rng = np.random.default_rng(5)
        w.append({"g": rng.integers(0, 3, 6000),
                  "s": rng.integers(0, 3, 6000).astype(np.int32)})
        cols, handles = w.finalize()
        tk.domain.columnar_cache.install_bulk(info, cols, handles)
        tk.must_exec("set tidb_executor_engine = 'tpu'")
        tk.must_exec("set tidb_device_stream_rows = 1500")
        rows = tk.must_query(
            "select s, count(*) from pgci group by s order by s").rows
        tk.must_exec("set tidb_device_stream_rows = 0")
        # AA and aa collate equal → 2 classes
        assert len(rows) == 2
        sc = [c for c in cols.values() if isinstance(c, LazyDictColumn)][0]
        assert sc._mat is None
        from tidb_tpu.utils.chunk import _PageRemapCodes
        ci_codes, _kd, _reps = sc.dict_encode_ci("utf8mb4_general_ci")
        assert isinstance(ci_codes, _PageRemapCodes)

    def test_chunk_is_paged_detection(self, tk):
        from tidb_tpu.utils.chunk import Chunk
        cols = open_paged_columns(tk._paged_root, tk._paged_info)
        assert chunk_is_paged(Chunk(list(cols.values())))


@pytest.fixture(scope="module")
def tkj(tmp_path_factory):
    """Paged FACT table + resident dimension tables: the streamed-probe
    join path (device_join._paged_join_agg)."""
    tk = TestKit()
    tk.must_exec("use test")
    tk.must_exec("create table fact (fk bigint, dk bigint, v bigint)")
    tk.must_exec("create table reffact (fk bigint, dk bigint, v bigint)")
    tk.must_exec("create table dim (dk bigint, dname varchar(8), "
                 "region bigint)")
    tk.must_exec("create table dim2 (region bigint, rname varchar(8))")

    rng = np.random.default_rng(11)
    nf, nd = 12_000, 40
    fk = np.arange(1, nf + 1, dtype=np.int64)
    dk = rng.integers(1, nd + 1, nf)
    v = rng.integers(0, 1000, nf)

    root = tmp_path_factory.mktemp("pagedj") / "fact"
    info = tk.domain.infoschema().table_by_name("test", "fact")
    w = PagedTableWriter(str(root), info)
    for lo in range(0, nf, 2_500):
        hi = min(lo + 2_500, nf)
        w.append({"fk": fk[lo:hi], "dk": dk[lo:hi], "v": v[lo:hi]})
    columns, handles = w.finalize()
    tk.domain.columnar_cache.install_bulk(info, columns, handles)

    rows = [f"({fk[i]}, {dk[i]}, {v[i]})" for i in range(nf)]
    for lo in range(0, nf, 3000):
        tk.must_exec("insert into reffact values "
                     + ",".join(rows[lo:lo + 3000]))
    for d in range(1, nd + 1):
        tk.must_exec(f"insert into dim values ({d}, 'd{d % 7}', {d % 5})")
    for r in range(5):
        tk.must_exec(f"insert into dim2 values ({r}, 'r{r}')")
    for t in ("reffact", "dim", "dim2"):
        tk.must_exec(f"analyze table {t}")
    return tk


JOINQ = ("select dname, count(*), sum(v) from {f}, dim "
         "where {f}.dk = dim.dk and v > 100 group by dname order by dname")

JOIN2Q = ("select rname, count(*), sum(v), min(v) from {f}, dim, dim2 "
          "where {f}.dk = dim.dk and dim.region = dim2.region "
          "group by rname order by rname")


class TestPagedProbeJoin:
    def test_single_join_parity(self, tkj):
        tkj.must_exec("set tidb_executor_engine = 'tpu'")
        tkj.must_exec("set tidb_device_stream_rows = 2500")
        dev = tkj.must_query(JOINQ.format(f="fact")).rows
        tkj.must_exec("set tidb_device_stream_rows = 0")
        tkj.must_exec("set tidb_executor_engine = 'host'")
        host = tkj.must_query(JOINQ.format(f="reffact")).rows
        assert dev == host and len(dev) > 0

    def test_chain_join_parity(self, tkj):
        tkj.must_exec("set tidb_executor_engine = 'tpu'")
        tkj.must_exec("set tidb_device_stream_rows = 2500")
        dev = tkj.must_query(JOIN2Q.format(f="fact")).rows
        tkj.must_exec("set tidb_device_stream_rows = 0")
        tkj.must_exec("set tidb_executor_engine = 'host'")
        host = tkj.must_query(JOIN2Q.format(f="reffact")).rows
        assert dev == host and len(dev) > 0

    def test_odd_tail_page(self, tkj):
        """Page size that does not divide the row count: the padded tail
        page must not leak padding rows into the aggregate."""
        tkj.must_exec("set tidb_executor_engine = 'tpu'")
        tkj.must_exec("set tidb_device_stream_rows = 1700")
        dev = tkj.must_query(JOINQ.format(f="fact")).rows
        tkj.must_exec("set tidb_device_stream_rows = 0")
        tkj.must_exec("set tidb_executor_engine = 'host'")
        host = tkj.must_query(JOINQ.format(f="reffact")).rows
        assert dev == host

    def test_group_overflow_restarts_the_pass_and_answers_exactly(
            self, tkj, monkeypatch):
        """Grouped by the fact key (no statistics on the paged table: 64
        groups estimated, about 250 a page kept): the first pass overflows
        its per-page capacity, the pass restarts once at the observed
        size, the folded state outgrows merge_cap, and the answer is the
        host's.  The second execution starts from the learned capacity:
        no restart, nothing compiled.  (Key span 16,384 > 2,500 page
        rows: a numpy tail answered this on XLA:CPU until ISSUE 29.)"""
        from tidb_tpu.executor import device_exec, device_join
        sql = ("select fk, count(*), sum(v), max(v) from {f}, dim "
               "where {f}.dk = dim.dk and v > 900 group by fk order by fk")

        def no_host_fold(*a, **k):
            raise AssertionError("the paged join folded in numpy")
        monkeypatch.setattr(device_exec, "_merge_states_host", no_host_fold)
        capacities, merge_caps = [], []
        orig_compile = device_join.compile_fragment

        def spy_compile(root, leaves, joins, agg_plan, agg_conds, caps,
                        capacity, *a, **k):
            capacities.append(capacity)
            return orig_compile(root, leaves, joins, agg_plan, agg_conds,
                                caps, capacity, *a, **k)
        monkeypatch.setattr(device_join, "compile_fragment", spy_compile)
        orig_merge = device_exec.merge_partial_states

        def spy_merge(state, parts, merge_cap, *a):
            out, grown = orig_merge(state, parts, merge_cap, *a)
            merge_caps.append((merge_cap, grown))
            return out, grown
        monkeypatch.setattr(device_exec, "merge_partial_states", spy_merge)

        tkj.must_exec("set tidb_result_cache = 'OFF'")
        tkj.must_exec("set tidb_executor_engine = 'tpu'")
        tkj.must_exec("set tidb_device_stream_rows = 2500")
        first = tkj.must_query(sql.format(f="fact")).rows
        stats = dict(device_join.LAST_PAGED_STATS.items())
        restarts, folds = list(capacities), list(merge_caps)
        del capacities[:], merge_caps[:]
        again = tkj.must_query(sql.format(f="fact")).rows
        plan = tkj.must_query("explain analyze "
                              + sql.format(f="fact")).rows
        tkj.must_exec("set tidb_device_stream_rows = 0")
        tkj.must_exec("set tidb_executor_engine = 'host'")
        host = tkj.must_query(sql.format(f="reffact")).rows
        assert len(host) > 1000 and first == host and again == host
        assert "engine:tpu" in [p for r in plan for p in r[2].split(", ")]
        # one discovery restart: two programs, the second at the observed
        # per-page group count
        assert len(restarts) == 2 and restarts[0] < restarts[1], restarts
        assert stats["capacity"] == restarts[1] and stats["pages"] == 5
        assert stats["groups"] == len(host)
        assert any(grown > cap for cap, grown in folds), folds
        # learned: the second and third executions build nothing, and
        # their merge starts at the learned total
        assert capacities == []
        assert merge_caps and all(cap == grown >= len(host)
                                  for cap, grown in merge_caps)
