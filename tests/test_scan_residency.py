"""Resident or streamed, from bytes (ISSUE 27): a scan-aggregate's input
stays in HBM when its used columns and the program's working set fit the
residency budget, and streams in blocks when it is paged or does not
fit; the choice is counted, and the streamed path's transfers and fetches
are under spans."""

import json

import numpy as np
import pytest

from tidb_tpu.executor import device_exec
from tidb_tpu.ops import device as dev
from tidb_tpu.ops import residency
from tidb_tpu.session import tracing
from tidb_tpu.sqltypes import FieldType, TYPE_LONGLONG
from tidb_tpu.storage.paged import DEFAULT_PAGE_ROWS
from tidb_tpu.testkit import TestKit
from tidb_tpu.utils.chunk import Column

GB = 1 << 30
#: TPC-H SF10 lineitem in its row bucket
SF10_ROWS = 59_986_052
SF10_BUCKET = dev.bucket_rows(SF10_ROWS, 2)


def _cols(*dtypes):
    """One-row stand-ins: upload_nbytes reads widths, not rows."""
    out = []
    for dt in dtypes:
        data = (np.array([b"x"], dtype=object) if dt is object
                else np.zeros(1, dtype=dt))
        out.append(Column(FieldType(tp=TYPE_LONGLONG), data))
    return out


#: Q1's seven lineitem columns: four decimals, two flags, one date
Q1_COLS = _cols(np.int64, np.int64, np.int64, np.int64, object, object,
                np.int32)


# -- the pure function --------------------------------------------------------

def test_bytes_are_what_to_device_col_uploads():
    assert SF10_BUCKET == 67_108_864
    assert residency.upload_nbytes(Q1_COLS, 1) == 4 * 9 + 3 * 5
    assert residency.upload_nbytes(Q1_COLS, SF10_BUCKET) == 51 * SF10_BUCKET
    tk = TestKit()
    tk.must_exec("use test")
    tk.must_exec("create table w (a bigint, s varchar(4), d date)")
    tk.must_exec("insert into w values (1, 'x', '2020-01-01'), "
                 "(2, 'y', '2020-01-02'), (3, null, null)")
    chunk = tk.session.columnar_cache().project(
        *(lambda info: (tk.session.columnar_cache().get(
            info, tk.session.store.begin()), info.public_columns(), info))(
            tk.domain.infoschema().table_by_name("test", "w")))
    before = residency.resident_bytes()
    for col in chunk.columns:
        dev.to_device_col(col, bucket=8)
    assert residency.resident_bytes() - before == residency.upload_nbytes(
        chunk.columns, 8)


@pytest.mark.parametrize("paged,col_bytes,budget,resident", [
    # SF10 lineitem's seven Q1 columns (3.4 GB) on a 16 GB chip
    (False, 51 * 67_108_864, 16 * GB, True),
    # ... under a budget set below them
    (False, 51 * 67_108_864, 3 * GB, False),
    # ... and below columns + working set, though above the columns
    (False, 51 * 67_108_864, 3_800_000_000, False),
    # Q1's columns at 201,326,592 rows (10.3 GB) still fit; at twice
    # that they do not
    (False, 51 * 201_326_592, 16 * GB, True),
    (False, 51 * 402_653_184, 16 * GB, False),
    # SF1 under the chip's budget, and under a toy one
    (False, 51 * 8_388_608, 16 * GB, True),
    (False, 51 * 8_388_608, 1 << 20, False),
    # 0 = unlimited (the in-process CPU backend)
    (False, 1 << 50, 0, True),
    # a paged input streams whatever the budget
    (True, 1, 16 * GB, False),
    (True, 1, 0, False),
])
def test_resident_or_streamed_from_bytes(paged, col_bytes, budget, resident):
    assert residency.scan_fits_resident(paged, col_bytes, budget) is resident


def test_budget_defaults_to_the_tenants_share():
    col_bytes = 51 * 67_108_864
    try:
        residency.set_budget(16 * GB)
        assert residency.scan_fits_resident(False, col_bytes)
        residency.set_budget(7 * GB // 2)
        assert not residency.scan_fits_resident(False, col_bytes)
        residency.set_budget(0)     # auto: unlimited on the CPU backend
        assert residency.scan_fits_resident(False, col_bytes)
    finally:
        residency.set_budget(0)


def test_working_set_share_is_a_constant_not_a_variable():
    from tidb_tpu.session import sysvars
    names = set(sysvars.get_registry())
    assert "tidb_device_mem_budget" in names
    assert not [n for n in names if "working_set" in n or "resident" in n]
    assert 0.0 < residency.SCAN_WORKING_SET <= 1.0


# -- through SQL --------------------------------------------------------------

N_ROWS = 20_000
QUERY = ("select grp, cat, count(*), sum(amount), min(amount), max(amount) "
         "from s where amount > 10 group by grp, cat order by grp, cat")
#: the table's three used columns are 8+1, 4+1 and 8+1 bytes a row: 23,171
#: rows of bucket x 23 B = 533 KB, and `amount` alone 209 KB: 200 KB holds
#: neither
SMALL_BUDGET = 200_000


@pytest.fixture(scope="module")
def tk():
    tk = TestKit()
    tk.must_exec("use test")
    tk.must_exec("create table s (grp int, cat varchar(8), amount int)")
    rows = [f"({i % 13}, 'c{i % 5}', {i % 97})" for i in range(N_ROWS)]
    for lo in range(0, len(rows), 2000):
        tk.must_exec("insert into s values " + ",".join(rows[lo:lo + 2000]))
    tk.must_exec("set tidb_result_cache = 'OFF'")
    yield tk
    tk.must_exec("set global tidb_device_mem_budget = 0")
    residency.set_budget(0)


def _engines(tk, sql):
    plan = tk.must_query("explain analyze " + sql).rows
    return [part for row in plan for part in row[2].split(", ")
            if part.startswith("engine:")]


def _pipelines(tk):
    return json.loads(tk.must_query("DIAG STATUS").rows[0][0])[
        "device_pipelines"]


def _host_rows(tk, sql):
    tk.must_exec("set tidb_executor_engine = 'host'")
    try:
        return tk.must_query(sql).rows
    finally:
        tk.must_exec("set tidb_executor_engine = 'tpu'")


@pytest.fixture
def budget(tk):
    """-> set(n): `set global tidb_device_mem_budget`, restored to auto."""
    def set_(n):
        tk.must_exec(f"set global tidb_device_mem_budget = {n}")
    tk.must_exec("set tidb_executor_engine = 'tpu'")
    tk.must_exec("set tidb_device_stream_rows = 0")
    yield set_
    tk.must_exec("set global tidb_device_mem_budget = 0")
    tk.must_exec("set tidb_device_stream_rows = 0")
    tk.must_exec("set tidb_executor_engine = 'auto'")


def test_default_budget_keeps_the_table_resident(tk, budget):
    before = _pipelines(tk)
    assert _engines(tk, QUERY) == ["engine:tpu"]
    after = _pipelines(tk)
    assert after["scan_resident"] - before["scan_resident"] == 1
    assert after["scan_streamed"] == before["scan_streamed"]
    assert after["stream_upload_bytes"] == before["stream_upload_bytes"]
    assert tk.must_query(QUERY).rows == _host_rows(tk, QUERY)


def test_a_budget_the_table_exceeds_streams_it(tk, budget):
    budget(SMALL_BUDGET)
    before = _pipelines(tk)
    assert _engines(tk, QUERY) == ["engine:tpu-stream"]
    after = _pipelines(tk)
    assert after["scan_streamed"] - before["scan_streamed"] == 1
    assert after["scan_resident"] == before["scan_resident"]
    # blocks of 4,096 rows (the largest power of two whose 23 B rows and
    # working set fit 200 KB), five of them, every one padded to the block
    assert after["stream_upload_bytes"] - before["stream_upload_bytes"] \
        == 5 * 4096 * 23
    got = tk.must_query(QUERY).rows
    assert got == _host_rows(tk, QUERY) and len(got) == 13 * 5
    budget(0)
    assert _engines(tk, QUERY) == ["engine:tpu"]


def test_block_rows_follow_the_budget(tk, budget):
    info = tk.domain.infoschema().table_by_name("test", "s")
    cache = tk.session.columnar_cache()
    chunk = cache.project(cache.get(info, tk.session.store.begin()),
                          info.public_columns(), info)

    class Plan:     # what _agg_used_columns reads
        group_exprs, aggs = (), ()

    class Cond:
        def columns_used(self, used):
            used.update((0, 1, 2))
    ctx = tk.session
    assert device_exec.scan_stream_rows(Plan, chunk, [Cond()], ctx) == 0
    for n, want in ((SMALL_BUDGET, 4096), (100_000, 2048), (1, 1024)):
        budget(n)
        assert device_exec.scan_stream_rows(Plan, chunk, [Cond()],
                                            ctx) == want
    budget(10 ** 15)
    assert device_exec.scan_stream_rows(Plan, chunk, [Cond()], ctx) == 0
    assert DEFAULT_PAGE_ROWS == 1 << 22


@pytest.mark.parametrize("sql,blocks", [
    # 13 x 5 groups pack into 128 buckets: the dense arm, at any length
    (QUERY, 0),
    ("select count(*), sum(amount) from s where grp < 7", 0),
    # 97 x 13 groups pass the dense bound: a program that sorts (on
    # XLA:CPU, scatters) takes a long input by pages
    ("select amount, grp, count(*) from s group by amount, grp", 1 << 22),
    ("select cat, sum(amount * 1.5e0) from s group by cat", 1 << 22),
])
def test_only_dense_programs_take_a_long_input_whole(tk, budget,
                                                     monkeypatch, sql,
                                                     blocks):
    chosen = []
    real = device_exec.scan_stream_rows

    def spy(*a, **k):
        chosen.append(real(*a, **k))
        return chosen[-1]
    monkeypatch.setattr(device_exec, "scan_stream_rows", spy)
    tk.must_query(sql)
    assert chosen == [0]                 # 20,000 rows: under the bound
    monkeypatch.setattr(device_exec, "_SORTED_SCAN_MAX_ROWS", 10_000)
    got = tk.must_query(sql).rows
    assert chosen == [0, blocks]
    assert sorted(got) == sorted(_host_rows(tk, sql))


def test_user_set_stream_rows_still_wins(tk, budget):
    tk.must_exec("set tidb_device_stream_rows = 3000")
    before = _pipelines(tk)
    assert _engines(tk, QUERY) == ["engine:tpu-stream"]
    after = _pipelines(tk)
    assert after["scan_streamed"] - before["scan_streamed"] == 1
    assert after["stream_upload_bytes"] - before["stream_upload_bytes"] \
        == 7 * 3000 * 23
    # ... over a budget too: the user's block, not the budget's
    budget(SMALL_BUDGET)
    before = _pipelines(tk)
    assert _engines(tk, QUERY) == ["engine:tpu-stream"]
    assert _pipelines(tk)["stream_upload_bytes"] \
        - before["stream_upload_bytes"] == 7 * 3000 * 23


@pytest.mark.parametrize("sql", [
    QUERY,
    "select count(*), sum(amount) from s",
    "select grp, count(distinct amount) from s group by grp order by grp",
])
def test_streamed_rows_equal_the_host_engines(tk, budget, sql):
    budget(SMALL_BUDGET)
    before = _pipelines(tk)
    got = tk.must_query(sql).rows
    assert _pipelines(tk)["scan_streamed"] - before["scan_streamed"] == 1
    assert got == _host_rows(tk, sql)


# -- spans --------------------------------------------------------------------

def _spans(tk, sql):
    """[(name, tags)] of the statement's span tree, in start order."""
    tk.must_exec("set tidb_trace_sampling_rate = 1")
    try:
        tk.must_query(sql)
        trees = [tr for tr in json.loads(tk.must_query(
            "DIAG TRACEJSON").rows[0][0])["rows"]
            if tr["root"].get("tags", {}).get("stmt") == "SelectStmt"]
    finally:
        tk.must_exec("set tidb_trace_sampling_rate = 0")
    out = []

    def walk(node):
        out.append((node["name"], node.get("tags", {})))
        for child in node.get("children", ()):
            walk(child)
    walk(trees[-1]["root"])
    return out


def test_streamed_blocks_and_fetches_are_under_spans(tk, budget):
    budget(SMALL_BUDGET)
    tk.must_query(QUERY)                        # compiled before the trace
    tracing.reset_for_tests()
    spans = _spans(tk, QUERY)
    uploads = [t for n, t in spans if n == "upload.h2d"]
    assert [u["cols"] for u in uploads] == [3] * 5
    assert [u["bytes"] for u in uploads] == [4096 * 23] * 5
    fetches = [t for n, t in spans if n == "fetch.d2h"]
    # the blocks' group counts, then the merged state
    assert len(fetches) >= 2 and all(f["bytes"] > 0 for f in fetches)
    assert "host.assemble" in {n for n, _t in spans}


def test_trace_statement_shows_the_streamed_spans(tk, budget):
    budget(SMALL_BUDGET)
    ops = [row[0] for row in tk.must_query("trace " + QUERY).rows]
    assert sum("upload.h2d" in o for o in ops) == 5
    assert any("fetch.d2h" in o for o in ops)


def test_resident_scan_keeps_one_upload_span(tk, budget):
    tk.must_query(QUERY)
    tracing.reset_for_tests()
    spans = _spans(tk, QUERY)
    uploads = [t for n, t in spans if n == "upload.h2d"]
    assert len(uploads) == 1 and uploads[0]["bytes"] == 0   # warm


def test_diag_status_lists_the_counters(tk):
    pipes = _pipelines(tk)
    assert {"scan_resident", "scan_streamed",
            "stream_upload_bytes"} <= set(pipes)
    assert all(isinstance(pipes[k], int) for k in
               ("scan_resident", "scan_streamed", "stream_upload_bytes"))
