"""Resident or sent, from bytes, for a join fragment's probe (ISSUE 31): a
probe leaf past the longest input a sorting program takes whole runs page
by page; its pages are slices of columns placed once through the
residency ledger when they fit the budget, and pages cut from the host's
columns and sent by the statement when they do not.  The choice is
counted and printed, both kinds of page are under spans, and no such
probe ever reaches the whole-input fragment."""

import json

import numpy as np
import pytest

import tidb_tpu.executor.device_join as dj
from tidb_tpu.executor import device_exec
from tidb_tpu.ops import device as dev
from tidb_tpu.ops import residency
from tidb_tpu.ops.device import DeviceUnsupported
from tidb_tpu.session import tracing
from tidb_tpu.testkit import TestKit

N_FACT, N_DIM, PAGE = 3000, 200, 512
#: the star's probe reads f.k, f.v, f.q: three int64 columns and masks
PROBE_ROW_BYTES = 3 * 9
PROBE_BUCKET = dev.bucket_rows(N_FACT, 2)
#: below the probe's 110,592 B at its 4,096-row bucket, above d's columns
SMALL_BUDGET = 40_000
STAR = ("select g, count(*), sum(v), min(q) from f, d where f.k = d.k "
        "and r = 'A' and q < 25 group by g order by g")
#: Q1.x's shape: no group key, and a filter that leaves whole pages dead
GLOBAL = ("select sum(v * q) from f, d where f.k = d.k and g = 3 "
          "and f.v >= 990")
#: a non-unique build: outside the paged-probe language
EXPANDING = ("select d.g, count(*) from f join e on f.k = e.k "
             "join d on e.k = d.k group by d.g order by d.g")


@pytest.fixture(scope="module")
def tk():
    residency.evict_all("join probe residency test")
    tk = TestKit()
    tk.must_exec("use test")
    tk.must_exec("create table d (k bigint primary key, g bigint, "
                 "r varchar(4))")
    tk.must_exec("create table e (k bigint, w bigint)")
    tk.must_exec("create table f (k bigint, v bigint, q bigint)")
    tk.must_exec("insert into d values " + ",".join(
        f"({i}, {i % 5}, '{'AB'[i % 2]}')" for i in range(1, N_DIM + 1)))
    tk.must_exec("insert into e values " + ",".join(
        f"({1 + i % 40}, {i})" for i in range(120)))
    rng = np.random.default_rng(31)
    k = rng.integers(1, N_DIM + 1, N_FACT)
    v = np.arange(N_FACT) % 1000        # f.v >= 990: ten rows a thousand
    q = rng.integers(1, 51, N_FACT)
    for lo in range(0, N_FACT, 1000):
        tk.must_exec("insert into f values " + ",".join(
            f"({k[i]}, {v[i]}, {q[i]})" for i in range(lo, lo + 1000)))
    tk.must_exec("set tidb_result_cache = 'OFF'")
    tk.must_exec("set tidb_device_dispatch_rows = 1")
    tk.must_exec("set tidb_executor_engine = 'tpu'")
    yield tk
    tk.must_exec("set global tidb_device_mem_budget = 0")
    residency.set_budget(0)


@pytest.fixture
def paged(monkeypatch):
    """Every in-memory probe is past the whole-input bound; pages of
    `PAGE` rows."""
    monkeypatch.setattr(device_exec, "_SORTED_SCAN_MAX_ROWS", 1000)
    monkeypatch.setattr(dj, "_PROBE_PAGE_ROWS", PAGE)


@pytest.fixture
def budget(tk):
    def set_(n):
        tk.must_exec(f"set global tidb_device_mem_budget = {n}")
    yield set_
    tk.must_exec("set global tidb_device_mem_budget = 0")


def _status(tk):
    st = json.loads(tk.must_query("DIAG STATUS").rows[0][0])
    return {**st["device_pipelines"],
            "upload_bytes": st["device_residency"]["upload_bytes"]}


def _grew(tk, run):
    """`run()`'s result and the growth of every counter over it."""
    before = _status(tk)
    out = run()
    after = _status(tk)
    return out, {k: after[k] - before[k] for k in after
                 if isinstance(after[k], (int, float))}


def _host_rows(tk, sql):
    tk.must_exec("set tidb_executor_engine = 'host'")
    try:
        return tk.must_query(sql).rows
    finally:
        tk.must_exec("set tidb_executor_engine = 'tpu'")


def _notes(tk, sql):
    plan = tk.must_query("explain analyze " + sql).rows
    return [part for row in plan for part in row[2].split(", ")]


# -- the choice ---------------------------------------------------------------

def test_under_the_bound_the_fragment_takes_the_probe_whole(tk):
    rows, grew = _grew(tk, lambda: tk.must_query(STAR).rows)
    assert rows == _host_rows(tk, STAR) and len(rows) == 5
    assert (grew["join_probe_resident"], grew["join_probe_sent"]) == (1, 0)
    assert grew["stream_upload_bytes"] == 0
    notes = _notes(tk, STAR)
    assert "probe:resident" in notes and "engine:tpu" in notes
    assert not [n for n in notes if n.startswith("pages:")]


def test_a_probe_that_fits_answers_by_resident_pages(tk, paged):
    whole = _host_rows(tk, STAR)
    residency.evict_all("first placement")
    first, grew1 = _grew(tk, lambda: tk.must_query(STAR).rows)
    assert first == whole
    assert (grew1["join_probe_resident"], grew1["join_probe_sent"]) == (1, 0)
    assert grew1["stream_upload_bytes"] == 0
    # the probe's three columns at the leaf's bucket, the dimension's, the
    # slot table: all through the ledger, once
    probe_bytes = PROBE_BUCKET * PROBE_ROW_BYTES
    assert grew1["upload_bytes"] > probe_bytes
    second, grew2 = _grew(tk, lambda: tk.must_query(STAR).rows)
    assert second == whole
    assert grew2["join_probe_resident"] == 1
    # nothing is sent again: under 1% of the probe's column bytes
    assert grew2["upload_bytes"] + grew2["stream_upload_bytes"] \
        < probe_bytes // 100
    assert dj.LAST_PAGED_STATS.stats["pages"] == -(-N_FACT // PAGE)
    notes = _notes(tk, STAR)
    assert {"probe:resident", "pages:6", "engine:tpu"} <= set(notes)


def test_rebuilt_indexes_are_all_a_second_statement_sends(tk, paged):
    """Another filter on the dimension rebuilds its slot table, and that
    is the whole upload."""
    tk.must_query(STAR)
    other = STAR.replace("r = 'A'", "r = 'B'")
    rows, grew = _grew(tk, lambda: tk.must_query(other).rows)
    assert rows == _host_rows(tk, other)
    assert 0 < grew["upload_bytes"] < PROBE_BUCKET * PROBE_ROW_BYTES // 10
    assert grew["stream_upload_bytes"] == 0


def test_a_probe_that_does_not_fit_answers_by_sent_pages(tk, paged, budget):
    whole = _host_rows(tk, STAR)
    budget(SMALL_BUDGET)
    rows, grew = _grew(tk, lambda: tk.must_query(STAR).rows)
    assert rows == whole
    assert (grew["join_probe_resident"], grew["join_probe_sent"]) == (0, 1)
    # the largest power-of-two page whose 27 B rows and working set fit
    # 40 KB is 1,024 rows: three pages, every one padded to the page
    assert dj.LAST_PAGED_STATS.stats["pages"] == 3
    assert grew["stream_upload_bytes"] == 3 * 1024 * PROBE_ROW_BYTES
    again, grew2 = _grew(tk, lambda: tk.must_query(STAR).rows)
    assert again == whole
    assert grew2["stream_upload_bytes"] == 3 * 1024 * PROBE_ROW_BYTES
    assert {"probe:sent", "pages:3", "engine:tpu"} <= set(_notes(tk, STAR))
    budget(0)
    assert "probe:resident" in _notes(tk, STAR)


def test_under_the_bound_a_probe_that_does_not_fit_is_sent_too(tk, budget):
    budget(SMALL_BUDGET)
    rows, grew = _grew(tk, lambda: tk.must_query(STAR).rows)
    assert rows == _host_rows(tk, STAR)
    assert (grew["join_probe_resident"], grew["join_probe_sent"]) == (0, 1)


def test_the_choice_reads_bytes_not_rows(tk, paged, budget):
    leaf = type("Leaf", (), {})()
    info = tk.domain.infoschema().table_by_name("test", "f")
    cache = tk.session.columnar_cache()
    leaf.chunk = cache.project(cache.get(info, tk.session.store.begin()),
                               info.public_columns(), info)
    leaf.ncols, leaf.offset = 3, 0
    ctx = tk.session
    assert dj.probe_pages(leaf, {0, 1, 2}, ctx) == (PAGE, True)
    # one column of the three fits where three do not
    budget(PROBE_BUCKET * 9 * 2)
    assert dj.probe_pages(leaf, {0}, ctx) == (PAGE, True)
    assert dj.probe_pages(leaf, {0, 1, 2}, ctx) == (2048, False)
    budget(SMALL_BUDGET)
    assert dj.probe_pages(leaf, {0, 1, 2}, ctx) == (1024, False)
    # a user's block length bounds a sent page, and chooses nothing
    tk.must_exec("set tidb_device_stream_rows = 700")
    try:
        assert dj.probe_pages(leaf, {0, 1, 2}, ctx) == (700, False)
        budget(0)
        assert dj.probe_pages(leaf, {0, 1, 2}, ctx) == (PAGE, True)
    finally:
        tk.must_exec("set tidb_device_stream_rows = 0")
    from tidb_tpu.session import sysvars
    names = set(sysvars.get_registry())
    assert not [n for n in names if "probe" in n or "page_rows" in n]
    assert not hasattr(dj, "_PAGED_MIN_ROWS")
    assert dj._PROBE_PAGE_ROWS < device_exec._SORTED_SCAN_MAX_ROWS


# -- never the whole-input fragment -------------------------------------------

@pytest.mark.parametrize("sql,why", [
    (STAR, "the paged path refuses"),
    (EXPANDING, "outside the paged-probe language"),
])
def test_a_long_probe_never_reaches_the_whole_fragment(tk, paged,
                                                       monkeypatch, sql,
                                                       why):
    def refuse(*a, **k):
        raise DeviceUnsupported("refused for the test")
    monkeypatch.setattr(dj, "_paged_join_agg", refuse)
    built = []
    real = dj.compile_fragment

    def spy(*a, **k):
        built.append(a)
        return real(*a, **k)
    monkeypatch.setattr(dj, "compile_fragment", spy)
    device_exec._PIPE_CACHE.clear()
    rows, grew = _grew(tk, lambda: tk.must_query(sql).rows)
    assert rows == _host_rows(tk, sql)
    assert not built, why
    assert (grew["join_probe_resident"], grew["join_probe_sent"]) == (0, 0)
    notes = _notes(tk, sql)             # the host executors answered
    assert not [n for n in notes if n.startswith(("engine:tpu", "fused:"))]


def test_under_the_bound_a_refused_paged_probe_falls_to_the_whole(
        tk, budget, monkeypatch):
    def refuse(*a, **k):
        raise DeviceUnsupported("refused for the test")
    monkeypatch.setattr(dj, "_paged_join_agg", refuse)
    budget(SMALL_BUDGET)
    rows, grew = _grew(tk, lambda: tk.must_query(STAR).rows)
    assert rows == _host_rows(tk, STAR)
    assert grew["join_probe_resident"] == 1


# -- pages --------------------------------------------------------------------

@pytest.mark.parametrize("sent", [False, True])
def test_a_global_aggregate_survives_pages_with_no_live_row(
        tk, paged, budget, sent):
    want = _host_rows(tk, GLOBAL)
    assert want != [(None,)]
    if sent:
        budget(SMALL_BUDGET)
    rows, grew = _grew(tk, lambda: tk.must_query(GLOBAL).rows)
    assert rows == want
    assert grew["join_probe_sent" if sent else "join_probe_resident"] == 1
    assert "engine:tpu" in _notes(tk, GLOBAL)


@pytest.mark.parametrize("bucket,page", [
    (4096, 512),        # the bucket is a multiple of the page
    (4344, 1000),       # ... and not: the last page is padded
    (23, 8), (23, 32),  # a page longer than the column
])
def test_resident_pages_are_the_columns_rows(bucket, page):
    import jax.numpy as jnp
    data = np.arange(bucket, dtype=np.int64) * 3
    mask = np.arange(bucket) % 7 == 0
    arrays = {5: (jnp.asarray(data), jnp.asarray(mask))}
    n_pages = -(-bucket // page)
    pages = dj._resident_pages(arrays, rows=page, pages=n_pages)
    assert len(pages) == n_pages
    for i, got in enumerate(pages):
        lo = i * page
        live = min(page, bucket - lo)
        d, m = (np.asarray(a) for a in got[5])
        assert d.shape == m.shape == (page,)
        assert (d[:live] == data[lo:lo + live]).all()
        assert (m[:live] == mask[lo:lo + live]).all()


# -- spans --------------------------------------------------------------------

def _spans(tk, sql):
    tk.must_exec("set tidb_trace_sampling_rate = 1")
    try:
        tk.must_query(sql)
        trees = [tr for tr in json.loads(tk.must_query(
            "DIAG TRACEJSON").rows[0][0])["rows"]
            if tr["root"].get("tags", {}).get("stmt") == "SelectStmt"]
    finally:
        tk.must_exec("set tidb_trace_sampling_rate = 0")
    out = []

    def walk(node, depth):
        out.append((node["name"], node.get("tags", {}), depth))
        for child in node.get("children", ()):
            walk(child, depth + 1)
    walk(trees[-1]["root"], 0)
    return out


@pytest.mark.parametrize("sent", [False, True])
def test_pages_and_fetches_are_under_spans(tk, paged, budget, sent):
    if sent:
        budget(SMALL_BUDGET)
    else:
        residency.evict_all("a first placement under the span")
    tk.must_query(STAR.replace("q < 25", "q < 26"))  # compiled before
    tracing.reset_for_tests()
    spans = _spans(tk, STAR.replace("q < 25", "q < 26"))
    names = [n for n, _t, _d in spans]
    uploads = [t for n, t, _d in spans if n == "upload.h2d"]
    if sent:
        # the dimension's placement, then one span a page
        assert [u["cols"] for u in uploads[1:]] == [3] * 3
        assert [u["bytes"] for u in uploads[1:]] == \
            [1024 * PROBE_ROW_BYTES] * 3
    else:
        # d's three used columns and the probe's three, in one span
        assert len(uploads) == 1 and uploads[0]["cols"] == 3 + 3
        assert uploads[0]["bytes"] == 0          # all resident already
    fetches = [t for n, t, _d in spans if n == "fetch.d2h"]
    assert len(fetches) >= 2 and all(f["bytes"] > 0 for f in fetches)
    assert "host.assemble" in names
    call = next(d for n, _t, d in spans if n == "supervisor.call")
    assert all(d > call for n, _t, d in spans
               if n in ("upload.h2d", "fetch.d2h", "host.assemble"))
