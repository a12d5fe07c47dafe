"""The join fragment compacts its probe path's live rows (device_join
``compact_to`` / ``compaction_points``): past the probe leaf's filter and
past every probe-shaped join, a relation whose learned live count fills
at most a quarter of it is cut to ``next_pow2(live)`` rows, and what
follows runs at that length.

For every join kind on the probe path (an inner and a left join on the
``uniq`` arm, a semi, an anti and a residual existence test, a CSR
expansion after a cut) the cut program answers as the uncut one and the
host engine; a cut exactly at the live count, no live row at all and a
within-bucket append that overflows the learned cut (the fragment runs
again, exactly); the rule at the benchmark's shapes; the counter, the
``EXPLAIN ANALYZE`` note and the trace agree.  The paged join cuts its
one program's probe path where the largest live count of any page
leaves few rows: a star with a filtered leaf and one with a filtered
dimension by resident and by sent pages, pages with no live row, a page
past its cut (the turn restarts, exactly), the aggregate at the cut's
length, the counter once a fragment.  The tables are a few thousand
rows, so the tests lower the rule's floor and the whole-input bound; the
benchmark's own shapes go through the rule unpatched.
"""

import json
import os
import pathlib
import re
import sys
import types

import numpy as np
import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

import tidb_tpu.executor.device_join as dj  # noqa: E402
from tidb_tpu.executor import device_exec  # noqa: E402
from tidb_tpu.testkit import TestKit  # noqa: E402

_N = 4000                          # fact rows: a 4,096-row bucket
_DIMS = 100


def _values(rows):
    return ",".join("(" + ",".join(str(v) for v in r) + ")" for r in rows)


@pytest.fixture(scope="module")
def tk():
    """`f` (fact: id, k -> d.k / e.k, g, v, s), `d` (unique k: every
    fifth key of f's missing, so a left join null-extends), `e` (k not
    unique: two rows for every ninth key, none for the rest)."""
    tk = TestKit()
    tk.must_exec("create table f (id bigint, k bigint, g bigint, v bigint, "
                 "s bigint)")
    tk.must_exec("create table d (k bigint, w bigint, c bigint)")
    tk.must_exec("create table e (k bigint, w bigint, s bigint)")
    i = np.arange(_N)
    tk.must_exec("insert into f values " + _values(zip(
        i, (i * 7) % _DIMS, i % 5, i, i % 3)))
    dk = [k for k in range(_DIMS) if k % 5 != 4]
    tk.must_exec("insert into d values " + _values(
        (k, k % 11, k % 4) for k in dk))
    tk.must_exec("insert into e values " + _values(
        (k, j, (k + j) % 3) for k in range(0, _DIMS, 9) for j in range(2)))
    tk.must_exec("set tidb_device_dispatch_rows = 1")
    tk.must_exec("set tidb_result_cache = 'OFF'")
    return tk


def _pipelines(tk):
    return json.loads(tk.must_query("DIAG STATUS").rows[0][0])[
        "device_pipelines"]


def _grew(before, after, *keys):
    return [after[k] - before[k] for k in keys]


def _drop_compiled():
    dj._CAP_STORE.clear()
    device_exec._PIPE_CACHE.clear()


def _notes(tk, sql, prefix):
    plan = tk.must_query("explain analyze " + sql).rows
    return [m for row in plan for m in re.findall(
        rf"(?:^|, )({re.escape(prefix)}[^,(]*(?:\([^)]*\))?)", row[2] or "")]


def _rows(tk, engine, sql):
    tk.must_exec(f"set tidb_executor_engine = '{engine}'")
    try:
        return [tuple(r) for r in tk.must_query(sql).rows]
    finally:
        tk.must_exec("set tidb_executor_engine = 'host'")


@pytest.fixture()
def low_floor(monkeypatch):
    """The rule as it stands, from 64 rows up (its floor is 65,536)."""
    monkeypatch.setattr(dj, "_COMPACT_MIN_ROWS", 64)
    _drop_compiled()
    yield
    _drop_compiled()


#: (sql, cuts of the settled program: the filtered leaf and the joins
#: after it whose live rows fill at most a quarter of the relation)
_KINDS = {
    # the leaf keeps 512 of 4,096, the join 1 in 5 of them again
    "inner_uniq": ("select d.c, count(*), sum(f.v), sum(d.w) from f "
                   "join d on f.k = d.k where f.v < 512 and d.w < 3 "
                   "group by d.c order by d.c", 2),
    # unmatched rows null-extend: the cut keeps them, with their NULLs
    "left_uniq": ("select f.g, count(*), count(d.k), sum(d.w) from f "
                  "left join d on f.k = d.k where f.v < 700 "
                  "group by f.g order by f.g", 1),
    # the leaf keeps 900 of 4,096 (a cut of 1,024), the semi join 1 in 9
    "semi": ("select f.g, count(*), sum(f.v) from f where f.v < 900 "
             "and exists (select * from e where e.k = f.k) "
             "group by f.g order by f.g", 2),
    "anti": ("select f.g, count(*), sum(f.v) from f where f.v < 900 "
             "and not exists (select * from e where e.k = f.k) "
             "group by f.g order by f.g", 1),
    # the residual is tested on every pair of a CSR expansion of the
    # cut relation's live rows
    "residual_exists": ("select f.g, count(*), sum(f.v) from f "
                        "where f.v < 900 and exists (select * from e "
                        "where e.k = f.k and e.s <> f.s) "
                        "group by f.g order by f.g", 2),
    # the expansion probes the cut relation: its slots map to cut rows
    "expand_after_cut": ("select f.g, count(*), count(e.k), sum(e.w) "
                         "from f left join e on f.k = e.k where f.v < 300 "
                         "group by f.g order by f.g", 1),
}


@pytest.mark.parametrize("kind", list(_KINDS))
def test_every_kind_answers_as_uncut_and_host(tk, monkeypatch, low_floor,
                                              kind):
    sql, cuts = _KINDS[kind]
    want = _rows(tk, "host", sql)
    assert want, "an empty answer proves nothing"
    # cut: the first execution learns the live counts, the second cuts
    assert _rows(tk, "tpu", sql) == want
    before = _pipelines(tk)
    assert _rows(tk, "tpu", sql) == want
    after = _pipelines(tk)
    assert _grew(before, after, "join_compactions", "capacity_reruns",
                 "compiles") == [cuts, 0, 1]
    # uncut: the rule answers "keep" everywhere, the program counts only
    monkeypatch.setattr(dj, "compact_to", lambda _live, _n: None)
    _drop_compiled()
    assert _rows(tk, "tpu", sql) == want
    before = _pipelines(tk)
    assert _rows(tk, "tpu", sql) == want
    assert _grew(before, _pipelines(tk), "join_compactions") == [0]


def test_a_cut_exactly_at_the_live_count(tk, low_floor):
    """512 live rows of the 4,096-row bucket: a cut of 512, every slot a
    row, none dropped."""
    sql = ("select d.c, count(*), sum(f.v) from f join d on f.k = d.k "
           "where f.v < 512 group by d.c order by d.c")
    want = _rows(tk, "host", sql)
    assert _rows(tk, "tpu", sql) == want
    sig = next(k[0] for k in dj._CAP_STORE if k[1] == ("live", -1))
    assert dj.learned(sig, ("live", -1)) == 512
    assert dj.compact_to(512, 4096) == 512
    before = _pipelines(tk)
    assert _rows(tk, "tpu", sql) == want
    assert _grew(before, _pipelines(tk), "join_compactions",
                 "capacity_reruns") == [1, 0]


def test_no_live_row_at_all(tk, low_floor):
    sql = ("select d.c, count(*), sum(f.v) from f join d on f.k = d.k "
           "where f.v < 0 group by d.c order by d.c")
    assert _rows(tk, "host", sql) == []
    assert _rows(tk, "tpu", sql) == []
    before = _pipelines(tk)
    assert _rows(tk, "tpu", sql) == []
    # the leaf is cut to 8 rows; the join after it keeps those
    assert _grew(before, _pipelines(tk), "join_compactions",
                 "capacity_reruns") == [1, 0]


def test_an_append_past_the_cut_runs_again_exactly(low_floor):
    """A within-bucket append raises the live count past the learned cut:
    the first run at the cut drops rows, says so, and the fragment runs
    again at the count's size; the answer is the host's."""
    tk = TestKit()
    tk.must_exec("create table a (id bigint, k bigint, v bigint)")
    tk.must_exec("create table b (k bigint, c bigint)")
    i = np.arange(3000)
    tk.must_exec("insert into a values " + _values(zip(i, i % 50, i)))
    tk.must_exec("insert into b values " + _values(
        (k, k % 3) for k in range(50)))
    tk.must_exec("set tidb_device_dispatch_rows = 1")
    tk.must_exec("set tidb_result_cache = 'OFF'")
    sql = ("select b.c, count(*), sum(a.v) from a join b on a.k = b.k "
           "where a.v < 256 group by b.c order by b.c")
    assert _rows(tk, "tpu", sql) == _rows(tk, "host", sql)
    before = _pipelines(tk)
    assert _rows(tk, "tpu", sql) == _rows(tk, "host", sql)
    assert _grew(before, _pipelines(tk), "join_compactions") == [1]
    # twenty more live rows: 276 of a bucket that stays 4,096
    tk.must_exec("insert into a values " + _values(
        (3000 + j, j % 50, j) for j in range(20)))
    want = _rows(tk, "host", sql)
    before = _pipelines(tk)
    assert _rows(tk, "tpu", sql) == want
    after = _pipelines(tk)
    assert _grew(before, after, "capacity_reruns", "join_compactions") == [
        1, 1]
    assert _rows(tk, "tpu", sql) == want
    assert _grew(after, _pipelines(tk), "capacity_reruns") == [0]
    _drop_compiled()


@pytest.mark.parametrize("live,n,cut", [
    (151_000, 8_388_608, 262_144),     # Q3 past orders
    (1_191_000, 8_388_608, 2_097_152),  # Q5 past region
    (180_000, 2_097_152, 262_144),     # ... then past orders
    (910_000, 8_388_608, 1_048_576),   # Q5 past orders, attached first
    (182_000, 1_048_576, 262_144),     # ... then past region
    (324_000, 8_388_608, 524_288),     # Q9 past part, attached first
    (72_444, 8_388_608, 131_072),      # Q21's live rows
    (57_000, 2_097_152, 65_536),       # Q4's orders filter
    (400, 8_388_608, 512),             # Q18's in-set
    (0, 8_388_608, 8),
    (150_000, 262_144, None),          # Q13's customers: all live
    (3_200_000, 8_388_608, None),      # Q3's lineitem filter keeps half
    (2_097_152, 8_388_608, 2_097_152),  # exactly a quarter
    (2_097_153, 8_388_608, None),
    (100, 65_535, None),               # under the floor
    (100, 65_536, 128),
    (None, 8_388_608, None),           # nothing learned yet
    # SSB SF10 by pages of 4,194,304 lineorder rows
    (549_000, 4_194_304, 1_048_576),   # Q1.1's filter on lineorder
    (83_500, 1_048_576, 131_072),      # ... then d_year = 1993
    (33_600, 1_048_576, 65_536),       # Q2.1's p_category past s_region
    (67_000, 262_144, None),           # Q4.1's p_mfgr past both regions
    (167_800, 4_194_304, 262_144),     # Q2.1's p_category, attached first
    (839_000, 4_194_304, 1_048_576),   # Q3.1 / Q4.1 past s_region first
])
def test_the_rule_at_the_benchmarks_shapes(live, n, cut):
    assert dj.compact_to(live, n) == cut


def test_the_points_of_a_chain(tk):
    """The probe leaf, then every probe-shaped join of the probe path, in
    the order the program evaluates them."""
    leaf = dj._Leaf(0, type("C", (), {"num_cols": 1})(), [], 0)
    dims = [dj._Leaf(i, type("C", (), {"num_cols": 1})(), [], i)
            for i in (1, 2, 3)]
    uniq = ("uniq", "right", None)
    j0 = dj._JoinNode(leaf, dims[0], [], [], [], 0)
    j0.strategy, j0.pos = uniq, 0
    j1 = dj._JoinNode(j0, dims[1], [], [], [], 0, kind="left")
    j1.strategy, j1.pos = ("expand", "right", None), 1
    j2 = dj._JoinNode(j1, dims[2], [], [], [], 0, kind="semi")
    j2.strategy, j2.pos = ("expand", "right", None), 2
    assert list(dj.compaction_points(j2).items()) == [
        (id(leaf), -1), (id(j0), 0), (id(j2), 2)]
    # a unique build on the left: the probe path turns right
    j3 = dj._JoinNode(dims[0], leaf, [], [], [], 0)
    j3.strategy, j3.pos = ("uniq", "left", None), 0
    assert list(dj.compaction_points(j3).items()) == [
        (id(leaf), -1), (id(j3), 0)]


def test_the_counter_the_note_and_the_trace_agree(tk, monkeypatch,
                                                  low_floor):
    """`device_pipelines.join_compactions` counts what the KEPT program's
    trace cut (`fn.compacted`), and EXPLAIN ANALYZE prints it."""
    sql, cuts = _KINDS["residual_exists"]
    built = []
    orig = dj.compile_fragment

    def spy(*a, **kw):
        fn = orig(*a, **kw)
        built.append(fn)
        return fn
    monkeypatch.setattr(dj, "compile_fragment", spy)
    _rows(tk, "tpu", sql)
    before = _pipelines(tk)
    _rows(tk, "tpu", sql)
    grew, = _grew(before, _pipelines(tk), "join_compactions")
    assert grew == cuts == len(built[-1].compacted)
    assert built[-1].compacted == [-1, max(
        k[1][1] for k in dj._CAP_STORE if isinstance(k[1], tuple))]
    tk.must_exec("set tidb_executor_engine = 'tpu'")
    notes = _notes(tk, sql, "join:") + _notes(tk, sql, "compact:")
    tk.must_exec("set tidb_executor_engine = 'host'")
    assert notes == ["join:direct x1 (semi x1, residual x1)",
                     f"compact:x{cuts}"]


# -- the paged join: every page runs one program, cut where the pages were ----

_PAGE = 1024                        # four pages of f's 4,000 rows
#: above d's columns and its slot table, below the probe's at its bucket:
#: the pages are cut from the host's columns and sent
_SMALL_BUDGET = 60_000

#: (sql, cuts of the settled program), each a star by pages
_STARS = {
    # f's filter keeps 1 row in 15 (69 of a page at most: a cut of 128),
    # d.w < 3 about a third of them again (21: 32)
    "filtered_leaf": ("select d.c, count(*), sum(f.v), sum(d.w) from f "
                      "join d on f.k = d.k where f.s = 0 and f.g = 1 "
                      "and d.w < 3 group by d.c order by d.c", 2),
    # every row of f is live until d.w = 3 keeps 7 keys of 100
    "filtered_dim": ("select d.c, count(*), sum(f.v) from f join d "
                     "on f.k = d.k where d.w = 3 group by d.c order by d.c",
                     1),
    # Q1.x's shape: no group key, and only the last page holds a live row
    "global": ("select sum(f.v * d.w), count(*) from f join d "
               "on f.k = d.k where f.v >= 3900 and d.c = 1", 2),
}


@pytest.fixture()
def paged(monkeypatch):
    """A probe past the whole-input bound (lowered to 1,000 rows) runs
    by pages of `_PAGE` rows."""
    monkeypatch.setattr(device_exec, "_SORTED_SCAN_MAX_ROWS", 1000)
    monkeypatch.setattr(dj, "_PROBE_PAGE_ROWS", _PAGE)


@pytest.fixture(params=["resident", "sent"])
def pages_from(request, tk):
    """Where the pages come from: the leaf's resident columns, or the
    host's, under a budget the probe does not fit."""
    from tidb_tpu.ops import residency
    if request.param == "sent":
        tk.must_exec(f"set global tidb_device_mem_budget = {_SMALL_BUDGET}")
    yield request.param
    tk.must_exec("set global tidb_device_mem_budget = 0")
    residency.set_budget(0)


@pytest.mark.parametrize("star", list(_STARS))
def test_a_paged_star_answers_as_uncut_and_host(tk, monkeypatch, low_floor,
                                                paged, pages_from, star):
    sql, cuts = _STARS[star]
    want = _rows(tk, "host", sql)
    assert want and want != [(None, "0")], "an empty answer proves nothing"
    # the first execution learns the pages' largest live counts, the
    # second cuts every page at them
    assert _rows(tk, "tpu", sql) == want
    before = _pipelines(tk)
    assert _rows(tk, "tpu", sql) == want
    after = _pipelines(tk)
    assert _grew(before, after, "join_compactions", "capacity_reruns",
                 "compiles", "join_probe_" + pages_from) == [cuts, 0, 1, 1]
    assert dj.LAST_PAGED_STATS.stats["pages"] > 1
    # uncut: the rule answers "keep" everywhere, the program counts only
    monkeypatch.setattr(dj, "compact_to", lambda _live, _n: None)
    _drop_compiled()
    assert _rows(tk, "tpu", sql) == want
    before = _pipelines(tk)
    assert _rows(tk, "tpu", sql) == want
    assert _grew(before, _pipelines(tk), "join_compactions") == [0]


def test_a_paged_global_aggregate_with_whole_pages_dead(tk, low_floor,
                                                        paged):
    """Three of the four pages hold no live row: the largest count is
    the last page's, and the empty pages' states merge to nothing."""
    sql, cuts = _STARS["global"]
    assert _rows(tk, "tpu", sql) == _rows(tk, "host", sql)
    sig = next(k[0] for k in dj._CAP_STORE if k[1] == ("live", -1))
    assert dj.learned(sig, ("live", -1)) == 100
    assert dj.compact_to(100, _PAGE) == 128
    before = _pipelines(tk)
    assert _rows(tk, "tpu", sql) == _rows(tk, "host", sql)
    assert _grew(before, _pipelines(tk), "join_compactions") == [cuts]


@pytest.mark.parametrize("sent", [False, True])
def test_a_page_past_its_cut_restarts_the_turn_exactly(low_floor, paged,
                                                       sent):
    """An append gives one page more live rows than the learned cut: the
    turn stops at that page's fetch, restarts from the first page at the
    count's size, and the answer is the host's."""
    from tidb_tpu.ops import residency
    tk = TestKit()
    tk.must_exec("create table a (id bigint, k bigint, v bigint)")
    tk.must_exec("create table b (k bigint, c bigint)")
    i = np.arange(3000)
    tk.must_exec("insert into a values " + _values(zip(i, i % 50, i)))
    tk.must_exec("insert into b values " + _values(
        (k, k % 3) for k in range(50)))
    tk.must_exec("set tidb_device_dispatch_rows = 1")
    tk.must_exec("set tidb_result_cache = 'OFF'")
    if sent:
        tk.must_exec(f"set global tidb_device_mem_budget = {_SMALL_BUDGET}")
    sql = ("select b.c, count(*), sum(a.v) from a join b on a.k = b.k "
           "where a.v < 100 group by b.c order by b.c")
    try:
        # the first page holds all 100 live rows: a cut of 128
        assert _rows(tk, "tpu", sql) == _rows(tk, "host", sql)
        before = _pipelines(tk)
        assert _rows(tk, "tpu", sql) == _rows(tk, "host", sql)
        assert _grew(before, _pipelines(tk), "join_compactions",
                     "join_probe_" + ("sent" if sent else "resident")) == [
                         1, 1]
        # 250 more live rows: 178 of them on the last 1,024-row page, all
        # on the last 2,048-row one (a sent page here), a cut of 256
        tk.must_exec("insert into a values " + _values(
            (3000 + j, j % 50, j % 100) for j in range(250)))
        want = _rows(tk, "host", sql)
        before = _pipelines(tk)
        assert _rows(tk, "tpu", sql) == want
        after = _pipelines(tk)
        assert _grew(before, after, "capacity_reruns",
                     "join_compactions") == [1, 1]
        assert _rows(tk, "tpu", sql) == want
        assert _grew(after, _pipelines(tk), "capacity_reruns") == [0]
    finally:
        tk.must_exec("set global tidb_device_mem_budget = 0")
        residency.set_budget(0)
        _drop_compiled()


def test_the_paged_aggregate_reads_the_cut_length(tk, monkeypatch,
                                                  low_floor, paged):
    """The partial aggregate's input, its capacity and the one-pass
    question (note_agg_spans) all read the length the last cut leaves,
    and an estimated capacity is capped at it."""
    sql = ("select f.id, sum(d.w) from f join d on f.k = d.k "
           "where f.s = 0 and f.g = 1 and d.w < 3 group by f.id "
           "order by f.id")
    want = _rows(tk, "host", sql)
    assert _rows(tk, "tpu", sql) == want
    aggs, spans = [], []
    orig_agg, orig_spans = dj.dev._agg_impl, dj.note_agg_spans

    def agg(*a, **kw):
        if kw.get("gathered"):
            aggs.append((a[4].shape[0], kw["capacity"]))
        return orig_agg(*a, **kw)

    def note(pack, agg_ops, capacity, n, **kw):
        spans.append((capacity, n))
        return orig_spans(pack, agg_ops, capacity, n, **kw)
    monkeypatch.setattr(dj.dev, "_agg_impl", agg)
    monkeypatch.setattr(dj, "note_agg_spans", note)
    assert _rows(tk, "tpu", sql) == want
    sig = next(k[0] for k in dj._CAP_STORE if k[1] == ("live", 0))
    cut = dj.compact_to(dj.learned(sig, ("live", 0)), 128)
    assert cut == 32
    assert aggs == [(cut, cut)] and spans == [(cut, cut)]
    # the group count forgotten, the estimate (f.id: a group a row) is
    # capped at the cut and not at the page
    del dj._CAP_STORE[(sig, "agg")]
    spans.clear()
    assert _rows(tk, "tpu", sql) == want
    assert spans == [(cut, cut)]


def test_the_paged_counter_the_note_and_the_trace_agree(tk, monkeypatch,
                                                        low_floor, paged):
    """`device_pipelines.join_compactions` counts the KEPT program's cuts
    (`fn.compacted`) once a fragment, whatever its pages, and EXPLAIN
    ANALYZE prints them beside the pages."""
    sql, cuts = _STARS["filtered_leaf"]
    built = []
    orig = dj.compile_fragment

    def spy(*a, **kw):
        fn = orig(*a, **kw)
        built.append(fn)
        return fn
    monkeypatch.setattr(dj, "compile_fragment", spy)
    _rows(tk, "tpu", sql)
    before = _pipelines(tk)
    _rows(tk, "tpu", sql)
    grew, = _grew(before, _pipelines(tk), "join_compactions")
    assert grew == cuts == len(built[-1].compacted)
    assert built[-1].compacted == [-1, 0]
    assert dj.LAST_PAGED_STATS.stats["pages"] == 4
    tk.must_exec("set tidb_executor_engine = 'tpu'")
    notes = (_notes(tk, sql, "compact:") + _notes(tk, sql, "pages:")
             + _notes(tk, sql, "probe:"))
    tk.must_exec("set tidb_executor_engine = 'host'")
    assert notes == [f"compact:x{cuts}", "pages:4", "probe:resident"]


# -- join.compactions_per_query: the reader and its entry --------------------

def _reader():
    from benchmark.harness.resolve import BENCH_DIR, load_module
    return load_module(os.path.join(
        BENCH_DIR, "layer_metrics", "join.compactions_per_query.py"),
        "per_layer metric")


def _obs(before, after, requests):
    from benchmark.harness import observe
    o = types.SimpleNamespace(
        status0={"device_pipelines": before},
        status1={"device_pipelines": after}, requests=[None] * requests)
    o.counter_delta = lambda *path: observe.delta(o.status0, o.status1,
                                                  *path)
    return o


@pytest.mark.parametrize("before,after,requests,want", [
    # a Q3 (two cuts) and a Q5 (three) a pair, four pairs
    ({"join_compactions": 7}, {"join_compactions": 27}, 8, 2.5),
    ({"join_compactions": 3}, {"join_compactions": 3}, 8, 0.0),  # a star
    ({"join_compactions": 3}, {"join_compactions": 5}, 0, None),
    ({}, {}, 8, None),                     # a program without the counter
])
def test_the_reader(before, after, requests, want):
    assert _reader().read(_obs(before, after, requests)) == want


def test_the_entry_and_the_cells_that_report_it():
    from benchmark.harness.resolve import ROOT, Cell
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    entry, = [m for m in spec["per_layer"]
              if m["name"] == "join.compactions_per_query"]
    assert {k: v for k, v in entry.items() if k != "workloads"} == {
        "name": "join.compactions_per_query", "unit": "count",
        "better": "higher", "source": "program_counter",
        "layer": "XLA programs", "moves": "query_geomean_s"}
    joins = {"tpch-sf1.q3q5", "ssb-sf10.flights", "tpch-sf1.q9q18",
             "tpch-sf1.q13q4", "tpch-sf1.q21", "tpch-sf1.q17"}
    assert set(entry["workloads"]) == joins
    for w in spec["workloads"]:
        names = {m["name"] for m, _mod in Cell(w["name"]).per_layer}
        assert ("join.compactions_per_query" in names) == (w["name"] in joins)
