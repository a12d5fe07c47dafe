"""The two sides of device_join._expand_rows: one scatter and one running
sum against a binary search per output slot (ISSUE 38).

Both map the `cap` output slots of an expansion to the probe rows that
emit them (`pi`), give every slot its offset inside its row's run
(`within`) and the exact `total`; which one a program traces is decided by
``dj.expand_one_pass(cap, n_probe)`` from the two static shapes alone.
The row map is compared with the rule patched to either side and against
numpy's ``repeat``; the lowered texts; the rule at the benchmark's shapes
and at its break-even; the counter by hand and through SQL; and an outer,
a hot-key and an overflowing expansion against the host engine on either
side of the rule.
"""

import json
import pathlib
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

import bench  # noqa: E402
import tidb_tpu.executor.device_join as dj  # noqa: E402
from benchmark.datasets import tpch_text  # noqa: E402
from benchmark.queries import q4, q13  # noqa: E402
from tidb_tpu.executor import device_exec  # noqa: E402
from tidb_tpu.testkit import TestKit  # noqa: E402
from tidb_tpu.utils.chunk import Column  # noqa: E402


# -- the row map, side against side ------------------------------------------

def _counts(case, n, cap):
    """Per-probe-row emission counts as eval_indexed hands them over."""
    rng = np.random.default_rng(n * 31 + cap)
    lam = 0.7 * cap / n
    cnt = rng.poisson(lam, size=n).astype(np.int64)
    if case == "nothing_at_the_start":
        cnt[:n // 5] = 0
    elif case == "runs_of_nothing":
        cnt[(np.arange(n) // 7) % 2 == 0] = 0
    elif case == "nothing_at_the_end":
        cnt[n - n // 3:] = 0
    elif case == "left":
        # a left join's maximum(cnt, 1) over the live rows of the bucket
        live = np.arange(n) < n - n // 4
        cnt = np.where(live, np.maximum(cnt, 1), 0)
    elif case == "empty":
        cnt[:] = 0
    elif case == "fills_the_capacity":
        cnt = np.bincount(rng.integers(0, n, size=cap),
                          minlength=n).astype(np.int64)
    elif case == "overflows":
        cnt = rng.poisson(3 * lam + 1, size=n).astype(np.int64)
    else:
        assert case == "some"
    return cnt


def _expand(monkeypatch, side, cnt, cap):
    monkeypatch.setattr(dj, "expand_one_pass", lambda _c, _n: side)
    return jax.device_get(jax.jit(lambda c: dj._expand_rows(c, cap))(
        jnp.asarray(cnt)))


# cap under, equal to and above n_probe; powers of two and not
@pytest.mark.parametrize("n,cap", [
    (256, 2048), (1024, 64), (1024, 1024), (1000, 256), (300, 1000),
    (777, 16), (1, 8)])
@pytest.mark.parametrize("case", [
    "some", "nothing_at_the_start", "runs_of_nothing", "nothing_at_the_end",
    "left", "empty", "fills_the_capacity", "overflows"])
def test_both_sides_give_the_same_row_map(monkeypatch, case, n, cap):
    cnt = _counts(case, n, cap)
    total = int(cnt.sum())
    if case == "fills_the_capacity":
        assert total == cap
    if case == "overflows":
        assert total > cap
    one_pass = _expand(monkeypatch, True, cnt, cap)
    search = _expand(monkeypatch, False, cnt, cap)
    for a, b in zip(one_pass[:2], search[:2]):
        assert a.shape == b.shape == (cap,)
        assert np.array_equal(a, b)         # slot for slot, past total too
    assert int(one_pass[2]) == int(search[2]) == total
    assert one_pass[2].dtype == search[2].dtype == np.int64
    # and both are what numpy's repeat says, in every slot that holds a row
    want = np.repeat(np.arange(n), cnt)[:cap]
    k = len(want)
    assert k == min(total, cap)
    pi, within = one_pass[0], one_pass[1]
    assert np.array_equal(pi[:k], want)
    first = np.concatenate([[0], np.cumsum(cnt)])
    assert np.array_equal(within[:k], np.arange(k) - first[want])
    assert (within[:k] < cnt[want]).all()
    # the slots past the last row read the last probe row
    assert (pi[k:] == n - 1).all()
    assert pi.min(initial=0) >= 0 and pi.max(initial=0) <= n - 1


def test_the_one_pass_side_traces_no_loop(monkeypatch):
    """One scatter of int32 ones, a running sum and a running max over
    the slots, no gather; the searched side is the loop of dependent
    gathers."""
    def text(side):
        monkeypatch.setattr(dj, "expand_one_pass", lambda _c, _n: side)
        return jax.jit(lambda c: dj._expand_rows(c, 4096)).lower(
            jnp.zeros(512, dtype=jnp.int64)).as_text()
    one_pass, search = text(True), text(False)
    assert "stablehlo.while" not in one_pass
    assert one_pass.count('"stablehlo.scatter"(') == 1
    assert '"stablehlo.gather"(' not in one_pass
    assert one_pass.count('"stablehlo.reduce_window"(') == 3   # + cnt's
    assert "tensor<4096xi32>" in one_pass
    assert "stablehlo.while" in search
    assert '"stablehlo.scatter"(' not in search


def test_join_expand_takes_the_helper(monkeypatch):
    """The in-program fallback (the mesh's other body, a join without a
    host index) maps its slots through the same helper and rule."""
    asked = []
    orig = dj.expand_one_pass

    def spy(cap, n_probe):
        asked.append((cap, n_probe))
        return orig(cap, n_probe)
    monkeypatch.setattr(dj, "expand_one_pass", spy)
    bk = np.array([5, 3, 5, 9, 5, 7, 0, 0], dtype=np.int64)
    bvalid = np.arange(8) < 6
    pk = np.array([5, 4, 9, 5, 3, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0],
                  dtype=np.int64)
    pvalid = np.arange(16) < 5
    pi, bi, valid, total = jax.device_get(jax.jit(
        lambda *a: dj._join_expand(*a, 32))(bk, bvalid, pk, pvalid))
    assert asked == [(32, 16)]
    assert int(total) == 3 + 0 + 1 + 3 + 1
    got = sorted(zip(pi[valid].tolist(), bi[valid].tolist()))
    assert got == [(0, 0), (0, 2), (0, 4), (2, 3), (3, 0), (3, 2), (3, 4),
                   (4, 1)]


# -- the rule -----------------------------------------------------------------

@pytest.mark.parametrize("cap,n_probe,one_pass", [
    (2097152, 185364, True),      # TPC-H Q13 at SF1: the customer bucket
    (1048576, 185364, True),      # its first-run capacity
    (2097152, 262144, True),      # the sweep's shape for it
    (2097152, 8388608, True),     # a wide expansion over a long probe
    (2097152, 2097152, True),
    (16384, 8388608, False),      # a learned capacity over a long probe
    (1024, 65536, False),         # the sweep's small shape: 1.2 / 1.6 ms
    (16384, 2048, True),          # Q13 at SF0.01
])
def test_the_rule_at_the_cells_shapes(cap, n_probe, one_pass):
    assert dj.expand_one_pass(cap, n_probe) is one_pass


def test_the_rule_is_the_two_prices():
    """cap x ceil(log2(n_probe + 1)) searched slot-steps at
    _EXPAND_SEARCH_PRICE scattered rows each against n_probe scattered
    rows."""
    n = 1 << 23
    least = -(-n // (24 * dj._EXPAND_SEARCH_PRICE))
    assert dj.expand_one_pass(least, n)
    assert not dj.expand_one_pass(least - 1, n)
    # one row fewer: cum has 2**23 entries, a step less, more slots tip
    more = -(-(n - 1) // (23 * dj._EXPAND_SEARCH_PRICE))
    assert more > least
    assert dj.expand_one_pass(more, n - 1)
    assert not dj.expand_one_pass(more - 1, n - 1)
    # more slots never go back to the search, numpy integers are fine
    assert all(dj.expand_one_pass(np.int64(c), np.int64(n))
               for c in (least, n // 2, n, 2 * n))


def test_the_rule_reads_its_arguments_only(monkeypatch):
    def no_backend():
        raise AssertionError("expand_one_pass asked for the backend")
    monkeypatch.setattr(jax, "default_backend", no_backend)
    monkeypatch.setattr(jax, "devices", no_backend)
    assert dj.expand_one_pass(2097152, 262144)
    assert not dj.expand_one_pass(16384, 8388608)


def test_expand_rows_asks_the_rule_with_its_static_shapes(monkeypatch):
    asked = []
    orig = dj.expand_one_pass

    def spy(cap, n_probe):
        asked.append((cap, n_probe))
        return orig(cap, n_probe)
    monkeypatch.setattr(dj, "expand_one_pass", spy)
    jax.jit(lambda c: dj._expand_rows(c, 128)).lower(
        jnp.zeros(2048, dtype=jnp.int64))
    assert asked == [(128, 2048)]


# -- the counter --------------------------------------------------------------

def _pipelines(tk):
    return json.loads(tk.must_query("DIAG STATUS").rows[0][0])[
        "device_pipelines"]


def _grew(before, after, *keys):
    return [after[k] - before[k] for k in keys]


def _drop_compiled():
    dj._CAP_STORE.clear()
    device_exec._PIPE_CACHE.clear()


def _notes(tk, sql, prefix):
    """EXPLAIN ANALYZE's annotations that start with `prefix`, a note's
    bracket (`join:direct x1 (left x1, expand x1 one-pass)`) included."""
    plan = tk.must_query("explain analyze " + sql).rows
    return [m for row in plan for m in re.findall(
        rf"(?:^|, )({re.escape(prefix)}[^,(]*(?:\([^)]*\))?)", row[2] or "")]


@pytest.mark.parametrize("one_pass", [True, False])
def test_note_join_expansion_counts_the_one_pass_programs(one_pass):
    keys = ("join_expand_rows", "join_expand_capacity",
            "join_expand_one_pass")
    before = device_exec.pipe_cache_stats()
    device_exec.note_join_expansion(1_531_769, 2_097_152, one_pass)
    after = device_exec.pipe_cache_stats()
    assert _grew(before, after, *keys) == [1_531_769, 2_097_152,
                                           int(one_pass)]


@pytest.fixture(scope="module")
def tpch_tk():
    want = {t: list(cols) for t, cols in tpch_text.SCHEMA.items()}
    tk = TestKit()
    tpch_text.load(tk, tpch_text.generate(3800000011, 0.02, want), want,
                   False, "test_join_expand_rows/tpch")
    tk.must_exec("set tidb_device_dispatch_rows = 1")
    tk.must_exec("set tidb_result_cache = 'OFF'")
    return tk


@pytest.mark.parametrize("name,sql,expands,one_pass,note", [
    # 3,000 customers (a 4,096-row bucket) into 32,768 learned slots
    ("q13", q13.SQL, 1, 1, "join:direct x1 (left x1, expand x1 one-pass)"),
    ("q4", q4.SQL, 0, 0, "join:direct x1 (semi x1)"),      # existence
    ("q3", bench.QUERIES["q3"], 0, 0, "join:direct x2"),   # unique builds
])
def test_the_counter_through_sql(tpch_tk, name, sql, expands, one_pass,
                                 note):
    tk = tpch_tk
    tk.must_exec("set tidb_executor_engine = 'tpu'")
    assert tk.must_query(sql).rows         # capacities learned
    before = _pipelines(tk)
    assert tk.must_query(sql).rows
    after = _pipelines(tk)
    assert _grew(before, after, "join_expand", "join_expand_one_pass",
                 "capacity_reruns") == [expands, one_pass, 0]
    assert _notes(tk, sql, "join:") == [note]
    tk.must_exec("set tidb_executor_engine = 'host'")


# -- parity through SQL, on either side of the rule --------------------------

_N_PROBE = 40_000                 # a 46,341-row bucket (2 ** 15.5)


@pytest.fixture(scope="module")
def tk():
    """`p` and `q` probe `b`, whose key is not unique: `b` holds two rows
    for every third key under 6,000 and 600 for key 7 (the hot key); `p`
    has 40,000 rows, one in nine with a NULL key, keys 0 .. 7,999 (five
    rows meet the hot key); `q` has 2,000, sixty of them on the hot key."""
    tk = TestKit()
    for t in "pq":
        tk.must_exec(
            f"create table {t} (id bigint, k bigint, g bigint, v bigint)")
    tk.must_exec("create table b (id bigint, k bigint, w bigint)")
    i = np.arange(_N_PROBE, dtype=np.int64)
    h = np.arange(2000, dtype=np.int64)
    bk = np.concatenate([np.repeat(np.arange(0, 6000, 3), 2),
                         np.full(600, 7)]).astype(np.int64)
    j = np.arange(len(bk), dtype=np.int64)
    tables = {
        "p": ({"id": i, "k": (i * 7) % 8000, "g": i % 5, "v": i % 11},
              {"k": i % 9 == 4}),
        "q": ({"id": h, "k": np.where(h % 33 == 0, 7, h * 5), "g": h % 4,
               "v": h % 13}, {}),
        "b": ({"id": j, "k": bk, "w": j % 3}, {}),
    }
    for name, (data, nulls) in tables.items():
        info = tk.domain.infoschema().table_by_name("test", name)
        n = len(data["id"])
        tk.domain.columnar_cache.install_bulk(
            info, {c.id: Column(c.ftype, data[c.name],
                                nulls.get(c.name, np.zeros(n, dtype=bool)))
                   for c in info.public_columns()},
            np.arange(1, n + 1, dtype=np.int64),
            content_tag=f"test_join_expand_rows/{name}")
    tk.must_exec("set tidb_device_dispatch_rows = 1")
    tk.must_exec("set tidb_result_cache = 'OFF'")
    return tk


# unmatched and NULL-keyed probe rows, null-extended
_LEFT = ("select p.g, count(*), count(b.id), sum(b.w), sum(p.v) from p "
         "left join b on p.k = b.k group by p.g order by p.g")
# sixty-one probe rows on a key of 600 build rows: 36,600 rows and more
# where the index's average match count sizes the first run at 8,192
_HOT = ("select b.w, q.g, count(*), sum(q.v) from q join b on q.k = b.k "
        "group by b.w, q.g order by b.w, q.g")
# 298 probe rows survive, none on the hot key
_FEW = ("select b.w, count(*), sum(p.v) from p join b on p.k = b.k "
        "where p.id >= 2 and p.id < 300 group by b.w order by b.w")
#: (sql, probe rows' bucket, learned slots, capacity reruns of the first
#: execution: the hot key overflows its estimate, the few rows are far
#: under theirs)
_SHAPES = {"left_outer": (_LEFT, 46341, 65536, 0),
           "hot_key": (_HOT, 2048, 65536, 1),
           "few_rows": (_FEW, 46341, 256, 1)}


def _parity(tk, sql):
    tk.must_exec("set tidb_executor_engine = 'host'")
    want = tk.must_query(sql).rows
    tk.must_exec("set tidb_executor_engine = 'tpu'")
    got = tk.must_query(sql).rows
    engines = _notes(tk, sql, "engine:")
    tk.must_exec("set tidb_executor_engine = 'host'")
    assert got and got == want
    assert engines == ["engine:tpu"]


@pytest.mark.parametrize("side", [True, False])
@pytest.mark.parametrize("shape", list(_SHAPES))
def test_expansions_answer_as_the_host_on_either_side(tk, monkeypatch,
                                                      shape, side):
    """Each expansion through programs forced to either side: the first
    execution (sized by the estimate, run again where that overflowed or
    was far too wide) and the settled one, row for row the host's."""
    sql, _n_probe, slots, reruns = _SHAPES[shape]
    monkeypatch.setattr(dj, "expand_one_pass", lambda _c, _n: side)
    _drop_compiled()
    before = _pipelines(tk)
    tk.must_exec("set tidb_executor_engine = 'tpu'")
    assert tk.must_query(sql).rows
    first = _pipelines(tk)
    assert _grew(before, first, "capacity_reruns", "join_expand",
                 "join_expand_one_pass") == [reruns, 1, int(side)]
    _parity(tk, sql)
    after = _pipelines(tk)
    # the note's EXPLAIN ANALYZE is an execution too
    assert _grew(first, after, "capacity_reruns", "join_expand",
                 "join_expand_capacity", "join_expand_one_pass") == [
        0, 2, 2 * slots, 2 * int(side)]
    _drop_compiled()


@pytest.mark.parametrize("shape,one_pass", [
    ("left_outer", True), ("hot_key", True),
    # 256 learned slots over the 46,341-row bucket are searched
    ("few_rows", False)])
def test_the_rule_places_each_expansion_by_its_shapes(tk, shape, one_pass):
    sql, n_probe, slots, _reruns = _SHAPES[shape]
    assert dj.expand_one_pass(slots, n_probe) is one_pass
    _drop_compiled()
    _parity(tk, sql)
    before = _pipelines(tk)
    _parity(tk, sql)
    after = _pipelines(tk)
    assert _grew(before, after, "join_expand", "join_expand_capacity",
                 "join_expand_one_pass", "capacity_reruns") == [
        2, 2 * slots, 2 * int(one_pass), 0]
    tk.must_exec("set tidb_executor_engine = 'tpu'")
    note, = _notes(tk, sql, "join:")
    tk.must_exec("set tidb_executor_engine = 'host'")
    kinds = "left x1, " * (shape == "left_outer")
    assert note == (f"join:direct x1 ({kinds}expand x1"
                    + " one-pass" * one_pass + ")")
    _drop_compiled()


def test_the_dispatcher_asks_the_rule_what_the_trace_asked(tk, monkeypatch):
    """The counter's side is the program's: `device_join_agg` asks
    expand_one_pass with the kept run's capacity and the probe side's
    capacity, the pair `_expand_rows` asked it with at trace time."""
    asked = []
    orig = dj.expand_one_pass

    def spy(cap, n_probe):
        asked.append((int(cap), int(n_probe)))
        return orig(cap, n_probe)
    monkeypatch.setattr(dj, "expand_one_pass", spy)
    _drop_compiled()
    tk.must_exec("set tidb_executor_engine = 'tpu'")
    assert tk.must_query(_LEFT).rows
    tk.must_exec("set tidb_executor_engine = 'host'")
    # the estimate (the bucket's rows x the index's average match count x
    # 1.5, and a slot a probe row) holds the 44,000 rows: one program,
    # traced once and counted once
    assert asked == [(262144, 46341)] * 2
    _drop_compiled()
