"""The two sides of ops/device._group_spans: one sort at input length
against a binary search per output slot.

Both turn the sort arm's boundary flags into the same `starts` (the
position of the g-th set flag, n past the last group); which one a
program traces is decided by ``dev.spans_one_pass(capacity, n)`` from the
two static shapes alone.  The span arithmetic is compared with the rule
patched to either side; ``_agg_impl`` is compared on shapes that fall on
either side by themselves; the rule and its counter have their own cases
at the end.
"""

import json
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from tidb_tpu.executor import device_exec  # noqa: E402
from tidb_tpu.ops import device as dev  # noqa: E402
from tidb_tpu.testkit import TestKit  # noqa: E402


# -- the span arithmetic, side against side ----------------------------------

def _flags(case, n):
    """(is_new, kept) as the sort arm hands them over: flags only among
    the first `kept` rows."""
    rng = np.random.default_rng(n)
    pos = np.arange(n)
    if case == "no_kept_row":
        return np.zeros(n, dtype=bool), 0
    if case == "first_flag_past_row_0":
        kept = n - n // 4
        f = (rng.random(n) < 0.2) & (pos < kept)
        f[:3] = False
        f[3] = True
        return f, kept
    if case == "every_row_a_group":
        return np.ones(n, dtype=bool), n
    if case == "one_group":
        f = np.zeros(n, dtype=bool)
        f[0] = True
        return f, n - 1
    assert case == "some"
    kept = n - n // 3
    f = (rng.random(n) < 0.3) & (pos < kept)
    f[0] = True
    return f, kept


def _spans(monkeypatch, side, is_new, kept, n, capacity):
    monkeypatch.setattr(dev, "spans_one_pass", lambda _c, _n: side)

    def f(flags, kept_d):
        starts, ends, end_idx, span_sum = dev._group_spans(
            flags, kept_d, n, capacity)
        z = jnp.arange(n, dtype=jnp.int64) * 7 - 3
        return starts, ends, end_idx, span_sum(z)
    return jax.device_get(jax.jit(f)(jnp.asarray(is_new),
                                     jnp.asarray(np.int64(kept))))


# n: a power of two and two that are not; capacity under, equal to and
# above n, and under the group count (overflow: the caller reads
# n_groups, the spans stay equal all the same)
@pytest.mark.parametrize("n,capacity", [
    (1024, 64), (1024, 1024), (1024, 2048), (1000, 256), (1000, 1000),
    (1000, 1003), (777, 16)])
@pytest.mark.parametrize("case", [
    "no_kept_row", "first_flag_past_row_0", "every_row_a_group",
    "one_group", "some"])
def test_both_sides_give_the_same_spans(monkeypatch, case, n, capacity):
    is_new, kept = _flags(case, n)
    one_pass = _spans(monkeypatch, True, is_new, kept, n, capacity)
    search = _spans(monkeypatch, False, is_new, kept, n, capacity)
    for a, b in zip(one_pass, search):
        assert a.dtype == b.dtype and a.shape == b.shape == (capacity,)
        assert np.array_equal(a, b)
    # and both are what the flags say: the g-th set flag, then n
    where = np.flatnonzero(is_new)[:capacity]
    want = np.full(capacity, n, dtype=np.int64)
    want[:len(where)] = where
    assert np.array_equal(one_pass[0], want)


def test_an_empty_input_pads_with_n(monkeypatch):
    for side in (True, False):
        starts, ends, _ei, sums = _spans(
            monkeypatch, side, np.zeros(0, dtype=bool), 0, 0, 4)
        assert starts.tolist() == ends.tolist() == sums.tolist() == [0] * 4


def test_the_one_pass_side_traces_no_loop(monkeypatch):
    """One sort of int32 positions, no `while`, no cumsum for the group
    id; the searched side is the loop of dependent gathers."""
    def text(side):
        monkeypatch.setattr(dev, "spans_one_pass", lambda _c, _n: side)
        return jax.jit(
            lambda f, k: dev._group_spans(f, k, 4096, 1024)[0]).lower(
            jnp.zeros(4096, dtype=bool), jnp.int64(0)).as_text()
    one_pass, search = text(True), text(False)
    assert "stablehlo.while" not in one_pass
    assert one_pass.count("stablehlo.sort") == 1
    # unstable: a stable sort carries a second operand on the chip
    assert "is_stable = false" in one_pass
    assert "tensor<4096xi32>" in one_pass
    assert "stablehlo.while" in search and "stablehlo.sort" not in search


# -- _agg_impl on shapes that fall on either side by themselves --------------

_N = dev._SPANS_ONE_PASS_MIN_ROWS        # the shortest input that may sort
_CAP_SORT = _N // 4                      # Q18's ratio: the one-pass side
_CAP_SEARCH = 512                        # few slots over many rows: search


def _agg(keys, key_nulls, vals, val_nulls, mask, ops, capacity, pack):
    out = jax.jit(
        lambda *a: dev._agg_impl(*a, n_keys=len(keys), agg_ops=ops,
                                 capacity=capacity, pack=pack))(
        tuple(jnp.asarray(k) for k in keys),
        tuple(jnp.asarray(k) for k in key_nulls),
        tuple(jnp.asarray(v) for v in vals),
        tuple(jnp.asarray(v) for v in val_nulls),
        jnp.asarray(mask))
    return jax.device_get(out)


def _live(out):
    ng = int(out[4])
    return ng, [np.asarray(x)[:ng] for x in
                jax.tree_util.tree_leaves(out[:4])]


def _agg_inputs(op, groups, packed):
    rng = np.random.default_rng(groups)
    key = rng.integers(0, groups, _N).astype(np.int64)
    key_null = rng.random(_N) < 0.01
    if op == "sum_f":
        v = rng.normal(size=_N)
    else:
        v = rng.integers(-10**6, 10**6, _N).astype(np.int64)
    if op == "cnt_dist":
        v = v % 5
    vn = rng.random(_N) < 0.25
    mask = rng.random(_N) < 0.7
    pack = ((int(groups).bit_length() + 1, 0),) if packed else None
    return (key,), (key_null,), (v,), (vn,), mask, pack


@pytest.mark.parametrize("packed", [True, False])
@pytest.mark.parametrize("op", ["count", "sum_i", "min", "max", "sum_f",
                                "first", "cnt_dist"])
def test_agg_impl_is_bit_equal_on_either_side_of_the_rule(op, packed):
    """The same rows through a program with few slots (the rule searches)
    and one with a slot for every fourth row (the rule sorts): every
    live slot bit-equal, float sums included (the spans are the same
    spans, so the segmented scan adds in the same order)."""
    assert dev.spans_one_pass(_CAP_SORT, _N)
    assert not dev.spans_one_pass(_CAP_SEARCH, _N)
    keys, key_nulls, vals, val_nulls, mask, pack = _agg_inputs(
        op, 300, packed)
    sort = _agg(keys, key_nulls, vals, val_nulls, mask, (op,), _CAP_SORT,
                pack)
    search = _agg(keys, key_nulls, vals, val_nulls, mask, (op,),
                  _CAP_SEARCH, pack)
    ng, a = _live(sort)
    ng_b, b = _live(search)
    assert ng == ng_b == 301          # 300 values and NULL's own group
    for x, y in zip(a, b):
        assert x.dtype == y.dtype and np.array_equal(x, y)


@pytest.mark.parametrize("op", ["count", "sum_i", "min", "max", "first"])
def test_a_group_every_few_rows_equals_numpy(op):
    """Q18's shape in small: about a group every four rows, through the
    one-pass side, against numpy."""
    groups = _CAP_SORT - 2000
    keys, key_nulls, vals, val_nulls, mask, pack = _agg_inputs(
        op, groups, True)
    key_nulls = (np.zeros(_N, dtype=bool),)
    out = _agg(keys, key_nulls, vals, val_nulls, mask, (op,), _CAP_SORT,
               pack)
    ng, (k, kn, res, res_null) = _live(out)
    key, v, vn = keys[0][mask], vals[0][mask], val_nulls[0][mask]
    uniq, first_row, inv = np.unique(key, return_index=True,
                                     return_inverse=True)
    assert ng == len(uniq) and np.array_equal(k, uniq) and not kn.any()
    nonnull = np.bincount(inv, weights=~vn, minlength=ng).astype(np.int64)
    if op == "count":
        want, want_null = nonnull, np.zeros(ng, dtype=bool)
    elif op == "sum_i":
        want = np.zeros(ng, dtype=np.int64)
        np.add.at(want, inv[~vn], v[~vn])
        want_null = nonnull == 0
    elif op == "first":
        want, want_null = v[first_row], vn[first_row]
    else:
        fill = np.iinfo(np.int64).max if op == "min" else \
            np.iinfo(np.int64).min
        want = np.full(ng, fill, dtype=np.int64)
        (np.minimum if op == "min" else np.maximum).at(
            want, inv[~vn], v[~vn])
        want_null = nonnull == 0
    assert np.array_equal(res_null, want_null)
    assert np.array_equal(res[~want_null], want[~want_null])


def test_more_groups_than_slots_still_reports_the_count():
    """Overflow on the one-pass side: n_groups says how many there were,
    the caller retries."""
    keys, key_nulls, vals, val_nulls, mask, pack = _agg_inputs(
        "sum_i", _CAP_SORT * 2, True)
    out = _agg(keys, key_nulls, vals, val_nulls, mask, ("sum_i",),
               _CAP_SORT, pack)
    want = len(np.unique(np.where(key_nulls[0], -1, keys[0])[mask]))
    assert int(out[4]) == want > _CAP_SORT
    assert out[5].all()


# -- the rule -----------------------------------------------------------------

@pytest.mark.parametrize("capacity,n,one_pass", [
    (16384, 8388608, False),      # TPC-H Q3 at SF1: 16,384 slots search
    (2097152, 8388608, True),     # Q18's inner aggregate at SF1 sorts
    (64, 8388608, False),         # Q18's first program
    (1024, 4194304, False),       # an SSB page
    (16384, 2097152, False),      # the mesh's shard of Q3
    (16384, 65536, False),        # the mesh's merge of four partials
    (1024, 15360, False),         # the fold of fifteen pages' states
    (32768, 131072, True),        # Q18's inner aggregate at SF0.02
    (131072, 131072, True),
    (262144, 131072, True),       # a capacity above n pads with n
    (131072, 131071, False),      # under the floor
])
def test_the_rule_at_the_cells_shapes(capacity, n, one_pass):
    assert dev.spans_one_pass(capacity, n) is one_pass


def test_the_rule_is_the_two_prices():
    """capacity x ceil(log2 n) searched rows at _SPANS_SEARCH_PRICE
    sorted rows each against n sorted rows, from the floor up."""
    n = 1 << 23
    least = -(-n // (23 * dev._SPANS_SEARCH_PRICE))
    assert dev.spans_one_pass(least, n)
    assert not dev.spans_one_pass(least - 1, n)
    # not a power of two: the steps round up to 24, so fewer slots tip
    odd = -(-(n + 1) // (24 * dev._SPANS_SEARCH_PRICE))
    assert odd < least
    assert dev.spans_one_pass(odd, n + 1)
    assert not dev.spans_one_pass(odd - 1, n + 1)
    # more slots never go back to the search, numpy integers are fine
    assert all(dev.spans_one_pass(np.int64(c), np.int64(n))
               for c in (least, n // 2, n, 2 * n))


def test_the_rule_reads_its_arguments_only(monkeypatch):
    def no_backend():
        raise AssertionError("spans_one_pass asked for the backend")
    monkeypatch.setattr(jax, "default_backend", no_backend)
    monkeypatch.setattr(jax, "devices", no_backend)
    assert dev.spans_one_pass(2097152, 8388608)
    assert not dev.spans_one_pass(16384, 8388608)


def test_group_spans_asks_the_rule_with_its_static_shapes(monkeypatch):
    asked = []
    orig = dev.spans_one_pass

    def spy(capacity, n):
        asked.append((capacity, n))
        return orig(capacity, n)
    monkeypatch.setattr(dev, "spans_one_pass", spy)
    jax.jit(lambda f, k: dev._group_spans(f, k, 2048, 128)[0]).lower(
        jnp.zeros(2048, dtype=bool), jnp.int64(0))
    assert asked == [(128, 2048)]


# -- the counter --------------------------------------------------------------

def _counter():
    return device_exec.pipe_cache_stats()["agg_spans_one_pass"]


@pytest.mark.parametrize("pack,ops,capacity,n,gathered,grows", [
    (None, ("sum_i",), 2097152, 8388608, False, 1),       # Q18's second
    (None, ("sum_i",), 64, 8388608, False, 0),            # Q18's first
    (((40, 0),), ("sum_i",), 16384, 8388608, True, 0),    # Q3
    (((40, 0),), ("sum_i",), 2097152, 8388608, True, 1),  # a join that sorts
    (((5, 0),), ("sum_i",), 32, 8388608, False, 0),       # the dense arm
    (((5, 0),), ("sum_i",), 32, 64, False, 0),
    # a dense-sized key space whose inputs are gathered takes the sort arm
    (((5, 0),), ("sum_i",), 131072, 131072, True, 1),
])
def test_note_agg_spans_counts_the_one_pass_programs(pack, ops, capacity, n,
                                                     gathered, grows):
    before = _counter()
    device_exec.note_agg_spans(pack, ops, capacity, n, gathered=gathered)
    assert _counter() - before == grows


@pytest.fixture(scope="module")
def orders_tk():
    """131,072 rows, a group every four rows: the shortest scan whose
    aggregate takes the one-pass side at its learned capacity."""
    from tidb_tpu.utils.chunk import Column
    tk = TestKit()
    tk.must_exec("create table t (k bigint, v bigint)")
    n = dev._SPANS_ONE_PASS_MIN_ROWS
    info = tk.domain.infoschema().table_by_name("test", "t")
    i = np.arange(n, dtype=np.int64)
    data = {"k": i // 4 * 3, "v": i % 7}
    tk.domain.columnar_cache.install_bulk(
        info, {c.id: Column(c.ftype, data[c.name], np.zeros(n, dtype=bool))
               for c in info.public_columns()},
        np.arange(1, n + 1, dtype=np.int64),
        content_tag=f"test_group_spans/t/n{n}")
    tk.must_exec("set tidb_result_cache = 'OFF'")
    return tk


def _pipelines(tk):
    return json.loads(tk.must_query("DIAG STATUS").rows[0][0])[
        "device_pipelines"]


def test_a_group_every_four_rows_counts_and_equals_the_host(orders_tk,
                                                           monkeypatch):
    """A scan aggregate with one group a few rows, as Q18's subquery is:
    its first program (the estimated capacity) may search, the program at
    the learned capacity sorts; DIAG STATUS lists the counter; the
    counter's shapes are the shapes the program was traced with."""
    tk = orders_tk
    sql = ("select k, sum(v), count(*) from t group by k "
           "having sum(v) > 11 order by k")
    tk.must_exec("set tidb_executor_engine = 'host'")
    want = tk.must_query(sql).rows
    assert len(want) > 1000
    traced, noted = [], []
    orig = dev.spans_one_pass

    def spy(capacity, n):
        import inspect
        caller = inspect.stack()[1].function
        (traced if caller == "_group_spans" else noted).append(
            (int(capacity), int(n), orig(capacity, n)))
        return orig(capacity, n)
    monkeypatch.setattr(dev, "spans_one_pass", spy)
    with device_exec._PIPE_LOCK:
        device_exec._PIPE_CACHE.clear()
    tk.must_exec("set tidb_executor_engine = 'tpu'")
    before = _pipelines(tk)
    assert "agg_spans_one_pass" in before
    assert tk.must_query(sql).rows == want
    after = _pipelines(tk)
    assert after["agg_sorted"] - before["agg_sorted"] == 1
    assert after["agg_spans_one_pass"] - before["agg_spans_one_pass"] == sum(
        side for _c, _n, side in noted) >= 1
    assert (32768, 131072, True) in noted
    # every program the fragment dispatched was traced at the shapes the
    # dispatcher counted it by
    assert set(noted) == set(traced)
