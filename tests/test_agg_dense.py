"""The dense arm of ops/device._agg_impl against the sort arm.

Small packed key spaces aggregate by one masked reduction per bucket
(``_agg_dense_impl``); everything else sorts.  Both arms answer to one
contract — groups in packed-key order, the first kept row represents a
group, integer sums wrap in int64, result_null = no non-null kept row —
so on the same inputs every live output slot must be bit-equal.  Each arm
is forced by patching ``dev.agg_arm``; the selection itself has its own
cases at the end.
"""

import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from tidb_tpu.executor import device_exec  # noqa: E402
from tidb_tpu.ops import device as dev  # noqa: E402

_I64 = np.iinfo(np.int64)


def _run(monkeypatch, arm, keys, key_nulls, vals, val_nulls, mask, ops,
         pack, capacity=64):
    monkeypatch.setattr(dev, "agg_arm", lambda _p, _o, _g=False: arm)
    out = jax.jit(
        lambda *a: dev._agg_impl(*a, n_keys=len(keys), agg_ops=ops,
                                 capacity=capacity, pack=pack))(
        tuple(jnp.asarray(k) for k in keys),
        tuple(jnp.asarray(k) for k in key_nulls),
        tuple(jnp.asarray(v) for v in vals),
        tuple(jnp.asarray(v) for v in val_nulls),
        jnp.asarray(mask))
    return jax.device_get(out)


def _assert_same(dense, sort):
    """Bit-equal over the live groups (slots past n_groups hold garbage
    in both arms), same dtypes everywhere, same n_groups and valid."""
    ng = int(dense[4])
    assert ng == int(sort[4])
    assert np.array_equal(dense[5], sort[5])
    a = jax.tree_util.tree_leaves(dense[:4])
    b = jax.tree_util.tree_leaves(sort[:4])
    assert len(a) == len(b)
    live = min(ng, a[0].shape[0])
    for x, y in zip(a, b):
        assert x.dtype == y.dtype and x.shape == y.shape
        assert np.array_equal(x[:live], y[:live])
    return ng


def _inputs(seed, n=4096, key_bits=(3, 2), null_keys=True):
    rng = np.random.default_rng(seed)
    keys, key_nulls = [], []
    for bits in key_bits:
        # values 0 .. 2^bits - 2: with the +1 shift they fill the key's
        # slots, slot 0 is NULL's
        keys.append(rng.integers(0, (1 << bits) - 1, n).astype(np.int64))
        key_nulls.append(rng.random(n) < 0.1 if null_keys
                         else np.zeros(n, dtype=bool))
    pack = tuple((bits, 0) for bits in key_bits)
    v = rng.integers(-10**6, 10**6, n).astype(np.int64)
    vn = rng.random(n) < 0.25
    mask = rng.random(n) < 0.6
    return keys, key_nulls, pack, v, vn, mask


@pytest.mark.parametrize("op", ["count", "sum_i", "min", "max", "first"])
@pytest.mark.parametrize("seed", [1, 2])
def test_each_op_is_bit_equal(monkeypatch, op, seed):
    """NULL keys (their own group), NULL values, a filter mask."""
    keys, key_nulls, pack, v, vn, mask = _inputs(seed)
    args = (keys, key_nulls, (v,), (vn,), mask, (op,), pack)
    ng = _assert_same(_run(monkeypatch, "dense", *args),
                      _run(monkeypatch, "sort", *args))
    assert ng > 8


def test_all_ops_together_share_null_rows(monkeypatch):
    """Q1's shape: sum + count over one column (avg) share the non-null
    indicator; several inputs, float min/max beside the integers."""
    keys, key_nulls, pack, v, vn, mask = _inputs(3)
    rng = np.random.default_rng(33)
    w = rng.integers(0, 100, v.shape[0]).astype(np.int64)
    wn = np.zeros(v.shape[0], dtype=bool)
    f = rng.random(v.shape[0])
    ops = ("sum_i", "count", "sum_i", "count", "min", "max", "first",
           "min", "max")
    vals = (v, v, w, w, v, v, v, f, f)
    nulls = (vn, vn, wn, wn, vn, vn, vn, vn, vn)
    args = (keys, key_nulls, vals, nulls, mask, ops, pack)
    _assert_same(_run(monkeypatch, "dense", *args),
                 _run(monkeypatch, "sort", *args))


def test_mask_empties_some_buckets(monkeypatch):
    """Buckets whose every row is filtered out are not groups; the live
    ones compact in packed-key order."""
    keys, key_nulls, pack, v, vn, mask = _inputs(4, key_bits=(4,))
    mask = mask & (keys[0] % 3 != 0)
    args = (keys, key_nulls, (v, v), (vn, vn), mask, ("sum_i", "first"),
            pack)
    dense = _run(monkeypatch, "dense", *args)
    ng = _assert_same(dense, _run(monkeypatch, "sort", *args))
    live_keys = dense[0][0][:ng][~dense[1][0][:ng]]
    assert ng < 15 and np.all(live_keys % 3 != 0)
    assert np.all(np.diff(live_keys) > 0)


def test_group_whose_values_are_all_null(monkeypatch):
    """A live group with no non-null value: sum/min/max are NULL, count
    is 0 and not NULL."""
    keys, key_nulls, pack, v, vn, mask = _inputs(5, key_bits=(3,),
                                                 null_keys=False)
    vn = vn | (keys[0] == 2)
    ops = ("sum_i", "count", "min", "max")
    args = (keys, key_nulls, (v,) * 4, (vn,) * 4, mask, ops, pack)
    dense = _run(monkeypatch, "dense", *args)
    ng = _assert_same(dense, _run(monkeypatch, "sort", *args))
    g = int(np.nonzero(dense[0][0][:ng] == 2)[0][0])
    assert [bool(rn[g]) for rn in dense[3]] == [True, False, True, True]
    assert int(dense[2][1][g]) == 0


def test_zero_kept_rows(monkeypatch):
    keys, key_nulls, pack, v, vn, mask = _inputs(6)
    args = (keys, key_nulls, (v, v), (vn, vn), np.zeros_like(mask),
            ("sum_i", "min"), pack)
    dense = _run(monkeypatch, "dense", *args)
    assert _assert_same(dense, _run(monkeypatch, "sort", *args)) == 0
    assert not dense[5].any()


@pytest.mark.parametrize("kept", ["some", "none"])
def test_no_group_key_is_one_group(monkeypatch, kept):
    """Q6's shape: _plan_agg gives a keyless aggregate the constant key 0
    under pack ((1, 0),): bucket 1 of 2."""
    _k, _kn, _p, v, vn, mask = _inputs(7)
    n = v.shape[0]
    if kept == "none":
        mask = np.zeros_like(mask)
    args = ([np.zeros(n, dtype=np.int64)], [np.zeros(n, dtype=bool)],
            (v, v, v), (vn, vn, vn), mask, ("sum_i", "count", "max"),
            ((1, 0),))
    dense = _run(monkeypatch, "dense", *args, capacity=16)
    ng = _assert_same(dense, _run(monkeypatch, "sort", *args, capacity=16))
    assert ng == (1 if kept == "some" else 0)
    if ng:
        keep = mask & ~vn
        assert int(dense[2][0][0]) == int(v[keep].sum())
        assert int(dense[2][1][0]) == int(keep.sum())


def test_sums_wrap_past_int64_the_same_way(monkeypatch):
    keys, key_nulls, pack, _v, vn, mask = _inputs(8, key_bits=(2,))
    rng = np.random.default_rng(88)
    v = rng.integers(_I64.max // 4, _I64.max, keys[0].shape[0],
                     dtype=np.int64)
    v[::3] = -v[::3]
    args = (keys, key_nulls, (v,), (vn,), mask, ("sum_i",), pack)
    dense = _run(monkeypatch, "dense", *args)
    ng = _assert_same(dense, _run(monkeypatch, "sort", *args))
    # the reference wraps too, and at least one group did overflow
    exact = [sum(int(x) for x in v[mask & ~vn & ~key_nulls[0]
                                   & (keys[0] == k)])
             for k in dense[0][0][:ng][~dense[1][0][:ng]]]
    assert any(not _I64.min <= e <= _I64.max for e in exact)
    got = dense[2][0][:ng][~dense[1][0][:ng]]
    assert [int(g) for g in got] == [
        (e + 2**63) % 2**64 - 2**63 for e in exact]


def test_more_groups_than_capacity_reports_the_count(monkeypatch):
    """The caller's retry contract: n_groups > capacity is reported, the
    outputs keep the capacity's shape."""
    keys, key_nulls, pack, v, vn, mask = _inputs(9, key_bits=(5,))
    args = (keys, key_nulls, (v, v), (vn, vn), mask, ("sum_i", "first"),
            pack)
    dense = _run(monkeypatch, "dense", *args, capacity=8)
    sort = _run(monkeypatch, "sort", *args, capacity=8)
    assert int(dense[4]) == int(sort[4]) > 8
    assert dense[2][0].shape == (8,) and dense[5].all()
    # the groups that fit are the first in packed-key order, as sorted
    # (the sort arm's LAST slot runs on to the end of the kept rows; on
    # an overflow the caller reads the count and never the body)
    for x, y in zip(jax.tree_util.tree_leaves(dense[:4]),
                    jax.tree_util.tree_leaves(sort[:4])):
        assert np.array_equal(x[:7], y[:7])
    grown = _run(monkeypatch, "dense", *args,
                 capacity=dev.next_pow2(int(dense[4])))
    _assert_same(grown, _run(monkeypatch, "sort", *args,
                             capacity=dev.next_pow2(int(dense[4]))))


def test_offset_keys_pack_like_the_sort_arm(monkeypatch):
    """A key packed from its column's min/max: (bits, -min)."""
    rng = np.random.default_rng(10)
    n = 3000
    k = rng.integers(9_000, 9_050, n).astype(np.int64)
    kn = rng.random(n) < 0.05
    v = rng.integers(0, 1000, n).astype(np.int64)
    vn = np.zeros(n, dtype=bool)
    pack = ((6, -9_000),)
    args = ([k], [kn], (v, v), (vn, vn), np.ones(n, dtype=bool),
            ("sum_i", "min"), pack)
    ng = _assert_same(_run(monkeypatch, "dense", *args),
                      _run(monkeypatch, "sort", *args))
    assert ng == 51    # 50 values and NULL


# -- which arm runs ----------------------------------------------------------

def _bits_of(buckets):
    return (buckets - 1).bit_length()


def test_arm_at_the_threshold_and_one_bit_over():
    bits = _bits_of(dev._DENSE_AGG_BUCKETS)
    ops = ("sum_i", "count", "min", "max", "first")
    assert dev.agg_arm(((bits, 0),), ops) == "dense"
    assert dev.agg_arm(((bits - 2, 0), (2, 5)), ops) == "dense"
    assert dev.agg_arm(((bits + 1, 0),), ops) == "sort"
    assert dev.agg_arm(((bits - 1, 0), (2, 0)), ops) == "sort"
    assert dev.agg_arm(None, ops) == "sort"


@pytest.mark.parametrize("op", ["sum_f", "cnt_dist"])
def test_ops_the_dense_arm_leaves_to_the_sort(op):
    assert dev.agg_arm(((2, 0),), ("sum_i", op)) == "sort"


def test_arm_does_not_ask_the_backend_below_the_bound(monkeypatch):
    """agg_arm reads its arguments only: below the bound, above it, with
    gathered inputs or without a packing, it never asks for the backend."""
    def boom():
        raise AssertionError("agg_arm asked for the backend")
    monkeypatch.setattr(jax, "default_backend", boom)
    assert dev.agg_arm(((1, 0),), ("sum_i",)) == "dense"
    bits = _bits_of(dev._DENSE_AGG_BUCKETS) + 1
    assert dev.agg_arm(((bits, 0),), ("sum_i",)) == "sort"
    assert dev.agg_arm(((bits, 0),), ("cnt_dist",)) == "sort"
    assert dev.agg_arm(((1, 0),), ("sum_i",), gathered=True) == "sort"
    assert dev.agg_arm(None, ("sum_i",)) == "sort"


@pytest.mark.parametrize("backend", ["tpu", "cpu"])
def test_gathered_inputs_keep_the_arm_they_had(monkeypatch, backend):
    """A join fragment's aggregate (inputs out of the probe's gather
    chain) is not the dense arm's, whatever its key space and whatever
    the backend is called."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert dev.agg_arm(((1, 0),), ("sum_i",), gathered=True) == "sort"
    assert dev.agg_arm(((1, 0),), ("sum_i",)) == "dense"


def test_one_bit_over_the_threshold_traces_the_sort():
    """_agg_impl asks agg_arm: a key space one bit past the bound lowers
    to a program with a sort, at the bound to one without."""
    bits = _bits_of(dev._DENSE_AGG_BUCKETS)
    n = 256

    def hlo(b):
        k = jnp.zeros(n, dtype=jnp.int64)
        z = jnp.zeros(n, dtype=bool)
        return jax.jit(lambda: dev._agg_impl(
            (k,), (z,), (k,), (z,), ~z, n_keys=1, agg_ops=("sum_i",),
            capacity=16, pack=((b, 0),))).lower().as_text()
    assert "stablehlo.sort" not in hlo(bits)
    assert "stablehlo.sort" in hlo(bits + 1)


# -- partial states ----------------------------------------------------------

def test_merge_partial_states_folds_densely(monkeypatch):
    """The streamed path's fold (concatenated partial states through
    _agg_impl with the merge ops) under a small pack: the chip's arm,
    and the same state the sort arm folds to."""
    keys, key_nulls, pack, v, vn, mask = _inputs(11, n=6000)
    ops = ("count", "sum_i", "min", "max", "first")
    merge_ops = tuple(device_exec._MERGE_OPS[o] for o in ops)
    assert dev.agg_arm(pack, ops) == dev.agg_arm(pack, merge_ops) == "dense"

    def fold(arm):
        monkeypatch.setattr(dev, "agg_arm", lambda _p, _o, _g=False: arm)
        parts = []
        for lo in range(0, 6000, 2000):
            sl = slice(lo, lo + 2000)
            parts.append(dev._agg_impl(
                tuple(jnp.asarray(k[sl]) for k in keys),
                tuple(jnp.asarray(k[sl]) for k in key_nulls),
                tuple(jnp.asarray(v[sl]) for _ in ops),
                tuple(jnp.asarray(vn[sl]) for _ in ops),
                jnp.asarray(mask[sl]), n_keys=2, agg_ops=ops, capacity=32,
                pack=pack))
        state, cap = device_exec.merge_partial_states(
            parts[0], parts[1:], 32, 2, len(ops), merge_ops, pack)
        assert cap == 32
        return jax.device_get(state)

    dense = fold("dense")
    ng = _assert_same(dense, fold("sort"))
    # and both equal the one-pass aggregate over all the rows
    whole = _run(monkeypatch, "sort", keys, key_nulls, (v,) * 5, (vn,) * 5,
                 mask, ops, pack, capacity=32)
    assert ng == int(whole[4])
    for x, y in zip(jax.tree_util.tree_leaves(dense[:4]),
                    jax.tree_util.tree_leaves(whole[:4])):
        assert np.array_equal(x[:ng], y[:ng])
