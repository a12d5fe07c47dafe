"""ANALYZE built from array operations (ISSUE 27): every statistic but
the sketch equals what the per-value code of PR 26 and before computed,
the sketch never under-counts, and a column that carries a dictionary is
analysed from its codes.

The plain reference below is that older code, kept here verbatim: one
blake2b per distinct value, a Python loop for the TopN remainder, a full
sort for the histogram, `np.unique` over decoded strings.
"""

import hashlib
import pathlib
import sys

import numpy as np
import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from benchmark.datasets import tpch  # noqa: E402
from benchmark.harness import install  # noqa: E402
from tidb_tpu.sqltypes import FieldType, TYPE_LONGLONG  # noqa: E402
from tidb_tpu.statistics import analyze, selectivity  # noqa: E402
from tidb_tpu.statistics.analyze import (  # noqa: E402
    CM_DEPTH, CM_VERSION, CM_WIDTH, HIST_BUCKETS, TOPN_SIZE, _val_key,
    build_cmsketch, cm_query)
from tidb_tpu.testkit import TestKit  # noqa: E402
from tidb_tpu.utils.chunk import Column  # noqa: E402

SEEDS = (1234567, 3000000019)
BULK_TABLES = [t for t in tpch.SCHEMA if t not in tpch.SQL_TABLES]


# -- the plain reference: statistics/analyze.py as of PR 26 -------------------

def _old_cm_indices(key):
    if isinstance(key, float) and key.is_integer():
        key = int(key)
    if isinstance(key, float):
        key = key.hex()
    digest = hashlib.blake2b(str(key).encode(), digest_size=16).digest()
    h1 = int.from_bytes(digest[:8], "little")
    h2 = int.from_bytes(digest[8:], "little") | 1
    return [((h1 + d * h2) & 0xFFFFFFFFFFFFFFFF) % CM_WIDTH
            for d in range(CM_DEPTH)]


def _old_build_cmsketch(values, counts):
    rows = [[0] * CM_WIDTH for _ in range(CM_DEPTH)]
    for v, c in zip(values, counts):
        for d, idx in enumerate(_old_cm_indices(_val_key(v))):
            rows[d][idx] += int(c)
    return rows


def _old_column_stats(col):
    nn = ~col.nulls
    data = col.data[nn]
    cs = {"null_count": int(col.nulls.sum())}
    if not len(data):
        cs["ndv"] = 0
        return cs
    uniques, counts = np.unique(data, return_counts=True)
    cs["ndv"] = int(len(uniques))
    k = min(TOPN_SIZE, len(uniques))
    top = np.argpartition(counts, -k)[-k:]
    top = top[np.argsort(counts[top])[::-1]]
    cs["topn"] = [[_val_key(uniques[i]), int(counts[i])] for i in top]
    top_set = set(top.tolist())
    rest = [i for i in range(len(uniques)) if i not in top_set]
    if rest:
        cs["cmsketch"] = _old_build_cmsketch(uniques[rest], counts[rest])
    if data.dtype != object:
        vals = data.astype(np.float64)
        cs["min"] = float(vals.min())
        cs["max"] = float(vals.max())
        nb = min(HIST_BUCKETS, len(uniques))
        if nb >= 2:
            sv = np.sort(vals)
            pos = ((np.arange(1, nb + 1) * len(sv)) // nb) - 1
            bounds = sv[pos]
            cum = np.searchsorted(sv, bounds, side="right")
            cs["hist"] = {"bounds": [float(b) for b in bounds],
                          "cum": [int(c) for c in cum]}
    return cs


def _but_the_sketch(cs):
    return {k: v for k, v in cs.items() if k != "cmsketch"}


def _true_counts(col):
    uniques, counts = np.unique(col.data[~col.nulls], return_counts=True)
    return uniques, counts


def _check_sketch(col, cs):
    """Never under the true count; absent values may read anything >= 0."""
    if "cmsketch" not in cs:
        return
    assert cs["cmsketch"]["v"] == CM_VERSION
    uniques, counts = _true_counts(col)
    in_top = {k for k, _c in cs["topn"]}
    step = max(len(uniques) // 400, 1)          # a few hundred queries
    for v, c in zip(uniques[::step], counts[::step]):
        key = _val_key(v)
        if key not in in_top:
            assert cm_query(cs["cmsketch"], key) >= c, key
    rows = np.asarray(cs["cmsketch"]["rows"])
    rest = int(counts.sum()) - sum(c for _k, c in cs["topn"])
    assert rows.shape == (CM_DEPTH, CM_WIDTH)
    assert (rows.sum(axis=1) == rest).all()     # every count, once a row


# -- TPC-H at SF0.01, two seeds ----------------------------------------------

@pytest.fixture(scope="module")
def installed():
    """{seed: (TestKit, {table: [(column info, Column)]})}"""
    out = {}
    for seed in SEEDS:
        want = {t: list(tpch.SCHEMA[t]) for t in BULK_TABLES}
        tables = tpch.generate(seed, 0.01, want)
        tk = TestKit()
        tk.must_exec(f"create database {tpch.DB}")
        tk.must_exec(f"use {tpch.DB}")
        cols = {}
        for t in BULK_TABLES:
            tk.must_exec(install.ddl(tpch.SCHEMA, t, want[t]))
            install._bulk_install(tk, tpch.DB, tpch.SCHEMA[t], t, tables[t],
                                  f"test/{seed}")
            info = tk.domain.infoschema().table_by_name(tpch.DB, t)
            cache = tk.session.columnar_cache()
            entry = cache.get(info, tk.session.store.begin())
            public = info.public_columns()
            chunk = cache.project(entry, public, info)
            cols[t] = list(zip(public, chunk.columns))
        out[seed] = (tk, cols)
    return out


@pytest.mark.parametrize("table", BULK_TABLES)
@pytest.mark.parametrize("seed", SEEDS)
def test_every_statistic_but_the_sketch_equals_the_reference(installed,
                                                             seed, table):
    _tk, cols = installed[seed]
    for ci, col in cols[table]:
        new = analyze._column_stats(col)
        old = _old_column_stats(col)
        assert _but_the_sketch(new) == _but_the_sketch(old), ci.name
        assert ("cmsketch" in new) == ("cmsketch" in old), ci.name
        _check_sketch(col, new)


@pytest.mark.parametrize("table", BULK_TABLES)
@pytest.mark.parametrize("seed", SEEDS)
def test_analyze_table_stores_them(installed, seed, table):
    """Through SQL: row_count, per-column statistics and index NDVs as
    stored, and exact equality estimates for TopN values."""
    tk, cols = installed[seed]
    tk.must_exec(f"analyze table {table}")
    info = tk.domain.infoschema().table_by_name(tpch.DB, table)
    stats = tk.session.domain.stats[info.id]
    n = len(cols[table][0][1])
    assert stats["row_count"] == n
    for ci, col in cols[table]:
        cs = stats["columns"][str(ci.id)]
        assert _but_the_sketch(cs) == _but_the_sketch(
            _old_column_stats(col)), ci.name
        for key, count in cs["topn"]:
            assert selectivity._eq_sel(cs, n, key) * n == \
                pytest.approx(count)


@pytest.mark.parametrize("column", ["l_returnflag", "l_shipmode",
                                    "o_orderpriority", "c_name", "p_name"])
def test_dictionary_column_is_counted_from_its_codes(installed, column,
                                                     monkeypatch):
    """No sort of Python objects: `np.unique` never sees the decoded
    strings, and the TopN holds strings."""
    table = {"l": "lineitem", "o": "orders", "c": "customer",
             "p": "part"}[column[0]]
    _tk, cols = installed[SEEDS[0]]
    col = next(c for ci, c in cols[table] if ci.name == column)

    real = np.unique

    def no_objects(arr, *a, **k):
        assert np.asarray(arr).dtype != object, "sorted Python objects"
        return real(arr, *a, **k)
    monkeypatch.setattr(np, "unique", no_objects)
    cs = analyze._column_stats(col)
    monkeypatch.undo()
    assert cs["topn"] and all(isinstance(k, str) for k, _c in cs["topn"])
    assert cs == {**_old_column_stats(col),
                  **({"cmsketch": cs["cmsketch"]} if "cmsketch" in cs
                     else {})}
    assert "hist" not in cs and "min" not in cs


# -- shapes the TPC-H tables do not have --------------------------------------

def _col(data, nulls=None):
    data = np.asarray(data)
    return Column(FieldType(tp=TYPE_LONGLONG), data,
                  None if nulls is None else np.asarray(nulls, dtype=bool))


_RNG = np.random.default_rng(27)
_SHAPES = {
    "small_range_ints": _col(_RNG.integers(-40, 40, 5000)),
    "wide_range_ints": _col(_RNG.integers(-2 ** 62, 2 ** 62, 5000)),
    "past_2_53": _col(2 ** 53 + _RNG.integers(0, 300, 4000)),
    "int32_days": _col(_RNG.integers(8000, 10500, 5000).astype(np.int32)),
    "floats": _col(np.round(_RNG.normal(0, 50, 5000), 1)),
    "skewed": _col(_RNG.zipf(1.3, 5000) % 1000),
    "with_nulls": _col(_RNG.integers(0, 300, 5000),
                       _RNG.random(5000) < 0.2),
    "one_value": _col(np.full(100, 7)),
    "two_values": _col(np.array([3, 3, 9])),
    "all_null": _col(np.zeros(10, dtype=np.int64), np.ones(10)),
    "empty": _col(np.zeros(0, dtype=np.int64)),
    "strings_no_dict": Column(
        FieldType(tp=TYPE_LONGLONG),
        np.array([b"w%03d" % (i % 37) for i in range(900)], dtype=object),
        np.arange(900) % 11 == 0),
    "wide_decimal_objects": Column(
        FieldType(tp=TYPE_LONGLONG),
        np.array([10 ** 25 + (i % 13) for i in range(500)], dtype=object)),
}


@pytest.mark.parametrize("shape", sorted(_SHAPES))
def test_shapes_equal_the_reference(shape):
    col = _SHAPES[shape]
    new = analyze._column_stats(col)
    old = _old_column_stats(col)
    assert _but_the_sketch(new) == _but_the_sketch(old)
    assert ("cmsketch" in new) == ("cmsketch" in old)
    _check_sketch(col, new)


# -- the sketch ---------------------------------------------------------------

@pytest.mark.parametrize("build,query,want", [
    (np.array([2, 7]), 2.0, 20),                 # int build, float query
    (np.array([2.0, 7.5]), 2, 20),               # float build, int query
    (np.array([2.0, 7.5]), np.int64(2), 20),
    (np.array([2.0, 7.5]), 7.5, 7),
    (np.array([2, 7], dtype=np.int32), 7, 7),    # int32 days
    (np.array([b"AIR", b"MAIL"], dtype=object), "AIR", 20),   # TopN key type
    (np.array([b"AIR", b"MAIL"], dtype=object), b"MAIL", 7),
    (np.array([b"a long value of many words", b"x"], dtype=object),
     "a long value of many words", 20),
    (np.array([10 ** 25, 5], dtype=object), 10 ** 25, 20),
    (np.array([10 ** 25, 5], dtype=object), 5.0, 7),
])
def test_query_keys_collide_with_build_keys(build, query, want):
    cm = build_cmsketch(build, np.array([20, 7]))
    assert cm_query(cm, query) == want


def test_string_hash_does_not_depend_on_the_widest_value():
    narrow = build_cmsketch(np.array([b"abc", b"de"], dtype=object), [5, 6])
    wide = build_cmsketch(np.array([b"abc", b"de", b"z" * 41],
                                   dtype=object), [5, 6, 0])
    assert narrow["rows"] == wide["rows"]


@pytest.mark.parametrize("blob", [
    [[9] * CM_WIDTH for _ in range(CM_DEPTH)],          # PR 26's bare rows
    {"v": CM_VERSION + 1, "rows": [[9] * CM_WIDTH] * CM_DEPTH},
    {"v": 1, "rows": [[9] * CM_WIDTH] * CM_DEPTH},
])
def test_sketch_of_another_version_gives_no_estimate(blob):
    """A stored blob whose hash this code does not share answers from the
    NDV, not from counters it would index wrongly."""
    assert cm_query(blob, 5) == 0
    cs = {"ndv": 108, "null_count": 0, "topn": [[1, 100]] * 8,
          "cmsketch": blob}
    assert selectivity._eq_sel(cs, 1000, 5) == pytest.approx(
        (1000 - 800) / 100 / 1000)


def test_a_million_distinct_values_build_in_seconds():
    """15M-distinct keys at SF10 must not cost a hash call each."""
    import time
    vals = np.arange(1_000_000, dtype=np.int64) * 4
    t0 = time.perf_counter()
    cm = build_cmsketch(vals, np.ones(len(vals), dtype=np.int64))
    assert time.perf_counter() - t0 < 5.0
    rows = np.asarray(cm["rows"])
    assert (rows.sum(axis=1) == len(vals)).all()
    assert rows.max() < 2 * len(vals) / CM_WIDTH + 200   # spread evenly
