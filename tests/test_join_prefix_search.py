"""A searched join looks its bucket up by address (ISSUE 34).

A ``sorted`` join index whose direct table does not fit gains a prefix
table over the key's high bits where the host sees that it pays
(``join_index._bucket_prefix``): the probe reads its bucket's two ends by
address and bisects the few keys between them.  Here: the rule (partsupp's
shapes at SF1 by arithmetic, a hot key, a table of a few rows, a
partitioned build); the bounded search against numpy's over every edge a
bucket has; every join kind over a prefixed index against the host engine
and against the plain search; the lowered text; the signature across
INSERTs under and past the bound; the counter and the note.
"""

import json
import pathlib
import sys

import jax
import numpy as np
import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

import tidb_tpu.executor.device_join as dj  # noqa: E402
from tidb_tpu.executor import device_exec, join_index  # noqa: E402
from tidb_tpu.executor.join_index import (  # noqa: E402
    _bucket_prefix, build_join_index)
from tidb_tpu.ops import device as dev  # noqa: E402
from tidb_tpu.ops import residency  # noqa: E402
from tidb_tpu.sqltypes import FieldType, TYPE_LONGLONG  # noqa: E402
from tidb_tpu.testkit import TestKit  # noqa: E402
from tidb_tpu.utils.chunk import Column  # noqa: E402

I64_MAX = np.iinfo(np.int64).max


def _col(vals, nulls=None):
    a = np.asarray(vals, dtype=np.int64)
    return Column(FieldType(tp=TYPE_LONGLONG), a,
                  np.zeros(len(a), dtype=bool) if nulls is None
                  else np.asarray(nulls, dtype=bool))


@pytest.fixture()
def searched(monkeypatch):
    """No slot table or CSR of over 64 KB fits (a span of 16,384 keys);
    a prefix over a few thousand rows does."""
    monkeypatch.setattr(join_index, "_DIRECT_MAX_BYTES", 64 << 10)


# -- (a) the rule: what the host observes -------------------------------------

def _partsupp(n_part, n_supp):
    """cl. 4.2.3's part_supplier: four suppliers a part, about a quarter
    of the suppliers apart."""
    pk = np.repeat(np.arange(1, n_part + 1, dtype=np.int64), 4)
    i = np.tile(np.arange(4, dtype=np.int64), n_part)
    return pk, (pk + i * (n_supp // 4 + (pk - 1) // n_supp)) % n_supp + 1


def test_partsupp_at_sf1_is_one_key_a_bucket():
    pk, sk = _partsupp(200_000, 10_000)
    idx = build_join_index((_col(pk), _col(sk)))
    assert idx.kind == "sorted" and idx.unique
    # quantized spans 212,992 x 10,240: 8.7 GB of slots
    assert (idx.span, idx.rows_len) == (2_181_038_080, 1_048_576)
    assert (idx.shift, idx.steps) == (11, 1)
    assert idx.prefix.shape == (1_064_960, 2)
    assert idx.prefix.dtype == np.int32
    assert idx.prefix[0, 0] == 0
    assert idx.prefix[-1, 1] == idx.n_valid == 800_000
    assert (idx.prefix[:, 1] - idx.prefix[:, 0]).max() == 1
    assert idx.low_keys.dtype == np.uint32
    assert idx.low_keys.shape == idx.sorted_keys.shape
    assert idx.sig().endswith("/prefix11.1")
    # what the device holds of it: 4 B a row less than the int64 keys
    a0, a1 = idx.host_arrays()
    assert a0 is idx.low_keys and a1 is idx.rows


def _check_buckets(idx):
    """bucket b's keys are sorted_keys[prefix[b, 0]:prefix[b, 1]], and
    the low bits order them as the keys do."""
    sk = idx.sorted_keys[:idx.n_valid]
    b = sk >> idx.shift
    buckets = np.arange(len(idx.prefix))
    assert np.array_equal(idx.prefix[:, 0],
                          np.searchsorted(b, buckets, side="left"))
    assert np.array_equal(idx.prefix[:, 1],
                          np.searchsorted(b, buckets, side="right"))
    assert idx.prefix[-1, 1] == idx.n_valid
    assert (idx.prefix[:, 1] - idx.prefix[:, 0]).max() < 1 << idx.steps
    low = idx.low_keys[:idx.n_valid].astype(np.int64)
    assert np.array_equal((b << idx.shift) | low, sk)
    # as many buckets as padded rows, to within sqrt(2)
    assert idx.rows_len / 1.42 <= len(idx.prefix) <= idx.rows_len * 1.42


@pytest.mark.parametrize("name,cols,shift,steps", [
    # 1,000 keys 0, 50, ..: span 53,248 over 1,024 rows: 64 a bucket
    ("sparse", lambda: (_col(range(0, 50_000, 50)),), 6, 2),
    # every key three times: a bucket of one key holds three rows
    ("triples", lambda: (_col(np.repeat(np.arange(0, 32_000, 32), 3)),),
     3, 2),
    ("pair", lambda: (_col(np.arange(2000) // 4 * 7),
                      _col(np.arange(2000) % 4 * 900)), 12, 3),
])
def test_the_prefix_is_csr_over_the_high_bits(searched, name, cols, shift,
                                              steps):
    idx = build_join_index(cols())
    assert idx.kind == "sorted" and idx.prefix is not None, name
    assert (idx.shift, idx.steps) == (shift, steps)
    _check_buckets(idx)


def test_a_hot_key_keeps_the_plain_search(searched):
    keys = np.concatenate([np.arange(0, 40_000, 40), np.full(600, 4000)])
    idx = build_join_index((_col(keys),))
    # 601 rows in one bucket: 10 steps + 2 gathers against 12
    assert idx.kind == "sorted" and idx.prefix is None
    assert idx.low_keys is None and "prefix" not in idx.sig()
    assert idx.host_arrays()[0] is idx.sorted_keys
    assert len(idx.device_arrays()) == 3
    # 255 rows in it, the most 8 steps find: 8 + 2 against 11
    keys = np.concatenate([np.arange(0, 40_000, 40), np.full(254, 4000)])
    idx = build_join_index((_col(keys),))
    assert idx.steps == 8 and idx.sig().endswith("/prefix5.8")
    _check_buckets(idx)


def test_a_table_of_a_few_rows_keeps_the_plain_search(searched):
    # 8 rows: 4 steps over the whole array, 1 + 2 with a prefix
    idx = build_join_index((_col(range(0, 8 << 14, 1 << 14)),))
    assert idx.kind == "sorted" and idx.rows_len == 8 and idx.steps == 1
    # ... 2 + 2 once a bucket holds two
    idx = build_join_index((_col([0, 1, 2 << 14, 3 << 14, 7 << 14]),))
    assert idx.kind == "sorted" and idx.prefix is None


@pytest.mark.parametrize("kw", [
    {"force_sorted": True},
    {"packs": ((0, 65_536),)},
    {"packs": ((0, 65_536),), "force_sorted": True, "pad_rows": 3000},
])
def test_a_partitioned_build_keeps_the_plain_search(searched, kw):
    idx = build_join_index((_col(range(0, 50_000, 50)),), **kw)
    assert idx.kind == "sorted" and idx.prefix is None
    assert "prefix" not in idx.sig()


def test_a_bucket_wider_than_32_bits_keeps_the_plain_search():
    keys = np.arange(1000, dtype=np.int64) << 33
    idx = build_join_index((_col(keys),))
    assert idx.kind == "sorted" and idx.prefix is None
    assert _bucket_prefix(keys, idx.span, idx.rows_len, np.int32) is None
    narrower = build_join_index((_col(keys >> 1),))
    assert (narrower.shift, narrower.steps) == (32, 1)
    _check_buckets(narrower)


def test_a_refused_table_keeps_the_plain_search(monkeypatch):
    monkeypatch.setattr(join_index, "_DIRECT_MAX_BYTES", 64)
    idx = build_join_index((_col(range(0, 50_000, 50)),))
    assert idx.kind == "sorted" and idx.prefix is None


def test_the_prefix_enters_the_residency_ledger(searched):
    idx = build_join_index((_col(range(0, 50_000, 50)),))
    before = residency.STATS["upload_bytes"]
    a0, a1, nv, prefix = idx.device_arrays()
    assert a0.dtype == np.uint32 and int(nv) == 1000
    assert residency.STATS["upload_bytes"] - before == (
        idx.low_keys.nbytes + idx.rows.nbytes + idx.prefix.nbytes)
    again = idx.device_arrays()
    assert again[0] is a0 and again[3] is prefix    # cached, not sent again
    residency.bump_epoch("test")                    # a fence drops both
    assert idx.device_arrays()[3] is not prefix


def test_an_insert_under_the_bound_keeps_the_signature(searched):
    base = np.arange(0, 128_000, 128)       # 1,000 keys, one a bucket
    one = build_join_index((_col(base),))
    assert (one.shift, one.steps) == (7, 1)
    # a key beside another, in its bucket: the bound of 1 is passed
    two = build_join_index((_col(np.append(base, 129)),))
    assert two.steps == 2 and two.sig() != one.sig()
    # a third in that bucket stays under 2**2 - 1
    three = build_join_index((_col(np.append(base, [129, 130])),))
    assert three.sig() == two.sig()
    # one in a bucket that was empty: nothing moves
    apart = build_join_index((_col(np.append(base, 129_280)),))
    assert apart.sig() == one.sig() and apart.n_valid == 1001


# -- (b) the bounded search against numpy's -----------------------------------

def _search_case(name):
    rng = np.random.default_rng(34)
    if name == "spread":          # one or two keys a bucket, gaps between
        keys = np.sort(rng.choice(200_000, 1500, replace=False))
    elif name == "runs":          # seven rows a key: buckets AT the bound
        keys = np.repeat(np.arange(0, 64_000, 64), 7)
    elif name == "clustered":     # empty buckets beside full ones
        keys = np.sort(np.concatenate(
            [c + rng.choice(40, 7, replace=False)
             for c in range(0, 300_000, 3000)]))
    elif name == "full":          # every padded row live: no sentinel
        keys = np.arange(0, 2048 * 9, 9)
    elif name == "one":
        keys = np.array([777])
    else:
        keys = np.zeros(0, dtype=np.int64)
    return keys.astype(np.int64)


@pytest.mark.parametrize("right", [False, True])
@pytest.mark.parametrize("name", ["spread", "runs", "clustered", "full",
                                  "one", "none"])
def test_the_bounded_search_is_numpys(name, right):
    keys = _search_case(name)
    pad = dev.bucket_rows(max(len(keys), 1))
    span = int(keys.max(initial=0)) + 1
    front = _bucket_prefix(keys, span, pad, np.int32)
    if front is None:
        pytest.skip("no prefix for this shape")
    shift, steps, prefix = front
    padded = np.concatenate([keys, np.full(pad - len(keys), I64_MAX)])
    low = (padded & ((1 << shift) - 1)).astype(np.uint32)
    rng = np.random.default_rng(3)
    probe = np.concatenate([
        keys, keys + 1, keys - 1, rng.integers(0, span, 4000),
        [0, span - 1]]).clip(0, span - 1).astype(np.int64)

    @jax.jit
    def run(low, probe, prefix):
        ends = prefix[probe >> shift]
        return dj._bucket_search(
            low, (probe & ((1 << shift) - 1)).astype(low.dtype),
            ends[:, 0], ends[:, 1], steps, right)

    pos, eq = (np.asarray(x) for x in run(low, probe, prefix))
    want = np.searchsorted(keys, probe, side="right" if right else "left")
    assert np.array_equal(pos, want)
    if not right:
        # a key is found only inside its own bucket
        inside = want < prefix[probe >> shift, 1]
        assert np.array_equal(eq, inside & (
            keys[np.minimum(want, max(len(keys) - 1, 0))] == probe
            if len(keys) else np.zeros(len(probe), bool)))
    assert "stablehlo.while" not in run.lower(low, probe, prefix).as_text()


# -- (c) every join kind over a prefixed index ---------------------------------

@pytest.fixture(scope="module")
def tk():
    """d: unique (a, b) pairs and a unique single key k with gaps, NULL
    keys, half the rows removed by `flag = 1`; m: three rows a key on
    every other key (an expansion); f: probe keys on the keys, beside
    them, in empty buckets, below and far above the pack range, NULL."""
    tk = TestKit()
    tk.must_exec("use test")
    tk.must_exec("create table d (id int primary key, k bigint, a bigint, "
                 "b bigint, grp int, amt int, flag int)")
    tk.must_exec("create table m (id int primary key, k bigint, w int)")
    tk.must_exec("create table f (id int primary key, k bigint, a bigint, "
                 "b bigint, v int)")
    rows = [f"({i}, {1000 + 97 * i}, {i // 4}, {(i % 4) * 2500 + i // 4}, "
            f"{i % 5}, {i * 7 % 31}, {i % 2})" for i in range(1200)]
    rows += ["(1200, null, 3, null, 1, 3, 1)", "(1201, null, null, 9, 2, 4, 0)"]
    tk.must_exec("insert into d values " + ",".join(rows))
    tk.must_exec("insert into m values " + ",".join(
        f"({i}, {1000 + 194 * (i // 3)}, {i % 11})" for i in range(1500)))
    rng = np.random.default_rng(34)
    probe = []
    for i in range(3000):
        r = rng.random()
        j = int(rng.integers(0, 1200))
        if r < 0.05:
            k, a, b = "null", "null", j
        elif r < 0.10:
            k, a, b = int(rng.integers(-500, 1000)), -3, j
        elif r < 0.15:
            k, a, b = int(rng.integers(10**6, 10**9)), j // 4, 10**7
        elif r < 0.45:
            k = 1000 + 97 * j + int(rng.integers(-2, 3))
            a, b = j // 4, (j % 4) * 2500 + j // 4 + int(rng.integers(-1, 2))
        else:
            k, a, b = 1000 + 97 * j, j // 4, (j % 4) * 2500 + j // 4
        probe.append(f"({i}, {k}, {a}, {b}, {int(rng.integers(0, 100))})")
    tk.must_exec("insert into f values " + ",".join(probe))
    for t in ("d", "m", "f"):
        tk.must_exec(f"analyze table {t}")
    tk.must_exec("set tidb_device_dispatch_rows = 1")
    tk.must_exec("set tidb_result_cache = 'OFF'")
    return tk


def _annotations(tk, sql, prefix):
    plan = tk.must_query("explain analyze " + sql).rows
    return [part for row in plan for part in row[2].split(", ")
            if part.startswith(prefix)]


def _pipelines(tk):
    return json.loads(tk.must_query("DIAG STATUS").rows[0][0])[
        "device_pipelines"]


def _drop_compiled():
    dj._CAP_STORE.clear()
    device_exec._PIPE_CACHE.clear()


def _device(tk, sql):
    """Rows of `sql` on the device engine, the (join kind, strategy,
    index) triples of the last fragment it compiled, and the lowered text
    of the last program it dispatched."""
    seen, texts = [], []
    orig_frag, orig_jit = dj.compile_fragment, dev.observed_jit

    def frag(root, leaves, joins, *a, **k):
        seen.append([(jn.kind,) + tuple(jn.strategy) for jn in joins])
        return orig_frag(root, leaves, joins, *a, **k)

    def jit(fn, **jit_kw):
        run = orig_jit(fn, **jit_kw)

        def call(*a, **k):
            texts.append(run.lower(*a, **k).as_text())
            return run(*a, **k)
        call.lower = run.lower
        return call

    dj.compile_fragment, dev.observed_jit = frag, jit
    _drop_compiled()
    try:
        tk.must_exec("set tidb_executor_engine = 'tpu'")
        rows = tk.must_query(sql).rows
        engines = _annotations(tk, sql, "engine:")
    finally:
        dj.compile_fragment, dev.observed_jit = orig_frag, orig_jit
        _drop_compiled()
    assert engines == ["engine:tpu"], engines
    return rows, seen[-1], texts[-1]


_IDS = iter(range(10_000, 20_000))


def _new_version(tk):
    """A build row no probe can match: the tables' cached indexes go,
    the answers stay."""
    i = next(_IDS)
    tk.must_exec(f"insert into d values ({i}, null, null, null, 0, 0, 0)")
    tk.must_exec(f"insert into m values ({i}, null, 0)")


_KINDS = [
    ("inner", "uniq", "select d.grp, count(*), sum(f.v + d.amt) from f "
     "join d on {on} where d.flag = 1 group by d.grp order by d.grp"),
    ("inner", "uniq", "select d.grp, count(*), sum(f.v + d.amt) from f "
     "join d on {on} group by d.grp order by d.grp"),
    ("left", "uniq", "select d.grp, count(*), count(d.id), sum(f.v) from f "
     "left join d on {on} and d.flag = 1 group by d.grp order by d.grp"),
    ("semi", "uniq", "select f.v, count(*) from f where exists (select 1 "
     "from d where {on} and d.flag = 1) group by f.v order by f.v"),
    ("anti", "uniq", "select f.v, count(*) from f where not exists (select "
     "1 from d where {on} and d.flag = 1) group by f.v order by f.v"),
    ("inner", "expand", "select m.w, count(*), sum(f.v) from f join m on "
     "f.k = m.k group by m.w order by m.w"),
    ("left", "expand", "select m.w, count(*), count(m.id), sum(f.v) from f "
     "left join m on f.k = m.k group by m.w order by m.w"),
    ("semi", "expand", "select f.v, count(*) from f where exists (select 1 "
     "from m where m.k = f.k) group by f.v order by f.v"),
    ("anti", "expand", "select f.v, count(*) from f where not exists "
     "(select 1 from m where m.k = f.k) group by f.v order by f.v"),
]
_ONS = {"single": "f.k = d.k", "composite": "f.a = d.a and f.b = d.b"}


@pytest.mark.parametrize("kind,strategy,sql,key", [
    case + (key,) for case in _KINDS for key in _ONS
    if "{on}" in case[2] or key == "single"])      # m has one key column
def test_join_kinds_over_a_prefixed_index(tk, searched, monkeypatch, kind,
                                          strategy, sql, key):
    sql = sql.format(on=_ONS[key])
    tk.must_exec("set tidb_executor_engine = 'host'")
    want = tk.must_query(sql).rows
    assert want
    rows, joins, text = _device(tk, sql)
    (jkind, jstrategy, _side, idx), = joins
    assert (jkind, jstrategy) == (kind, strategy)
    assert idx.kind == "sorted" and idx.prefix is not None
    _check_buckets(idx)
    assert rows == want
    # the same fragment over the index as the parent built it
    monkeypatch.setattr(join_index, "_bucket_prefix", lambda *a: None)
    _new_version(tk)
    plain_rows, plain_joins, plain_text = _device(tk, sql)
    assert plain_joins[0][3].prefix is None and plain_rows == want
    # ... whose program loops once a search, and the prefixed one never
    searches = 1 if strategy == "uniq" else 2
    assert (plain_text.count("stablehlo.while")
            - text.count("stablehlo.while")) == searches
    monkeypatch.undo()
    _new_version(tk)


# -- (d) INSERTs under and past the bound, the counter, the note ----------------

def _fresh_tk(keys):
    tk = TestKit()
    tk.must_exec("use test")
    tk.must_exec("create table d (id int primary key, k bigint, grp int)")
    tk.must_exec("create table f (id int primary key, k bigint, v int)")
    tk.must_exec("insert into d values " + ",".join(
        f"({i}, {k}, {i % 3})" for i, k in enumerate(keys)))
    tk.must_exec("insert into f values " + ",".join(
        f"({i}, {(i * 64) % 131_072}, {i % 7})" for i in range(4000)))
    tk.must_exec("analyze table d")
    tk.must_exec("analyze table f")
    tk.must_exec("set tidb_device_dispatch_rows = 1")
    tk.must_exec("set tidb_result_cache = 'OFF'")
    return tk


_SQL = ("select d.grp, count(*), sum(f.v) from f join d on f.k = d.k "
        "group by d.grp order by d.grp")


def _check(tk):
    tk.must_exec("set tidb_executor_engine = 'tpu'")
    rows = tk.must_query(_SQL).rows
    tk.must_exec("set tidb_executor_engine = 'host'")
    assert rows == tk.must_query(_SQL).rows and rows
    tk.must_exec("set tidb_executor_engine = 'tpu'")


def test_an_insert_compiles_only_past_the_bound(searched):
    _drop_compiled()
    tk = _fresh_tk(range(0, 128_000, 128))
    _check(tk)
    _check(tk)                                    # capacities settled
    before = _pipelines(tk)
    assert _annotations(tk, _SQL, "join:") == ["join:search x1 (prefix x1)"]
    # a key into an empty bucket: the numpy index is rebuilt, no program
    tk.must_exec("insert into d values (5000, 129280, 1)")
    _check(tk)
    after = _pipelines(tk)
    assert after["compiles"] == before["compiles"]
    assert after["misses"] == before["misses"]
    # a key into a taken bucket passes the bound of one: one new program
    tk.must_exec("insert into d values (5001, 129, 2)")
    _check(tk)
    assert _pipelines(tk)["misses"] - after["misses"] == 1
    assert _annotations(tk, _SQL, "join:") == ["join:search x1 (prefix x1)"]
    _check(tk)                    # its group capacity settled, as any new
    grown = _pipelines(tk)        # signature's is
    # ... which a third key of that bucket fits (2**2 - 1)
    tk.must_exec("insert into d values (5002, 130, 0)")
    _check(tk)
    assert _pipelines(tk)["misses"] == grown["misses"]
    assert _pipelines(tk)["compiles"] == grown["compiles"]
    _drop_compiled()


def test_the_counter_and_the_note(searched):
    _drop_compiled()
    tk = _fresh_tk(range(0, 128_000, 128))
    _check(tk)
    before = _pipelines(tk)
    _check(tk)
    after = _pipelines(tk)
    assert [after[k] - before[k] for k in (
        "join_direct", "join_search", "join_search_prefixed")] == [0, 1, 1]
    assert _annotations(tk, _SQL, "join:") == ["join:search x1 (prefix x1)"]
    # a hot key: searched, and not from a bucket
    hot = _fresh_tk(list(range(0, 128_000, 128)) + [4096] * 600)
    _check(hot)
    before = _pipelines(hot)
    _check(hot)
    after = _pipelines(hot)
    assert [after[k] - before[k] for k in (
        "join_direct", "join_search", "join_search_prefixed")] == [0, 1, 0]
    # ... and, its build no longer unique, CSR-expanded (PR 37's note)
    assert _annotations(hot, _SQL, "join:") == [
        "join:search x1 (expand x1 one-pass)"]
    assert after["join_expand"] - before["join_expand"] == 1
    _drop_compiled()
