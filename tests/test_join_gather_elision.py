"""The join fragment gathers only what can differ (ISSUE 30).

``device_join.compile_fragment`` keeps a leaf's row map as the identity
until a join re-indexes the leaf (its columns and masks are then read in
place, and a map composed through the identity is the index itself), and
gives a re-indexed column the host knows to hold no NULL a constant mask.
Here: every join kind and layout against the host engine over tables
with NULLs on both sides; a column's first NULL finding a new program on
each of the three paths that compile a fragment; padding rows that no
kept row can read; the lowered programs of Q3 and Q5; and the counters
that say what a dispatched fragment's program gathered and what it left
out.
"""

import json
import pathlib
import pickle
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from benchmark.datasets import tpch  # noqa: E402
from benchmark.queries import q3, q5  # noqa: E402
import tidb_tpu.executor.device_join as dj  # noqa: E402
from tidb_tpu.executor import device_exec, hybrid_join, join_index  # noqa: E402
from tidb_tpu.ops import residency  # noqa: E402
from tidb_tpu.sqltypes import FieldType, TYPE_LONGLONG  # noqa: E402
from tidb_tpu.storage import paged  # noqa: E402
from tidb_tpu.testkit import TestKit  # noqa: E402
from tidb_tpu.utils.chunk import Chunk, Column, LazyDictColumn  # noqa: E402

SEED = 3000000007


# -- the data ------------------------------------------------------------------

def _null_or(rng, p, v):
    return "null" if rng.random() < p else str(v)


def _tables(tk):
    """f: the probe side, NULLs in its keys and values, keys off both
    ends of the dimensions' range.  d: unique keys 10, 14, .. with NULL
    keys, NULL payloads and a NULL-able foreign key; d0: the same keys,
    no NULL anywhere.  e: a second dimension.  m: three rows a key."""
    tk.must_exec("use test")
    tk.must_exec("create table f (id int primary key, k bigint, "
                 "k2 bigint, v int, w int)")
    tk.must_exec("create table d (id int primary key, k bigint, grp int, "
                 "amt int, ek bigint)")
    tk.must_exec("create table d0 (id int primary key, k bigint, grp int, "
                 "amt int)")
    tk.must_exec("create table e (id int primary key, k bigint, "
                 "name varchar(8), z int)")
    tk.must_exec("create table m (id int primary key, k bigint, w int, "
                 "ek bigint)")
    rng = np.random.default_rng(30)
    tk.must_exec("insert into e values " + ",".join(
        f"({i}, {100 + 3 * i}, 'e{i % 6}', {_null_or(rng, .2, i * 5 % 17)})"
        for i in range(20)))
    ek = lambda: _null_or(rng, .1, 100 + 3 * int(rng.integers(0, 24)))
    rows = [f"({i}, {10 + 4 * i}, {_null_or(rng, .1, i % 5)}, "
            f"{_null_or(rng, .15, i * 7 % 31)}, {ek()})" for i in range(200)]
    rows += ["(200, null, 1, 3, 103)", "(201, null, 2, null, null)"]
    tk.must_exec("insert into d values " + ",".join(rows))
    tk.must_exec("insert into d0 values " + ",".join(
        f"({i}, {10 + 4 * i}, {i % 5}, {i * 7 % 31})" for i in range(200)))
    tk.must_exec("insert into m values " + ",".join(
        f"({i}, {10 + 8 * (i // 3)}, {_null_or(rng, .1, i % 11)}, {ek()})"
        for i in range(300)))
    probe = []
    for i in range(3000):
        r = rng.random()
        if r < 0.05:
            k = "null"
        elif r < 0.10:
            k = str(int(rng.integers(-50, 10)))
        elif r < 0.15:
            k = str(int(rng.integers(10**6, 10**9)))
        else:
            k = str(int(rng.integers(10, 900)))
        probe.append(
            f"({i}, {k}, {_null_or(rng, .05, int(rng.integers(10, 900)))}, "
            f"{_null_or(rng, .07, int(rng.integers(0, 100)))}, "
            f"{_null_or(rng, .3, int(rng.integers(0, 9)))})")
    tk.must_exec("insert into f values " + ",".join(probe))
    for t in ("f", "d", "d0", "e", "m"):
        tk.must_exec(f"analyze table {t}")
    tk.must_exec("set tidb_device_dispatch_rows = 1")
    tk.must_exec("set tidb_result_cache = 'OFF'")
    return tk


@pytest.fixture(scope="module")
def tks():
    """One store per index layout: an index is cached on its key column
    with the layout it was built under."""
    return {"dense": _tables(TestKit()), "sorted": _tables(TestKit())}


@pytest.fixture
def tk_of(tks, monkeypatch):
    def get(layout):
        if layout == "sorted":
            monkeypatch.setattr(join_index, "_DIRECT_MAX_BYTES", 64)
        return tks[layout]
    return get


def _annotations(tk, sql, prefix):
    plan = tk.must_query("explain analyze " + sql).rows
    return [part for row in plan for part in row[2].split(", ")
            if part.startswith(prefix)]


def _gather_counts(tk):
    st = json.loads(tk.must_query("DIAG STATUS").rows[0][0])
    st = st["device_pipelines"]
    return st["join_gathers"], st["join_gathers_elided"]


class _Spy:
    """What compile_fragment was asked for and what its programs were
    called with: [(leaves, joins, nonnull, fn)], [(fn, env, jidx,
    n_lives)]."""

    def __init__(self, monkeypatch):
        self.built, self.calls = [], []
        orig = dj.compile_fragment

        def spy(root, leaves, joins, agg_plan, agg_conds, caps, capacity,
                key_pack, agg_meta, nonnull, **kw):
            fn = orig(root, leaves, joins, agg_plan, agg_conds, caps,
                      capacity, key_pack, agg_meta, nonnull, **kw)
            self.built.append((leaves, joins, tuple(nonnull), fn))

            def call(env, jidx, n_lives):
                self.calls.append((fn, env, jidx, n_lives))
                return fn(env, jidx, n_lives)
            call.gathers, call.lower = fn.gathers, fn.lower
            return call
        monkeypatch.setattr(dj, "compile_fragment", spy)
        dj._CAP_STORE.clear()
        device_exec._PIPE_CACHE.clear()


def _parity(tk, sql):
    tk.must_exec("set tidb_executor_engine = 'tpu'")
    dev_rows = tk.must_query(sql).rows
    engines = _annotations(tk, sql, "engine:")
    tk.must_exec("set tidb_executor_engine = 'host'")
    assert dev_rows == tk.must_query(sql).rows, sql
    assert dev_rows
    return engines


# -- (a) every kind and layout against the host engine -------------------------

_CASES = {
    # NULLs in the probe leaf's key and value columns, read in place
    "probe_nulls":
        "select d0.grp, count(*), count(f.v), sum(f.v), sum(f.v + d0.amt) "
        "from f join d0 on f.k = d0.k group by d0.grp order by d0.grp",
    "probe_null_group_key":
        "select f.w, count(*), count(f.v), min(f.k) from f join d0 "
        "on f.k = d0.k group by f.w order by f.w",
    # NULLs in a dimension's key and payload columns: masks still gathered
    "dim_nulls":
        "select d.grp, count(*), count(d.amt), sum(d.amt), sum(f.v) "
        "from f join d on f.k = d.k group by d.grp order by d.grp",
    # a dimension reached through another's NULL-able foreign key
    "chain":
        "select e.name, d.grp, count(*), count(e.z), sum(d.amt + f.v) "
        "from f join d on f.k = d.k join e on d.ek = e.k "
        "group by e.name, d.grp order by e.name, d.grp",
    "chain_nullfree":
        "select d0.grp, e.name, count(*), sum(d0.amt), count(e.z) "
        "from f join d0 on f.k = d0.k join e on f.k2 = e.k "
        "group by d0.grp, e.name order by d0.grp, e.name",
    # null extension over a NULL-free build column: constant OR extension
    "left_nullfree_build":
        "select d0.grp, count(*), count(d0.amt), sum(d0.amt), sum(f.v) "
        "from f left join d0 on f.k = d0.k group by d0.grp order by d0.grp",
    "left_residual":
        "select d0.grp, count(*), count(d0.amt), sum(d0.amt) from f "
        "left join d0 on f.k = d0.k and d0.amt > 10 "
        "group by d0.grp order by d0.grp",
    "left_nullable_build":
        "select d.grp, count(*), count(d.amt), sum(d.amt), count(d.k) "
        "from f left join d on f.k = d.k group by d.grp order by d.grp",
    "semi":
        "select f.w, count(*), sum(f.v) from f where exists (select 1 "
        "from d0 where d0.k = f.k) group by f.w order by f.w",
    "anti":
        "select f.w, count(*), sum(f.v) from f where not exists (select 1 "
        "from d0 where d0.k = f.k) group by f.w order by f.w",
    # maps that stop being the identity: CSR expansion re-indexes the
    # probe leaf, and a later join re-indexes everything before it
    "csr":
        "select m.w, count(*), count(m.w), sum(f.v), count(f.v) from f "
        "join m on f.k = m.k group by m.w order by m.w",
    "csr_left":
        "select m.w, count(*), count(m.w), sum(f.v) from f "
        "left join m on f.k = m.k group by m.w order by m.w",
    "csr_then_dim":
        "select e.name, count(*), count(m.w), sum(f.v), count(e.z) from f "
        "join m on f.k = m.k join e on m.ek = e.k "
        "group by e.name order by e.name",
    "dim_then_csr":
        "select d0.grp, m.w, count(*), sum(d0.amt), sum(f.v) from f "
        "join d0 on f.k = d0.k join m on f.k2 = m.k "
        "group by d0.grp, m.w order by d0.grp, m.w",
    # computed keys: the in-program sort join re-indexes both sides
    "in_program":
        "select d0.grp, count(*), sum(d0.amt), count(f.v) from f join d0 "
        "on f.k + 1 = d0.k + 1 group by d0.grp order by d0.grp",
}


@pytest.mark.parametrize("layout", ["dense", "sorted"])
@pytest.mark.parametrize("case", sorted(_CASES))
def test_rows_equal_the_host_engine(tk_of, case, layout):
    assert _parity(tk_of(layout), _CASES[case]) == ["engine:tpu"]


def test_an_expansion_composes_a_row_map(tk_of, monkeypatch):
    """After `f join d0` d0's map is one index (elided); the CSR join
    that follows re-indexes it, and reading d0.amt then costs a map
    gather; f, re-indexed once, is no longer read in place."""
    spy = _Spy(monkeypatch)
    assert _parity(tk_of("dense"), _CASES["dim_then_csr"]) == ["engine:tpu"]
    leaves, joins, _nn, fn = spy.built[-1]
    kinds = sorted(jn.strategy[0] for jn in joins)
    assert kinds == ["expand", "uniq"]
    assert dj._inplace_leaf(joins[-1]) is None
    g = fn.gathers
    # f.k2 (the second probe key) or f.v is gathered through `pi`
    assert g["emitted"] >= 4 and g["elided"] >= 2


# -- (b) a column's first NULL finds a new program -----------------------------

def _first_null(tk, sql, update, spy, ran):
    """Run `sql` over a NULL-free build column, give the column its
    first NULL (same shapes, same index, same signature), run again."""
    tk.must_exec("set tidb_executor_engine = 'host'")
    before = tk.must_query(sql).rows
    tk.must_exec("set tidb_executor_engine = 'tpu'")
    # twice: the second execution settles on the learned capacities, so
    # that nothing but the fact can change the pipeline key below
    assert tk.must_query(sql).rows == before
    assert tk.must_query(sql).rows == before
    assert ran() >= 2
    n_built = len(spy.built)
    facts0 = spy.built[-1][2]
    tk.must_exec(update)
    n0 = ran()
    dev_rows = tk.must_query(sql).rows
    assert ran() > n0, "the second execution left the path under test"
    tk.must_exec("set tidb_executor_engine = 'host'")
    after = tk.must_query(sql).rows
    assert after != before, "the NULL must change the answer"
    assert dev_rows == after
    assert len(spy.built) > n_built, "a stale program answered"
    facts1 = spy.built[-1][2]
    assert set(facts1) < set(facts0)
    return facts0, facts1


def _page_by(monkeypatch, rows):
    """Every in-memory probe runs in pages of `rows` rows: the longest
    input a sorting program takes whole, and the page, lowered."""
    monkeypatch.setattr(device_exec, "_SORTED_SCAN_MAX_ROWS", 0)
    monkeypatch.setattr(dj, "_PROBE_PAGE_ROWS", rows)


def _g_tables(tk, nf=2600, ng=180):
    tk.must_exec("use test")
    tk.must_exec("create table gf (id int primary key, k bigint, v int)")
    tk.must_exec("create table g (id int primary key, k bigint, grp int, "
                 "amt int)")
    tk.must_exec("insert into g values " + ",".join(
        f"({i}, {5 + 3 * i}, {i % 4}, {i * 11 % 37})" for i in range(ng)))
    rng = np.random.default_rng(31)
    tk.must_exec("insert into gf values " + ",".join(
        f"({i}, {int(rng.integers(0, 5 + 3 * ng))}, "
        f"{int(rng.integers(0, 50))})" for i in range(nf)))
    tk.must_exec("set tidb_device_dispatch_rows = 1")
    tk.must_exec("set tidb_result_cache = 'OFF'")
    return tk


_G_SQL = ("select g.grp, count(*), count(g.amt), sum(g.amt), sum(gf.v) "
          "from gf join g on gf.k = g.k group by g.grp order by g.grp")
_G_UPDATE = "update g set amt = null where id in (3, 4, 5, 6, 7, 8)"


def test_first_null_resident(monkeypatch):
    tk = _g_tables(TestKit())
    spy = _Spy(monkeypatch)
    facts0, facts1 = _first_null(tk, _G_SQL, _G_UPDATE, spy,
                                 lambda: len(spy.calls))
    assert len(facts0) - len(facts1) == 1      # g.amt, nothing else


def test_first_null_paged(monkeypatch):
    tk = _g_tables(TestKit())
    _page_by(monkeypatch, 500)
    spy = _Spy(monkeypatch)
    pages = []
    orig = dj._paged_join_agg

    def paged_spy(*a, **k):
        pages.append(1)
        return orig(*a, **k)
    monkeypatch.setattr(dj, "_paged_join_agg", paged_spy)
    _first_null(tk, _G_SQL, _G_UPDATE, spy, lambda: len(pages))
    assert dj.LAST_PAGED_STATS.stats["pages"] == 6


def test_first_null_hybrid(monkeypatch):
    residency.evict_all("gather-elision test")
    hybrid_join._THROUGHPUT.clear()
    tk = TestKit()
    tk.must_exec("use test")
    tk.must_exec("create table hf (fk bigint, v bigint)")
    tk.must_exec("create table big (id bigint primary key, w1 bigint, "
                 "w2 bigint, w3 bigint, w4 bigint)")
    tk.must_exec("insert into big values " + ",".join(
        f"({i},{i % 7},{i % 11},{i % 13},{i % 17})" for i in range(6000)))
    rng = np.random.default_rng(3)
    tk.must_exec("insert into hf values " + ",".join(
        f"({int(rng.integers(0, 6000))},{int(rng.integers(1, 50))})"
        for _ in range(8000)))
    sql = ("select w1, sum(v*w2) s, count(w2) n, sum(w3+w4) t, count(*) c "
           "from hf, big where fk = id group by w1 order by w1")
    spy = _Spy(monkeypatch)
    try:
        tk.must_exec("set global tidb_device_mem_budget = 120000")
        _first_null(tk, sql, "update big set w2 = null where id < 40", spy,
                    lambda: hybrid_join.STATS["hj_runs"])
        assert hybrid_join.STATS["hj_partitions"] > \
            hybrid_join.STATS["hj_spilled_partitions"], "no device half"
    finally:
        tk.must_exec("set global tidb_device_mem_budget = 0")
        residency.set_budget(0)


# -- (c) padding rows ----------------------------------------------------------

@pytest.mark.parametrize("case", ["chain_nullfree", "left_nullfree_build",
                                  "csr_then_dim"])
def test_no_kept_row_reads_a_padding_row(tk_of, monkeypatch, case):
    """Uploads are padded to a row bucket; rows past a leaf's live count
    carry null=True, which a constant mask no longer reads.  Poison the
    padding (garbage data, null=False): the answer must not move, so no
    row a `valid` mask keeps addresses one."""
    spy = _Spy(monkeypatch)
    tk = tk_of("dense")
    assert _parity(tk, _CASES[case]) == ["engine:tpu"]
    leaves, _joins, nonnull, _fn = spy.built[-1]
    fn, env, jidx, n_lives = spy.calls[-1]
    assert nonnull, "the case must run on constant masks"
    padded = 0
    poisoned = {}
    for leaf in leaves:
        live = int(n_lives[leaf.leaf_id])
        for i in range(leaf.ncols):
            d, nl = env[leaf.offset + i]
            assert d.shape[0] >= live
            if d.shape[0] > live:
                padded += 1
                assert np.asarray(nl)[live:].all(), "padding reads NULL"
            junk = jnp.full((d.shape[0] - live,), 7_777_777, dtype=d.dtype)
            poisoned[leaf.offset + i] = (
                jnp.concatenate([d[:live], junk]),
                jnp.concatenate([nl[:live],
                                 jnp.zeros(d.shape[0] - live, dtype=bool)]))
    assert padded >= 2 * len(leaves), "probe and build must both be padded"
    want = jax.tree_util.tree_leaves(fn(env, jidx, n_lives))
    got = jax.tree_util.tree_leaves(fn(poisoned, jidx, n_lives))
    assert len(want) == len(got)
    for a, b in zip(want, got):
        assert np.array_equal(np.asarray(a), np.asarray(b))


# -- (d) the lowered programs of Q3 and Q5 -------------------------------------

@pytest.fixture(scope="module")
def tpch_tk():
    want = {t: list(tpch.SCHEMA[t]) for t in tpch.SCHEMA}
    tk = TestKit()
    tpch.load(tk, tpch.generate(SEED, 0.01, want), want, False,
              f"test_join_gather_elision/{SEED}")
    tk.must_exec("set tidb_device_dispatch_rows = 1")
    tk.must_exec("set tidb_result_cache = 'OFF'")
    return tk


_PASS_THROUGH = ("select", "add", "convert", "broadcast_in_dim", "compare",
                 "reshape", "clamp", "constant", "iota")
_DEF = re.compile(r"^\s*(%[\w#.]+) = \"?stablehlo\.(\w+)\"?[ (](.*)$")


def _iota_gathers(text):
    """Gathers of a StableHLO text whose indices are built from `iota`s
    and constants alone (jnp's `x[arange(n)]`: iota -> wrap negative
    indices -> convert -> broadcast -> gather)."""
    defs = {}
    for ln in text.splitlines():
        m = _DEF.match(ln)
        if m:
            name, op, rest = m.groups()
            defs[name] = (op, re.findall(r"%[\w#.]+", rest.split(" : ")[0]))

    def only_iota(name, seen=()):
        """'iota' / 'const' / None (reads something else)."""
        op, args = defs.get(name, ("arg", []))
        if op not in _PASS_THROUGH or name in seen:
            return None
        if op == "iota":
            return "iota"
        kinds = [only_iota(a, seen + (name,)) for a in args]
        if None in kinds:
            return None
        return "iota" if "iota" in kinds else "const"

    return [name for name, (op, args) in defs.items()
            if op == "gather" and only_iota(args[1]) == "iota"]


def test_the_reader_finds_an_identity_gather():
    n = 64
    text = jax.jit(lambda d, i: (d[jnp.arange(n)], d[i])).lower(
        jnp.zeros(n), jnp.zeros(n, dtype=jnp.int32)).as_text()
    assert text.count('"stablehlo.gather"(') == 2
    assert len(_iota_gathers(text)) == 1


def _probe_gathers(compiled_text):
    """Gather instructions of an optimised HLO text under k_join_probe."""
    return sum(1 for ln in compiled_text.splitlines()
               if " gather(" in ln and "k_join_probe" in ln)


#: gathers under k_join_probe in the optimised XLA:CPU program of the
#: parent commit (136a537) for this file's data and query texts, counted
#: there with _probe_gathers; the fragment's index lookups (one gather of
#: a slot table a join, of which XLA:CPU folds one of Q5's five) are
#: among them
_PARENT_PROBE_GATHERS = {"q3": 15, "q5": 23}


@pytest.mark.parametrize("name,sql,n_joins,emitted,elided", [
    ("q3", q3.SQL, 2, 3, 10), ("q5", q5.SQL, 5, 4, 15)])
def test_q3_q5_programs_hold_no_identity_gather(
        tpch_tk, monkeypatch, name, sql, n_joins, emitted, elided):
    # the program that keeps its probe leaf whole: a cut to the live rows
    # gathers that leaf too (tests/test_join_compaction.py)
    monkeypatch.setattr(dj, "compact_to", lambda _live, _n: None)
    spy = _Spy(monkeypatch)
    assert _parity(tpch_tk, sql) == ["engine:tpu"]
    fn, env, jidx, n_lives = spy.calls[-1]
    assert fn.gathers == {"emitted": emitted, "elided": elided}
    low = fn.lower(env, jidx, n_lives)
    assert _iota_gathers(low.as_text()) == []
    left = _probe_gathers(low.compile().as_text())
    # what the chain still gathers, plus at most one slot-table lookup
    # a join
    assert emitted < left <= emitted + n_joins
    assert _PARENT_PROBE_GATHERS[name] - left == elided
    # lineitem is read in place: its facts are not the program's
    leaves, joins, nonnull, _fn = spy.built[-1]
    probe = dj._inplace_leaf(joins[-1])
    assert probe.chunk.num_rows == max(lf.chunk.num_rows for lf in leaves)
    assert not any(probe.offset <= g < probe.offset + probe.ncols
                   for g in nonnull)


# -- (e) the counters and EXPLAIN ANALYZE --------------------------------------

def test_one_bump_per_dispatched_fragment(tpch_tk, monkeypatch):
    tk = tpch_tk
    monkeypatch.setattr(dj, "compact_to", lambda _live, _n: None)
    tk.must_exec("set tidb_executor_engine = 'tpu'")
    tk.must_query(q5.SQL)
    tk.must_query(q3.SQL)                      # warm: no retry below
    g0, e0 = _gather_counts(tk)
    tk.must_query(q5.SQL)
    tk.must_query(q3.SQL)
    tk.must_query(q3.SQL)
    g1, e1 = _gather_counts(tk)
    assert (g1 - g0, e1 - e0) == (4 + 3 + 3, 15 + 10 + 10)
    assert _annotations(tk, q3.SQL, "gathers:") == ["gathers:3 (-10)"]
    assert _annotations(tk, q5.SQL, "gathers:") == ["gathers:4 (-15)"]
    assert _annotations(tk, q5.SQL, "join:") == ["join:direct x5"]


def test_a_capacity_retry_counts_once(tk_of):
    tk = tk_of("dense")
    sql = ("select m.w, count(*), sum(f.v) from f join m on f.k = m.k "
           "where f.v < 97 group by m.w order by m.w")
    dj._CAP_STORE.clear()
    device_exec._PIPE_CACHE.clear()
    tk.must_exec("set tidb_executor_engine = 'tpu'")
    g0, e0 = _gather_counts(tk)
    t0 = device_exec.pipe_cache_stats()["misses"]
    tk.must_query(sql)
    assert device_exec.pipe_cache_stats()["misses"] - t0 >= 2, \
        "expected a capacity retry on the first execution"
    g1, e1 = _gather_counts(tk)
    tk.must_query(sql)
    g2, e2 = _gather_counts(tk)
    assert (g1 - g0, e1 - e0) == (g2 - g1, e2 - e1)
    assert g1 > g0 and e1 > e0


def test_a_paged_fragment_counts_once(tk_of, monkeypatch):
    tk = tk_of("dense")
    sql = _CASES["chain_nullfree"]
    tk.must_exec("set tidb_executor_engine = 'tpu'")
    tk.must_query(sql)
    g0, e0 = _gather_counts(tk)
    tk.must_query(sql)
    g1, e1 = _gather_counts(tk)
    _page_by(monkeypatch, 500)
    rows = tk.must_query(sql).rows
    assert dj.LAST_PAGED_STATS.stats["pages"] == 6
    g2, e2 = _gather_counts(tk)
    # six pages, one program, one count: the page is the leaf in place
    assert (g2 - g1, e2 - e1) == (g1 - g0, e1 - e0)
    tk.must_exec("set tidb_executor_engine = 'host'")
    assert rows == tk.must_query(sql).rows


@pytest.mark.parametrize("engine,sql", [
    ("tpu-mpp", q3.SQL),
    ("tpu", "select l_returnflag, count(*), sum(l_quantity) from lineitem "
            "where l_shipdate > date '1995-03-15' group by l_returnflag "
            "order by l_returnflag"),
])
def test_the_mesh_and_the_scan_bump_neither(tpch_tk, engine, sql):
    """The mesh's IN-PROGRAM joins (a build over the broadcast size
    threshold, here lowered to a byte) and a scan; a mesh fragment on the
    indexed path counts as one chip's does (tests/test_mpp_indexed.py)."""
    tk = tpch_tk
    tk.must_exec(f"set tidb_executor_engine = '{engine}'")
    tk.must_exec("set tidb_broadcast_join_threshold_size = 1")
    before = _gather_counts(tk)
    try:
        rows = tk.must_query(sql).rows
        assert _annotations(tk, sql, "engine:") == [f"engine:{engine}"]
        assert _annotations(tk, sql, "gathers:") == []
    finally:
        tk.must_exec("set tidb_broadcast_join_threshold_size = 104857600")
    assert _gather_counts(tk) == before
    tk.must_exec("set tidb_executor_engine = 'host'")
    assert rows == tk.must_query(sql).rows


# -- (f) the fact and who needs it ---------------------------------------------

def _col(vals, nulls=None):
    a = np.asarray(vals, dtype=np.int64)
    return Column(FieldType(tp=TYPE_LONGLONG), a,
                  None if nulls is None else np.asarray(nulls, dtype=bool))


def test_has_nulls_is_read_from_the_data_and_cached():
    c = _col([1, 2, 3])
    assert c._has_nulls is None and c.has_nulls() is False
    assert c._has_nulls is False
    n = _col([1, 2, 3], [False, True, False])
    assert n.has_nulls() is True
    back = pickle.loads(pickle.dumps(n))       # a process-local cache
    assert back._has_nulls is None and back.has_nulls() is True
    lazy = LazyDictColumn(FieldType(tp=TYPE_LONGLONG),
                          np.array([0, 1], dtype=np.int32), [b"a", b"b"])
    assert lazy.has_nulls() is False
    assert pickle.loads(pickle.dumps(lazy))._has_nulls is None
    # a write installs new Columns: slices and takes start without a fact
    assert n.take(np.array([0, 2]))._has_nulls is None
    assert n.take(np.array([0, 2])).has_nulls() is False


def _chain(*leaf_cols, strategies):
    leaves, off = [], 0
    for i, cols in enumerate(leaf_cols):
        leaves.append(dj._Leaf(i, Chunk(list(cols)), [], off))
        off += len(cols)
    node = leaves[0]
    for i, st in enumerate(strategies):
        node = dj._JoinNode(node, leaves[i + 1], [], [], [], 0)
        node.strategy = st
    return node, leaves


def test_the_facts_a_fragment_needs(monkeypatch):
    fact = [_col([1, 2, 3, 4]), _col([5, 6, 7, 8])]
    dim = [_col([1, 2]), _col([3, 4], [False, True]), _col([9, 9])]
    dim2 = [_col([7])]
    uniq = ("uniq", "right", None)
    root, leaves = _chain(fact, dim, dim2, strategies=[uniq, uniq])
    assert dj._inplace_leaf(root) is leaves[0]
    used = {0, 1, 2, 3, 5}
    # the leaf read in place needs none; dim's NULL column has none;
    # dim's unused column (4) is not asked
    assert dj.nonnull_cols(root, leaves, used) == (2, 5)
    for c in fact + [dim[2]]:
        assert c._has_nulls is None, "scanned for a fact nobody needs"
    # an expansion re-indexes the probe leaf: its facts are needed too
    root, leaves = _chain(fact, dim, dim2,
                          strategies=[("expand", "right", None), uniq])
    assert dj._inplace_leaf(root) is None
    assert dj.nonnull_cols(root, leaves, used) == (0, 1, 2, 5)
    # a paged column is never scanned and counts as nullable
    monkeypatch.setattr(paged, "is_paged", lambda c: c is dim2[0])
    dim2[0]._has_nulls = None
    assert dj.nonnull_cols(root, leaves, used) == (0, 1, 2)
    assert dim2[0]._has_nulls is None
