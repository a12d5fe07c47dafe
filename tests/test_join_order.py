"""The fact-first join chain attaches first the build whose filter lets
the probe path cut (``device_join._attach_rank``): a build whose index,
built under its leaf's filter, keeps at most ``1 / _COMPACT_FACTOR`` of
its rows goes ahead of the others, the one whose cut would leave the
fewest rows first; every other build keeps the size order.

Held here: the order TPC-H Q5 and Q9 and SSB Q2.1-Q4.1 attach in, and
that the first cut of Q5 and Q9 now comes after one lookup; that
Q3, Q4, Q13, Q17, Q18's outer fragment, Q21's inner chain and the mesh's
Q3 keep the order, the pipeline keys and the program texts the size rule
alone gives; the rule on synthetic chains (a build that keeps 30% stays
in size order, a larger one that keeps 10% goes first), on a deferred
build and at the benchmark's shapes; answers equal to the host engine
and the numpy references on two seeds; the counters, the ``EXPLAIN
ANALYZE`` note and the benchmark's reader of them.  Tables at SF0.02
(TPC-H) and SF0.01 (SSB), the compaction rule's floor lowered to 1,024
rows as ``tests/test_ssb.py`` lowers it; the benchmark's own shapes go
through the rank unpatched.
"""

import importlib
import json
import os
import pathlib
import sys
import types

import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from benchmark.datasets import ssb, tpch_text  # noqa: E402
from tidb_tpu.executor import device_exec  # noqa: E402
from tidb_tpu.executor import device_join as dj  # noqa: E402
from tidb_tpu.executor import mpp_exec  # noqa: E402
from tidb_tpu.ops import device as dev  # noqa: E402
from tidb_tpu.testkit import TestKit  # noqa: E402

TPCH = ("q3", "q4", "q5", "q9", "q13", "q17", "q18", "q21")
SSB = ("ssb_q1_1", "ssb_q2_1", "ssb_q3_1", "ssb_q4_1")
MODS = {t: importlib.import_module(f"benchmark.queries.{t}")
        for t in TPCH + SSB}
#: TPC-H seeds whose SF0.02 data give every template an answer (Q18's
#: large orders, Q17's Brand#23 / MED BOX lines: tests/test_tpch_q9q18.py,
#: tests/test_tpch_q17.py); SSB's as tests/test_ssb.py chose them
SEEDS = {"tpch": (7, 3300200101), "ssb": (3100200341, 3100200343)}

_LOADED = {}


def _loaded(kind, seed):
    """(tables, TestKit) of one data set and seed, made once a module."""
    if (kind, seed) not in _LOADED:
        data, sf = (tpch_text, 0.02) if kind == "tpch" else (ssb, 0.01)
        want = {t: list(cols) for t, cols in data.SCHEMA.items()}
        tables = data.generate(seed, sf, want)
        tk = TestKit()
        data.load(tk, tables, want, False, f"test_join_order/{kind}/{seed}")
        tk.must_exec("set tidb_device_dispatch_rows = 1")
        tk.must_exec("set tidb_result_cache = 'OFF'")
        tk.must_exec("set tidb_mpp_devices = 4")
        _LOADED[kind, seed] = tables, tk
    return _LOADED[kind, seed]


def _kind(template):
    return "ssb" if template.startswith("ssb") else "tpch"


def _rows(tk, engine, sql):
    tk.must_exec(f"set tidb_executor_engine = '{engine}'")
    return [tuple(r) for r in tk.must_query(sql).rows]


def _pipelines(tk):
    return json.loads(tk.must_query("DIAG STATUS").rows[0][0])[
        "device_pipelines"]


def _drop_compiled():
    dj._CAP_STORE.clear()
    device_exec._PIPE_CACHE.clear()


@pytest.fixture(autouse=True)
def low_floor(monkeypatch):
    """The compaction rule from 1,024 rows up (its floor is 65,536), and
    nothing learned or compiled before or after a test."""
    monkeypatch.setattr(dj, "_COMPACT_MIN_ROWS", 1024)
    _drop_compiled()
    yield
    _drop_compiled()


def _size_rule(monkeypatch):
    """Rank every candidate by its size alone, as the chain did before
    the cutting build went first."""
    monkeypatch.setattr(dj, "_attach_rank", lambda leaf, idx, fact_rows: (
        1, 0, leaf.chunk.num_rows, leaf.leaf_id))


def _n_rows(cols):
    """Rows of a generated table: its first column's length (a string
    column is a (codes, dictionary) pair)."""
    first = next(iter(cols.values()))
    return len(first[0] if isinstance(first, tuple) else first)


class _Chains:
    """The chains _reorder_fact_first built while it is open: for each,
    its builds' tables (named by their row counts) and `selective`
    marks."""

    def __init__(self, monkeypatch, tables):
        self.names = {_n_rows(cols): t for t, cols in tables.items()}
        assert len(self.names) == len(tables), "row counts name the tables"
        self.built = []    # [(tables, marks)] a chain, as it was built
        orig = dj._reorder_fact_first

        def spy(leaves, joins, assume_unique=frozenset()):
            got = orig(leaves, joins, assume_unique)
            if got is not None:
                self.built.append((
                    [self.names[jn.right.chunk.num_rows] for jn in got[1]],
                    [jn.selective for jn in got[1]]))
            return got
        monkeypatch.setattr(dj, "_reorder_fact_first", spy)
        monkeypatch.setattr(mpp_exec, "_reorder_fact_first", spy)

    def orders(self):
        return [tables for tables, _marks in self.built]

    def marks(self):
        return [marks for _tables, marks in self.built]


# -- the order: the cutting build first --------------------------------------

#: the builds of each changed chain in the order they attach, and the
#: step that took a cutting build ahead of a smaller candidate.  At
#: SF0.01 SSB's 20 suppliers are its smallest dimension, so Q3.1 and Q4.1
#: attach `supplier` first by either rule (at SF10 `date`'s 2,556 rows
#: are: test_the_first_pick_at_the_benchmarks_shapes)
_CHANGED = {
    "q5": (["orders", "supplier", "nation", "region", "customer"], 0),
    "q9": (["part", "supplier", "nation", "partsupp", "orders"], 0),
    "ssb_q2_1": (["part", "supplier", "date"], 0),
    "ssb_q3_1": (["supplier", "customer", "date"], None),
    "ssb_q4_1": (["supplier", "customer", "part", "date"], None),
}


@pytest.mark.parametrize("template", list(_CHANGED))
def test_the_chain_attaches_the_cutting_build_first(monkeypatch, template):
    mod = MODS[template]
    tables, tk = _loaded(_kind(template), SEEDS[_kind(template)][0])
    chains = _Chains(monkeypatch, tables)
    assert _rows(tk, "tpu", mod.SQL) == _rows(tk, "host", mod.SQL)
    order, step = _CHANGED[template]
    assert chains.orders() == [order]
    assert chains.marks() == [[i == step for i in range(len(order))]]


#: the first probe-path cut past a join, by the rule and by size alone:
#: Q9 attached `supplier` and `nation` before `part`, Q5 `supplier`,
#: `nation`, `region` (which cut) before `orders`.  (SSB Q2.1's 20
#: suppliers at SF0.01 keep a fifth of lineorder: they cut at the first
#: join by either rule)
_FIRST_CUT = {"q5": (0, 2), "q9": (0, 2)}


@pytest.mark.parametrize("template", list(_FIRST_CUT))
@pytest.mark.parametrize("rule", ["cut_first", "size"])
def test_the_first_cut_comes_after_one_lookup(monkeypatch, template, rule):
    """The settled program's first cut past a join (``fn.compacted``:
    the positions its trace cut) is at the chain's first join."""
    if rule == "size":
        _size_rule(monkeypatch)
    mod = MODS[template]
    _tables, tk = _loaded(_kind(template), SEEDS[_kind(template)][0])
    built = []
    orig = dj.compile_fragment

    def spy(*a, **kw):
        fn = orig(*a, **kw)
        built.append(fn)
        return fn
    monkeypatch.setattr(dj, "compile_fragment", spy)
    want = _rows(tk, "host", mod.SQL)
    for _ in range(2):     # the first execution learns the live counts
        assert _rows(tk, "tpu", mod.SQL) == want
    first = min(pos for pos in built[-1].compacted if pos >= 0)
    assert first == _FIRST_CUT[template][rule == "size"]


@pytest.mark.parametrize("template", list(_CHANGED))
@pytest.mark.parametrize("seed_at", [0, 1])
def test_the_answers_are_the_host_s_and_the_reference_s(template, seed_at):
    mod = MODS[template]
    tables, tk = _loaded(_kind(template), SEEDS[_kind(template)][seed_at])
    want = mod.reference(tables)
    assert want and want != [(None,)], "an empty answer proves nothing"
    assert _rows(tk, "host", mod.SQL) == want
    assert _rows(tk, "tpu", mod.SQL) == want


# -- the chains the rule leaves alone ------------------------------------------

class _Dispatched:
    """Every pipeline key the join fragments asked for, and the lowered
    text of every program dispatched, while it is open."""

    def __init__(self, monkeypatch):
        self.keys, self.texts = [], []
        acquire = dj.acquire_pipeline

        def keyed(key, *a, **kw):
            self.keys.append(key)
            return acquire(key, *a, **kw)
        monkeypatch.setattr(dj, "acquire_pipeline", keyed)
        observed = dev.observed_jit

        def lowered(fn, **jit_kw):
            run = observed(fn, **jit_kw)

            def call(*a, **k):
                self.texts.append(run.lower(*a, **k).as_text())
                return run(*a, **k)
            call.lower = run.lower
            return call
        monkeypatch.setattr(dev, "observed_jit", lowered)


#: name -> (engine, the chain by size an execution builds: none for a
#: template whose fragment is not a chain of inner joins)
_KEPT = {
    "q3": ("tpu", [["orders", "customer"]]),
    "q18": ("tpu", [["orders", "customer"]]),
    "q21": ("tpu", [["supplier", "nation", "orders"]]),
    "q4": ("tpu", []),
    "q13": ("tpu", []),
    "q17": ("tpu", []),
    "mesh_q3": ("tpu-mpp", [["orders", "customer"]]),
}


def _run_kept(monkeypatch, name):
    engine, _chains = _KEPT[name]
    mod = MODS[name.removeprefix("mesh_")]
    tables, tk = _loaded("tpch", SEEDS["tpch"][0])
    want = _rows(tk, "host", mod.SQL)
    chains = _Chains(monkeypatch, tables)
    seen = _Dispatched(monkeypatch)
    for _ in range(2):     # the first execution learns, the second settles
        assert _rows(tk, engine, mod.SQL) == want
    _drop_compiled()
    return chains, seen


@pytest.mark.parametrize("name", list(_KEPT))
def test_a_chain_with_no_cutting_build_keeps_its_keys_and_texts(
        monkeypatch, name):
    with monkeypatch.context() as m:
        _size_rule(m)
        by_size, size_seen = _run_kept(m, name)
    chains, seen = _run_kept(monkeypatch, name)
    assert chains.orders() == by_size.orders() == _KEPT[name][1] * 2
    assert not any(any(marks) for marks in chains.marks())
    assert seen.keys == size_seen.keys and seen.texts == size_seen.texts
    assert seen.texts, "no program was dispatched"


# -- the rule on chains made for it ---------------------------------------------

_N = 4000


@pytest.fixture(scope="module")
def star():
    """`f` (4,000 rows) with keys into `a` (50 rows, no filter in the
    queries), `b` (400 rows: `b.w < 3` keeps 30%) and `c` (1,000 rows:
    `c.w = 0` keeps 10%)."""
    tk = TestKit()
    tk.must_exec("create table f (ka bigint, kb bigint, kc bigint, v bigint)")
    tk.must_exec("create table a (k bigint, g bigint)")
    tk.must_exec("create table b (k bigint, w bigint)")
    tk.must_exec("create table c (k bigint, w bigint)")
    tk.must_exec("insert into f values " + ",".join(
        f"({i % 50}, {i * 7 % 400}, {i * 13 % 1000}, {i})"
        for i in range(_N)))
    tk.must_exec("insert into a values " + ",".join(
        f"({k}, {k % 4})" for k in range(50)))
    tk.must_exec("insert into b values " + ",".join(
        f"({k}, {k % 10})" for k in range(400)))
    tk.must_exec("insert into c values " + ",".join(
        f"({k}, {k % 10})" for k in range(1000)))
    tk.must_exec("set tidb_device_dispatch_rows = 1")
    tk.must_exec("set tidb_result_cache = 'OFF'")
    names = {_N: "f", 50: "a", 400: "b", 1000: "c"}
    return tk, {t: {"k": [0] * n} for n, t in names.items()}


_STAR = {
    # 30% of `b` kept: more than a quarter, so `a` (smaller) goes first
    "kept_30": ("select a.g, count(*), sum(f.v) from f, a, b "
                "where f.ka = a.k and f.kb = b.k and b.w < 3 "
                "group by a.g order by a.g", ["a", "b"], [False, False]),
    # 10% of `c` kept: the larger `c` goes ahead of the unfiltered `a`
    "kept_10": ("select a.g, count(*), sum(f.v) from f, a, c "
                "where f.ka = a.k and f.kc = c.k and c.w = 0 "
                "group by a.g order by a.g", ["c", "a"], [True, False]),
}


@pytest.mark.parametrize("shape", list(_STAR))
def test_the_rule_on_a_star(monkeypatch, star, shape):
    tk, tables = star
    sql, order, marks = _STAR[shape]
    chains = _Chains(monkeypatch, tables)
    assert _rows(tk, "tpu", sql) == _rows(tk, "host", sql)
    assert chains.orders() == [order] and chains.marks() == [marks]


def test_a_deferred_build_keeps_its_place(monkeypatch, star):
    """A build the hybrid join indexes by partitions (``assume_unique``)
    has no index at plan time: it ranks by its size, behind the smaller
    `a`, where with its filtered index it went first."""
    tk, tables = star
    sql = _STAR["kept_10"][0]
    seen = []
    orig = dj._reorder_fact_first

    def spy(leaves, joins, assume_unique=frozenset()):
        seen.append((leaves, joins))
        return orig(leaves, joins, assume_unique)
    monkeypatch.setattr(dj, "_reorder_fact_first", spy)
    _rows(tk, "tpu", sql)
    leaves, joins = seen[0]
    c_id, = [leaf.leaf_id for leaf in leaves if leaf.chunk.num_rows == 1000]
    _root, chain = orig(leaves, joins, assume_unique=frozenset((c_id,)))
    assert [jn.right.leaf_id for jn in chain] == [
        next(leaf.leaf_id for leaf in leaves if leaf.chunk.num_rows == 50),
        c_id]
    assert chain[1].strategy == ("uniq", "right", None)
    assert not any(jn.selective for jn in chain)


def _stand_in(lid, rows, kept=None):
    """A (leaf, index) pair as _attach_rank reads them: `kept` rows of
    `rows` under the leaf's filter, or no filter."""
    leaf = types.SimpleNamespace(leaf_id=lid,
                                 chunk=types.SimpleNamespace(num_rows=rows))
    idx = types.SimpleNamespace(filtered=kept is not None,
                                n_valid=rows if kept is None else kept,
                                n_rows=rows)
    return leaf, idx


@pytest.mark.parametrize("fact,cands,first", [
    # SSB SF10 (60M lineorder rows): date 2,556, supplier 20,000 (a
    # region keeps 1 in 5), customer 300,000 (1 in 5), part 800,000
    (60_000_000, {"date": (2556, None), "supplier": (20_000, 4000),
                  "part": (800_000, 32_000)}, "part"),           # Q2.1
    (60_000_000, {"date": (2556, 2192), "supplier": (20_000, 4000),
                  "customer": (300_000, 60_000)}, "supplier"),   # Q3.1
    (60_000_000, {"date": (2556, None), "supplier": (20_000, 4000),
                  "customer": (300_000, 60_000),
                  "part": (800_000, 320_000)}, "supplier"),      # Q4.1
    # TPC-H SF1 (6M lineitem rows)
    (6_000_000, {"supplier": (10_000, None), "part": (200_000, 10_800),
                 "partsupp": (800_000, None),
                 "orders": (1_500_000, None)}, "part"),          # Q9
    (6_000_000, {"supplier": (10_000, None),
                 "orders": (1_500_000, 227_000)}, "orders"),     # Q5
    (6_000_000, {"supplier": (10_000, None),
                 "orders": (1_500_000, 730_000)}, "supplier"),   # Q21
    (6_000_000, {"orders": (1_500_000, 727_000)}, "orders"),     # Q3
])
def test_the_first_pick_at_the_benchmarks_shapes(fact, cands, first):
    ranked = sorted(cands, key=lambda t: dj._attach_rank(
        *_stand_in(list(cands).index(t), *cands[t]), fact))
    assert ranked[0] == first


@pytest.mark.parametrize("kept,rows,want", [
    (250, 1000, (0, 64, 1000, 0)),     # exactly a quarter: cuts
    (251, 1000, (1, 0, 1000, 0)),      # past it: the size order
    (0, 1000, (0, 8, 1000, 0)),        # nothing kept at all
    (None, 1000, (1, 0, 1000, 0)),     # no filter
    (0, 0, (0, 8, 0, 0)),              # an empty build under a filter
])
def test_the_rank(kept, rows, want):
    assert dj._attach_rank(*_stand_in(0, rows, kept), 256) == want


# -- the counters and the note -------------------------------------------------

#: (chains, selective chains) one execution adds: a chain is counted for
#: every dispatched fragment whose inner joins _reorder_fact_first
#: chained; Q18's inner aggregate is a scan fragment, Q4 / Q13 / Q17 /
#: SSB Q1.1 build no chain
_COUNTED = {"q3": (1, 0), "q4": (0, 0), "q5": (1, 1), "q9": (1, 1),
            "q13": (0, 0), "q17": (0, 0), "q18": (1, 0), "q21": (1, 0),
            "ssb_q1_1": (0, 0), "ssb_q2_1": (1, 1), "ssb_q3_1": (1, 0),
            "ssb_q4_1": (1, 0)}


@pytest.mark.parametrize("template", list(_COUNTED))
def test_the_counters_and_the_note(template):
    mod = MODS[template]
    _tables, tk = _loaded(_kind(template), SEEDS[_kind(template)][0])
    want = _rows(tk, "host", mod.SQL)
    for _ in range(2):
        before = _pipelines(tk)
        assert _rows(tk, "tpu", mod.SQL) == want
        after = _pipelines(tk)
        assert [after[k] - before[k] for k in (
            "join_chains", "join_chains_selective")] == list(
                _COUNTED[template])
    plan = tk.must_query("explain analyze " + mod.SQL).rows
    notes = [p for row in plan for p in (row[2] or "").split(", ")]
    assert (notes.count("order:selective")
            == _COUNTED[template][1]), notes


def test_the_mesh_counts_its_chain_as_one_chip_s():
    _tables, tk = _loaded("tpch", SEEDS["tpch"][0])
    sql = MODS["q3"].SQL
    want = _rows(tk, "host", sql)
    before = _pipelines(tk)
    assert _rows(tk, "tpu-mpp", sql) == want
    after = _pipelines(tk)
    assert [after[k] - before[k] for k in (
        "join_chains", "join_chains_selective")] == [1, 0]


# -- join.selective_first_share: the reader and its entry ---------------------

_NAME = "join.selective_first_share"


def _reader():
    from benchmark.harness.resolve import BENCH_DIR, load_module
    return load_module(os.path.join(BENCH_DIR, "layer_metrics",
                                    _NAME + ".py"), "per_layer metric")


def _obs(before, after):
    from benchmark.harness import observe
    o = types.SimpleNamespace(
        status0={"device_pipelines": before},
        status1={"device_pipelines": after})
    o.counter_delta = lambda *path: observe.delta(o.status0, o.status1,
                                                  *path)
    return o


@pytest.mark.parametrize("before,after,want", [
    # the star: Q2.1, Q3.1, Q4.1 a round, three rounds (Q1.1 builds none)
    ({"join_chains": 5, "join_chains_selective": 2},
     {"join_chains": 14, "join_chains_selective": 11}, 100.0),
    # Q5 beside Q3, or Q9 beside Q18's outer fragment: eight pairs
    ({"join_chains": 0, "join_chains_selective": 0},
     {"join_chains": 16, "join_chains_selective": 8}, 50.0),
    # Q21, the mesh's Q3: chains, none of them changed
    ({"join_chains": 3, "join_chains_selective": 0},
     {"join_chains": 9, "join_chains_selective": 0}, 0.0),
    # no chain in the window
    ({"join_chains": 3, "join_chains_selective": 1},
     {"join_chains": 3, "join_chains_selective": 1}, None),
    ({}, {}, None),                       # a program without the counters
])
def test_the_reader(before, after, want):
    assert _reader().read(_obs(before, after)) == want


def test_the_entry_and_the_cells_that_report_it():
    from benchmark.harness.resolve import ROOT, Cell
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    entry, = [m for m in spec["per_layer"] if m["name"] == _NAME]
    assert {k: v for k, v in entry.items() if k != "workloads"} == {
        "name": _NAME, "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "XLA programs",
        "moves": "query_geomean_s"}
    chains = {"tpch-sf1.q3q5", "tpch-sf1-mpp4.q3", "ssb-sf10.flights",
              "tpch-sf1.q9q18", "tpch-sf1.q21"}
    assert set(entry["workloads"]) == chains
    for w in spec["workloads"]:
        names = {m["name"] for m, _mod in Cell(w["name"]).per_layer}
        assert (_NAME in names) == (w["name"] in chains)
