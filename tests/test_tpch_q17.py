"""TPC-H Q17 in the specification's own text: the plain numpy reference,
the host engine and the `tpu` engine give the same row on two seeds.  The
correlated ``0.2 * avg(l_quantity)`` plans as a semi join against
``lineitem group by l_partkey`` with the ``<`` as its residual, and under
the `tpu` engine the join is ONE fused `engine:tpu` fragment whose build
is that aggregate's result: a derived leaf, made by the aggregate's own
device fragment under the span ``join.derived_build``, indexed like a
table and passed to the program as arguments.  What the derived leaf
places on the device is the statement's: released when the fragment
ends, never served to a later request.  The benchmark's cell
`tpch-sf1.q17` runs it at SF1 on the chip; this file holds it at a size
XLA:CPU takes in seconds."""

import gc
import json
import pathlib
import sys

import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from benchmark.datasets import tpch  # noqa: E402
from benchmark.queries import q17, q18  # noqa: E402
from tidb_tpu.executor import device_join as dj  # noqa: E402
from tidb_tpu.ops import residency  # noqa: E402
from tidb_tpu.session import tracing  # noqa: E402
from tidb_tpu.testkit import TestKit  # noqa: E402

SF = 0.02
#: two seeds whose parts of Brand#23 / MED BOX have lines under their
#: threshold (3 and 5 parts at SF0.02), so the answer is not NULL
SEEDS = (7, 2100000001)


def _load(seed, sf=SF):
    want = {t: list(cols) for t, cols in tpch.SCHEMA.items()}
    tables = tpch.generate(seed, sf, want)
    tk = TestKit()
    tpch.load(tk, tables, want, False, f"test_tpch_q17/{seed}/{sf}")
    tk.must_exec("set tidb_device_dispatch_rows = 1")
    tk.must_exec("set tidb_result_cache = 'OFF'")
    return tables, tk


_LOADED = {}


def _loaded(seed):
    """(tables, TestKit) of one seed, made once a module."""
    if seed not in _LOADED:
        _LOADED[seed] = _load(seed)
    return _LOADED[seed]


def _rows(tk, engine, sql):
    tk.must_exec(f"set tidb_executor_engine = '{engine}'")
    return [tuple(r) for r in tk.must_query(sql).rows]


def _plan_notes(tk, sql):
    """[(operator, [notes])] of EXPLAIN ANALYZE."""
    return [(row[0].strip("└─ "), (row[2] or "").split(", "))
            for row in tk.must_query("explain analyze " + sql).rows]


def _pipelines(tk):
    return json.loads(tk.must_query("DIAG STATUS").rows[0][0])[
        "device_pipelines"]


def _grew(before, after, *keys):
    return [after[k] - before[k] for k in keys]


def _find(node, name):
    out = [node] if node["name"] == name else []
    for c in node.get("children", ()):
        out += _find(c, name)
    return out


# -- reference == host == tpu, in the specification's text ----------------------

def _spec_text():
    src = (pathlib.Path(__file__).parent / "test_tpch.py").read_text()
    body = src.split("def test_q17(tk):")[1].split('"""')[1]
    return " ".join(body.split())


def test_the_template_is_the_specifications_text():
    assert " ".join(q17.SQL.split()) == _spec_text()
    assert "p_brand = 'Brand#23'" in q17.SQL
    assert "p_container = 'MED BOX'" in q17.SQL


@pytest.mark.parametrize("seed", SEEDS)
def test_reference_host_and_tpu_agree(seed):
    tables, tk = _loaded(seed)
    want = q17.reference(tables)
    assert want != [(None,)], "an empty answer proves nothing"
    assert q17.counts(tables)["lines_under"] > 0
    assert _rows(tk, "host", q17.SQL) == want
    assert _rows(tk, "tpu", q17.SQL) == want


def test_the_plan_is_a_semi_join_against_the_regrouped_aggregate():
    _tables, tk = _loaded(SEEDS[0])
    plan = [(r[0].strip("└─ "), r[1]) for r in
            tk.must_query("explain " + q17.SQL).rows]
    assert "apply" not in str(plan).lower()
    (semi,) = [info for op, info in plan if info.startswith("semi")]
    assert "other:lt(" in semi
    assert ("HashAgg", "group by:[Col#0(l_partkey)], "
            "funcs:[avg(Col#1(l_quantity))]") in plan


# -- one fragment with a derived build, counted and printed ---------------------

@pytest.mark.parametrize("seed", SEEDS)
def test_q17_is_one_fragment_whose_build_is_derived(seed):
    tables, tk = _loaded(seed)
    groups = q17.counts(tables)["groups"]
    _rows(tk, "tpu", q17.SQL)              # capacities learned
    before = _pipelines(tk)
    _rows(tk, "tpu", q17.SQL)
    after = _pipelines(tk)
    # the part lookup and the semi join over the derived leaf, its
    # residual tested on the one row its unique key gathers
    assert _grew(before, after, "join_direct", "join_search", "join_semi",
                 "join_residual", "join_expand") == [2, 0, 1, 1, 0]
    assert _grew(before, after, "join_derived", "join_derived_rows",
                 "semi_insets", "unsupported", "compiles") == [
        1, groups, 0, 0, 0]
    # the derived leaf is a new relation every request: its index is built
    # anew (the part's is cached)
    assert _grew(before, after, "join_index_builds") == [1]
    plan = _plan_notes(tk, q17.SQL)
    engines = [(op, n) for op, notes in plan for n in notes
               if n.startswith("engine:")]
    # the join fragment, and the aggregate that made its build
    assert [n for _op, n in engines] == ["engine:tpu", "engine:tpu"]
    (outer,) = [notes for op, notes in plan if any(
        n.startswith("join:") for n in notes)]
    assert "engine:tpu" in outer
    assert "join:direct x2 (semi x1, residual x1)" in ", ".join(outer)
    assert f"derived:x1 (rows {groups})" in outer
    assert "agg:sort" in outer and "probe:resident" in outer
    assert not [n for _op, notes in plan for n in notes
                if n.startswith("device_unsupported:")]


def test_the_derived_build_has_a_span_and_counts_alike_traced_or_not():
    tables, tk = _loaded(SEEDS[1])
    groups = q17.counts(tables)["groups"]
    _rows(tk, "tpu", q17.SQL)
    grown = []
    for rate in (0, 1):
        tk.must_exec(f"set tidb_trace_sampling_rate = {rate}")
        before = _pipelines(tk)
        tk.must_query(q17.SQL)
        grown.append(_grew(before, _pipelines(tk), "join_derived",
                           "join_derived_rows"))
    tree = tracing.last_trace().to_dict()["root"]
    tk.must_exec("set tidb_trace_sampling_rate = 0")
    assert grown == [[1, groups], [1, groups]]
    (sp,) = _find(tree, "join.derived_build")
    assert sp["tags"]["rows"] == groups and sp["tags"]["cols"] == 2
    assert sp["tags"]["bytes"] > 0
    # the aggregate's own device fragment nests inside the span, and the
    # join fragment's around it
    assert len(_find(sp, "device.dispatch")) == 1
    assert len(_find(tree, "device.dispatch")) == 2
    assert not _find(tree, "subquery.materialize")
    # and TRACE <stmt> shows it
    tk.must_exec("set tidb_executor_engine = 'tpu'")
    ops = [row[0] for row in tk.must_query("trace " + q17.SQL).rows]
    assert sum("join.derived_build" in o for o in ops) == 1


def test_the_outer_program_is_keyed_alike_on_both_seeds(monkeypatch):
    """Derived values are arguments: the join fragment's pipeline key holds
    nothing of the derived leaf's data.  Two things in it follow the
    tables, not the derived build, and are held still here: the cuts to
    the live rows (off), and the packs of part's filtered index, the
    quantized range of the keys its filter keeps (the S0 line of the
    signature)."""
    keys = []
    orig = dj.acquire_pipeline

    def spy(key, build, dict_refs, **kw):
        if kw.get("shape") == "join":
            keys.append(key)
        return orig(key, build, dict_refs, **kw)
    monkeypatch.setattr(dj, "acquire_pipeline", spy)
    monkeypatch.setattr(dj, "compact_to", lambda live, n: None)
    got = []
    for seed in SEEDS:
        tables, tk = _loaded(seed)
        _rows(tk, "tpu", q17.SQL)          # capacities learned
        keys.clear()
        assert _rows(tk, "tpu", q17.SQL) == q17.reference(tables)
        (key,) = keys
        sig = key[0].split("\n")
        (derived,) = [ln for ln in sig if ln.startswith("J0/semi:")]
        assert sig[sig.index(derived) + 1].startswith("S1:uniq/right/dense/")
        got.append(([ln for ln in sig if not ln.startswith("S0:")],
                    key[1:]))
    assert got[0] == got[1]


def test_what_the_derived_leaf_places_is_released_with_the_statement():
    _tables, tk = _loaded(SEEDS[0])
    _rows(tk, "tpu", q17.SQL)          # the tables' columns are resident
    gc.collect()
    first = residency.snapshot()
    for _ in range(10):
        _rows(tk, "tpu", q17.SQL)
        # nothing of the statement's is left on the ledger
        assert not [e for e in list(residency._ENTRIES.values())
                    if e.scoped]
    gc.collect()
    last = residency.snapshot()
    assert last["entries"] == first["entries"]
    assert last["hbm_bytes_cached"] == first["hbm_bytes_cached"]
    assert last["hbm_evictions"] == first["hbm_evictions"]
    # the derived leaf's two columns and its slot table, each request
    assert last["statement_releases"] - first["statement_releases"] == 30
    assert residency.verify_ledger()["ok"]


def test_a_scoped_upload_evicts_nothing_and_leaves_with_release():
    import numpy as np
    residency.set_budget(residency.resident_bytes() + 4096)
    try:
        table, derived = residency.CacheOwner(), residency.CacheOwner()
        residency.publish(table, np.zeros(256, np.int64), None)
        ev0 = residency.snapshot()["hbm_evictions"]
        residency.publish(derived, np.zeros(512, np.int64), None,
                          scoped=True)
        # past the budget, and the table's column stays
        assert residency.lookup(table, 256) is not None
        assert residency.lookup(derived, 512) is not None
        assert residency.release((derived, table)) == 2
        assert residency.lookup(derived, 512) is None
        assert residency.release((derived,)) == 0
        assert residency.snapshot()["hbm_evictions"] == ev0
        assert residency.verify_ledger()["ok"]
    finally:
        residency.set_budget(0)


# -- by hand: a write between two requests ------------------------------------

@pytest.fixture()
def hand():
    tk = TestKit()
    tk.must_exec("create table part (p_partkey bigint primary key, "
                 "p_brand varchar(10), p_container varchar(10))")
    tk.must_exec("create table lineitem (l_partkey bigint, l_quantity "
                 "decimal(15,2), l_extendedprice decimal(15,2))")
    tk.must_exec("insert into part values (1, 'Brand#23', 'MED BOX'), "
                 "(2, 'Brand#23', 'MED BOX'), (3, 'Brand#23', 'MED BOX'), "
                 "(4, 'Brand#23', 'LG BOX'), (5, 'Brand#12', 'MED BOX'), "
                 "(6, 'Brand#23', 'MED BOX')")
    # part 1: avg 15.25, threshold 3.05; part 3: avg 25, threshold 5 and
    # a line of 5 (not under it); parts 4 and 5 are not asked for
    lines = [(1, 1, 100.5), (1, 10, 1000), (1, 20, 2000), (1, 30, 3000),
             (2, 5, 50), (2, 5, 50), (3, 5, 77.77), (3, 45, 4500),
             (4, 1, 1), (4, 50, 50), (5, 1, 1), (5, 50, 50)]
    tk.must_exec("insert into lineitem values " + ", ".join(
        f"({p}, {q}, {e})" for p, q, e in lines))
    tk.must_exec("set tidb_device_dispatch_rows = 1")
    tk.must_exec("set tidb_result_cache = 'OFF'")
    return tk


def test_a_write_between_two_requests_is_read(hand):
    answers = []
    for write in (None, "(3, 1, 7)", "(1, 1, 0.5), (2, 1, 1)",
                  "(6, 2, 14)"):
        if write is not None:
            hand.must_exec("insert into lineitem values " + write)
        want = _rows(hand, "host", q17.SQL)
        assert _rows(hand, "tpu", q17.SQL) == want
        answers.append(want)
    # 100.50 / 7; then part 3's new line (threshold 3.4); then part 1's
    # (threshold 2.48) and part 2's (threshold 0.7333334: not under it);
    # then part 6's lone line, equal to its own average
    assert answers == [[("14.357143",)], [("15.357143",)],
                       [("15.428571",)], [("15.428571",)]]
    plan = _plan_notes(hand, q17.SQL)
    assert "derived:x1 (rows 6)" in [n for _op, ns in plan for n in ns]


# -- what the derived build does not take --------------------------------------

@pytest.fixture(scope="module")
def strings():
    tk = TestKit()
    tk.must_exec("create table t (k bigint, s varchar(8), v bigint)")
    tk.must_exec("create table u (k bigint, s varchar(8), w bigint)")
    tk.must_exec("insert into t values " + ", ".join(
        f"({i % 7}, 'n{i % 5}', {i % 11})" for i in range(60)))
    tk.must_exec("insert into u values " + ", ".join(
        f"({i % 7}, 'n{i % 5}', {i % 13})" for i in range(40)))
    tk.must_exec("set tidb_device_dispatch_rows = 1")
    tk.must_exec("set tidb_result_cache = 'OFF'")
    return tk


@pytest.mark.parametrize("sql,why", [
    ("select count(*), sum(v) from t where v < (select max(w) from u "
     "where u.s = t.s)", "string/float join keys"),
    ("select count(*), sum(v) from t where s < (select max(u.s) from u "
     "where u.k = t.k)", "derived build column"),
])
def test_a_derived_build_the_device_cannot_hold_says_so(strings, sql, why):
    want = _rows(strings, "host", sql)
    assert want[0][0] not in ("0", 0)
    before = _pipelines(strings)
    assert _rows(strings, "tpu", sql) == want
    after = _pipelines(strings)
    # refused before the build ran: the aggregate subquery runs once, on
    # the host path
    assert _grew(before, after, "unsupported", "join_derived") == [1, 0]
    notes = [n for _op, ns in _plan_notes(strings, sql) for n in ns]
    (said,) = [n for n in notes if n.startswith("device_unsupported:")]
    assert why in said


# -- Q18's in-set fold keeps its path ------------------------------------------

def test_q18s_in_set_fold_is_not_a_derived_leaf():
    tables, tk = _loaded(SEEDS[0])
    want = q18.reference(tables)
    for _ in range(2):                     # capacities and cuts learned
        _rows(tk, "tpu", q18.SQL)
    tk.must_exec("set tidb_trace_sampling_rate = 1")
    before = _pipelines(tk)
    assert [tuple(r) for r in tk.must_query(q18.SQL).rows] == want
    tree = tracing.last_trace().to_dict()["root"]
    after = _pipelines(tk)
    tk.must_exec("set tidb_trace_sampling_rate = 0")
    assert _grew(before, after, "semi_insets", "join_derived",
                 "unsupported", "compiles") == [1, 0, 0, 0]
    (sp,) = _find(tree, "subquery.materialize")
    assert set(sp["tags"]) == {"rows", "kept"}
    assert sp["tags"]["kept"] == len(want)
    assert not _find(tree, "join.derived_build")
