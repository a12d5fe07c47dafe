"""The host's work inside a fragment has names (ISSUE 35): a span and a
counter on every join-index build (a miss of the one index a key column
caches; a hit says nothing), ``fetch.d2h`` split into ``device.wait`` and
``fetch.copy`` under a live trace and left as its one ``device_get``
without one, and a counter and an event on every program a fragment runs
again at another capacity.  No environment variable, no second
stopwatch."""

import ast
import json
import pathlib
import sys

import numpy as np
import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from benchmark.harness.observe import find_spans  # noqa: E402
import tidb_tpu.executor.device_join as dj  # noqa: E402
from tidb_tpu.executor import device_exec, join_index  # noqa: E402
from tidb_tpu.executor.join_index import build_join_index  # noqa: E402
from tidb_tpu.session import tracing  # noqa: E402
from tidb_tpu.sqltypes import FieldType, TYPE_LONGLONG  # noqa: E402
from tidb_tpu.testkit import TestKit  # noqa: E402
from tidb_tpu.utils.chunk import Column  # noqa: E402

PKG = pathlib.Path(device_exec.__file__).resolve().parents[1]
INDEX_TAGS = {"layout", "rows", "kept", "bytes", "filtered", "prefix"}


def _col(vals, nulls=None):
    a = np.asarray(vals, dtype=np.int64)
    return Column(FieldType(tp=TYPE_LONGLONG), a,
                  np.zeros(len(a), dtype=bool) if nulls is None
                  else np.asarray(nulls, dtype=bool))


def _pipes():
    return device_exec.pipe_cache_stats()


@pytest.fixture
def traced():
    """run(fn) -> (fn()'s result, the span tree of a trace begun around
    it).  The ring and the counters end as they began."""
    def run(fn):
        tr = tracing.begin("statement")
        try:
            out = fn()
        finally:
            tracing.finish(tr)
        return out, tr.to_dict()["root"]
    yield run
    tracing.reset_for_tests()


# -- A: join.index_build ------------------------------------------------------

def test_two_equal_calls_build_and_count_once(traced):
    cols = (_col(range(0, 400, 4)),)
    before = _pipes()["join_index_builds"]
    (first, second), root = traced(
        lambda: (build_join_index(cols), build_join_index(cols)))
    assert first is second and first.kind == "dense"
    assert _pipes()["join_index_builds"] == before + 1
    assert len(find_spans(root, "join.index_build")) == 1


def _dense():
    return (_col(range(0, 400, 4)),), {}


def _dense_filtered():
    keys = np.arange(0, 400, 4)
    return (_col(keys),), {"mask_fn": lambda: keys < 200,
                           "cache_tag": "k<200"}


def _sorted_prefixed():
    return (_col(range(0, 50_000, 50)),), {}


def _sorted_plain():
    return (_col(range(1, 65)),), {"force_sorted": True}


def _no_index():
    # two columns whose packed span passes 2^62: the negative entry
    return (_col([0, 1 << 40]), _col([0, 1 << 40])), {}


@pytest.mark.parametrize("make,patch_direct,want", [
    # bytes: the slot table over the quantized span (416 x int32)
    (_dense, None, dict(layout="dense", rows=100, kept=100,
                        bytes=416 * 4, filtered=False, prefix=False)),
    (_dense_filtered, None, dict(layout="dense", rows=100, kept=50,
                                 filtered=True, prefix=False)),
    # the slot table (200 KB) refused, the prefix (about 8 KB) admitted
    (_sorted_prefixed, 1 << 14, dict(layout="sorted", rows=1000, kept=1000,
                                     filtered=False, prefix=True)),
    (_sorted_plain, None, dict(layout="sorted", rows=64, kept=64,
                               bytes=64 * 8 + 64 * 4, filtered=False,
                               prefix=False)),
    (_no_index, None, dict(layout="none", rows=2, kept=2, bytes=0,
                           filtered=False, prefix=False)),
], ids=["dense", "dense-filtered", "sorted-prefix", "sorted", "none"])
def test_the_miss_opens_the_span_with_its_six_tags(
        traced, monkeypatch, make, patch_direct, want):
    if patch_direct is not None:
        monkeypatch.setattr(join_index, "_DIRECT_MAX_BYTES", patch_direct)
    cols, kw = make()
    idx, root = traced(lambda: build_join_index(cols, **kw))
    (sp,) = find_spans(root, "join.index_build")
    tags = sp["tags"]
    assert set(tags) == INDEX_TAGS
    assert {k: tags[k] for k in want} == want
    assert (idx is None) == (want["layout"] == "none")
    if idx is not None:
        sent = [a for a in (*idx.host_arrays(), idx.prefix) if a is not None]
        assert tags["bytes"] == sum(a.nbytes for a in sent) > 0
    # the hit: the same object back, no span, no count
    before = _pipes()["join_index_builds"]
    again, root = traced(lambda: build_join_index(cols, **kw))
    assert again is idx and not find_spans(root, "join.index_build")
    assert _pipes()["join_index_builds"] == before


def test_alternating_two_filter_tags_builds_every_time(traced):
    """One Column holds one index: Q3 and Q5 alternating over `orders`
    (ROADMAP S3(3)'s case), pinned as today's behaviour."""
    keys = np.arange(0, 400, 4)
    cols = (_col(keys),)
    tags = {"a": lambda: keys < 200, "b": lambda: keys >= 200}
    before = _pipes()["join_index_builds"]

    def alternate():
        return [build_join_index(cols, mask_fn=tags[t], cache_tag=t)
                for t in "abab"]
    built, root = traced(alternate)
    assert len({id(i) for i in built}) == 4
    assert _pipes()["join_index_builds"] == before + 4
    spans = find_spans(root, "join.index_build")
    assert [s["tags"]["kept"] for s in spans] == [50] * 4
    assert all(s["tags"]["filtered"] for s in spans)


def test_a_build_without_a_trace_counts_and_opens_nothing():
    assert tracing.active() is None
    before = _pipes()["join_index_builds"]
    assert build_join_index((_col(range(10)),)).kind == "dense"
    assert _pipes()["join_index_builds"] == before + 1
    assert tracing.span("join.index_build") is tracing._NOOP


def test_a_filter_that_raises_leaves_the_span_marked(traced):
    def boom():
        raise ValueError("filter")

    def run():
        with pytest.raises(ValueError):
            build_join_index((_col(range(10)),), mask_fn=boom,
                             cache_tag="boom")
    _out, root = traced(run)
    (sp,) = find_spans(root, "join.index_build")
    assert sp["tags"] == {"error": "ValueError"}


# -- B: device.wait and fetch.copy inside fetch.d2h ---------------------------

def _tree():
    import jax.numpy as jnp
    a = jnp.arange(12, dtype=jnp.int64)
    return {"sum": a.sum(), "pair": (a[:5] * 2, (a > 3)[:7])}


def _same(x, y):
    import jax
    lx, tx = jax.tree_util.tree_flatten(x)
    ly, ty = jax.tree_util.tree_flatten(y)
    return tx == ty and all(
        isinstance(a, np.ndarray) and a.dtype == b.dtype
        and np.array_equal(a, b) for a, b in zip(lx, ly))


def test_fetch_nests_the_wait_and_the_copy_under_a_live_trace(traced):
    plain = device_exec._fetch(_tree)
    got, root = traced(lambda: device_exec._fetch(_tree))
    assert _same(got, plain)
    (d2h,) = find_spans(root, "fetch.d2h")
    assert [c["name"] for c in d2h["children"]] == ["device.wait",
                                                     "fetch.copy"]
    wait, copy = d2h["children"]
    assert copy["tags"] == {"arrays": 3, "bytes": 8 + 5 * 8 + 7}
    assert d2h["tags"] == {"bytes": 8 + 5 * 8 + 7}
    assert wait.get("tags", {}) == {}
    # the children lie inside the parent, in order
    assert d2h["start_s"] <= wait["start_s"] <= copy["start_s"]
    assert wait["duration_s"] + copy["duration_s"] <= d2h["duration_s"]


@pytest.fixture
def jax_calls(monkeypatch):
    """[name] of every device_get / block_until_ready `_fetch` makes."""
    jax = device_exec.jax
    calls = []
    for name in ("device_get", "block_until_ready"):
        real = getattr(jax, name)

        def spy(x, _real=real, _name=name):
            calls.append(_name)
            return _real(x)
        monkeypatch.setattr(jax, name, spy)
    return calls


def test_fetch_without_a_trace_is_the_one_device_get(jax_calls):
    made = []

    def make():
        made.append(1)
        return _tree()
    assert tracing.active() is None
    out = device_exec._fetch(make)
    assert jax_calls == ["device_get"] and made == [1]
    assert _same(out, device_exec.jax.device_get(_tree()))


def test_fetch_under_a_trace_waits_then_copies(traced, jax_calls):
    traced(lambda: device_exec._fetch(_tree))
    assert jax_calls == ["block_until_ready", "device_get"]


def test_fetch_whose_span_was_dropped_is_the_one_device_get(
        traced, jax_calls, monkeypatch):
    """A trace that is full gives `fetch.d2h` no span: nothing to nest
    the parts under, so the plain call."""
    monkeypatch.setattr(tracing, "MAX_SPANS", 1)
    out, root = traced(lambda: device_exec._fetch(_tree))
    assert jax_calls == ["device_get"] and not root.get("children")
    assert _same(out, device_exec.jax.device_get(_tree()))


# -- C: capacity_reruns and fragment.rerun ------------------------------------

N_GROUPS = 500          # a key column without statistics estimates 64


@pytest.fixture(scope="module")
def tk():
    tk = TestKit()
    tk.must_exec("use test")
    tk.must_exec("create table wide (g bigint, v bigint)")
    tk.must_exec("insert into wide values " + ",".join(
        f"({i % N_GROUPS}, {i})" for i in range(2 * N_GROUPS)))
    tk.must_exec("create table t (a bigint primary key, b bigint)")
    tk.must_exec("create table u (k bigint primary key, v bigint)")
    tk.must_exec("insert into t values " + ",".join(
        f"({i}, {i % 3})" for i in range(64)))
    tk.must_exec("insert into u values " + ",".join(
        f"({i}, {i * 2})" for i in range(64)))
    tk.must_exec("create table d (k bigint primary key, g bigint)")
    tk.must_exec("create table f (k bigint, v bigint)")
    tk.must_exec("insert into d values " + ",".join(
        f"({i}, {i % 7})" for i in range(1, 201)))
    tk.must_exec("insert into f values " + ",".join(
        f"({1 + i % 200}, {i % 150})" for i in range(1500)))
    tk.must_exec("set tidb_result_cache = 'OFF'")
    tk.must_exec("set tidb_device_dispatch_rows = 1")
    tk.must_exec("set tidb_executor_engine = 'tpu'")
    return tk


def _status(tk):
    return json.loads(tk.must_query("DIAG STATUS").rows[0][0])[
        "device_pipelines"]


def _traced_query(tk, sql):
    """(rows, the statement's span tree, growth of capacity_reruns)."""
    tk.must_exec("set tidb_trace_sampling_rate = 1")
    tracing.reset_for_tests()
    before = _status(tk)["capacity_reruns"]
    try:
        rows = tk.must_query(sql).rows
        trees = [tr for tr in json.loads(tk.must_query(
            "DIAG TRACEJSON").rows[0][0])["rows"]
            if tr["root"].get("tags", {}).get("stmt") == "SelectStmt"]
    finally:
        tk.must_exec("set tidb_trace_sampling_rate = 0")
    return rows, trees[-1], _status(tk)["capacity_reruns"] - before


def _events(node, name):
    out = [e for e in node.get("events", ()) if e["name"] == name]
    for child in node.get("children", ()):
        out += _events(child, name)
    return out


def test_a_scan_aggregate_past_its_estimate_reruns_on_every_execution(tk):
    """`device_agg` forgets the capacity it learned (ROADMAP S7): the
    counter and the event say so on each of three executions."""
    sql = "select g, sum(v) from wide group by g"
    for _ in range(3):
        rows, tr, grew = _traced_query(tk, sql)
        assert len(rows) == N_GROUPS and grew == 1
        assert tr["dropped"] == 0
        (ev,) = _events(tr["root"], "fragment.rerun")
        assert ev["tags"] == {"shape": "agg", "capacity": 512,
                              "groups": N_GROUPS}
        # two programs ran: two round trips before the body's
        assert len(find_spans(tr["root"], "device.wait")) == \
            len(find_spans(tr["root"], "fetch.d2h")) >= 2
    # without a trace the counter still counts
    before = _status(tk)["capacity_reruns"]
    assert len(tk.must_query(sql).rows) == N_GROUPS
    assert _status(tk)["capacity_reruns"] == before + 1


def test_a_warm_join_fragment_reruns_nothing(tk):
    sql = ("select t.b, sum(u.v) from t join u on t.a = u.k "
           "where u.v >= 0 group by t.b")
    want = tk.must_query(sql).rows      # compiles; learns its capacities
    tk.must_query(sql)
    rows, tr, grew = _traced_query(tk, sql)
    assert rows == want and grew == 0
    assert not _events(tr["root"], "fragment.rerun")
    # its indexes are cached: no build, and the fetch still splits
    assert not find_spans(tr["root"], "join.index_build")
    d2h = find_spans(tr["root"], "fetch.d2h")
    assert d2h and all(
        [c["name"] for c in f["children"]] == ["device.wait", "fetch.copy"]
        for f in d2h)


def test_a_paged_join_restarts_once_and_remembers(tk, monkeypatch):
    """A page with more groups than the estimate restarts the pass (one
    rerun, with the pages it had dispatched) and the fold of the pages'
    states outgrows its first capacity (another); none on the next
    execution; LAST_PAGED_STATS holds facts, not seconds."""
    monkeypatch.setattr(device_exec, "_SORTED_SCAN_MAX_ROWS", 1000)
    monkeypatch.setattr(dj, "_PROBE_PAGE_ROWS", 512)
    sql = ("select f.v, count(*) from f, d where f.k = d.k "
           "group by f.v order by f.v")
    seen = []
    real = dj.LAST_PAGED_STATS.update

    def keep(kv):
        seen.append(dict(kv))
        real(kv)
    monkeypatch.setattr(dj.LAST_PAGED_STATS, "update", keep)
    rows, tr, grew = _traced_query(tk, sql)
    assert len(rows) == 150 and grew == 2
    ev, merge = _events(tr["root"], "fragment.rerun")
    assert ev["tags"]["shape"] == "join.paged"
    assert ev["tags"]["groups"] == 150 and ev["tags"]["capacity"] == 256
    assert 1 <= ev["tags"]["pages"] <= 3
    # the fold began at the first estimate too, and grew once
    assert merge["tags"] == {"shape": "merge", "capacity": 256,
                             "groups": 150}
    assert seen[-1] == {"pages": 3, "capacity": 256, "groups": 150}
    rows2, tr2, grew2 = _traced_query(tk, sql)
    assert rows2 == rows and grew2 == 0
    assert not _events(tr2["root"], "fragment.rerun")


# -- what went ----------------------------------------------------------------

def test_no_environment_variable_beside_the_tracing():
    hits = [str(p.relative_to(PKG)) for p in PKG.rglob("*.py")
            if "TIDB_TPU_DEBUG_JOIN" in p.read_text()]
    assert hits == []


@pytest.mark.parametrize("fn", ["_paged_join_agg", "device_join_agg",
                                "_join_agg", "FragmentRunner"])
def test_no_second_stopwatch_in_the_join_fragments(fn):
    tree = ast.parse(pathlib.Path(dj.__file__).read_text())
    (top,) = [n for n in ast.walk(tree)
              if isinstance(n, (ast.FunctionDef, ast.ClassDef))
              and n.name == fn]
    names = {n.attr for n in ast.walk(top) if isinstance(n, ast.Attribute)}
    names |= {n.id for n in ast.walk(top) if isinstance(n, ast.Name)}
    assert not names & {"perf_counter", "environ", "stderr"}
