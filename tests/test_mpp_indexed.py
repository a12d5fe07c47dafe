"""The mesh probes by direct address too (ISSUE 32).

A ``tpu-mpp`` join fragment in the paged join's language whose builds
have host-built direct indexes runs ``device_join.compile_fragment``'s own
body on every shard (``mpp_exec._indexed_chain`` decides, ``_shard_program``
wraps): held here to the host engine row for row, to the one-chip
fragment's counters, to a lowered text with no sort outside the aggregate,
no ``all_to_all`` and no gather through an ``iota``, and to zero compiles
inside a bucket.  The OTHER side (a non-unique or searched build, a build
over ``tidb_broadcast_join_threshold_size``, a bushy build, a non-inner
join) keeps the in-program joins, and is held to the same answers.  Last,
the refactor that exposed the body moved no one-chip program.
"""

import hashlib
import json

import pytest

from test_join_gather_elision import _iota_gathers
from benchmark.datasets import ssb, tpch
from benchmark.queries import q1, q3, q5, q6, q18, ssb_q2_1
from tidb_tpu.executor import device_exec, device_join, join_index, mpp_exec
from tidb_tpu.executor.mpp_exec import MPP_STATS
from tidb_tpu.ops import device as dev
from tidb_tpu.testkit import TestKit

pytestmark = pytest.mark.multichip


# -- data: a snowflake with NULLs where the fragment can meet one -------------

def _snowflake(tk, n_li=501, n_ord=120, n_cust=30):
    """region <- nation <- cust <- ord <- li -> supp: TPC-H's Q3 / Q5
    snowflake at toy size, keys sparse (3 of every 4 values unused), with a
    NULL in a build column the aggregate groups by (`ord.pri`), in a build
    column that is the next join's probe key (`ord.ck`) and in the probe
    leaf's own key (`li.ok`)."""
    tk.must_exec("create table region (rk bigint primary key, rname "
                 "varchar(8))")
    tk.must_exec("insert into region values (0, 'R0'), (4, 'R1'), (8, 'R2')")
    tk.must_exec("create table nation (nk bigint primary key, nname "
                 "varchar(8), rk bigint)")
    tk.must_exec("insert into nation values " + ",".join(
        f"({4 * i}, 'N{i}', {4 * (i % 3)})" for i in range(9)))
    tk.must_exec("create table supp (sk bigint primary key, nk bigint)")
    tk.must_exec("insert into supp values " + ",".join(
        f"({4 * i}, {4 * (i % 9)})" for i in range(20)))
    tk.must_exec("create table cust (ck bigint primary key, seg varchar(8), "
                 "nk bigint)")
    tk.must_exec("insert into cust values " + ",".join(
        f"({4 * i}, 'S{i % 3}', {4 * (i % 9)})" for i in range(n_cust)))
    tk.must_exec("create table ord (ok bigint primary key, ck bigint, "
                 "od bigint, pri bigint)")
    tk.must_exec("insert into ord values " + ",".join(
        f"({4 * i}, {'null' if i % 17 == 5 else 4 * (i % n_cust)}, "
        f"{100 + i % 50}, {'null' if i % 11 == 3 else i % 4})"
        for i in range(n_ord)))
    tk.must_exec("create table li (lk bigint primary key, ok bigint, "
                 "sk bigint, price bigint, disc bigint, sd bigint)")
    tk.must_exec("insert into li values " + ",".join(
        f"({i}, {'null' if i % 23 == 7 else 4 * (i * 7 % (n_ord + 9))}, "
        f"{4 * (i % 20)}, {1000 + i}, {i % 10}, {90 + i % 70})"
        for i in range(n_li)))


_SHAPES = {
    # Q3: two joins down a snowflake arm, three group keys (one with a
    # NULL group), filters on all three leaves
    "q3": ("select li.ok, sum(li.price * (100 - li.disc)) as revenue, "
           "ord.od, ord.pri from cust, ord, li "
           "where cust.seg = 'S1' and cust.ck = ord.ck and li.ok = ord.ok "
           "and ord.od < 140 and li.sd > 100 "
           "group by li.ok, ord.od, ord.pri "
           "order by revenue desc, ord.od, li.ok limit 10", 2),
    # Q5: five joins, an equality between two joined leaves, one
    # dictionary group key
    "q5": ("select nation.nname, sum(li.price * (100 - li.disc)) as revenue "
           "from cust, ord, li, supp, nation, region "
           "where cust.ck = ord.ck and li.ok = ord.ok and li.sk = supp.sk "
           "and cust.nk = supp.nk and supp.nk = nation.nk "
           "and nation.rk = region.rk and region.rname = 'R1' "
           "and ord.od >= 105 group by nation.nname "
           "order by revenue desc", 5),
    "single": ("select ord.pri, count(1), sum(li.price + ord.od) "
               "from li, ord where li.ok = ord.ok group by ord.pri "
               "order by ord.pri", 1),
    # a PROBE key that reads two leaves: no fact-first chain
    # (`_reorder_fact_first` declines), but every join of the planner's
    # tree has a unique direct index on its right, as on one chip
    "spanning": ("select count(1), sum(li.price) from li "
                 "join supp on li.sk = supp.sk "
                 "join ord on li.ok + supp.nk = ord.ok", 2),
}


def _tk(devices=4):
    tk = TestKit()
    tk.must_exec(f"set tidb_mpp_devices = {devices}")
    tk.must_exec("set tidb_result_cache = 'OFF'")
    tk.must_exec("set tidb_device_dispatch_rows = 1")
    return tk


@pytest.fixture()
def tk():
    t = _tk()
    _snowflake(t)
    return t


def _rows(tk, sql, engine):
    tk.must_exec(f"set tidb_executor_engine = '{engine}'")
    return tk.must_query(sql).rows


def _annotations(tk, sql, engine):
    tk.must_exec(f"set tidb_executor_engine = '{engine}'")
    plan = tk.must_query("explain analyze " + sql).rows
    parts = [part for row in plan for part in row[2].split(", ")]
    return {k: [p for p in parts if p.startswith(k + ":")]
            for k in ("engine", "join", "gathers")}


def _mesh_equals_host(tk, sql):
    """Rows of `sql` on the mesh, equal to the host engine's; -> (rows,
    growth of every MPP_STATS counter)."""
    want = _rows(tk, sql, "host")
    assert want, "an empty reference proves nothing"
    before = dict(MPP_STATS)
    got = _rows(tk, sql, "tpu-mpp")
    assert got == want, (got[:5], want[:5])
    return got, {k: MPP_STATS[k] - before[k] for k in before}


def _drop_compiled():
    with device_exec._PIPE_LOCK:
        device_exec._PIPE_CACHE.clear()


# -- the indexed side ----------------------------------------------------------

@pytest.mark.parametrize("shape", list(_SHAPES))
def test_indexed_fragment_equals_the_host_engine(tk, shape):
    sql, _n = _SHAPES[shape]
    _rows_, grew = _mesh_equals_host(tk, sql)
    assert grew["fragments"] == 1 and grew["indexed_fragments"] == 1
    assert grew["shuffle_joins"] == 0
    # again, from the learned capacities: one fragment, one count
    _rows_, grew = _mesh_equals_host(tk, sql)
    assert (grew["fragments"], grew["indexed_fragments"],
            grew["retries"]) == (1, 1, 0)


@pytest.mark.parametrize("shape", list(_SHAPES))
def test_explain_analyze_counts_what_one_chip_counts(tk, shape):
    sql, n_joins = _SHAPES[shape]
    one = _annotations(tk, sql, "tpu")
    mesh = _annotations(tk, sql, "tpu-mpp")
    assert one["engine"] == ["engine:tpu"]
    assert mesh["engine"] == ["engine:tpu-mpp"]
    assert mesh["join"] == [f"join:direct x{n_joins}"] == one["join"]
    assert mesh["gathers"] == one["gathers"] and mesh["gathers"]
    st = json.loads(tk.must_query("DIAG STATUS").rows[0][0])
    assert st["device_mpp"]["indexed_fragments"] == MPP_STATS[
        "indexed_fragments"]


class _Lowered:
    """Lowered text of every program dispatched while it is open."""

    def __init__(self, monkeypatch):
        self.texts = []
        orig = dev.observed_jit

        def spy(fn, **jit_kw):
            run = orig(fn, **jit_kw)

            def call(*a, **k):
                self.texts.append(run.lower(*a, **k).as_text())
                return run(*a, **k)
            call.lower = run.lower
            return call
        monkeypatch.setattr(dev, "observed_jit", spy)
        _drop_compiled()

    def take(self):
        out, self.texts = self.texts, []
        return out


@pytest.mark.parametrize("shape", list(_SHAPES))
def test_the_mesh_text_sorts_only_to_aggregate(tk, monkeypatch, shape):
    """No all_to_all, no gather through an iota (the probe leaf is read
    in place on its shard), and exactly the aggregate's sorts: the
    one-chip program's, once for the shard's partial state and once for
    the merge of the gathered ones."""
    sql, _n = _SHAPES[shape]
    low = _Lowered(monkeypatch)
    _rows(tk, sql, "tpu")
    _rows(tk, sql, "tpu")
    one = low.take()[-1]          # the settled one-chip program
    _drop_compiled()
    _mesh_equals_host(tk, sql)
    _mesh_equals_host(tk, sql)
    mesh = low.take()[-1]
    _drop_compiled()
    assert "all_gather" in mesh and "all_to_all" not in mesh
    assert "all_gather" not in one
    sorts = one.count("stablehlo.sort")
    assert sorts and mesh.count("stablehlo.sort") == 2 * sorts
    assert _iota_gathers(mesh) == []


def _traces_misses():
    st = device_exec.pipe_cache_stats()
    return st["traces"], st["misses"]


@pytest.mark.parametrize("table,row", [
    ("li", "(9001, 4, 4, 999999, 1, 150)"),   # the sharded probe leaf
    ("ord", "(9, 4, 101, 2)"),                 # a build: a key in a gap
])
def test_insert_inside_the_bucket_compiles_nothing(tk, table, row):
    sql, _n = _SHAPES["q3"]
    first, _g = _mesh_equals_host(tk, sql)
    _mesh_equals_host(tk, sql)                 # learned capacities settle
    before = _traces_misses()
    tk.must_exec(f"insert into {table} values {row}")
    again, grew = _mesh_equals_host(tk, sql)
    assert grew["indexed_fragments"] == 1 and grew["retries"] == 0
    assert _traces_misses() == before
    if table == "li":
        assert again != first                  # the new row is counted


@pytest.mark.parametrize("n_li,devices,live_shards", [
    (9, 8, 2),        # 2 rows a shard, bucket 8: six shards hold padding
    (501, 4, 4),      # 126 rows a shard but the last, which holds 123
])
def test_shards_with_no_live_row_and_uneven_shards(n_li, devices,
                                                   live_shards):
    tk = _tk(devices)
    _snowflake(tk, n_li=n_li)
    per_shard = -(-n_li // devices)
    psb = dev.bucket_rows(per_shard, dev.shape_buckets(tk.session))
    assert -(-n_li // psb) == live_shards
    sql, _n = _SHAPES["single"]
    _r, grew = _mesh_equals_host(tk, sql)
    assert grew["indexed_fragments"] == 1
    count = "select count(1), sum(li.price) from li, ord where li.ok = ord.ok"
    _r, grew = _mesh_equals_host(tk, count)
    assert grew["indexed_fragments"] == 1


# -- the other side: the in-program joins, as before ---------------------------

class _Expansions:
    """Counts the traces of the in-program join (`_join_expand`)."""

    def __init__(self, monkeypatch):
        self.n = 0
        orig = mpp_exec._join_expand

        def spy(*a, **k):
            self.n += 1
            return orig(*a, **k)
        monkeypatch.setattr(mpp_exec, "_join_expand", spy)
        _drop_compiled()


def _setup_nonunique(tk, monkeypatch):
    # two build rows a key: no unique index
    tk.must_exec("create table dup (ok bigint, w bigint)")
    tk.must_exec("insert into dup values " + ",".join(
        f"({4 * (i % 60)}, {i})" for i in range(120)))
    return ("select count(1), sum(li.price + dup.w) from li, dup "
            "where li.ok = dup.ok")


def _setup_sorted(tk, monkeypatch):
    # no byte is affordable: every index is `sorted` (binary search)
    monkeypatch.setattr(join_index, "_DIRECT_MAX_BYTES", 0)
    return _SHAPES["q3"][0]


def _setup_over_size(tk, monkeypatch):
    # every build's used columns are over the byte threshold
    tk.must_exec("set tidb_broadcast_join_threshold_size = 64")
    return _SHAPES["q3"][0]


def _setup_bushy(tk, monkeypatch):
    # the two small tables are joined first and the pair is the build:
    # its keys span two leaves, and no leaf has an index for them
    return ("select count(1), sum(li.price) from ord "
            "join supp on ord.ck = supp.sk "
            "join li on li.ok = ord.ok + supp.nk")


_OTHER_SIDE = {"nonunique": _setup_nonunique, "sorted": _setup_sorted,
               "over_size": _setup_over_size, "bushy": _setup_bushy}


@pytest.mark.parametrize("case", list(_OTHER_SIDE))
def test_fragments_outside_the_language_join_in_the_program(
        tk, monkeypatch, case):
    sql = _OTHER_SIDE[case](tk, monkeypatch)
    spy = _Expansions(monkeypatch)
    _r, grew = _mesh_equals_host(tk, sql)
    _drop_compiled()
    assert grew["fragments"] == 1 and grew["indexed_fragments"] == 0
    assert spy.n > 0
    ann = _annotations(tk, sql, "tpu-mpp")
    assert ann["engine"] == ["engine:tpu-mpp"]
    assert ann["join"] == [] and ann["gathers"] == []


def test_a_build_over_both_thresholds_is_shuffled(tk):
    tk.must_exec("set tidb_broadcast_join_threshold_size = 64")
    tk.must_exec("set tidb_broadcast_join_threshold_count = 50")
    _r, grew = _mesh_equals_host(tk, _SHAPES["q3"][0])
    assert (grew["fragments"], grew["indexed_fragments"],
            grew["shuffle_joins"]) == (1, 0, 1)
    # the row count alone no longer shuffles a build with a direct index
    tk.must_exec("set tidb_broadcast_join_threshold_size = 104857600")
    _r, grew = _mesh_equals_host(tk, _SHAPES["q3"][0])
    assert (grew["indexed_fragments"], grew["shuffle_joins"]) == (1, 0)


def test_a_non_inner_join_is_still_refused(tk):
    sql = ("select ord.pri, count(1), sum(li.price) from li left join ord "
           "on li.ok = ord.ok group by ord.pri order by ord.pri")
    want = _rows(tk, sql, "host")
    before = dict(MPP_STATS)
    assert _rows(tk, sql, "tpu-mpp") == want
    assert MPP_STATS == before
    assert _annotations(tk, sql, "tpu-mpp")["engine"] != ["engine:tpu-mpp"]


# -- the refactor moved no one-chip program -----------------------------------

SEED = 7

#: SHA-1 (12 digits) of `low.as_text()` of the SETTLED one-chip program
#: (the one the learned capacities arrive at) of the benchmark's Q3 and Q5
#: over its generator's data at SF0.02, seed 7: equal at PR 31's commit
#: 89fa25c and after ISSUE 32 exposed `compile_fragment`'s body.  A PR
#: that means to change the one-chip join program replaces them: the cut
#: of the probe path to its live rows (device_join.compact_to) replaced
#: both (d36b0c62329a and 04adf5b6664f until then; Q3 cuts past `orders`,
#: Q5 past `region`), and so did dropping the program's count of the rows
#: its aggregate kept, an output no caller read (79c551d0fab1 and
#: 462304f26d8c until then).  Attaching first the build whose filter
#: lets the probe path cut (device_join._attach_rank) replaced Q5's
#: (7d5b61db57a0 until then: `orders` now goes ahead of `supplier`);
#: Q3's chain has one candidate a step and kept its text.
_SETTLED = {"q3": "b8c7f3260415", "q5": "50b086a64ecc"}


@pytest.fixture(scope="module")
def tpch_tk():
    want = {t: list(tpch.SCHEMA[t]) for t in tpch.SCHEMA}
    tk = _tk()
    tpch.load(tk, tpch.generate(SEED, 0.02, want), want, False,
              f"test_mpp_indexed/{SEED}")
    return tk


def _sha(text):
    return hashlib.sha1(text.encode()).hexdigest()[:12]


@pytest.mark.parametrize("name,sql", [("q3", q3.SQL), ("q5", q5.SQL)])
def test_exposing_the_body_moved_no_one_chip_program(tpch_tk, monkeypatch,
                                                     name, sql):
    """The one-chip program's text, as dispatched, (1) equals the text of
    the exposed body passed through `program=` into the default wrapper,
    and (2) hashes to what the parent commit's did."""
    want = _rows(tpch_tk, sql, "host")
    low = _Lowered(monkeypatch)
    assert _rows(tpch_tk, sql, "tpu") == want
    assert _rows(tpch_tk, sql, "tpu") == want
    default = low.take()[-1]
    _drop_compiled()

    bodies = []
    orig = device_join.compile_fragment

    def program(run):
        bodies.append(run)
        return device_exec._timed_jit(run)

    def spy(*a, **kw):
        assert kw.get("program") is None       # one chip passes none
        return orig(*a, **kw, program=program)
    monkeypatch.setattr(device_join, "compile_fragment", spy)
    assert _rows(tpch_tk, sql, "tpu") == want
    wrapped = low.take()[-1]
    _drop_compiled()
    assert bodies and bodies[-1].__name__ == "run"
    assert _sha(wrapped) == _sha(default) == _SETTLED[name]


# -- a prefix before a searched index moved no program that searches nothing --

#: SHA-1 (12 digits) of every program the THIRD execution dispatches (the
#: learned capacities settled by then), over the benchmark's data at SF0.02
#: seed 7 (SSB: SF0.01, seed 3100200341), whole schema: equal at PR 33's
#: commit 1e23e2c and after ISSUE 34 gave a `sorted` join index its prefix
#: table.  Every join here is `dense` and passes no new argument; Q18 is
#: its inner scan aggregate at the capacity it learned on its first
#: execution, then its outer join fragment.
#: ISSUE 36 replaced ONE of the nine: Q18's second (73101b935985 until
#: then), 32,768 slots over 131,072 rows, which `dev.spans_one_pass`
#: puts on the one-pass side of `_group_spans`; every program that stays
#: on the search repeats.  The cut of the probe path to its live rows
#: replaced the two one-chip join fragments: Q18's
#: outer (bd1b6c50d898 until then: the in-set leaves a few hundred of
#: lineitem's rows) and SSB Q2.1 whole at SF0.01 (99d230717088; at SF10
#: it runs by pages, whose program cuts nothing).  Dropping the join
#: fragment's count of its aggregate's kept rows replaced the same two
#: (fd55eee58b6b and b0266a9b390b until then); the mesh's program, which
#: never returned it, kept its text.  Attaching first the build whose
#: filter lets the probe path cut (device_join._attach_rank) replaced
#: SSB Q2.1's (4e2080ef29fe until then: `part`, 1 in 25 kept, now goes
#: ahead of the 20 suppliers); Q18's outer chain and the mesh's Q3 have
#: one candidate a step and kept theirs.  Starting `device_agg` at the
#: capacity it knows replaced Q1's (ea0e7b39e1e9 until then: 4,096 slots
#: from the estimate, now its 32 dense buckets) and dropped Q18's program
#: at the estimate's capacity (a66673799df8), which a warm execution no
#: longer runs; the learned one kept its text.
_UNSEARCHED = {
    "q1": ("tpu", q1.SQL, ["8828d255d3d6"]),
    "q6": ("tpu", q6.SQL, ["de2b533191b0"]),
    "q18": ("tpu", q18.SQL,
            ["91253664a01f", "19c605712068"]),
    "mesh_q3": ("tpu-mpp", q3.SQL, ["6b2a6791d773"]),
    "ssb_q2_1": ("tpu", ssb_q2_1.SQL, ["ca8a5a3dac5b"]),
}


@pytest.fixture(scope="module")
def ssb_tk():
    want = {t: list(cols) for t, cols in ssb.SCHEMA.items()}
    tk = _tk()
    ssb.load(tk, ssb.generate(3100200341, 0.01, want), want, False,
             "test_mpp_indexed/ssb")
    return tk


@pytest.mark.parametrize("name", list(_UNSEARCHED))
def test_a_program_that_searches_nothing_kept_its_text(
        tpch_tk, ssb_tk, monkeypatch, name):
    engine, sql, settled = _UNSEARCHED[name]
    tk = ssb_tk if name.startswith("ssb") else tpch_tk
    want = _rows(tk, sql, "host")
    low = _Lowered(monkeypatch)
    for _ in range(3):
        low.take()
        assert _rows(tk, sql, engine) == want
    _drop_compiled()
    assert [_sha(t) for t in low.take()] == settled
