"""Bucketed static shapes + compiled-fragment cache (the compile-amortization
layer): geometric row buckets (ops/device.py bucket_rows) pad device uploads
to canonical shapes with the live count traced, so a delta append, a second
table of similar size, or a different scale factor re-dispatches an already
compiled XLA program instead of re-tracing. Covers:

- the bucket policy itself (monotone, geometric, sysvar-disable),
- the recompile regression: a within-bucket delta performs ZERO new jax
  traces; crossing a bucket boundary performs exactly the expected ones,
- padding invariants: bucket-padding rows never appear in filter / join /
  agg / topk / window output, including nearly-all-padded edge buckets,
- the per-fragment-shape circuit breaker scope,
- the eval_scalar NEWDECIMAL-scale root fix (SET @r = 0.3 stays 0.3).
"""

import numpy as np
import pytest

from tidb_tpu.ops import device as dev
from tidb_tpu.executor.device_exec import pipe_cache_stats
from tidb_tpu.testkit import TestKit
from tidb_tpu.utils.chunk import Column


# ---------------------------------------------------------------------------
# bucket policy
# ---------------------------------------------------------------------------

class TestBucketPolicy:
    def test_monotone_and_covering(self):
        prev = 0
        for n in range(1, 5000, 7):
            b = dev.bucket_rows(n)
            assert b >= n
            assert b >= prev  # monotone in n
            prev = b

    def test_geometric_growth(self):
        # per_double=2 → powers of sqrt(2): padding overhead <= ~19%
        for n in (100, 10_000, 1_000_000):
            b = dev.bucket_rows(n, 2)
            assert b / n <= 2 ** 0.5 + 1e-9

    def test_bucket_count_per_doubling(self):
        # distinct buckets in [1024, 4096) == per_double * 2
        for per_double in (1, 2, 4):
            bs = {dev.bucket_rows(n, per_double)
                  for n in range(1025, 4097)}
            assert len(bs) == per_double * 2

    def test_disabled_returns_exact(self):
        assert dev.bucket_rows(12345, 0) == 12345

    def test_floor(self):
        assert dev.bucket_rows(1) == 8
        assert dev.bucket_rows(8) == 8
        assert dev.bucket_rows(9) == 12

    def test_pad_host(self):
        d = dev.pad_host(np.arange(5, dtype=np.int64), 8)
        assert d.shape == (8,) and (d[5:] == 0).all()
        nl = dev.pad_host(np.zeros(5, dtype=bool), 8, True)
        assert nl[5:].all() and not nl[:5].any()
        same = np.arange(5)
        assert dev.pad_host(same, 5) is not None
        assert len(dev.pad_host(same, 3)) == 5  # never truncates


# ---------------------------------------------------------------------------
# recompile regression: one compile per bucket, zero per within-bucket delta
# ---------------------------------------------------------------------------

def _install_fact(tk, table, n, n_keys=50, db="test"):
    """Bulk-install a fact-shaped table (a pk handle, k FK, v value,
    s dict string) — values bounded so delta rows can stay in-range."""
    tk.must_exec(f"create table {table} (a bigint primary key, k bigint, "
                 "v bigint, s varchar(8))")
    info = tk.session.infoschema().table_by_name(db, table)
    rng = np.random.default_rng(7)
    cols = {c.name: c for c in info.public_columns()}
    sdict = np.array([b"xx", b"yy", b"zz"], dtype=object)
    codes = rng.integers(0, 3, n).astype(np.int64)
    scol = Column(cols["s"].ftype, sdict[codes], np.zeros(n, dtype=bool))
    scol.set_dict(codes.astype(np.int32), sdict)
    columns = {
        cols["a"].id: Column(cols["a"].ftype, np.arange(1, n + 1)),
        cols["k"].id: Column(cols["k"].ftype,
                             rng.integers(1, n_keys + 1, n)),
        cols["v"].id: Column(cols["v"].ftype, rng.integers(0, 101, n)),
        cols["s"].id: scol,
    }
    tk.domain.columnar_cache.install_bulk(
        info, columns, np.arange(1, n + 1, dtype=np.int64))
    return info


def _traces():
    return pipe_cache_stats()["traces"]


class TestRecompileRegression:
    """The tentpole's measurable promise (fixed-seed compile-cache smoke):
    repeated runs with growing deltas re-trace once per BUCKET, not once
    per row count."""

    def test_agg_zero_recompile_within_bucket(self):
        tk = TestKit()
        _install_fact(tk, "b1", 2000)
        tk.must_exec("set tidb_executor_engine = 'tpu'")
        q = ("select s, sum(v), count(*) from b1 where v >= 10 "
             "group by s order by s")
        cold = tk.must_query(q).rows
        t0 = _traces()
        assert tk.must_query(q).rows == cold  # steady re-run
        assert _traces() == t0, "re-run of identical data re-traced"
        # within-bucket delta: 2000 → 2002 stays inside bucket 2048;
        # values/strings inside existing ranges so packs and dictionary
        # content are stable
        tk.must_exec("insert into b1 values (2001, 5, 50, 'xx'), "
                     "(2002, 6, 7, 'yy')")
        rows = tk.must_query(q).rows
        assert rows != cold  # the delta is visible...
        assert _traces() == t0, \
            "within-bucket delta append forced an XLA re-trace"

    def test_agg_one_recompile_per_bucket_crossing(self):
        tk = TestKit()
        _install_fact(tk, "b2", 2040)
        tk.must_exec("set tidb_executor_engine = 'tpu'")
        q = "select s, sum(v) from b2 group by s order by s"
        tk.must_query(q)
        t0 = _traces()
        # 2040 → 2100 crosses bucket 2048 → 2897: exactly one new program
        vals = ", ".join(f"({2040 + i}, 1, 1, 'zz')" for i in range(1, 61))
        tk.must_exec(f"insert into b2 values {vals}")
        tk.must_query(q)
        t1 = _traces()
        assert t1 > t0, "bucket crossing must compile the new shape"
        # further within-(new-)bucket deltas: no more traces
        tk.must_exec("insert into b2 values (9001, 2, 3, 'xx')")
        tk.must_query(q)
        assert _traces() == t1

    def test_join_fragment_zero_recompile_within_bucket(self):
        tk = TestKit()
        _install_fact(tk, "jf", 2000)
        tk.must_exec("create table jd (k bigint primary key, g varchar(8))")
        for i in range(1, 51):
            tk.must_exec(f"insert into jd values ({i}, 'g{i % 5}')")
        tk.must_exec("set tidb_executor_engine = 'tpu'")
        q = ("select jd.g, sum(jf.v) from jf join jd on jf.k = jd.k "
             "group by jd.g order by jd.g")
        cold = tk.must_query(q).rows
        # second run may legitimately compile ONCE more: the learned-size
        # store (_CAP_STORE) jumps to tight capacities discovered by the
        # first run — the documented once-per-fragment-ever discovery
        assert tk.must_query(q).rows == cold
        t0 = _traces()
        assert tk.must_query(q).rows == cold  # steady state
        assert _traces() == t0
        # delta on the FACT side only: the dims (and their join indexes)
        # are untouched, the fact re-encodes to identical dictionary
        # content and the same bucket → compiled fragment reused
        tk.must_exec("insert into jf values (2001, 5, 50, 'xx')")
        assert tk.must_query(q).rows != cold
        assert _traces() == t0, \
            "fact-side within-bucket delta re-traced the join fragment"

    def test_build_side_delta_zero_recompile_within_bucket(self):
        """The LAST recompile trigger (ROADMAP item 1): a build-side
        INSERT changes the join index's row count — n_valid now rides as
        a TRACED scalar over bucket-padded index arrays, so a
        within-bucket (and within-quantized-pack-range) build delta
        rebuilds only the cheap numpy index and reuses the compiled
        fragment."""
        tk = TestKit()
        _install_fact(tk, "jb", 2000, n_keys=50)
        # SPARSE dim keys (2..100 even): a later odd-key INSERT stays
        # inside the quantized pack range AND keeps the build unique
        tk.must_exec("create table jbd (k bigint primary key, "
                     "g varchar(8))")
        for i in range(1, 51):
            tk.must_exec(f"insert into jbd values ({2 * i}, 'g{i % 5}')")
        tk.must_exec("set tidb_executor_engine = 'tpu'")
        q = ("select jbd.g, sum(jb.v) from jb join jbd on jb.k = jbd.k "
             "group by jbd.g order by jbd.g")
        cold = tk.must_query(q).rows
        assert tk.must_query(q).rows == cold  # learned-size settle
        t0 = _traces()
        assert tk.must_query(q).rows == cold  # steady state
        assert _traces() == t0
        # BUILD-side delta: key 31 is absent, odd, inside [2,100] (the
        # quantized pack range), 'g1' already in the dictionary; 50→51
        # index entries stays inside the rows bucket (64) and the leaf
        # bucket — the index rebuilds host-side, the program re-dispatches
        tk.must_exec("insert into jbd values (31, 'g1')")
        host = None
        try:
            tk.must_exec("set tidb_executor_engine = 'host'")
            host = tk.must_query(q).rows
        finally:
            tk.must_exec("set tidb_executor_engine = 'tpu'")
        got = tk.must_query(q).rows
        assert got == host and got != cold
        assert _traces() == t0, \
            "build-side within-bucket delta re-traced the join fragment"

    @pytest.mark.parametrize("case,group,first_builds,groups", [
        # 5 groups, estimated right: ONE program ever
        ("few_groups", "{dim}.g", 1, range(5, 6)),
        # grouped by the fact's key: the estimate (64) overflows, the
        # fragment recompiles once at the observed count and learns it
        ("learned_capacity", "{fact}.a", 2, range(2001, 70_001)),
    ])
    def test_join_fragment_compiles_only_for_its_aggregate_capacity(
            self, case, group, first_builds, groups, monkeypatch):
        """A fact-length fragment (92,682-row bucket) whose filter keeps
        one row in twenty: the aggregate runs at the fact length, so the
        only shape a join fragment ever learns is its capacities.  The
        first execution builds one program plus one per capacity it had
        to discover; the second builds at most the one at the learned
        aggregate capacity; the third builds nothing.  (Until ISSUE 29
        XLA:CPU also recompiled such a fragment to compact its kept
        rows, which the chip never did.)"""
        from tidb_tpu.executor import device_join
        tk = TestKit()
        table, dim = f"jc_{case}", f"jcd_{case}"
        _install_fact(tk, table, 70_000)
        tk.must_exec(f"create table {dim} (k bigint primary key, "
                     "g varchar(8))")
        tk.must_exec(f"insert into {dim} values " + ", ".join(
            f"({i}, 'g{i % 5}')" for i in range(1, 51)))
        tk.must_exec(f"analyze table {dim}")
        builds = []
        orig = device_join.compile_fragment

        def spy(root, leaves, joins, agg_plan, agg_conds, caps, capacity,
                *a, **k):
            builds.append(capacity)
            return orig(root, leaves, joins, agg_plan, agg_conds, caps,
                        capacity, *a, **k)
        monkeypatch.setattr(device_join, "compile_fragment", spy)
        tk.must_exec("set tidb_result_cache = 'OFF'")
        tk.must_exec("set tidb_executor_engine = 'tpu'")
        group = group.format(dim=dim, fact=table)
        q = (f"select {group}, sum({table}.v), count(*) from {table} "
             f"join {dim} on {table}.k = {dim}.k where {table}.v >= 96 "
             f"group by {group} order by {group}")
        first = tk.must_query(q).rows
        assert len(first) in groups
        assert len(builds) == first_builds, builds
        assert builds == sorted(builds)
        learned = dev.next_pow2(max(len(first), 16))
        del builds[:]
        t0 = _traces()
        assert tk.must_query(q).rows == first
        assert builds in ([], [learned]), (builds, learned)
        assert _traces() - t0 == len(builds)
        t1 = _traces()
        del builds[:]
        assert tk.must_query(q).rows == first
        assert builds == [] and _traces() == t1
        tk.must_exec("set tidb_executor_engine = 'host'")
        assert tk.must_query(q).rows == first


# ---------------------------------------------------------------------------
# padding invariants: padded rows never escape
# ---------------------------------------------------------------------------

def _parity(tk, q):
    tk.must_exec("set tidb_executor_engine = 'tpu'")
    d = tk.must_query(q).rows
    tk.must_exec("set tidb_executor_engine = 'host'")
    h = tk.must_query(q).rows
    tk.must_exec("set tidb_executor_engine = 'auto'")
    assert d == h, f"device/host divergence for {q!r}: {d} vs {h}"
    return d


class TestPaddingInvariants:
    @pytest.fixture()
    def tk(self):
        tk = TestKit()
        tk.must_exec("create table p (a bigint primary key, k bigint, "
                     "v bigint, s varchar(8))")
        # n=9 → bucket 12: three padding rows in every upload
        for i in range(1, 10):
            tk.must_exec(f"insert into p values ({i}, {i % 3}, {i * 10}, "
                         f"'s{i % 2}')")
        return tk

    def test_unfiltered_count(self, tk):
        # no WHERE at all: only the n_live mask stands between the padding
        # and the count
        assert _parity(tk, "select count(*) from p") == [("9",)]

    def test_unfiltered_sum_min_max(self, tk):
        _parity(tk, "select sum(v), min(v), max(v), avg(v) from p")

    def test_filter_and_group(self, tk):
        _parity(tk, "select k, count(*), sum(v) from p where v >= 20 "
                    "group by k order by k")

    def test_string_group_keys(self, tk):
        _parity(tk, "select s, count(*) from p group by s order by s")

    def test_topk(self, tk):
        _parity(tk, "select k, sum(v) from p group by k "
                    "order by 2 desc limit 2")

    def test_count_distinct(self, tk):
        _parity(tk, "select k, count(distinct v) from p group by k "
                    "order by k")

    def test_join(self, tk):
        tk.must_exec("create table pd (k bigint primary key, nm varchar(8))")
        for i in range(3):
            tk.must_exec(f"insert into pd values ({i}, 'n{i}')")
        _parity(tk, "select pd.nm, sum(p.v) from p join pd on p.k = pd.k "
                    "group by pd.nm order by pd.nm")

    def test_window(self, tk):
        _parity(tk, "select a, k, row_number() over "
                    "(partition by k order by v desc), "
                    "sum(v) over (partition by k order by v) "
                    "from p order by a")

    def test_window_no_columns(self, tk):
        # count(*) OVER () reads no columns at all: the device program's
        # env is empty and the row count must come from the plan, not an
        # env array (code-review regression)
        _parity(tk, "select a, count(*) over () from p order by a")

    def test_single_row_edge_bucket(self):
        # n=1 in bucket 8: nearly every row of the upload is padding
        tk = TestKit()
        tk.must_exec("create table e1 (a bigint primary key, v bigint)")
        tk.must_exec("insert into e1 values (1, 42)")
        assert _parity(tk, "select count(*), sum(v) from e1") \
            == [("1", "42")]
        _parity(tk, "select v, count(*) from e1 group by v")

    def test_all_nulls_edge_bucket(self):
        # padding rows are null-masked; real NULL rows must still group
        # apart from padding
        tk = TestKit()
        tk.must_exec("create table e2 (a bigint primary key, v bigint)")
        for i in range(1, 10):
            tk.must_exec(f"insert into e2 values ({i}, null)")
        assert _parity(tk, "select count(*), count(v) from e2") \
            == [("9", "0")]
        _parity(tk, "select v, count(*) from e2 group by v")


# ---------------------------------------------------------------------------
# per-fragment-shape circuit breaker scope
# ---------------------------------------------------------------------------

class TestBreakerShapeScope:
    def test_one_shape_cools_down_alone(self):
        from tidb_tpu.executor.circuit import get_breaker
        from tidb_tpu.executor.device_exec import (run_device,
                                                   DeviceUnsupported)
        tk = TestKit()
        br = get_breaker(tk.session, shape="join")
        for _ in range(br.threshold):
            br.record_failure(RuntimeError("XlaRuntimeError: boom"))
        assert br.snapshot()["state"] == "open"
        assert get_breaker(tk.session, shape="agg").snapshot()["state"] \
            == "closed"
        # join fragments degrade, agg fragments keep running on-device
        with pytest.raises(DeviceUnsupported):
            run_device(tk.session, lambda: 1, shape="join")
        assert run_device(tk.session, lambda: 1, shape="agg") == 1

    def test_snapshot_names_shape(self):
        from tidb_tpu.executor.circuit import CircuitBreaker
        assert CircuitBreaker(shape="window").snapshot()["shape"] \
            == "window"


# ---------------------------------------------------------------------------
# eval_scalar NEWDECIMAL scale (root-cause fix)
# ---------------------------------------------------------------------------

class TestEvalScalarDecimal:
    def test_user_var_decimal_literal(self):
        tk = TestKit()
        tk.must_exec("set @r = 0.3")
        assert tk.must_query("select @r").rows == [("0.3",)]

    def test_user_var_negative_decimal(self):
        tk = TestKit()
        tk.must_exec("set @x = -0.5")
        assert tk.must_query("select @x").rows == [("-0.5",)]

    def test_user_var_decimal_expression(self):
        tk = TestKit()
        tk.must_exec("set @s = 1.25 + 0.25")
        assert tk.must_query("select @s").rows == [("1.50",)]

    def test_user_var_in_comparison(self):
        tk = TestKit()
        tk.must_exec("create table ud (v decimal(5,2))")
        tk.must_exec("insert into ud values (0.25), (0.35)")
        tk.must_exec("set @r = 0.3")
        assert tk.must_query(
            "select v from ud where v > @r").rows == [("0.35",)]

    def test_sysvar_decimal(self):
        tk = TestKit()
        tk.must_exec("set global tidb_auto_analyze_ratio = 0.3")
        assert tk.must_query(
            "select @@global.tidb_auto_analyze_ratio").rows == [("0.3",)]

    def test_column_default_decimal_scale(self):
        tk = TestKit()
        tk.must_exec("create table dd (a decimal(5,2) default 1.5, "
                     "b int)")
        tk.must_exec("insert into dd (b) values (1)")
        assert tk.must_query("select a from dd").rows == [("1.50",)]

    def test_internal_repr_unchanged_for_dml(self):
        tk = TestKit()
        tk.must_exec("create table di (a decimal(7,3))")
        tk.must_exec("insert into di values (2.345), (-0.5)")
        assert tk.must_query("select a from di order by a").rows \
            == [("-0.500",), ("2.345",)]
