"""Multi-chip MPP carry-over regressions (ROADMAP item 1): the single-chip
compile-amortization stack across the 8-device virtual mesh (conftest
forces XLA_FLAGS=--xla_force_host_platform_device_count=8).

Pinned here:
- ZERO-RECOMPILE: a within-bucket INSERT followed by re-running an MPP
  join+agg query dispatches the already-compiled SPMD program — no new
  XLA traces, no pipe-cache misses — with bit-exact host parity
  (the acceptance regression; exactly one compile per bucket shape
  across two rounds).
- PADDING INVARIANTS: per-shard bucket padding (nearly-all-padded edge
  buckets — 9 live rows sharded over 8 devices pad to 8 bucket rows per
  shard) can never survive an exchange, a join probe, or the
  partial/final agg merge (mirrors tests/test_shape_bucket.py meshwide).
- HOT-KEY SKEW: a dominant probe-side key overflows the radix exchange's
  initial sub-bucket capacity; the retry jumps to the exact requirement
  (capacity growth), converges with zero dropped rows (parity), and the
  retry count surfaces in EXPLAIN ANALYZE.
- EPOCH FENCE: a backend fence invalidates every mesh placement — the
  next dispatch re-places from host columns, never serves stale shards.
"""

import json
import urllib.request

import pytest

from tidb_tpu.executor.device_exec import pipe_cache_stats
from tidb_tpu.executor import mpp_exec
from tidb_tpu.executor.mpp_exec import MPP_STATS
from tidb_tpu.testkit import TestKit

pytestmark = pytest.mark.multichip


def _traces():
    return pipe_cache_stats()["traces"]


def _misses():
    return pipe_cache_stats()["misses"]


def _host_rows(tk, q):
    tk.must_exec("set tidb_executor_engine = 'host'")
    rows = tk.must_query(q).rows
    tk.must_exec("set tidb_executor_engine = 'tpu-mpp'")
    return rows


def _mpp_parity(tk, q, expect_mpp=True):
    host = _host_rows(tk, q)
    before = MPP_STATS["fragments"]
    mpp = tk.must_query(q).rows
    assert mpp == host, (f"mpp/host divergence for {q!r}\n"
                         f"host({len(host)}): {host[:5]}\n"
                         f"mpp({len(mpp)}): {mpp[:5]}")
    if expect_mpp:
        assert MPP_STATS["fragments"] > before, \
            f"query never reached the mesh path: {q!r}"
    return mpp


def _make_fact_dim(tk, n_fact=320, n_dim=40, hot_frac=0.0):
    """fact(k -> dim.k, v) + dim(k, g): FK join + group-by shapes.
    hot_frac routes that fraction of fact rows onto ONE key (skew)."""
    tk.must_exec("create table dim (k bigint primary key, g varchar(8), "
                 "w bigint)")
    vals = ",".join(f"({i}, 'g{i % 5}', {i * 3})" for i in range(1, n_dim + 1))
    tk.must_exec(f"insert into dim values {vals}")
    tk.must_exec("create table fact (a bigint primary key, k bigint, "
                 "v bigint)")
    n_hot = int(n_fact * hot_frac)
    rows = []
    for i in range(1, n_fact + 1):
        k = 7 if i <= n_hot else (i % n_dim) + 1
        rows.append(f"({i}, {k}, {i * 10})")
    tk.must_exec("insert into fact values " + ",".join(rows))


JOIN_AGG_Q = ("select dim.g, count(1), sum(fact.v + dim.w) from fact, dim "
              "where fact.k = dim.k group by dim.g order by dim.g")


@pytest.fixture()
def tk():
    t = TestKit()
    t.must_exec("set tidb_mpp_devices = 8")
    t.must_exec("set tidb_executor_engine = 'tpu-mpp'")
    return t


class TestZeroRecompile:
    """The acceptance regression: one compile per bucket shape, ever."""

    def test_join_agg_zero_recompile_within_bucket(self, tk):
        _make_fact_dim(tk)
        host0 = _host_rows(tk, JOIN_AGG_Q)
        cold = tk.must_query(JOIN_AGG_Q).rows
        assert cold == host0
        t0, m0 = _traces(), _misses()
        # round 2: same data — the compiled pipeline and learned
        # capacities must serve it without a single new trace or miss
        assert tk.must_query(JOIN_AGG_Q).rows == cold
        assert _traces() == t0, "warm MPP round re-traced"
        assert _misses() == m0, "warm MPP round missed the pipe cache"
        # within-bucket INSERT: 320 fact rows shard to 40/shard →
        # bucket 46; +2 rows stays inside. The delta re-places the
        # columns (new identity) but re-dispatches the SAME executable.
        tk.must_exec("insert into fact values (321, 3, 11), (322, 4, 12)")
        host1 = _host_rows(tk, JOIN_AGG_Q)
        assert host1 != host0  # the delta is visible...
        got = tk.must_query(JOIN_AGG_Q).rows
        assert got == host1   # ...and bit-exact vs the host engine
        assert _traces() == t0, \
            "within-bucket INSERT re-traced the MPP pipeline"
        assert _misses() == m0, \
            "within-bucket INSERT missed the compiled-pipeline cache"

    def test_shuffle_join_zero_recompile_within_bucket(self, tk):
        # build side above the (lowered) broadcast threshold: the radix
        # all_to_all exchange path must hold the same zero-recompile
        # property — exchange caps are learned per signature
        tk.must_exec("create table bigdim (k bigint primary key, w bigint)")
        tk.must_exec("insert into bigdim values " + ",".join(
            f"({i}, {i})" for i in range(1, 101)))
        tk.must_exec("create table bfact (a bigint primary key, k bigint, "
                     "v bigint)")
        tk.must_exec("insert into bfact values " + ",".join(
            f"({i}, {(i % 100) + 1}, {i})" for i in range(1, 241)))
        tk.must_exec("set tidb_broadcast_join_threshold_count = 50")
        # bigdim's key is unique and its index direct: its BYTES must
        # pass the size threshold too, or the indexed path broadcasts it
        tk.must_exec("set tidb_broadcast_join_threshold_size = 1")
        q = ("select count(1), sum(bfact.v + bigdim.w) from bfact, bigdim "
             "where bfact.k = bigdim.k")
        before_sh = MPP_STATS["shuffle_joins"]
        host0 = _host_rows(tk, q)
        assert tk.must_query(q).rows == host0
        assert MPP_STATS["shuffle_joins"] > before_sh, \
            "build side above threshold never took the shuffle path"
        t0, m0 = _traces(), _misses()
        assert tk.must_query(q).rows == host0
        assert _traces() == t0 and _misses() == m0
        tk.must_exec("insert into bfact values (241, 9, 90)")
        host1 = _host_rows(tk, q)
        assert tk.must_query(q).rows == host1
        assert _traces() == t0, \
            "within-bucket INSERT re-traced the shuffle pipeline"

    def test_scan_agg_zero_recompile_within_bucket(self, tk):
        _make_fact_dim(tk)
        q = ("select k, count(1), sum(v) from fact group by k "
             "order by k limit 5")
        host0 = _host_rows(tk, q)
        assert tk.must_query(q).rows == host0
        t0 = _traces()
        tk.must_exec("insert into fact values (321, 1, 10)")
        host1 = _host_rows(tk, q)
        assert tk.must_query(q).rows == host1
        assert _traces() == t0


class TestMppPaddingInvariants:
    """Nearly-all-padded edge buckets over the mesh: 9 live rows shard to
    2/shard → per-shard bucket 8 → 64 total slots, 55 of them padding.
    None of it may survive any stage."""

    def _tiny(self, tk, n=9):
        tk.must_exec("create table pdim (k bigint primary key, "
                     "g varchar(4))")
        tk.must_exec("insert into pdim values " + ",".join(
            f"({i}, 'g{i % 2}')" for i in range(1, 4)))
        tk.must_exec("create table pf (a bigint primary key, k bigint, "
                     "v bigint)")
        tk.must_exec("insert into pf values " + ",".join(
            f"({i}, {(i % 3) + 1}, {i * 10})" for i in range(1, n + 1)))

    def test_unfiltered_count_sees_only_live_rows(self, tk):
        self._tiny(tk)
        # no WHERE: only the traced n_live mask stands between 55 padding
        # slots and the count
        assert _mpp_parity(tk, "select count(1) from pf") == [("9",)]

    def test_agg_merge_never_counts_padding(self, tk):
        self._tiny(tk)
        # partial states ride all_gather to every shard; the final merge
        # re-aggregates them — padded partial slots must stay invalid
        _mpp_parity(tk, "select k, count(1), sum(v), min(v), max(v) "
                        "from pf group by k order by k")

    def test_join_probe_never_matches_padding(self, tk):
        self._tiny(tk)
        # padding rows carry k=0 data with null=True: neither the zero
        # value nor the null may probe into pdim
        _mpp_parity(tk, "select pdim.g, count(1), sum(pf.v) from pf, pdim "
                        "where pf.k = pdim.k group by pdim.g order by pdim.g")

    def test_exchange_never_ships_padding(self, tk):
        self._tiny(tk, n=24)
        # force the radix all_to_all exchange on a nearly-padded leaf:
        # 24 rows shard to 3/shard → bucket 8; build side 12 > threshold 4
        tk.must_exec("create table pb (k bigint primary key, w bigint)")
        tk.must_exec("insert into pb values " + ",".join(
            f"({i}, {i})" for i in range(1, 13)))
        tk.must_exec("set tidb_broadcast_join_threshold_count = 4")
        tk.must_exec("set tidb_broadcast_join_threshold_size = 1")
        before = MPP_STATS["shuffle_joins"]
        _mpp_parity(tk, "select count(1), sum(pf.v + pb.w) from pf, pb "
                        "where pf.k = pb.k")
        assert MPP_STATS["shuffle_joins"] > before

    def test_null_keys_never_exchange(self, tk):
        self._tiny(tk)
        tk.must_exec("insert into pf values (100, null, 1000)")
        # a NULL join key must not match — and must not be confused with
        # the null-marked padding rows riding the same columns
        _mpp_parity(tk, "select count(1), sum(pf.v) from pf, pdim "
                        "where pf.k = pdim.k")

    def test_filter_on_nearly_padded_leaf(self, tk):
        self._tiny(tk)
        _mpp_parity(tk, "select count(1), sum(v) from pf where v > 30")


class TestHotKeySkewExchange:
    """Seeded dominant-key convergence through the radix exchange's
    overflow-retry path (satellite): capacity grows to the exact
    requirement, zero rows dropped (parity), retries surfaced."""

    def _skewed(self, tk):
        # 70% of fact rows carry ONE key: the (dest, sub) radix bucket
        # holding it overflows the initial per-sub-bucket capacity, the
        # host retries at next_pow2(exact need). Build side is uniform so
        # the build-skew broadcast guard stays out of the way.
        _make_fact_dim(tk, n_fact=320, n_dim=64, hot_frac=0.7)
        tk.must_exec("set tidb_broadcast_join_threshold_count = 30")
        tk.must_exec("set tidb_broadcast_join_threshold_size = 1")

    Q = ("select count(1), sum(fact.v + dim.w) from fact, dim "
         "where fact.k = dim.k")

    def test_hot_key_converges_no_drops(self, tk):
        self._skewed(tk)
        before_sh = MPP_STATS["shuffle_joins"]
        before_ovf = MPP_STATS["exchange_overflow_retries"]
        _mpp_parity(tk, self.Q)  # parity == zero dropped rows
        assert MPP_STATS["shuffle_joins"] > before_sh, \
            "skew test never took the shuffle path"
        assert MPP_STATS["exchange_overflow_retries"] > before_ovf, \
            "hot key never overflowed the initial exchange capacity"

    def test_retry_count_in_explain_analyze(self, tk):
        self._skewed(tk)
        tk.must_query(self.Q)  # pay the discovery retry first
        rows = tk.must_query(f"explain analyze {self.Q}").rows
        blob = "\n".join(" ".join(str(c) for c in r) for r in rows)
        assert "mpp_exchange_overflow_retries" in blob, \
            f"exchange retry count missing from EXPLAIN ANALYZE:\n{blob}"
        assert "mpp_place_bytes" in blob


class TestMeshEpochFence:
    """Tentpole (c): a post-fence mesh can never serve stale shards."""

    def test_fence_invalidates_placements_then_reparity(self, tk):
        from tidb_tpu.executor import supervisor
        _make_fact_dim(tk)
        host = _host_rows(tk, JOIN_AGG_Q)
        assert tk.must_query(JOIN_AGG_Q).rows == host
        bytes_before = mpp_exec.place_cache_bytes()
        assert bytes_before > 0, "mesh placements not on the ledger"
        supervisor.fence("test: mesh fence")
        # every placement is epoch-stale now: the gauge reads 0 through
        # the ledger, and the next dispatch re-places from host columns
        assert mpp_exec.place_cache_bytes() == 0
        assert tk.must_query(JOIN_AGG_Q).rows == host
        assert mpp_exec.place_cache_bytes() > 0

    def test_ledger_accounts_placement_bytes(self, tk):
        from tidb_tpu.ops import residency
        _make_fact_dim(tk)
        tk.must_query(JOIN_AGG_Q)
        led = residency.verify_ledger()
        assert led["ok"], f"ledger drift with mesh placements: {led}"
        # the placement gauge reads THROUGH the ledger: it can never
        # exceed what the ledger accounts
        assert mpp_exec.place_cache_bytes() <= residency.resident_bytes()


class TestMppGaugesSurfaced:
    def test_status_and_metrics(self, tk):
        _make_fact_dim(tk)
        tk.must_query(JOIN_AGG_Q)
        from tidb_tpu.server.http_status import StatusServer
        srv = StatusServer(tk.domain, port=0).start()
        try:
            base = f"http://127.0.0.1:{srv.port}"
            status = json.load(urllib.request.urlopen(f"{base}/status"))
            mpp = status["device_mpp"]
            assert mpp["fragments"] > 0
            assert mpp["mpp_place_bytes"] > 0
            metrics = urllib.request.urlopen(f"{base}/metrics").read()
            assert b"mpp_place_bytes" in metrics
            assert b"mpp_fragments" in metrics
        finally:
            srv.shutdown()
