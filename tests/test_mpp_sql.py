"""The MPP engine through the FULL SQL path: `set tidb_executor_engine =
'tpu-mpp'` routes eligible scan/join/agg fragments onto the 8-device
virtual CPU mesh (conftest) as ONE shard_map-jitted SPMD program —
sharded fact scan, broadcast dimension joins, partial aggregation,
all_gather exchange, replicated final merge.

Each test asserts host-engine parity AND (for eligible shapes) that the
mesh path actually executed, via mpp_exec.MPP_STATS — silent fallback
to the single-chip or host path would otherwise pass parity trivially.
Reference: planner/core/fragment.go:37,64 (fragments at exchange
boundaries), store/copr/mpp.go:65, executor/mpp_gather.go:102."""

import pytest

from tidb_tpu.executor.mpp_exec import MPP_STATS

from test_tpch import make_tpch_tk


@pytest.fixture(scope="module")
def tk():
    t = make_tpch_tk(db="tpch_mpp")
    t.must_exec("set tidb_mpp_devices = 8")
    return t


def mpp_vs_host(tk, sql, expect_mpp=True):
    tk.must_exec("set tidb_executor_engine = 'host'")
    host = tk.must_query(sql).rows
    before = MPP_STATS["fragments"]
    tk.must_exec("set tidb_executor_engine = 'tpu-mpp'")
    mpp = tk.must_query(sql).rows
    ran_mpp = MPP_STATS["fragments"] - before
    tk.must_exec("set tidb_executor_engine = 'auto'")
    assert host == mpp, (f"mpp/host divergence\nhost({len(host)}): "
                         f"{host[:5]}\nmpp({len(mpp)}): {mpp[:5]}")
    if expect_mpp:
        assert ran_mpp > 0, "query never reached the mesh path"
    return host


def test_q1_scan_agg(tk):
    rows = mpp_vs_host(tk, """
        select l_returnflag, l_linestatus, sum(l_quantity) as sum_qty,
               sum(l_extendedprice) as sum_base_price,
               sum(l_extendedprice * (1 - l_discount)) as sum_disc_price,
               avg(l_quantity) as avg_qty, count(1) as count_order
        from lineitem where l_shipdate <= '1998-09-02'
        group by l_returnflag, l_linestatus
        order by l_returnflag, l_linestatus""")
    assert rows


def test_q6_global_agg(tk):
    rows = mpp_vs_host(tk, """
        select sum(l_extendedprice * l_discount) as revenue from lineitem
        where l_shipdate >= '1994-01-01' and l_shipdate < '1995-01-01'
          and l_discount between 0.02 and 0.08 and l_quantity < 24""")
    assert len(rows) == 1


def test_q3_join_agg(tk):
    mpp_vs_host(tk, """
        select l_orderkey, sum(l_extendedprice * (1 - l_discount)) as rev,
               o_orderdate, o_shippriority
        from customer, orders, lineitem
        where c_mktsegment = 'BUILDING' and c_custkey = o_custkey
          and l_orderkey = o_orderkey and o_orderdate < '1995-03-15'
          and l_shipdate > '1995-03-15'
        group by l_orderkey, o_orderdate, o_shippriority
        order by rev desc, o_orderdate limit 10""")


def test_q5_multiway_join_agg(tk):
    mpp_vs_host(tk, """
        select n_name, sum(l_extendedprice * (1 - l_discount)) as revenue
        from customer, orders, lineitem, supplier, nation, region
        where c_custkey = o_custkey and l_orderkey = o_orderkey
          and l_suppkey = s_suppkey and c_nationkey = s_nationkey
          and s_nationkey = n_nationkey and n_regionkey = r_regionkey
          and r_name = 'ASIA' and o_orderdate >= '1994-01-01'
          and o_orderdate < date_add('1994-01-01', interval 1 year)
        group by n_name order by revenue desc""")


def test_q9_expr_group_key(tk):
    mpp_vs_host(tk, """
        select nationx, o_year, sum(amount) as sum_profit
        from (select n_name as nationx, year(o_orderdate) as o_year,
                     l_extendedprice * (1 - l_discount)
                     - ps_supplycost * l_quantity as amount
              from part, supplier, lineitem, partsupp, orders, nation
              where s_suppkey = l_suppkey and ps_suppkey = l_suppkey
                and ps_partkey = l_partkey and p_partkey = l_partkey
                and o_orderkey = l_orderkey and s_nationkey = n_nationkey
                and p_name like '%thing%'
             ) as profit
        group by nationx, o_year order by nationx, o_year desc""")


def test_q10_wide_group_keys(tk):
    mpp_vs_host(tk, """
        select c_custkey, c_name,
               sum(l_extendedprice * (1 - l_discount)) as revenue,
               c_acctbal, n_name, c_address, c_phone, c_comment
        from customer, orders, lineitem, nation
        where c_custkey = o_custkey and l_orderkey = o_orderkey
          and o_orderdate >= '1993-10-01'
          and o_orderdate < date_add('1993-10-01', interval 3 month)
          and l_returnflag = 'R' and c_nationkey = n_nationkey
        group by c_custkey, c_name, c_acctbal, c_phone, n_name,
                 c_address, c_comment
        order by revenue desc limit 20""")


def test_q18_semi_join_fallback(tk):
    """Q18's IN-subquery becomes a semi join — outside the broadcast-MPP
    fragment language, so it must FALL BACK cleanly with exact parity
    (the subquery's own group-by still rides the mesh)."""
    mpp_vs_host(tk, """
        select c_name, c_custkey, o_orderkey, o_orderdate, o_totalprice,
               sum(l_quantity)
        from customer, orders, lineitem
        where o_orderkey in (select l_orderkey from lineitem
                             group by l_orderkey
                             having sum(l_quantity) > 100)
          and c_custkey = o_custkey and o_orderkey = l_orderkey
        group by c_name, c_custkey, o_orderkey, o_orderdate, o_totalprice
        order by o_totalprice desc, o_orderdate limit 100""",
        expect_mpp=False)


def test_min_max_first_aggs(tk):
    mpp_vs_host(tk, """
        select l_returnflag, min(l_quantity), max(l_extendedprice),
               min(l_shipdate), max(l_shipdate), count(l_comment)
        from lineitem group by l_returnflag order by l_returnflag""")


def test_agg_retry_capacity_overflow(tk):
    """High-cardinality group key forces the bounded partial state to
    overflow and the host to retry with doubled capacity."""
    before = MPP_STATS["fragments"]
    mpp_vs_host(tk, """
        select l_orderkey, l_linenumber, count(1), sum(l_quantity)
        from lineitem group by l_orderkey, l_linenumber
        order by l_orderkey, l_linenumber limit 50""")
    assert MPP_STATS["fragments"] > before


class TestShuffleJoin:
    """Hash-shuffle (all_to_all) MPP join, SQL-reachable: when the build
    side exceeds tidb_broadcast_join_threshold_count, BOTH sides are
    hash-repartitioned over the mesh by join key before the local join
    (reference: planner/core/fragment.go Hash exchange type,
    store/copr/mpp.go:65; exhaust_physical_plans.go broadcast-vs-shuffle
    by build size)."""

    def _shuffle_vs_host(self, tk, sql, threshold):
        tk.must_exec("set tidb_executor_engine = 'host'")
        host = tk.must_query(sql).rows
        before = MPP_STATS["shuffle_joins"]
        tk.must_exec(f"set tidb_broadcast_join_threshold_count = {threshold}")
        # a unique-keyed build with a direct host index is broadcast
        # whatever its row count (the indexed mesh path) unless its
        # BYTES pass the size threshold too: lower both to shuffle
        tk.must_exec("set tidb_broadcast_join_threshold_size = "
                     f"{1 if threshold < 10240 else 104857600}")
        tk.must_exec("set tidb_executor_engine = 'tpu-mpp'")
        try:
            mpp = tk.must_query(sql).rows
        finally:
            tk.must_exec("set tidb_executor_engine = 'auto'")
            tk.must_exec("set tidb_broadcast_join_threshold_count = 10240")
            tk.must_exec(
                "set tidb_broadcast_join_threshold_size = 104857600")
        ran = MPP_STATS["shuffle_joins"] - before
        assert host == mpp, (f"shuffle/host divergence\nhost({len(host)}): "
                             f"{host[:5]}\nmpp({len(mpp)}): {mpp[:5]}")
        return ran

    def test_q18_shape_fact_fact_shuffle(self, tk):
        # lineitem |><| orders, both above the (lowered) threshold: the
        # Q18 inner join shape the broadcast path cannot afford at scale
        ran = self._shuffle_vs_host(tk, """
            select o_orderstatus, count(1), sum(l_quantity)
            from orders, lineitem where o_orderkey = l_orderkey
            group by o_orderstatus order by o_orderstatus""", threshold=50)
        assert ran > 0, "build side above threshold never took shuffle"

    def test_below_threshold_stays_broadcast(self, tk):
        ran = self._shuffle_vs_host(tk, """
            select o_orderstatus, count(1), sum(l_quantity)
            from orders, lineitem where o_orderkey = l_orderkey
            group by o_orderstatus order by o_orderstatus""",
            threshold=1000000)
        assert ran == 0, "tiny build side must stay broadcast"

    def test_shuffle_with_filters_and_dims(self, tk):
        # shuffle bottom join + broadcast dimension above it + leaf conds
        # (pre-exchange filters) — the Q3-with-big-orders shape
        ran = self._shuffle_vs_host(tk, """
            select c_mktsegment, sum(l_extendedprice * (1 - l_discount))
            from customer, orders, lineitem
            where c_custkey = o_custkey and l_orderkey = o_orderkey
              and l_shipdate > '1995-03-15'
            group by c_mktsegment order by c_mktsegment""", threshold=50)
        assert ran > 0

    def test_shuffle_multi_key_join(self, tk):
        ran = self._shuffle_vs_host(tk, """
            select count(1), sum(ps_availqty)
            from partsupp, lineitem
            where ps_partkey = l_partkey and ps_suppkey = l_suppkey""",
            threshold=40)
        assert ran > 0


def test_q18_full_shape_on_mesh(tk):
    """The complete Q18 shape — semi-filter subquery + 3-table join +
    wide group keys + TopN — end-to-end with the mesh engine selected
    (VERDICT r3 #7). The outer join+agg fragment must execute on the
    mesh; the ORDER BY/LIMIT runs over the replicated merged result."""
    rows = mpp_vs_host(tk, """
        select c_name, c_custkey, o_orderkey, o_orderdate, o_totalprice,
               sum(l_quantity)
        from customer, orders, lineitem
        where o_orderkey in (select l_orderkey from lineitem
                             group by l_orderkey
                             having sum(l_quantity) > 60)
          and c_custkey = o_custkey and o_orderkey = l_orderkey
        group by c_name, c_custkey, o_orderkey, o_orderdate, o_totalprice
        order by o_totalprice desc, o_orderdate limit 20""")
    assert rows


class TestSkewExchange:
    def test_adversarial_skew_falls_back_to_broadcast(self, tk):
        """A hash exchange with one key owning half the build rows would
        funnel half the table into one shard's bucket; the host-side skew
        guard (join-index max_cnt vs the even share) must route the join
        to the Broadcast exchange instead — and parity must hold."""
        tk.must_exec("create table skewb (k bigint, v bigint)")
        vals = ",".join(
            f"({1 if i % 2 == 0 else i}, {i})" for i in range(800))
        tk.must_exec(f"insert into skewb values {vals}")
        tk.must_exec("create table skewp (k bigint, w bigint)")
        vals = ",".join(f"({i % 400}, {i})" for i in range(1600))
        tk.must_exec(f"insert into skewp values {vals}")
        tk.must_exec("set tidb_broadcast_join_threshold_count = 50")
        tk.must_exec("set tidb_executor_engine = 'host'")
        sql = ("select count(1), sum(skewp.w + skewb.v) from skewp, skewb "
               "where skewp.k = skewb.k")
        host = tk.must_query(sql).rows
        before_skew = MPP_STATS["skew_broadcasts"]
        before_frag = MPP_STATS["fragments"]
        tk.must_exec("set tidb_executor_engine = 'tpu-mpp'")
        try:
            mpp = tk.must_query(sql).rows
        finally:
            tk.must_exec("set tidb_executor_engine = 'auto'")
            tk.must_exec("set tidb_broadcast_join_threshold_count = 10240")
        assert host == mpp, (host, mpp)
        assert MPP_STATS["fragments"] > before_frag
        assert MPP_STATS["skew_broadcasts"] > before_skew, \
            "hot-key build side took the Hash exchange anyway"

    def test_mild_skew_keeps_hash_exchange(self, tk):
        """Near-uniform keys must NOT trip the skew guard — the Hash
        exchange stays (it's the scalable path)."""
        tk.must_exec("create table evenb (k bigint, v bigint)")
        vals = ",".join(f"({i % 200}, {i})" for i in range(800))
        tk.must_exec(f"insert into evenb values {vals}")
        tk.must_exec("create table evenp (k bigint, w bigint)")
        vals = ",".join(f"({i % 200}, {i})" for i in range(1600))
        tk.must_exec(f"insert into evenp values {vals}")
        tk.must_exec("set tidb_broadcast_join_threshold_count = 50")
        tk.must_exec("set tidb_executor_engine = 'host'")
        sql = ("select count(1), sum(evenp.w + evenb.v) from evenp, evenb "
               "where evenp.k = evenb.k")
        host = tk.must_query(sql).rows
        before_sh = MPP_STATS["shuffle_joins"]
        tk.must_exec("set tidb_executor_engine = 'tpu-mpp'")
        try:
            mpp = tk.must_query(sql).rows
        finally:
            tk.must_exec("set tidb_executor_engine = 'auto'")
            tk.must_exec("set tidb_broadcast_join_threshold_count = 10240")
        assert host == mpp, (host, mpp)
        assert MPP_STATS["shuffle_joins"] > before_sh, \
            "uniform keys should keep the Hash exchange"
