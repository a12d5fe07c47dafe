"""Version-tolerant join-key packs (executor/join_index.py
_quantize_range): a dimension-table delta that slightly widens a packed
key range must re-use the compiled join fragment — zero new XLA compiles
— instead of recompiling because an exact min/max moved (ROADMAP
"version-tolerant pack" open item)."""

import numpy as np
import pytest

from tidb_tpu.executor.join_index import _quantize_range, build_join_index
from tidb_tpu.sqltypes import FieldType, TYPE_LONGLONG
from tidb_tpu.testkit import TestKit
from tidb_tpu.utils.chunk import Column


def _col(vals):
    a = np.asarray(vals, dtype=np.int64)
    return Column(FieldType(tp=TYPE_LONGLONG), a,
                  np.zeros(len(a), dtype=bool))


class TestQuantizedPacks:
    def test_quantize_covers_and_is_stable(self):
        mn, mx = _quantize_range(1, 100)
        assert mn <= 1 and mx >= 100
        # a within-slack widening lands on the SAME quantized range
        assert _quantize_range(1, mx) == (mn, mx)
        assert _quantize_range(mn, 100) == (mn, mx)
        # far outside: the range moves (no unbounded slack)
        assert _quantize_range(1, 10 * (mx + 1)) != (mn, mx)

    def test_quantize_degenerate_and_negative(self):
        assert _quantize_range(5, 5) == (5, 5)
        mn, mx = _quantize_range(-50, 50)
        assert mn <= -50 and mx >= 50

    def test_index_packs_stable_across_within_slack_delta(self):
        base = build_join_index((_col(range(1, 101)),))
        mn, span = base.packs[0]
        widened = build_join_index((_col(list(range(2, 101)) + [mn + span - 1]),))
        assert widened.packs == base.packs
        assert widened.kind == base.kind == "dense"
        # a unique dense build is a slot table over the quantized span
        assert widened.slots.shape == base.slots.shape == (span,)
        assert widened.sig() == base.sig()

    def test_non_unique_csr_shape_stable_across_within_slack_delta(self):
        base = build_join_index((_col(list(range(1, 101)) * 2),))
        mn, span = base.packs[0]
        widened = build_join_index(
            (_col(list(range(2, 101)) * 2 + [1, mn + span - 1]),))
        assert widened.packs == base.packs
        assert widened.kind == base.kind == "dense"
        assert not base.unique and base.slots is None
        assert widened.starts.shape == base.starts.shape == (span + 1,)
        assert widened.sig() == base.sig()

    def test_slack_region_matches_nothing(self):
        """Correctness under slack: probe keys inside the widened-but-
        unpopulated region must find zero matches, like any miss."""
        idx = build_join_index((_col([10, 20, 30]),))
        mn, span = idx.packs[0]
        assert mn <= 10 and mn + span - 1 >= 30
        # slot table: every slack slot (and every gap) reads "absent"
        assert idx.kind == "dense" and idx.unique
        assert np.count_nonzero(idx.slots >= 0) == 3
        assert sorted(idx.slots[idx.slots >= 0]) == [0, 1, 2]
        # CSR (non-unique build): counts are zero for every slack slot
        dup = build_join_index((_col([10, 20, 20, 30]),))
        assert dup.kind == "dense" and not dup.unique
        assert np.diff(np.asarray(dup.starts)).sum() == 4


class TestZeroCompileDelta:
    @pytest.fixture()
    def tk(self):
        tk = TestKit()
        tk.must_exec("use test")
        tk.must_exec("create table f (id int primary key, k int, v int)")
        tk.must_exec("create table d (id int primary key, k int, grp int,"
                     " amt int)")
        tk.must_exec("insert into f values " + ",".join(
            f"({i},{i % 100 + 1},{i % 53})" for i in range(512)))
        tk.must_exec("insert into d values " + ",".join(
            f"({i},{i},{i % 4},{i * 11 % 71})" for i in range(1, 101)))
        tk.must_exec("analyze table f")
        tk.must_exec("analyze table d")
        tk.must_exec("set tidb_executor_engine = 'tpu'")
        tk.must_exec("set tidb_device_dispatch_rows = 1")
        return tk

    Q = ("select d.grp, sum(f.v + d.amt) from f join d on f.k = d.k "
         "group by d.grp order by d.grp")

    def test_within_slack_dim_delta_zero_new_compiles(self, tk):
        from tidb_tpu.executor.device_exec import pipe_cache_stats
        # two warmups: compile + absorb the learned-size shrink recompile
        tk.must_query(self.Q)
        tk.must_query(self.Q)
        st0 = pipe_cache_stats(thread_local=True)
        tk.must_query(self.Q)
        st1 = pipe_cache_stats(thread_local=True)
        assert st1["traces"] == st0["traces"], "steady state must be warm"

        # the dim delta: widen the key range within the pack's slack
        # (range [1,100] quantizes with >= 3 keys of headroom) without
        # changing the row count
        tk.must_exec("update d set k = 103 where k = 100")
        st2 = pipe_cache_stats(thread_local=True)
        rows = tk.must_query(self.Q).rows
        st3 = pipe_cache_stats(thread_local=True)
        assert st3["traces"] == st2["traces"], (
            "a within-slack dimension delta must re-use the compiled "
            "fragment (zero new XLA compiles)")
        # and the answer tracks the delta (host parity)
        tk.must_exec("set tidb_executor_engine = 'host'")
        assert rows == tk.must_query(self.Q).rows

    def test_out_of_slack_delta_still_correct(self, tk):
        """Far outside the slack the pack legitimately moves — the
        fragment recompiles and stays correct (no stale-range reuse)."""
        tk.must_query(self.Q)
        tk.must_exec("update d set k = 5000 where k = 100")
        rows = tk.must_query(self.Q).rows
        tk.must_exec("set tidb_executor_engine = 'host'")
        assert rows == tk.must_query(self.Q).rows
