"""Streamed device aggregation: batched host→HBM transfers with on-device
partial-state merge (the cop-iterator overlap analog, reference:
store/copr/coprocessor.go:399; long-operand scaling per SURVEY §5)."""

import random

import pytest

from tidb_tpu.testkit import TestKit

N_ROWS = 20_000
BATCH = 3_000  # forces 7 blocks


def _rows_equal(a, b, float_cols=()):
    """Row-set equality with ulp-tolerance on float columns (partial-sum
    order legitimately changes the last digits)."""
    if len(a) != len(b):
        return False
    for ra, rb in zip(a, b):
        for i, (va, vb) in enumerate(zip(ra, rb)):
            if i in float_cols:
                if va is None or vb is None:
                    if va != vb:
                        return False
                elif abs(float(va) - float(vb)) > 1e-9 * max(
                        1.0, abs(float(va))):
                    return False
            elif va != vb:
                return False
    return True


@pytest.fixture(scope="module")
def tk():
    tk = TestKit()
    tk.must_exec("use test")
    tk.must_exec("create table s (grp int, cat varchar(8), amount int, "
                 "price double, d date)")
    random.seed(7)
    rows = []
    for i in range(N_ROWS):
        rows.append(f"({i % 13}, 'c{i % 5}', {i % 97}, "
                    f"{round(random.random() * 10, 3)}, "
                    f"'202{i % 3}-0{i % 9 + 1}-15')")
    for lo in range(0, len(rows), 2000):
        tk.must_exec("insert into s values " + ",".join(rows[lo:lo + 2000]))
    return tk


QUERY = ("select grp, cat, count(*), sum(amount), min(amount), max(amount), "
         "avg(price) from s where amount > 10 group by grp, cat "
         "order by grp, cat")


class TestStreamedCountDistinct:
    """Streamed COUNT(DISTINCT x): per-block (group, x) pair dedup +
    one final cnt_dist over the concatenated pairs (the two-phase
    distinct agg, reference executor/aggregate.go)."""

    def _both(self, tk, sql):
        import tidb_tpu.executor.device_exec as de
        calls = []
        orig = de._stream_count_distinct

        def spy(*a, **k):
            r = orig(*a, **k)
            calls.append(1)
            return r

        de._stream_count_distinct = spy
        try:
            tk.must_exec("set tidb_executor_engine = 'tpu'")
            tk.must_exec(f"set tidb_device_stream_rows = {BATCH}")
            stream = tk.must_query(sql).rows
        finally:
            de._stream_count_distinct = orig
            tk.must_exec("set tidb_device_stream_rows = 0")
        assert calls, "streamed count-distinct path did not run"
        tk.must_exec("set tidb_executor_engine = 'host'")
        host = tk.must_query(sql).rows
        assert stream == host, sql
        return stream

    def test_grouped(self, tk):
        self._both(tk, "select grp, count(distinct amount) from s "
                       "group by grp order by grp")

    def test_global(self, tk):
        rows = self._both(tk, "select count(distinct amount) from s")
        assert rows[0][0] == "97"

    def test_nulls_ignored(self, tk):
        tk.must_exec("create table cdn (g bigint, v bigint)")
        tk.must_exec("insert into cdn values (1,1),(1,null),(1,1),(1,2),"
                     "(2,null),(2,null)")
        tk.must_exec("set tidb_executor_engine = 'tpu'")
        tk.must_exec("set tidb_device_stream_rows = 2")
        rows = tk.must_query("select g, count(distinct v) from cdn "
                             "group by g order by g").rows
        tk.must_exec("set tidb_device_stream_rows = 0")
        assert rows == [("1", "2"), ("2", "0")]


class TestStreamedAgg:
    def test_parity_stream_vs_whole_vs_host(self, tk):
        tk.must_exec("set tidb_executor_engine = 'tpu'")
        tk.must_exec(f"set tidb_device_stream_rows = {BATCH}")
        stream = tk.must_query(QUERY).rows
        tk.must_exec("set tidb_device_stream_rows = 0")
        whole = tk.must_query(QUERY).rows
        tk.must_exec("set tidb_executor_engine = 'host'")
        host = tk.must_query(QUERY).rows
        tk.must_exec("set tidb_executor_engine = 'auto'")
        assert _rows_equal(stream, whole, float_cols={6})
        assert _rows_equal(stream, host, float_cols={6})
        assert len(stream) == 13 * 5

    def test_stream_fragment_annotated(self, tk):
        tk.must_exec("set tidb_executor_engine = 'tpu'")
        tk.must_exec(f"set tidb_device_stream_rows = {BATCH}")
        txt = "\n".join(" ".join(map(str, r)) for r in
                        tk.must_query("explain analyze " + QUERY).rows)
        tk.must_exec("set tidb_executor_engine = 'auto'")
        assert "tpu-stream" in txt

    def test_global_agg_streams(self, tk):
        tk.must_exec("set tidb_executor_engine = 'tpu'")
        tk.must_exec(f"set tidb_device_stream_rows = {BATCH}")
        got = tk.must_query("select count(*), sum(amount) from s").rows
        tk.must_exec("set tidb_executor_engine = 'host'")
        want = tk.must_query("select count(*), sum(amount) from s").rows
        tk.must_exec("set tidb_executor_engine = 'auto'")
        assert got == want

    def test_date_group_key_streams(self, tk):
        tk.must_exec("set tidb_executor_engine = 'tpu'")
        tk.must_exec(f"set tidb_device_stream_rows = {BATCH}")
        got = tk.must_query("select d, count(*) from s group by d "
                            "order by d").rows
        tk.must_exec("set tidb_executor_engine = 'host'")
        want = tk.must_query("select d, count(*) from s group by d "
                             "order by d").rows
        tk.must_exec("set tidb_executor_engine = 'auto'")
        assert got == want

    def test_tail_batch_smaller_than_block(self, tk):
        """N_ROWS % BATCH != 0: the tail block retraces and still merges."""
        assert N_ROWS % BATCH != 0
        tk.must_exec("set tidb_executor_engine = 'tpu'")
        tk.must_exec(f"set tidb_device_stream_rows = {BATCH}")
        got = tk.must_query("select grp, count(*) from s group by grp "
                            "order by grp").rows
        tk.must_exec("set tidb_executor_engine = 'auto'")
        assert sum(int(r[1]) for r in got) == N_ROWS


class TestWideKeySpanFoldsOnTheDevice:
    """A packed key span wider than a block (the case a numpy tail took
    on XLA:CPU until ISSUE 29): each block aggregates in its program (the
    sort arm), the partial states fold through merge_partial_states on
    the device, and merge_cap grows when the blocks' groups together
    outnumber it."""

    def test_merge_cap_grows_and_the_answer_is_the_hosts(self, monkeypatch):
        from tidb_tpu.executor import device_exec
        from tidb_tpu.ops import device as dev
        tk = TestKit()
        tk.must_exec("use test")
        tk.must_exec("create table w (k int, v int)")
        # 9,000 rows, 3 blocks of 3,000; block b holds the 600 keys
        # 70,000*b + 7*j (five rows each): 600 groups a block, 1,800 in
        # all, over a span of 144,194 (18 bits, 262,144 > 3,000)
        rows = [f"({70_000 * (i // BATCH) + 7 * (i % 600)}, {i % 101})"
                for i in range(3 * BATCH)]
        for lo in range(0, len(rows), 3000):
            tk.must_exec("insert into w values "
                         + ",".join(rows[lo:lo + 3000]))

        def no_host_fold(*a, **k):
            raise AssertionError("the streamed scan folded in numpy")
        monkeypatch.setattr(device_exec, "_merge_states_host", no_host_fold)
        folds = []
        orig = device_exec.merge_partial_states

        def spy(state, parts, merge_cap, n_keys, nvals, merge_ops, key_pack):
            out, grown = orig(state, parts, merge_cap, n_keys, nvals,
                              merge_ops, key_pack)
            folds.append((key_pack, merge_cap, grown, int(out[4])))
            return out, grown
        monkeypatch.setattr(device_exec, "merge_partial_states", spy)
        # fold after every block: a running state meets new partials
        monkeypatch.setattr(device_exec, "_MERGE_BUDGET_ROWS", 1)
        sql = ("select k, count(*), sum(v), min(v), max(v) from w "
               "where v > 3 group by k order by k")
        tk.must_exec("set tidb_result_cache = 'OFF'")
        tk.must_exec("set tidb_executor_engine = 'tpu'")
        tk.must_exec(f"set tidb_device_stream_rows = {BATCH}")
        got = tk.must_query(sql).rows
        plan = tk.must_query("explain analyze " + sql).rows
        tk.must_exec("set tidb_device_stream_rows = 0")
        tk.must_exec("set tidb_executor_engine = 'host'")
        want = tk.must_query(sql).rows
        assert len(want) == 1800 and got == want
        notes = [p for r in plan for p in r[2].split(", ")]
        assert "engine:tpu-stream" in notes and "agg:sort" in notes
        packs = {f[0] for f in folds}
        assert len(packs) == 1 and None not in packs
        (bits, _offset), = packs.pop()
        assert (1 << bits) > BATCH
        assert dev.agg_arm(((bits, 0),), ("sum_i",)) == "sort"
        assert any(grown > cap for _p, cap, grown, _ng in folds), folds
        assert max(ng for *_x, ng in folds) == 1800
