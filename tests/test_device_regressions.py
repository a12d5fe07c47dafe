"""Device-engine regressions: host and tpu engines must agree.

Each case was a reproduced divergence (code review round 1): empty global
aggregate, NULL-vs--1 group key collision, first_row NULL preservation."""

import time

import pytest

from tidb_tpu.testkit import TestKit


@pytest.fixture(scope="module")
def tk():
    tk = TestKit()
    tk.must_exec("create database devreg")
    tk.must_exec("use devreg")
    tk.must_exec("create table t (a bigint, b bigint)")
    tk.must_exec("insert into t values (-1, 1), (null, 2), (5, 3)")
    tk.must_exec("create table t2 (g bigint, b bigint)")
    tk.must_exec("insert into t2 values (1, null), (1, 7)")
    return tk


def both_engines(tk, sql):
    tk.must_exec("set tidb_executor_engine = 'host'")
    host = tk.must_query(sql).rows
    tk.must_exec("set tidb_executor_engine = 'tpu'")
    tpu = tk.must_query(sql).rows
    tk.must_exec("set tidb_executor_engine = 'auto'")
    assert host == tpu, f"\nhost: {host}\ntpu:  {tpu}"
    return host


def test_empty_global_agg(tk):
    rows = both_engines(
        tk, "select count(*), sum(b), min(b) from t where a > 100")
    assert rows == [("0", None, None)]


def test_null_key_not_merged_with_minus_one(tk):
    rows = both_engines(
        tk, "select a, count(*) from t group by a order by a is null, a")
    assert rows == [("-1", "1"), ("5", "1"), (None, "1")]


def test_first_row_keeps_null(tk):
    rows = both_engines(tk, "select g, b from t2 group by g")
    assert rows == [("1", None)]


def test_min_max_with_nulls_and_negatives(tk):
    rows = both_engines(
        tk, "select a, min(b), max(b), avg(b) from t group by a "
            "order by a is null, a")
    assert rows == [("-1", "1", "1", "1.0000"),
                    ("5", "3", "3", "3.0000"),
                    (None, "2", "2", "2.0000")]


class TestCountDistinctDevice:
    """COUNT(DISTINCT) on the device kernel: value-runs per group in a
    value-extended sort (ops/device.py cnt_dist), with collation-aware
    parity against the host engine (which dedups _ci strings by sort
    key — 'abc' and 'ABC' are ONE distinct value, MySQL semantics)."""

    @pytest.fixture()
    def dtk(self):
        tk = TestKit()
        tk.must_exec("use test")
        tk.must_exec("create table cdt (g bigint, v bigint, "
                     "sv varchar(8) collate utf8mb4_general_ci)")
        vals = ",".join(
            f"({i % 4}, {(i * 7) % 23}, "
            f"'{'AbC' if i % 3 else 'aBc'}{i % 5}')" for i in range(3000))
        tk.must_exec(f"insert into cdt values {vals}")
        tk.must_exec("insert into cdt values (1, null, null)")
        return tk

    def _parity(self, tk, sql):
        tk.must_exec("set tidb_executor_engine = 'host'")
        host = tk.must_query(sql).rows
        tk.must_exec("set tidb_executor_engine = 'tpu'")
        dev = tk.must_query(sql).rows
        tk.must_exec("set tidb_executor_engine = 'auto'")
        assert host == dev, (host[:4], dev[:4])
        return host

    def test_int_count_distinct(self, dtk):
        rows = self._parity(dtk, "select g, count(distinct v), count(v) "
                                 "from cdt group by g order by g")
        assert len(rows) == 4

    def test_ci_string_count_distinct(self, dtk):
        rows = self._parity(dtk, "select g, count(distinct sv) from cdt "
                                 "group by g order by g")
        # 5 suffixes; AbC/aBc collate equal under _ci → 5 distinct
        assert all(r[1] == "5" for r in rows), rows

    def test_global_count_distinct(self, dtk):
        self._parity(dtk, "select count(distinct v), count(distinct sv), "
                          "count(*) from cdt")

    def test_nulls_excluded(self, dtk):
        rows = self._parity(dtk, "select count(distinct v) from cdt "
                                 "where g = 1")
        assert rows  # the injected NULL row never counts

    def test_null_group_key_with_garbage_data(self, dtk):
        """Rows in a NULL-keyed group carry arbitrary underlying data
        (join gathers clip to real rows); the group sort must mask the
        key under the null flag or distinct runs splinter (review r4)."""
        tk = dtk
        tk.must_exec("create table ng (k bigint, v bigint)")
        vals = ",".join(
            (f"(null, {i % 6})" if i % 2 else f"({i % 3}, {i % 6})")
            for i in range(2000))
        tk.must_exec(f"insert into ng values {vals}")
        self._parity(tk, "select k, count(distinct v), count(*) from ng "
                         "group by k order by k")


def test_engine_hint_survives_nested_subquery_eval():
    """Advisor r4 (medium): a correlated/EXISTS subquery executed
    mid-statement goes through Session.run_query -> build_executor, which
    resets the statement-scoped READ_FROM_STORAGE pin on the shared
    session; the outer statement's pin must be restored so fragments built
    after the first subquery evaluation still honor the hint."""
    from tidb_tpu.testkit import TestKit
    tk = TestKit()
    tk.must_exec("create table eh (a int, b int)")
    tk.must_exec("insert into eh values (1, 10), (2, 20)")
    sess = tk.session
    sess.stmt_engine_hint = "host"  # outer statement's pin
    from tidb_tpu.parser import parse_one
    stmt = parse_one("select min(a) from eh")
    rows, _fts = sess._expr_ctx.eval_subquery(stmt)
    assert rows
    assert sess.stmt_engine_hint == "host"
    # and the built-plan path (uncorrelated subquery reuse)
    plan = sess.plan_query(parse_one("select max(a) from eh"))
    sess.stmt_engine_hint = "host"
    rows, _fts = sess._expr_ctx.eval_built_plan(plan)
    assert rows
    assert sess.stmt_engine_hint == "host"


class TestTopkCacheGuard:
    """Regression (ISSUE 11 guarded-state): _TOPK_CACHE was a bare dict.
    The fence path (supervisor._reinit_backend) cleared it UNLOCKED
    while executor threads installed kernels into it, so an install
    racing the clear could re-publish a kernel pinning the torn-down
    PJRT client.  Structural access now happens under _PIPE_LOCK."""

    @staticmethod
    def _topk(device_exec, vals, k=2):
        import jax.numpy as jnp
        keys = [jnp.asarray(vals, dtype=jnp.int64)]
        nulls = [jnp.zeros(len(vals), dtype=bool)]
        return device_exec._topk_indices(
            keys, nulls, [], [], len(vals) - 1, len(vals),
            (("key", 0, False),), k)

    def test_lookup_and_install_hold_pipe_lock(self, monkeypatch):
        from tidb_tpu.executor import device_exec

        class AssertingDict(dict):
            def get(self, *a, **k):
                assert device_exec._PIPE_LOCK.locked()
                return dict.get(self, *a, **k)

            def setdefault(self, *a, **k):
                assert device_exec._PIPE_LOCK.locked()
                return dict.setdefault(self, *a, **k)

        monkeypatch.setattr(device_exec, "_TOPK_CACHE", AssertingDict())
        # cold install, then a cache hit: both sides locked
        for _ in range(2):
            idx = self._topk(device_exec, [3, 1, 2, 0])
            assert [int(i) for i in idx] == [1, 2]
        assert len(device_exec._TOPK_CACHE) == 1

    def test_fence_clear_runs_under_pipe_lock(self, monkeypatch):
        import jax
        from tidb_tpu.executor import device_exec, supervisor

        cleared = []

        class AssertingDict(dict):
            def clear(self):
                cleared.append(device_exec._PIPE_LOCK.locked())
                return dict.clear(self)

        monkeypatch.setattr(device_exec, "_TOPK_CACHE",
                            AssertingDict(stale="kernel"))
        # pretend off-CPU so the fence takes the real clear path, but
        # neutralize the client teardown (the in-process CPU client must
        # survive for the rest of the suite)
        monkeypatch.setattr(jax, "default_backend", lambda: "faketpu")
        monkeypatch.setattr(jax, "clear_caches", lambda: None)
        import jax.extend.backend as jax_backend
        monkeypatch.setattr(jax_backend, "clear_backends", lambda: None)
        supervisor._reinit_backend()
        assert cleared == [True]
        assert dict(device_exec._TOPK_CACHE) == {}

    def test_concurrent_install_and_clear_consistent(self):
        """Threaded chaos assertion: installs racing clears corrupt
        nothing — every call returns the right indices and the cache
        ends structurally sound."""
        import threading
        from tidb_tpu.executor import device_exec

        errs = []

        def hammer(vals, want):
            try:
                for _ in range(12):
                    idx = self._topk(device_exec, vals)
                    assert [int(i) for i in idx] == want
            except Exception as e:  # pragma: no cover - fail loudly
                errs.append(e)

        def clearer():
            try:
                for _ in range(30):
                    with device_exec._PIPE_LOCK:
                        device_exec._TOPK_CACHE.clear()
                    time.sleep(0.002)
            except Exception as e:  # pragma: no cover - fail loudly
                errs.append(e)

        threads = [
            threading.Thread(target=hammer, args=([3, 1, 2, 0], [1, 2])),
            threading.Thread(target=hammer, args=([9, 5, 7, 0], [1, 2])),
            threading.Thread(target=clearer),
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert errs == []
