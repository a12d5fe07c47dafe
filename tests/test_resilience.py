"""Resilience layer: unified backoff budgets (utils/backoff.py), the
device→host circuit breaker (executor/circuit.py), failpoint hygiene, and
the new sysvar knobs (reference: store/tikv/backoff.go Backoffer +
pingcap/failpoint)."""

import time

import pytest

from tidb_tpu.errors import (BackoffExhaustedError, ErrCode, LockedError,
                             TiDBError, WriteConflictError)
from tidb_tpu.executor.circuit import CircuitBreaker, get_breaker
from tidb_tpu.testkit import TestKit
from tidb_tpu.utils import failpoint
from tidb_tpu.utils.backoff import (Backoffer, ExchangeError, classify,
                                    CLASS_DEVICE, CLASS_EXCHANGE,
                                    CLASS_FAULT, CLASS_REGION,
                                    CLASS_TRANSPORT)
from tidb_tpu.utils.failpoint import FailpointError


@pytest.fixture()
def tk():
    tk = TestKit()
    tk.must_exec("use test")
    return tk


# -- error taxonomy -----------------------------------------------------------

class TestClassify:
    def test_region_class(self):
        assert classify(WriteConflictError("w")) == CLASS_REGION
        assert classify(LockedError("l")) == CLASS_REGION

    def test_exchange_and_fault(self):
        assert classify(ExchangeError("x")) == CLASS_EXCHANGE
        assert classify(FailpointError("f")) == CLASS_FAULT

    def test_transport_class(self):
        assert classify(ConnectionRefusedError("refused")) == CLASS_TRANSPORT
        assert classify(RuntimeError("Connection refused")) == CLASS_TRANSPORT

    def test_filesystem_oserrors_are_not_transport(self):
        # FileNotFoundError is a bug to surface, not a transport fault
        # to retry/degrade on
        from tidb_tpu.utils.backoff import CLASS_OTHER
        assert classify(FileNotFoundError("page.bin")) == CLASS_OTHER
        assert classify(PermissionError("denied")) == CLASS_OTHER

    def test_device_class(self):
        class XlaRuntimeError(Exception):
            pass
        assert classify(XlaRuntimeError("boom")) == CLASS_DEVICE
        assert classify(RuntimeError("RESOURCE_EXHAUSTED: hbm")) \
            == CLASS_DEVICE


# -- Backoffer ---------------------------------------------------------------

class TestBackoffer:
    def test_attempt_cap_raises_classified(self):
        bo = Backoffer(budget_ms=10_000, seed=7, sleep=False)
        err = ExchangeError("send failed")
        with pytest.raises(BackoffExhaustedError) as ei:
            for _ in range(100):
                bo.backoff("exchangeRetry", err)
        e = ei.value
        assert e.code == ErrCode.BackoffExhausted
        assert e.retry_kind == "exchangeRetry"
        assert e.error_class == CLASS_EXCHANGE
        assert "send failed" in str(e)

    def test_sleep_budget_exhausts(self):
        bo = Backoffer(budget_ms=5, seed=1, sleep=False)
        with pytest.raises(BackoffExhaustedError):
            for _ in range(1000):
                bo.backoff("txnLock", LockedError("l"))
        assert bo.slept_ms <= 5

    def test_weight_scales_budget(self):
        assert Backoffer(budget_ms=100, weight=3).budget_ms == 300

    def test_deterministic_with_seed(self):
        def curve(seed):
            bo = Backoffer(budget_ms=10_000, seed=seed, sleep=False)
            out = []
            for _ in range(8):
                bo.backoff("txnRetry")
                out.append(bo.slept_ms)
            return out
        assert curve(42) == curve(42)
        assert curve(42) != curve(43)

    def test_check_killed_interrupts(self):
        def boom():
            raise TiDBError("Query execution was interrupted",
                            code=ErrCode.QueryInterrupted)
        bo = Backoffer(budget_ms=10_000, check_killed=boom)
        with pytest.raises(TiDBError) as ei:
            bo.backoff("txnLock")
        assert ei.value.code == ErrCode.QueryInterrupted

    def test_for_session_clamps_to_max_execution_time(self, tk):
        tk.must_exec("set max_execution_time = 7")
        bo = Backoffer.for_session(tk.session)
        # the cap clamps the WEIGHTED budget: tidb_backoff_weight (2)
        # must not stretch retries past the execution window
        assert bo.budget_ms == pytest.approx(7.0)

    def test_for_session_weight_scales_unclamped(self, tk):
        tk.must_exec("set tidb_backoff_weight = 3")
        bo = Backoffer.for_session(tk.session, budget_ms=100)
        assert bo.budget_ms == pytest.approx(300.0)

    def test_wall_clock_deadline_counts_work_time(self):
        """A wall-clock Backoffer charges slow re-executions against the
        deadline, not only its own sleeps (innodb_lock_wait_timeout is a
        hard elapsed-time bound)."""
        bo = Backoffer(budget_ms=30, wall_clock=True, sleep=False)
        time.sleep(0.05)  # the "statement re-execution" burning the clock
        with pytest.raises(BackoffExhaustedError) as ei:
            bo.backoff("txnLock", LockedError("l"))
        assert "deadline" in str(ei.value)
        assert bo.remaining_ms() == 0.0

    def test_growth_kind_never_sleeps(self):
        bo = Backoffer(budget_ms=1)  # any sleep would blow this budget
        for _ in range(11):
            bo.backoff("exchangeGrow")
        with pytest.raises(BackoffExhaustedError):
            bo.backoff("exchangeGrow")


# -- failpoint hygiene (satellite) -------------------------------------------

class TestFailpointHygiene:
    def test_enabled_context_manager_never_leaks(self):
        with pytest.raises(RuntimeError):
            with failpoint.enabled("some-point", "panic"):
                assert failpoint.list_active() == {"some-point": "panic"}
                raise RuntimeError("body blew up")
        assert failpoint.list_active() == {}

    def test_list_active_snapshot(self):
        failpoint.enable("a", "panic")
        failpoint.enable("b", "return(3)")
        try:
            active = failpoint.list_active()
            assert active == {"a": "panic", "b": "return(3)"}
            active["c"] = "x"  # mutating the snapshot must not leak back
            assert "c" not in failpoint.list_active()
        finally:
            failpoint.disable_all()

    def test_concurrent_disable_race(self):
        """inject() vs disable(): the hit-count/active read is atomic under
        one lock — hammering both never counts a hit for a disabled point
        into a freshly re-enabled one (the torn-read satellite fix)."""
        import threading
        stop = threading.Event()

        def flipper():
            while not stop.is_set():
                failpoint.enable("race-point", "return(1)")
                failpoint.disable("race-point")

        t = threading.Thread(target=flipper)
        t.start()
        try:
            for _ in range(2000):
                failpoint.inject("race-point")  # must never raise
        finally:
            stop.set()
            t.join()
            failpoint.disable_all()

    def test_n_return_action(self):
        with failpoint.enabled("np", "2*return(9)"):
            assert failpoint.inject("np") == 9
            assert failpoint.inject("np") == 9
            assert failpoint.inject("np") is None


# -- circuit breaker ----------------------------------------------------------

class TestCircuitBreakerUnit:
    def test_open_after_threshold_and_recover(self):
        now = [0.0]
        br = CircuitBreaker(threshold=3, cooldown_s=10.0,
                            clock=lambda: now[0])
        for _ in range(2):
            br.record_failure(RuntimeError("x"))
        assert br.state == "closed" and br.allow()
        br.record_failure(RuntimeError("x"))
        assert br.state == "open" and not br.allow()
        now[0] += 10.0
        assert br.state == "half-open"
        assert br.allow()        # the single probe slot
        assert not br.allow()    # everyone else stays host-side
        br.record_success()
        assert br.state == "closed" and br.allow()

    def test_failed_probe_reopens(self):
        now = [0.0]
        br = CircuitBreaker(threshold=1, cooldown_s=5.0,
                            clock=lambda: now[0])
        br.record_failure(RuntimeError("x"))
        now[0] += 5.0
        assert br.allow()
        br.record_failure(RuntimeError("still dead"))
        assert br.state == "open" and not br.allow()

    def test_threshold_zero_disables(self):
        br = CircuitBreaker(threshold=0)
        for _ in range(100):
            br.record_failure(RuntimeError("x"))
        assert br.allow()

    def test_success_resets_failure_count(self):
        br = CircuitBreaker(threshold=3)
        br.record_failure(RuntimeError("x"))
        br.record_failure(RuntimeError("x"))
        br.record_success()
        br.record_failure(RuntimeError("x"))
        assert br.state == "closed"

    def test_stale_verdicts_leave_live_probe_alone(self):
        """A fragment admitted while CLOSED that reports its verdict after
        the breaker opened and ANOTHER thread won the probe must neither
        close the breaker nor free/kill the live probe slot (the
        half-open race the threaded chaos mode exercises)."""
        import threading as _t
        now = [0.0]
        br = CircuitBreaker(threshold=1, cooldown_s=5.0,
                            clock=lambda: now[0])
        br.record_failure(RuntimeError("x"))
        now[0] += 5.0
        probed = _t.Event()
        release = _t.Event()

        def prober():
            assert br.allow()  # wins the single probe slot
            probed.set()
            release.wait(5.0)
            br.record_success()

        t = _t.Thread(target=prober)
        t.start()
        assert probed.wait(5.0)
        # stale verdicts from THIS thread while the probe is in flight:
        br.record_success()
        assert br.state == "half-open" and not br.allow(), (
            "stale success must not close the breaker mid-probe")
        br.record_failure(RuntimeError("late straggler"))
        assert br.state == "half-open" and not br.allow(), (
            "stale failure must not reopen/steal the live probe's slot")
        release.set()
        t.join(5.0)
        assert br.state == "closed" and br.allow()

    def test_stale_success_does_not_close_open_breaker(self):
        """A fragment admitted before the breaker tripped that succeeds
        mid-cooldown (no probe in flight) must not close the breaker —
        waiting fragments would re-dispatch to the hung backend and each
        pay a full deadline; recovery goes through the probe."""
        now = [0.0]
        br = CircuitBreaker(threshold=1, cooldown_s=10.0,
                            clock=lambda: now[0])
        br.record_failure(RuntimeError("hang"))
        assert br.state == "open"
        br.record_success()  # the stale straggler
        assert br.state == "open" and not br.allow()
        now[0] += 10.0       # cooldown elapses → probe recovers normally
        assert br.allow()
        br.record_success()
        assert br.state == "closed"

    def test_stale_success_after_released_probe_keeps_half_open(self):
        """A prober that exits via release_probe (no verdict) leaves the
        slot free in HALF_OPEN; a straggler's stale success must not
        close the breaker — the next PROBE's verdict decides."""
        now = [0.0]
        br = CircuitBreaker(threshold=1, cooldown_s=5.0,
                            clock=lambda: now[0])
        br.record_failure(RuntimeError("hang"))
        now[0] += 5.0
        assert br.allow()          # probe admitted...
        br.release_probe()         # ...exits with no verdict
        br.record_success()        # straggler from before the open
        assert br.state == "half-open", (
            "stale success must not close a probe-less half-open breaker")
        assert br.allow()          # a real probe still recovers it
        br.record_success()
        assert br.state == "closed"

    def test_vanished_probe_slot_is_reclaimed(self):
        """A probe owner that died without any verdict (no success, no
        failure, no release) must not wedge the breaker host-side
        forever: allow() reclaims the slot after the grace window."""
        now = [0.0]
        br = CircuitBreaker(threshold=1, cooldown_s=1.0,
                            clock=lambda: now[0])
        br.record_failure(RuntimeError("x"))
        now[0] += 1.0
        assert br.allow()          # probe taken ... and its owner vanishes
        assert not br.allow()      # slot held
        now[0] += 600.0            # past cooldown but INSIDE the reclaim
        assert not br.allow(), (   # floor: a slow live probe keeps its slot
            "a probe within the reclaim floor must not be robbed")
        now[0] += 600.0            # way past max(cooldown, reclaim floor)
        assert br.allow(), "stale probe slot must be reclaimable"
        assert br.snapshot()["probe_reclaims"] == 1
        br.record_success()
        assert br.state == "closed"

    def test_concurrent_allow_single_probe_slot(self):
        """N threads hammering allow()/record_* concurrently: at most ONE
        probe admission per half-open window, every exit path releases,
        and the breaker is never wedged at the end."""
        import threading as _t
        br = CircuitBreaker(threshold=1, cooldown_s=0.01)
        br.record_failure(RuntimeError("x"))
        time.sleep(0.02)  # → half-open
        admitted = []
        mu = _t.Lock()
        start = _t.Barrier(8)

        def hammer(tid):
            start.wait(5.0)
            for i in range(200):
                if br.allow():
                    with mu:
                        admitted.append(tid)
                    # alternate every exit path run_device uses
                    if i % 3 == 0:
                        br.record_failure(RuntimeError("probe failed"))
                        time.sleep(0.011)  # let the cooldown elapse
                    elif i % 3 == 1:
                        br.release_probe()  # no-verdict exit
                    else:
                        br.record_success()

        threads = [_t.Thread(target=hammer, args=(t,)) for t in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(30.0)
        assert not any(t.is_alive() for t in threads)
        snap = br.snapshot()
        assert snap["state"] in ("closed", "open", "half-open")
        # not wedged: after the cooldown the breaker must admit a probe
        # and a success must close it
        time.sleep(0.02)
        deadline = time.monotonic() + 2.0
        while not br.allow() and time.monotonic() < deadline:
            time.sleep(0.005)
        br.record_success()
        assert br.state == "closed" and br.allow()


class TestCircuitBreakerEndToEnd:
    def test_device_faults_flip_to_host_and_recover(self, tk):
        """Acceptance: failpoint-forced device failures flip queries to the
        host engine mid-corpus with CORRECT results; the breaker closes
        again after cooldown."""
        tk.must_exec("create table t (a int, b int)")
        tk.must_exec("insert into t values " + ",".join(
            f"({i % 7},{i})" for i in range(128)))
        q = "select a, sum(b) from t group by a order by a"
        golden = tk.must_query(q).rows
        tk.must_exec("set tidb_executor_engine = 'tpu'")
        tk.must_exec("set global tidb_device_circuit_threshold = 2")
        tk.must_exec("set global tidb_device_circuit_cooldown = 0.1")
        br = get_breaker(tk.session)
        with failpoint.enabled("device-agg-exec", "panic"):
            for _ in range(4):  # mid-corpus: every query still correct
                assert tk.must_query(q).rows == golden
        assert br.state == "open"
        assert br.snapshot()["degraded"] >= 1
        time.sleep(0.12)
        assert br.state == "half-open"
        assert tk.must_query(q).rows == golden  # successful probe
        assert br.state == "closed"

    def test_breaker_isolated_per_domain(self, tk):
        other = TestKit()  # a second embedded cluster
        get_breaker(tk.session).record_failure(RuntimeError("x"))
        assert get_breaker(other.session).snapshot()["failures"] == 0

    def test_user_errors_are_not_health_signals(self, tk):
        """A TiDBError from the device path (a genuine user error) must
        pass through run_device without tripping the breaker."""
        from tidb_tpu.executor.device_exec import run_device
        br = get_breaker(tk.session)
        before = br.snapshot()["failures"]
        def user_error():
            raise TiDBError("Division by zero", code=ErrCode.DivisionByZero)
        with pytest.raises(TiDBError):
            run_device(tk.session, user_error)
        assert br.snapshot()["failures"] == before

    def test_unclassified_bugs_propagate(self, tk):
        """A programming bug (KeyError) is not a device-health signal:
        it must surface, not silently degrade to host."""
        from tidb_tpu.executor.device_exec import run_device
        br = get_breaker(tk.session)
        before = br.snapshot()["failures"]
        def bug():
            raise KeyError("missing column slot")
        with pytest.raises(KeyError):
            run_device(tk.session, bug)
        assert br.snapshot()["failures"] == before

    def test_probe_slot_released_on_unsupported_fragment(self, tk):
        """A HALF_OPEN probe fragment that raises DeviceUnsupported gives
        no health verdict — the probe slot must free for the next
        fragment instead of wedging the breaker host-side forever."""
        from tidb_tpu.executor.device_exec import (run_device,
                                                   DeviceUnsupported)
        tk.must_exec("set global tidb_device_circuit_threshold = 1")
        tk.must_exec("set global tidb_device_circuit_cooldown = 0.01")
        br = get_breaker(tk.session)
        br.record_failure(RuntimeError("RESOURCE_EXHAUSTED"))
        time.sleep(0.02)
        assert br.state == "half-open"
        def unsupported():
            raise DeviceUnsupported("empty input")
        with pytest.raises(DeviceUnsupported):
            run_device(tk.session, unsupported)
        # slot freed: a healthy fragment can still win the probe and close
        assert run_device(tk.session, lambda: "ok") == "ok"
        assert br.state == "closed"


# -- lock-wait budgets route through the Backoffer ---------------------------

class TestLockWaitBudget:
    def test_lock_wait_timeout_is_classified(self, tk):
        tk.must_exec("create table lw (id int primary key, v int)")
        tk.must_exec("insert into lw values (1, 1)")
        tk.must_exec("set innodb_lock_wait_timeout = 1")
        tk2 = tk.new_session()
        tk2.must_exec("use test")
        tk2.must_exec("set innodb_lock_wait_timeout = 1")
        tk.must_exec("begin")
        tk.must_exec("update lw set v = 2 where id = 1")
        t0 = time.monotonic()
        e = tk2.exec_error("update lw set v = 3 where id = 1")
        el = time.monotonic() - t0
        assert e.code == ErrCode.LockWaitTimeout
        assert el < 30, "budget must bound the wait, not loop forever"
        tk.must_exec("commit")
        tk2.must_exec("update lw set v = 4 where id = 1")  # recovers


# -- sysvar knobs (satellite) -------------------------------------------------

class TestResilienceSysvars:
    @pytest.mark.parametrize("name,default", [
        ("tidb_device_circuit_threshold", "5"),
        ("tidb_device_circuit_cooldown", "30"),
        ("tidb_backoff_weight", "2"),
    ])
    def test_defaults_visible(self, tk, name, default):
        tk.must_query(f"show variables like '{name}'").check(
            [(name, default)])

    def test_round_trip(self, tk):
        tk.must_exec("set tidb_device_circuit_threshold = 9")
        tk.must_exec("set tidb_device_circuit_cooldown = 1.5")
        tk.must_exec("set tidb_backoff_weight = 4")
        tk.must_query(
            "show variables like 'tidb_device_circuit%'").check_unordered(
            [("tidb_device_circuit_threshold", "9"),
             ("tidb_device_circuit_cooldown", "1.5")])
        tk.must_query("select @@tidb_backoff_weight").check([("4",)])

    def test_select_session_var(self, tk):
        tk.must_query("select @@tidb_device_circuit_threshold").check(
            [("5",)])

    def test_int_clamps_at_floor(self, tk):
        tk.must_exec("set tidb_device_circuit_threshold = -3")
        tk.must_query("select @@tidb_device_circuit_threshold").check(
            [("0",)])

    def test_float_rejects_garbage(self, tk):
        e = tk.exec_error("set tidb_device_circuit_cooldown = 'soon'")
        assert isinstance(e, TiDBError)

    def test_float_rejects_nan_and_clamps_negative(self, tk):
        # NaN sails past min/max clamps (all comparisons False) and would
        # wedge an opened breaker forever
        e = tk.exec_error("set tidb_device_circuit_cooldown = 'nan'")
        assert isinstance(e, TiDBError)
        tk.must_exec("set tidb_device_circuit_cooldown = '-5'")
        tk.must_query("select @@tidb_device_circuit_cooldown").check(
            [("0",)])


# -- coordinator failpoints (tentpole: failpoint expansion) ------------------

class TestCoordinatorFaults:
    def test_campaign_loss_skips_gc_round(self, tk):
        gw = tk.session.domain.gc_worker
        with failpoint.enabled("coordinator-campaign-loss", "return(1)"):
            out = gw.run_once()
        assert out.get("skipped") is True
        # campaigns succeed again once the fault clears
        assert tk.session.domain.coordinator.campaign("gc", "tidb-0")

    def test_tso_skew_keeps_monotonic(self, tk):
        coord = tk.session.domain.coordinator
        before = coord.tso()
        with failpoint.enabled("coordinator-tso-skew", "return(1048576)"):
            jumped = coord.tso()
        after = coord.tso()
        assert before < jumped < after
        assert jumped - before > 1048576

    def test_heartbeat_lost_then_recovers(self, tk):
        coord = tk.session.domain.coordinator
        with failpoint.enabled("coordinator-heartbeat-lost", "return(1)"):
            assert coord.heartbeat("tidb-0") is False
        assert coord.heartbeat("tidb-0") is True

    def test_lease_expire_lets_new_holder_win(self, tk):
        coord = tk.session.domain.coordinator
        assert coord.campaign("ddl-owner", "node-a", ttl_s=300)
        assert not coord.campaign("ddl-owner", "node-b")
        with failpoint.enabled("coordinator-lease-expire", "return(1)"):
            assert coord.campaign("ddl-owner", "node-b")
        assert coord.leader("ddl-owner") == "node-b"
