"""Fleet OLTP chaos bench (ISSUE 19): a TPC-C-shaped NewOrder/Payment
mix across N worker processes over the serving fabric, group-commit WAL
(``tidb_wal_fsync = 'interval'``), with per-round consistency invariants
and kill/stall chaos.

What one run asserts, every round:

* **money conservation** — Payment moves ``amt`` into ``w_ytd`` AND
  ``d_ytd`` AND out of ``c_balance`` atomically, so in any single
  snapshot ``sum(w_ytd) == sum(d_ytd) == -sum(c_balance)``;
* **order/sequence atomicity** — NewOrder's district-counter increment
  and its order insert commit together:
  ``sum(d_next_o_id) - n_districts == count(orders)``;
* **acked rows survive** — every client-acked NewOrder key is re-read
  after each chaos event (including from the respawned worker, which
  recovered the shared log from scratch);
* **read your peers' writes** — a marker committed under fsync
  ``commit`` on worker A is visible to a SINGLE immediate read on every
  other worker: the reader's ts acquisition waits on the fleet committed
  frontier (kv/shared_store.fresh_read_ts).  A value older than the
  marker is a SILENT STALE READ and fails the run unless the worker
  loudly annotated the downgrade (freshness_stale_ok); a classified
  9011 refusal is loud and therefore clean.

Chaos rounds: SIGKILL one worker mid-mix (measures respawn + recovery
wall clock), then SIGSTOP-stall one worker under load (survivors must
keep serving; the resumed worker must catch up and pass the peer-read
probe).  Freshness-wait latency (p50/p99) is aggregated from every
worker's ``freshness_wait_seconds`` histogram over DIAG metrics.

A CPU bench: a chip belongs to ONE process and this is N workers plus
the fleet's compile server, so until ROADMAP R2 gives the fleet a
chip-ownership model every spawned process is pinned to
JAX_PLATFORMS=cpu (this parent never initialises a backend); its
timings are host-platform numbers, not device metrics.

CLI: ``python bench_oltp.py --procs 3 --smoke`` is the fixed-seed CI
preset (tier-1 via tests/test_serve.py); it emits one ``serve_oltp``
JSON summary line.
"""

from __future__ import annotations

import argparse
import json
import os
import random
import signal
import sys
import threading
import time

import tidb_tpu  # noqa: F401  (x64 on)

#: TPC-C-shaped corpus dimensions (tiny on purpose: the CONTENTION is
#: the workload — a handful of district rows shared by every client is
#: what produces cross-worker write conflicts)
N_WH = 2
N_DIST = 4          # districts per warehouse
N_CUST = 10         # customers per district
N_DISTRICTS = N_WH * N_DIST

#: conflict-class error codes: the clean retryable outcomes of two
#: workers racing one district row (WriteConflict / TxnRetryable /
#: resolved-lock dup insert)
CONFLICT_CODES = (9007, 8002, 1062)
#: the loud classified stale-read refusal (errors.FreshnessWaitError)
FRESHNESS_CODE = 9011

RESPAWN_BUDGET_S = 30.0
#: SIGSTOP stall length: long enough to stall mid-2PC writes, short of
#: the 2s fleet lease timeout (a reclaimed slot would turn the stall
#: round into a second kill round)
STALL_S = 1.0
#: bound for the eventual-visibility probe under fsync 'interval'
#: (frontier publish trails a client ack by <= one flush period; 2s is
#: ~100 flush periods of slack)
CONVERGE_S = 2.0

_EMIT_LOCK = threading.Lock()


def _emit(obj) -> None:
    with _EMIT_LOCK:
        print(json.dumps(obj), flush=True)


def _pctl(sorted_vals, q: float):
    if not sorted_vals:
        return None
    i = min(int(q * len(sorted_vals)), len(sorted_vals) - 1)
    return round(sorted_vals[i], 2)


def _dk(w: int, d: int) -> int:
    return w * 100 + d


def _ck(w: int, d: int, c: int) -> int:
    return _dk(w, d) * 1000 + c


def _ok(dk: int, o_id: int) -> int:
    return dk * 100000 + o_id


def _oltp_seed(domain, seeded: bool = False):
    """Worker-side data init (TIDB_TPU_FABRIC_INIT hook).  Pure KV:
    under the durable shared store only the FIRST worker writes; the
    rest replay the schema and rows from the shared log."""
    from tidb_tpu.testkit import TestKit
    if seeded:
        return
    tk = TestKit(domain)
    tk.must_exec("use test")
    tk.must_exec("create table warehouse (w_id int primary key, "
                 "w_ytd int)")
    tk.must_exec("create table district (d_key int primary key, "
                 "w_id int, d_next_o_id int, d_ytd int)")
    tk.must_exec("create table customer (c_key int primary key, "
                 "c_balance int)")
    tk.must_exec("create table orders (o_key int primary key, "
                 "d_key int, o_entry int)")
    tk.must_exec("create table marker (id int primary key, v int)")
    tk.must_exec("insert into warehouse values " + ",".join(
        f"({w}, 0)" for w in range(1, N_WH + 1)))
    tk.must_exec("insert into district values " + ",".join(
        f"({_dk(w, d)}, {w}, 1, 0)"
        for w in range(1, N_WH + 1) for d in range(1, N_DIST + 1)))
    tk.must_exec("insert into customer values " + ",".join(
        f"({_ck(w, d, c)}, 0)"
        for w in range(1, N_WH + 1) for d in range(1, N_DIST + 1)
        for c in range(1, N_CUST + 1)))
    tk.must_exec("insert into marker values (1, 0)")


def _conn(port):
    from tidb_tpu.fabric.client import FleetClient
    c = FleetClient(port)
    c.must_exec("use test")
    return c


def _diag(port, kind: str) -> dict:
    """One worker's DIAG payload (empty on an unreachable peer — the
    stats feed must never fail a run)."""
    try:
        from tidb_tpu.fabric.client import FleetClient
        c = FleetClient(port, timeout=5.0)
        try:
            c.must_exec("use test")
            _cols, rows = c.must_query(f"DIAG {kind}")
            return json.loads(rows[0][0])
        finally:
            c.close()
    except Exception:  # noqa: BLE001 — diagnostics-only feed
        return {}


def _hist_pctls(merged_bounds, merged_counts, qs):
    """Percentiles from a cumulative-free bucket histogram: the value of
    a quantile is its bucket's UPPER bound (the /metrics convention);
    the overflow bucket reports the top bound."""
    total = sum(merged_counts)
    out = []
    for q in qs:
        if total == 0:
            out.append(0.0)
            continue
        rank = q * total
        acc = 0
        val = merged_bounds[-1]
        for i, n in enumerate(merged_counts):
            acc += n
            if acc >= rank:
                val = merged_bounds[min(i, len(merged_bounds) - 1)]
                break
        out.append(val)
    return out


class _Stats:
    """Shared mutable run state (one lock, bumped from client threads)."""

    def __init__(self):
        self.mu = threading.Lock()
        self.counts = {"new_order_ok": 0, "payment_ok": 0,
                       "conflicts": 0, "clean_errors": 0,
                       "freshness_refusals": 0, "wire_drops": 0,
                       "write_attempts": 0}
        self.read_ms: list = []
        self.txn_ms: list = []
        self.acked_orders: list = []   # committed o_key values
        self.violations: list = []

    def bump(self, key, n=1):
        with self.mu:
            self.counts[key] += n

    def violate(self, what):
        with self.mu:
            self.violations.append(what)


def _classified(c, st: _Stats, sql_steps) -> bool:
    """Run a txn's statements; True on commit-acked.  An 'err' outcome
    is classified: conflict codes count toward the conflict rate, 9011
    is the loud freshness refusal, anything else a clean error.  The
    txn is rolled back on any error (best-effort; the server also
    rolls back on connection teardown)."""
    for sql in sql_steps:
        kind, payload = c.query(sql)
        if kind == "err":
            code = payload[0]
            if code in CONFLICT_CODES:
                st.bump("conflicts")
            elif code == FRESHNESS_CODE:
                st.bump("freshness_refusals")
            else:
                st.bump("clean_errors")
            c.query("rollback")
            return False
        if kind == "rows" and not payload[1]:
            # read step found no row (e.g. district mid-conflict):
            # treat as a clean abort, not a crash
            st.bump("clean_errors")
            c.query("rollback")
            return False
    return True


def _new_order(c, st: _Stats, rng) -> None:
    w = rng.randrange(1, N_WH + 1)
    dk = _dk(w, rng.randrange(1, N_DIST + 1))
    st.bump("write_attempts")
    t0 = time.monotonic()
    kind, payload = c.query("begin")
    if kind == "err":
        st.bump("clean_errors")
        return
    kind, payload = c.query(
        f"select d_next_o_id from district where d_key = {dk}")
    if kind != "rows" or not payload[1]:
        st.bump("clean_errors")
        c.query("rollback")
        return
    o_id = int(payload[1][0][0])
    ok = _classified(c, st, (
        f"update district set d_next_o_id = {o_id + 1} "
        f"where d_key = {dk}",
        f"insert into orders values ({_ok(dk, o_id)}, {dk}, "
        f"{int(time.time())})",
        "commit",
    ))
    if ok:
        with st.mu:
            st.counts["new_order_ok"] += 1
            st.acked_orders.append(_ok(dk, o_id))
            st.txn_ms.append((time.monotonic() - t0) * 1000.0)


def _payment(c, st: _Stats, rng) -> None:
    w = rng.randrange(1, N_WH + 1)
    d = rng.randrange(1, N_DIST + 1)
    ck = _ck(w, d, rng.randrange(1, N_CUST + 1))
    amt = rng.randrange(1, 50)
    st.bump("write_attempts")
    t0 = time.monotonic()
    kind, _ = c.query("begin")
    if kind == "err":
        st.bump("clean_errors")
        return
    ok = _classified(c, st, (
        f"update warehouse set w_ytd = w_ytd + {amt} where w_id = {w}",
        f"update district set d_ytd = d_ytd + {amt} "
        f"where d_key = {_dk(w, d)}",
        f"update customer set c_balance = c_balance - {amt} "
        f"where c_key = {ck}",
        "commit",
    ))
    if ok:
        with st.mu:
            st.counts["payment_ok"] += 1
            st.txn_ms.append((time.monotonic() - t0) * 1000.0)


def _point_read(c, st: _Stats, rng) -> None:
    w = rng.randrange(1, N_WH + 1)
    d = rng.randrange(1, N_DIST + 1)
    t0 = time.monotonic()
    kind, payload = c.query(
        f"select d_next_o_id, d_ytd from district "
        f"where d_key = {_dk(w, d)}")
    if kind == "err":
        if payload[0] == FRESHNESS_CODE:
            st.bump("freshness_refusals")
        else:
            st.bump("clean_errors")
        return
    with st.mu:
        st.read_ms.append((time.monotonic() - t0) * 1000.0)


def _mix_round(fleet, st: _Stats, *, n_threads, n_ops, seed, round_no,
               live_slots, chaos: bool):
    """One round of the NewOrder/Payment/read mix, client threads spread
    over the live workers' direct ports."""
    from tidb_tpu.fabric.client import WireError

    def worker(tid):
        rng = random.Random((seed << 16) ^ (round_no << 8) ^ tid)
        port = fleet.direct_port(live_slots[tid % len(live_slots)])
        try:
            c = _conn(port)
        except WireError:
            st.bump("wire_drops")
            if not chaos:
                st.violate(f"round {round_no}: wire failure on connect "
                           "without chaos")
            return
        try:
            for _ in range(n_ops):
                r = rng.random()
                try:
                    if r < 0.40:
                        _new_order(c, st, rng)
                    elif r < 0.75:
                        _payment(c, st, rng)
                    else:
                        _point_read(c, st, rng)
                except WireError:
                    st.bump("wire_drops")
                    if not chaos:
                        st.violate(f"round {round_no}: wire drop "
                                   "without chaos")
                    return
        finally:
            try:
                c.close()
            except Exception:  # noqa: BLE001
                pass

    threads = [threading.Thread(target=worker, args=(t,), daemon=True)
               for t in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(300.0)
    assert not any(t.is_alive() for t in threads), "STUCK oltp clients"


def _check_invariants(fleet, st: _Stats, slot: int, label: str):
    """The round-end consistency audit from ONE worker, all sums read in
    a single snapshot txn."""
    c = _conn(fleet.direct_port(slot))
    try:
        c.must_exec("begin")
        sw = int(c.must_query("select sum(w_ytd) from warehouse")[1][0][0])
        sd = int(c.must_query("select sum(d_ytd) from district")[1][0][0])
        sc = int(c.must_query(
            "select sum(c_balance) from customer")[1][0][0])
        n_orders = int(c.must_query(
            "select count(*) from orders")[1][0][0])
        sum_next = int(c.must_query(
            "select sum(d_next_o_id) from district")[1][0][0])
        c.must_exec("commit")
    finally:
        c.close()
    if not (sw == sd == -sc):
        st.violate(f"{label}: MONEY LEAK on slot {slot}: sum(w_ytd)={sw} "
                   f"sum(d_ytd)={sd} -sum(c_balance)={-sc}")
    if sum_next - N_DISTRICTS != n_orders:
        st.violate(f"{label}: ORDER/SEQUENCE SPLIT on slot {slot}: "
                   f"sum(d_next_o_id)-{N_DISTRICTS}={sum_next - N_DISTRICTS}"
                   f" but count(orders)={n_orders}")
    return {"orders": n_orders, "ytd": sw}


def _check_acked_survive(fleet, st: _Stats, slot: int, label: str,
                         rng, sample_n: int = 20):
    """Spot-check that client-acked NewOrder keys exist on `slot` (the
    full count is covered by the sequence invariant; the sample pins
    concrete acked keys, including after a kill/recover)."""
    with st.mu:
        acked = list(st.acked_orders)
    if not acked:
        return
    sample = rng.sample(acked, min(sample_n, len(acked)))
    c = _conn(fleet.direct_port(slot))
    try:
        for key in sample:
            rows = c.must_query(
                f"select o_key from orders where o_key = {key}")[1]
            if not rows:
                st.violate(f"{label}: ACKED ROW LOST on slot {slot}: "
                           f"committed order {key} missing")
    finally:
        c.close()


def _stale_counters(fleet, slots) -> dict:
    """slot -> freshness_stale_ok (loud-downgrade counter) via DIAG."""
    out = {}
    for s in slots:
        fab = _diag(fleet.direct_port(s), "status").get("fabric", {})
        out[s] = int(fab.get("freshness_stale_ok", 0) or 0)
    return out


def _peer_read_probe(fleet, st: _Stats, writer: int, readers, label: str,
                     marker_seq: list, *, strict: bool):
    """Commit a marker bump on `writer`, read it back from every slot in
    `readers`.  strict=True flips the GLOBAL fsync policy to 'commit' on
    the writer for the bump, so the frontier publish PRECEDES the ack
    and a single immediate read per peer must see it.  strict=False (the
    'interval' mix policy) allows the frontier to trail one flush
    period, so the probe retries within CONVERGE_S.  Either way a read
    that returns an older value without a loud stale_ok downgrade (or a
    classified 9011 refusal) is a silent-stale violation."""
    from tidb_tpu.fabric.client import WireError

    pre_stale = _stale_counters(fleet, readers)
    marker_seq[0] += 1
    n = marker_seq[0]
    w = _conn(fleet.direct_port(writer))
    try:
        if strict:
            w.must_exec("set global tidb_wal_fsync = 'commit'")
        w.must_exec("begin")
        w.must_exec(f"update marker set v = {n} where id = 1")
        w.must_exec("commit")
    finally:
        if strict:
            try:
                w.must_exec("set global tidb_wal_fsync = 'interval'")
            except WireError:
                pass
        w.close()

    for s in readers:
        deadline = time.monotonic() + (0 if strict else CONVERGE_S)
        while True:
            c = _conn(fleet.direct_port(s))
            try:
                kind, payload = c.query(
                    "select v from marker where id = 1")
            finally:
                c.close()
            if kind == "err":
                if payload[0] == FRESHNESS_CODE:
                    # the LOUD classified refusal: clean by contract
                    st.bump("freshness_refusals")
                    break
                st.violate(f"{label}: peer-read probe error on slot {s}:"
                           f" {payload}")
                break
            v = int(payload[1][0][0])
            if v >= n:
                break
            if time.monotonic() < deadline:
                time.sleep(0.02)
                continue
            post = _stale_counters(fleet, [s])
            if post.get(s, 0) > pre_stale.get(s, 0):
                # the worker ANNOUNCED the downgrade — loud, clean
                st.bump("freshness_refusals")
                break
            st.violate(
                f"{label}: SILENT STALE READ on slot {s}: marker v={v} "
                f"< committed {n} with no stale_ok downgrade and no "
                "9011 refusal")
            break


def run_oltp(procs: int = 3, n_threads: int = 6, n_ops: int = 8,
             seed: int = 0, chaos: bool = True, emit=_emit) -> dict:
    """Drive the OLTP chaos bench; returns the ``serve_oltp`` summary
    dict (also emitted).  Raises AssertionError on any consistency
    violation — tests call this in-process, the CLI exits 1."""
    from tidb_tpu.fabric.fleet import Fleet

    assert procs >= 2, "the cross-worker contract needs >= 2 workers"
    assert not chaos or procs >= 3, (
        "chaos rounds need >= 3 workers: two DISTINCT survivors must "
        "keep serving while one is down")
    rng = random.Random(seed)
    st = _Stats()
    marker_seq = [0]
    fleet = Fleet(procs, init="bench_oltp:_oltp_seed",
                  # the throughput mix runs under GROUP COMMIT: acks
                  # ride the interval flusher, frontier publish trails
                  # by <= one flush period (the strict peer-read probe
                  # flips to 'commit' per round to pin immediacy)
                  sysvars={"tidb_wal_fsync": "interval"},
                  # N workers + a compile server cannot share one chip
                  env_extra={"JAX_PLATFORMS": "cpu"})
    t_boot = time.monotonic()
    fleet.start(timeout_s=300.0)
    emit({"metric": "oltp_fleet_up", "procs": procs, "port": fleet.port,
          "boot_s": round(time.monotonic() - t_boot, 2), "seed": seed,
          "chaos": chaos})
    kill_recover_s = None
    stall_round = False
    t_run = time.monotonic()
    try:
        all_slots = list(range(procs))
        round_no = 0

        # -- round 0: fault-free baseline --------------------------------
        t0 = time.monotonic()
        _mix_round(fleet, st, n_threads=n_threads, n_ops=n_ops,
                   seed=seed, round_no=round_no, live_slots=all_slots,
                   chaos=False)
        _check_invariants(fleet, st, all_slots[0], "round0")
        _peer_read_probe(fleet, st, writer=all_slots[0],
                         readers=all_slots[1:], label="round0",
                         marker_seq=marker_seq, strict=True)
        _peer_read_probe(fleet, st, writer=all_slots[-1],
                         readers=all_slots[:-1], label="round0-rev",
                         marker_seq=marker_seq, strict=False)
        emit({"metric": "oltp_round", "round": 0, "kind": "baseline",
              "wall_s": round(time.monotonic() - t0, 2),
              **dict(st.counts)})

        if chaos:
            # -- round 1: SIGKILL one worker mid-mix ---------------------
            round_no += 1
            victim = rng.choice(all_slots[1:])  # keep slot0 as auditor
            survivors = [s for s in all_slots if s != victim]
            old_pid = fleet.worker_pid(victim)
            t0 = time.monotonic()
            killer = threading.Timer(
                0.3, lambda: fleet.kill_worker(victim, signal.SIGKILL))
            killer.start()
            _mix_round(fleet, st, n_threads=n_threads, n_ops=n_ops,
                       seed=seed, round_no=round_no,
                       live_slots=all_slots, chaos=True)
            killer.join()
            assert fleet.wait_respawn(victim, old_pid,
                                      RESPAWN_BUDGET_S), (
                f"worker {victim} not respawned within "
                f"{RESPAWN_BUDGET_S}s")
            kill_recover_s = round(time.monotonic() - t0, 2)
            _check_invariants(fleet, st, survivors[0], "round1-survivor")
            # the RESPAWNED worker recovered the shared log from zero:
            # acked rows and all sums must be intact THERE too
            _check_invariants(fleet, st, victim, "round1-respawned")
            _check_acked_survive(fleet, st, victim, "round1-respawned",
                                 rng)
            _peer_read_probe(fleet, st, writer=survivors[0],
                             readers=[victim] + survivors[1:],
                             label="round1", marker_seq=marker_seq,
                             strict=True)
            emit({"metric": "oltp_round", "round": 1, "kind": "kill",
                  "victim": victim, "recover_s": kill_recover_s,
                  "wall_s": round(time.monotonic() - t0, 2),
                  **dict(st.counts)})

            # -- round 2: SIGSTOP-stall one worker under load ------------
            round_no += 1
            stall_round = True
            victim = rng.choice(all_slots[1:])
            survivors = [s for s in all_slots if s != victim]
            pid = fleet.worker_pid(victim)
            t0 = time.monotonic()
            os.kill(pid, signal.SIGSTOP)
            try:
                _mix_round(fleet, st, n_threads=n_threads,
                           n_ops=max(2, n_ops // 2), seed=seed,
                           round_no=round_no, live_slots=survivors,
                           chaos=True)
                # survivors serve each other's writes while a member
                # is frozen mid-whatever
                _peer_read_probe(fleet, st, writer=survivors[0],
                                 readers=survivors[1:],
                                 label="round2-stalled",
                                 marker_seq=marker_seq, strict=True)
            finally:
                if time.monotonic() - t0 < STALL_S:
                    time.sleep(STALL_S - (time.monotonic() - t0))
                os.kill(pid, signal.SIGCONT)
            # the resumed worker must catch its tail up and pass the
            # SAME immediate-visibility bar as everyone else
            _peer_read_probe(fleet, st, writer=survivors[0],
                             readers=[victim], label="round2-resumed",
                             marker_seq=marker_seq, strict=True)
            _check_invariants(fleet, st, victim, "round2-resumed")
            emit({"metric": "oltp_round", "round": 2, "kind": "stall",
                  "victim": victim, "stall_s": STALL_S,
                  "wall_s": round(time.monotonic() - t0, 2),
                  **dict(st.counts)})

        # -- final audit from EVERY worker (identical answers) -----------
        finals = {s: _check_invariants(fleet, st, s, "final")
                  for s in all_slots}
        if len({(v["orders"], v["ytd"]) for v in finals.values()}) > 1:
            st.violate(f"final: workers disagree on committed state: "
                       f"{finals}")
        _check_acked_survive(fleet, st, all_slots[0], "final", rng)
        wall_s = time.monotonic() - t_run

        # -- freshness histogram, fleet-merged over DIAG -----------------
        bounds, counts = None, None
        waits = timeouts = stale_ok = 0
        for s in all_slots:
            h = (_diag(fleet.direct_port(s), "metrics")
                 .get("hists", {}).get("freshness_wait_seconds"))
            if h:
                if bounds is None:
                    bounds = h["bounds"]
                    counts = [0] * len(h["counts"])
                counts = [a + b for a, b in zip(counts, h["counts"])]
            fab = _diag(fleet.direct_port(s), "status").get("fabric", {})
            waits += int(fab.get("freshness_waits", 0) or 0)
            timeouts += int(fab.get("freshness_timeouts", 0) or 0)
            stale_ok += int(fab.get("freshness_stale_ok", 0) or 0)
        if bounds:
            p50, p99 = _hist_pctls(bounds, counts, (0.50, 0.99))
        else:
            p50 = p99 = 0.0

        with st.mu:
            c = dict(st.counts)
            read_ms = sorted(st.read_ms)
            txn_ms = sorted(st.txn_ms)
            n_acked = len(st.acked_orders)
            violations = list(st.violations)
        txns_ok = c["new_order_ok"] + c["payment_ok"]
        summary = {
            "metric": "serve_oltp", "procs": procs,
            "threads": n_threads, "ops": n_ops, "seed": seed,
            "chaos": chaos, "wall_s": round(wall_s, 2),
            # tpmC-shaped: committed business txns per minute
            "tpmC": round(txns_ok / wall_s * 60.0, 1),
            "txns_ok": txns_ok, "new_orders": c["new_order_ok"],
            "payments": c["payment_ok"], "acked_orders": n_acked,
            "conflict_rate": round(
                c["conflicts"] / max(c["write_attempts"], 1), 4),
            "conflicts": c["conflicts"],
            "clean_errors": c["clean_errors"],
            "wire_drops": c["wire_drops"],
            "freshness_wait_p50_ms": round(p50 * 1000.0, 3),
            "freshness_wait_p99_ms": round(p99 * 1000.0, 3),
            "freshness_waits": waits,
            "freshness_timeouts": timeouts,
            "freshness_stale_ok": stale_ok,
            "freshness_refusals": c["freshness_refusals"],
            "txn_p50_ms": _pctl(txn_ms, 0.50),
            "txn_p99_ms": _pctl(txn_ms, 0.99),
            "read_p50_ms": _pctl(read_ms, 0.50),
            "read_p99_ms": _pctl(read_ms, 0.99),
            "kill_recover_s": kill_recover_s,
            "stall_round": stall_round,
            "violations": len(violations),
        }
        emit(summary)
        assert not violations, (
            "OLTP CONSISTENCY VIOLATIONS:\n" + "\n".join(violations))
        assert txns_ok > 0, "no transaction ever committed"
        return summary
    finally:
        drained = fleet.shutdown()
        emit({"metric": "oltp_fleet_drained",
              **(drained or {"ok": False})})
        assert drained and drained["ok"], (
            f"FLEET DRAIN LEAK (leases/running/dedup): {drained}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--procs", type=int, default=3)
    ap.add_argument("--threads", type=int, default=6)
    ap.add_argument("--ops", type=int, default=8,
                    help="operations per client thread per round")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--no-chaos", action="store_true",
                    help="baseline round only (no kill/stall rounds)")
    ap.add_argument("--smoke", action="store_true",
                    help="fixed-seed CI preset (3 workers, chaos on)")
    args = ap.parse_args(argv)
    if args.smoke:
        args.procs, args.threads, args.ops, args.seed = 3, 6, 6, 0
    try:
        summary = run_oltp(procs=args.procs, n_threads=args.threads,
                           n_ops=args.ops, seed=args.seed,
                           chaos=not args.no_chaos)
    except AssertionError as e:
        _emit({"metric": "oltp_violation", "error": str(e)[:2000]})
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
