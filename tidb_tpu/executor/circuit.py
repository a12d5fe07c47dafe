"""Device-engine circuit breaker: graceful degradation to the host engine.

The failure it guards against: a compile endpoint that refuses every
connection ("Connection refused"), or a device that fails every dispatch,
makes each fragment pay the full failure latency one by one (the July 2026
TPC-H SF1 run on a v5e lost Q5, Q9 and Q18 that way), and a fragment class
that is slower on the device than on the host (Q3 ran 0.562× there) has no
policy to stop paying for it.  The breaker formalizes the informal host
fallback hinted at in device_exec.py: after N classified device failures the device
engine OPENS for a cooldown window — fragments degrade to the (always
correct) host engine immediately instead of timing out one by one — then a
HALF_OPEN probe re-admits one fragment and a success closes the breaker.

States (the classic Nygard breaker, per-(Domain, fragment shape): embedded
test clusters stay isolated, and a failure mode specific to one fragment
class — agg vs join vs window — cools down only that class while healthy
shapes keep running on-device):

    CLOSED     normal: device dispatch allowed, failures counted
    OPEN       cooling down: allow() is False, everything runs host-side
    HALF_OPEN  cooldown elapsed: ONE probe runs device-side; success
               closes, failure re-opens

Knobs (session/sysvars.py): tidb_device_circuit_threshold (failures to
open; 0 disables), tidb_device_circuit_cooldown (seconds OPEN)."""

from __future__ import annotations

import logging
import threading
import time

log = logging.getLogger("tidb_tpu.circuit")

CLOSED, OPEN, HALF_OPEN = "closed", "open", "half-open"

#: a HALF_OPEN probe outstanding longer than max(cooldown, this) without
#: any verdict is presumed lost (its thread died or was abandoned on a
#: path that skipped release_probe) — allow() reclaims the slot so the
#: breaker can never wedge host-side forever.  Minutes-scale on purpose:
#: a LIVE probe may legitimately sit in a post-fence cold XLA compile
#: far past the cooldown (a ~6 min compile of one fragment has been
#: measured on a v5e), and stealing its slot would admit a
#: second probe and orphan the first one's verdict; the floor only needs
#: to be finite, not snappy
_PROBE_RECLAIM_FLOOR_S = 900.0


class CircuitBreaker:
    def __init__(self, threshold: int = 5, cooldown_s: float = 30.0,
                 clock=time.monotonic, shape: str = "agg"):
        self._mu = threading.Lock()
        self.shape = shape  # fragment class this breaker guards
        self.threshold = int(threshold)
        self.cooldown_s = float(cooldown_s)
        self._clock = clock
        self._state = CLOSED
        self._failures = 0
        self._opened_at = 0.0
        self._probing = False
        # the probe slot's owner token: the SESSION when one is known,
        # else the thread ident.  Thread ident alone is not enough
        # cross-session — an embedded server runs many sessions on one
        # thread, and a stale verdict from session B must not pass the
        # owner check and resolve session A's probe (the multi-tenant
        # half-open race).  Keying on the session (not (thread, session))
        # also keeps the verdict valid when a SUPERVISED dispatch records
        # it from a worker thread (mpp_exec's exchange-exhaustion path):
        # a session runs one statement at a time, so one session = at
        # most one fragment verdict in flight.
        self._probe_owner = None
        self._probe_started = 0.0
        self.stats = {"opened": 0, "degraded": 0, "failures": 0,
                      "probes": 0, "probe_reclaims": 0}
        #: per-resource-group reporting (stat lines keyed by tenant):
        #: which tenants are paying the degradations/failures.  Reporting
        #: ONLY — breaker state stays per (Domain, shape): device health
        #: is a property of the hardware path, not of who dispatched
        self.stats_by_group: dict = {}
        self.last_error = ""

    @staticmethod
    def _token(session):
        if session is not None:
            return ("sid", session)
        return ("tid", threading.get_ident())

    def _group_stats(self, group):
        st = self.stats_by_group.get(group)
        if st is None:
            # group names are a free-form session sysvar: cap the stat
            # lines, folding new names into one overflow bucket (same
            # rule as scheduler.GROUP_STATS_CAP) so a fresh-name-per-
            # connection client cannot grow the snapshot forever
            from .scheduler import GROUP_STATS_CAP, OVERFLOW_GROUP
            if len(self.stats_by_group) >= GROUP_STATS_CAP:
                group = OVERFLOW_GROUP
                st = self.stats_by_group.get(group)
            if st is None:
                st = self.stats_by_group[group] = {"degraded": 0,
                                                   "failures": 0}
        return st

    def configure(self, threshold: int | None = None,
                  cooldown_s: float | None = None):
        with self._mu:
            if threshold is not None:
                self.threshold = int(threshold)
            if cooldown_s is not None:
                self.cooldown_s = float(cooldown_s)

    @property
    def state(self) -> str:
        with self._mu:
            return self._peek_state()

    def _peek_state(self) -> str:
        if (self._state == OPEN
                and self._clock() - self._opened_at >= self.cooldown_s):
            return HALF_OPEN
        return self._state

    def allow(self, session=None, group=None) -> bool:
        """May a fragment dispatch to the device right now?  In HALF_OPEN
        exactly one caller wins the probe slot; the rest stay host-side
        until the probe's verdict is in.  The slot is owned by the
        SESSION (thread ident only as the no-session fallback — see the
        _probe_owner field comment), so two sessions' simultaneous probe
        grants on the same shape resolve to one probe even when an
        embedded server multiplexes both onto one thread, while a
        supervised dispatch's worker-thread verdict still matches.  A
        probe whose owner vanished without any verdict (thread died on a
        path outside run_device's release discipline) is reclaimed after
        a grace window instead of wedging every future caller host-side."""
        with self._mu:
            if self.threshold <= 0:  # breaker disabled
                return True
            st = self._peek_state()
            if st == CLOSED:
                return True
            if st == HALF_OPEN:
                if (self._probing and self._clock() - self._probe_started
                        > max(self.cooldown_s, _PROBE_RECLAIM_FLOOR_S)):
                    self.stats["probe_reclaims"] += 1
                    self._probing = False
                    self._probe_owner = None
                if not self._probing:
                    self._state = HALF_OPEN
                    self._probing = True
                    self._probe_owner = self._token(session)
                    self._probe_started = self._clock()
                    self.stats["probes"] += 1
                    return True
            self.stats["degraded"] += 1
            if group is not None:
                self._group_stats(group)["degraded"] += 1
            return False

    def release_probe(self, session=None):
        """The probe fragment exited WITHOUT a health verdict (it raised
        DeviceUnsupported / a user error before touching the device) —
        free the HALF_OPEN probe slot so another fragment can probe,
        instead of wedging the breaker with _probing stuck True.
        Ownership-checked: a stale fragment admitted before the breaker
        opened must not free a live probe's slot (one probe at a time)."""
        with self._mu:
            if (self._peek_state() == HALF_OPEN and self._probing
                    and self._probe_owner == self._token(session)):
                self._probing = False
                self._probe_owner = None

    def record_success(self, session=None):
        with self._mu:
            if self._probing and self._probe_owner != self._token(session):
                # a STALE fragment (admitted while CLOSED, finishing after
                # the breaker opened) succeeds while another thread's probe
                # is in flight: good news, but the probe owns the verdict —
                # reset the failure streak without touching the probe slot
                # or closing the breaker out from under the prober
                self._failures = 0
                return
            if self._state in (OPEN, HALF_OPEN) and not self._probing:
                # stale success with no probe in flight (a fragment
                # admitted before the breaker tripped, finishing
                # mid-cooldown — or after a prober released its slot with
                # no verdict): recovery goes through a HALF_OPEN probe's
                # OWN verdict, not through stragglers racing the hangs
                # that opened the breaker
                self._failures = 0
                return
            if self._state in (HALF_OPEN, OPEN):
                log.info("device circuit closed (probe succeeded)")
            self._state = CLOSED
            self._failures = 0
            self._probing = False
            self._probe_owner = None

    def record_failure(self, err=None, session=None, group=None):
        from ..utils.backoff import classify
        with self._mu:
            self.stats["failures"] += 1
            if group is not None:
                self._group_stats(group)["failures"] += 1
            if err is not None:
                self.last_error = f"{classify(err)}: {err}"
            if self.threshold <= 0:
                return
            if self._probing and self._probe_owner != self._token(session):
                # stale verdict during a live probe (see record_success):
                # count it, but the slot and the state belong to the probe
                self._failures += 1
                return
            if self._state == HALF_OPEN:
                # failed probe: back to a full cooldown
                self._reopen()
                return
            self._failures += 1
            if self._failures >= self.threshold:
                self._reopen()

    def _reopen(self):
        self._state = OPEN
        self._opened_at = self._clock()
        self._failures = 0
        self._probing = False
        self._probe_owner = None
        self.stats["opened"] += 1
        log.warning("device circuit OPEN for %s fragments for %.1fs "
                    "(last error: %s)",
                    self.shape, self.cooldown_s, self.last_error)

    def snapshot(self) -> dict:
        with self._mu:
            return {"state": self._peek_state(), "shape": self.shape,
                    "failures": self._failures,
                    "threshold": self.threshold,
                    "cooldown_s": self.cooldown_s,
                    "last_error": self.last_error,
                    "by_group": {g: dict(st) for g, st
                                 in self.stats_by_group.items()},
                    **self.stats}


#: process-wide fallback for contexts with no Domain (bare device calls),
#: one breaker per fragment shape
_GLOBALS: dict = {}


def get_breaker(ctx=None, shape: str = "agg") -> CircuitBreaker:
    """The device breaker for this execution context and fragment SHAPE
    (agg / join / window): one per (Domain, shape) so embedded test
    clusters are isolated AND one failing fragment class cools down
    without degrading healthy paths — a join-shape XLA bug must not push
    scan-aggregates off the device (ROADMAP: finer per-fragment-shape
    breaker). Falls back to a module-global per-shape breaker when the
    context has no Domain.

    Knobs are read from the breaker's OWN scope — the Domain's GLOBAL
    variables (`SET GLOBAL tidb_device_circuit_*`) — on every fetch, so
    SET GLOBAL takes effect on the next fragment.  A session-scoped SET
    must NOT reconfigure the shared breaker: concurrent sessions would
    clobber each other's threshold/cooldown mid-OPEN."""
    dom = getattr(ctx, "domain", None)
    if dom is not None:
        # dict.setdefault is atomic under the GIL: concurrent sessions
        # (threaded chaos, server connections) racing the first fetch must
        # converge on ONE breaker per shape, not each keep their own
        brs = dom.__dict__.setdefault("_device_breakers", {})
        br = brs.get(shape)
        if br is None:
            br = brs.setdefault(shape, CircuitBreaker(shape=shape))
        try:
            gv = dom.global_vars
            br.configure(
                threshold=int(gv.get("tidb_device_circuit_threshold", 5)),
                cooldown_s=float(
                    gv.get("tidb_device_circuit_cooldown", 30.0)))
        except Exception:
            pass
        return br
    br = _GLOBALS.get(shape)
    if br is None:
        br = _GLOBALS.setdefault(shape, CircuitBreaker(shape=shape))
    if ctx is not None:  # bare context: its own view is the only scope
        try:
            br.configure(
                threshold=int(ctx.get_sysvar("tidb_device_circuit_threshold")),
                cooldown_s=float(
                    ctx.get_sysvar("tidb_device_circuit_cooldown")))
        except Exception:
            pass
    return br
