"""One cost currency for every physical decision, with calibrated
constants (reference: planner/core/find_best_task.go costs every
operator's alternatives in one unit; the constants live in sysvars like
tidb_opt_seek_factor / tidb_opt_cpu_factor and can be tuned without code
changes — sessionctx/variable/sysvar.go).

The unit is "one vectorized scanned row" (scan_row ≡ 1.0). Everything
else — KV seeks, hash-table builds, sort comparisons, device dispatch —
is expressed as multiples of it, measured on THIS machine by
``calibrate()``: a ~30ms micro-bench at server/bench startup whose
results land in the global sysvars, so EXPLAIN costs describe the
hardware actually running the query. Tests flip plans by SETting the
sysvars — never by editing constants.
"""

from __future__ import annotations

import time

import numpy as np

#: (sysvar name, default) — defaults match the hand-tuned r4 constants so
#: an uncalibrated process plans exactly as before
COST_VARS = (
    ("tidb_opt_scan_row_cost", 1.0),      # vectorized scan, per row
    ("tidb_opt_seek_cost", 8.0),          # KV point seek + decode, per key
    ("tidb_opt_seek_base", 30.0),         # per-access-path fixed seek cost
    ("tidb_opt_hash_build_cost", 2.0),    # hash-table insert, per build row
    ("tidb_opt_merge_sort_cost", 0.05),   # sort comparison, per row·log2
    ("tidb_opt_agg_row_cost", 2.0),       # host group-by, per input row
    ("tidb_opt_device_row_cost", 0.02),   # device pipeline, per row
    # default chosen so the UNCALIBRATED breakeven equals the historical
    # 65536-row auto-mode dispatch floor: 65536*(agg 2 + scan 1 - 0.02)
    ("tidb_opt_device_dispatch_cost", 195000.0),  # per fused dispatch
)


class CostModel:
    __slots__ = ("scan_row", "seek", "seek_base", "hash_build",
                 "merge_sort", "agg_row", "device_row", "device_dispatch")

    def __init__(self, scan_row, seek, seek_base, hash_build, merge_sort,
                 agg_row, device_row, device_dispatch):
        self.scan_row = scan_row
        self.seek = seek
        self.seek_base = seek_base
        self.hash_build = hash_build
        self.merge_sort = merge_sort
        self.agg_row = agg_row
        self.device_row = device_row
        self.device_dispatch = device_dispatch

    @classmethod
    def from_ctx(cls, ctx) -> "CostModel":
        vals = []
        for name, dflt in COST_VARS:
            v = dflt
            if ctx is not None:
                # planner exposes get_sysvar(name, scope); executors and
                # sessions expose get_sysvar(name) — accept both (a silent
                # fallback to defaults here would make the calibrated
                # sysvars dead knobs)
                try:
                    v = float(ctx.get_sysvar(name, "session"))
                except TypeError:
                    try:
                        v = float(ctx.get_sysvar(name))
                    except Exception:
                        v = dflt
                except Exception:
                    v = dflt
            vals.append(v)
        return cls(*vals)

    def device_breakeven_rows(self) -> int:
        """Input size where the fused device pipeline beats the host agg —
        auto engine mode's dispatch floor, DERIVED from the calibrated
        constants instead of a hard-coded row count."""
        gain = max(self.agg_row + self.scan_row - self.device_row, 1e-9)
        return int(self.device_dispatch / gain)


def calibrate(n: int = 1 << 18, seed: int = 0) -> dict:
    """Measure the host-side constants on this machine → {sysvar: value},
    normalized to scan_row = 1.0. Device constants are deliberately NOT
    measured here (a jit round trip at startup costs a cold compile);
    their defaults were taken on XLA:CPU and have never been calibrated
    on a chip — `auto` routing prices the device from assumptions, so pin
    `tidb_executor_engine` where the placement matters. They can be
    overridden like any sysvar."""
    rng = np.random.default_rng(seed)
    data = rng.integers(0, 1 << 40, n)
    keys = rng.integers(0, n // 4, n)

    def best_of(f, reps=3):
        t = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            f()
            t = min(t, time.perf_counter() - t0)
        return t

    scan_s = best_of(lambda: (data > (1 << 39)).sum())
    scan_row_ns = max(scan_s / n, 1e-12)

    # KV point seek analog: python dict lookup + int decode (the embedded
    # store's get path is a dict probe + version walk)
    d = {int(k): i for i, k in enumerate(keys[: 1 << 14])}
    probe = [int(k) for k in keys[: 1 << 14]]

    def seeks():
        s = 0
        for k in probe:
            s += d[k]
        return s

    seek_s = best_of(seeks)
    seek_ns = seek_s / len(probe)

    hash_s = best_of(lambda: np.unique(keys, return_inverse=True))
    hash_ns = hash_s / n

    sort_s = best_of(lambda: np.argsort(data, kind="stable"))
    sort_ns = sort_s / (n * np.log2(n))

    # host group-by row cost ~ factorize + scatter-add passes
    agg_s = best_of(lambda: np.bincount(
        np.clip(keys, 0, n // 4), weights=data.astype(np.float64)))
    agg_ns = hash_ns + agg_s / n

    unit = scan_row_ns
    return {
        "tidb_opt_scan_row_cost": 1.0,
        "tidb_opt_seek_cost": round(seek_ns / unit, 3),
        "tidb_opt_seek_base": round(30 * seek_ns / unit / 8, 3),
        "tidb_opt_hash_build_cost": round(hash_ns / unit, 3),
        "tidb_opt_merge_sort_cost": round(sort_ns / unit, 4),
        "tidb_opt_agg_row_cost": round(agg_ns / unit, 3),
        # device constants converted into the measured unit from assumed
        # wall times (dispatch ~3ms sync over a local PJRT path, device
        # row throughput ~20G rows/s) — a true measurement needs a jit
        # round trip this budget can't afford; override via the sysvars
        "tidb_opt_device_dispatch_cost": round(3e6 / (unit * 1e9), 0),
        "tidb_opt_device_row_cost": round(0.05 / (unit * 1e9), 4),
    }


def apply_calibration(domain, values: dict | None = None) -> dict:
    """Run (or take) a calibration and install it as GLOBAL sysvars —
    every session planning after this prices plans with the measured
    constants. Returns what was installed."""
    vals = values if values is not None else calibrate()
    for name, v in vals.items():
        domain.global_vars[name] = str(v)
    return vals
