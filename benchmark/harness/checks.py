"""What the worker's own counters say about a window: did every
fragment run on the pinned engine, undegraded?  A correct answer from
the host engine under a configuration that pins the device engine is a
failure, not a fast result."""

from .observe import delta


def engines(plan_rows) -> list:
    """Every ``engine:<name>`` annotation of an EXPLAIN ANALYZE result."""
    out = []
    for row in plan_rows:
        for part in (row[2] or "").split(", "):
            if part.startswith("engine:"):
                out.append(part[len("engine:"):])
    return out


def degraded(s0: dict, s1: dict, mesh: bool, attempted: int) -> list:
    """One line per degradation the counters show between two
    ``DIAG STATUS`` bodies, each ``(count, text)``."""
    bad = []

    def note(n, what):
        if n > 0:
            bad.append((int(n), f"{what} +{int(n)}"))

    for shape, b1 in s1["device_breakers"].items():
        b0 = s0["device_breakers"].get(shape, {})   # new in the window: 0
        for k in ("failures", "degraded", "opened"):
            note(b1[k] - b0.get(k, 0), f"breaker[{shape}].{k}")
    for k in ("rejected_full", "rejected_timeout"):
        note(delta(s0, s1, "device_scheduler", k), f"scheduler.{k}")
    note(sum(s1["device_scheduler"]["degradations_by_group"].values())
         - sum(s0["device_scheduler"]["degradations_by_group"].values()),
         "admission degradations")
    for k in ("compile_pending_fragments", "breaker_degrades", "bg_failed"):
        note(delta(s0, s1, "device_compiler", k), f"compiler.{k}")
    for k in ("fences", "hangs"):
        note(delta(s0, s1, "device_supervisor", k), f"supervisor.{k}")
    if mesh and attempted and delta(s0, s1, "device_mpp", "fragments") <= 0:
        bad.append((attempted, "mpp_fragments did not advance: nothing "
                               "ran through the mesh"))
    return bad
