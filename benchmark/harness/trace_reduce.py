"""Reduce a ``jax.profiler`` trace (``.xplane.pb``) to device numbers.

Per device plane: the union of the intervals in which an operation ran
(busy) against the traced window (idle share), every operation's SELF
time grouped by HLO category, the collective operations' time and the
part of it during which nothing else ran on that chip.  From the trace
as a whole: the ten operations that took most device time and the
longest idle gaps, each gap labelled by what the profiler's own host
planes show under it (else ``host_unattributed``).

What counts as a device operation: on a TPU, the events of the
``XLA Ops`` line of each ``/device:TPU:<n>`` plane.  On XLA:CPU (the
rehearsal, and the committed CPU trace the tests read) there is no
device plane; the events that carry an ``hlo_op`` stat on the host
plane's PjRt client threads are used instead, as ONE device.
"""

import collections
import glob
import os
import re

#: HLO category by operation name, first match wins
CATEGORIES = (
    ("all-to-all", re.compile(r"all[-_]to[-_]all")),
    ("all-gather", re.compile(r"all[-_]gather")),
    ("all-reduce", re.compile(r"all[-_]reduce|reduce[-_]scatter")),
    ("collective-other", re.compile(r"collective[-_]permute|^send|^recv")),
    ("sort", re.compile(r"sort")),
    ("gather", re.compile(r"gather")),
    ("scatter", re.compile(r"scatter")),
    ("copy", re.compile(r"copy|transpose|bitcast|reshape")),
    ("fusion", re.compile(r"fusion")),
)
COLLECTIVES = ("all-to-all", "all-gather", "all-reduce", "collective-other")
TOP = 10


def find_xplane(trace_dir: str) -> "str | None":
    """The newest ``.xplane.pb`` under a ``start_trace`` directory."""
    found = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    return max(found, key=os.path.getmtime) if found else None


def category(name: str) -> str:
    low = name.lower()
    for cat, pat in CATEGORIES:
        if pat.search(low):
            return cat
    return "other"


def _op_name(name: str) -> str:
    """'%fusion.12 = f32[...] fusion(...)' -> 'fusion.12'."""
    return name.split(" = ", 1)[0].lstrip("%").strip()


def union(intervals: list) -> list:
    """Merged, sorted [start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _length(merged: list) -> float:
    return sum(e - s for s, e in merged)


def _subtract(a: list, b: list) -> list:
    """Merged intervals of `a` not covered by merged `b`."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append([cur, b[k][0]])
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append([cur, e])
    return out


def self_times(events: list) -> list:
    """[(name, start, end, self_ns)] for (name, start, end) events of one
    line: an event's self time is its duration minus the part that
    events starting inside it cover (children, or an overlapping later
    op), so the self times of a line sum to its busy time."""
    out = []
    stack = []   # indices into out
    for name, s, e in sorted(events, key=lambda x: (x[1], -x[2])):
        while stack and out[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            p = out[stack[-1]]
            out[stack[-1]] = (p[0], p[1], p[2],
                              p[3] - (min(e, p[2]) - s))
        out.append((name, s, e, e - s))
        stack.append(len(out) - 1)
    return out


def _device_lines(profile) -> "tuple[dict, list]":
    """({device name: [(op name, start_ns, end_ns)]}, host events)."""
    devices = collections.OrderedDict()
    host = []
    cpu_ops = []
    for plane in profile.planes:
        is_dev = plane.name.startswith("/device:TPU:")
        for line in plane.lines:
            if is_dev:
                if line.name != "XLA Ops":
                    continue
                devices.setdefault(plane.name, []).extend(
                    (_op_name(ev.name), ev.start_ns,
                     ev.start_ns + ev.duration_ns) for ev in line.events)
            elif plane.name.startswith("/host:"):
                for ev in line.events:
                    if ev.duration_ns <= 0:
                        continue
                    item = (ev.name, ev.start_ns,
                            ev.start_ns + ev.duration_ns)
                    if any(k == "hlo_op" for k, _v in ev.stats):
                        cpu_ops.append(item)
                    else:
                        host.append(item)
    if not devices and cpu_ops:
        devices["/host:CPU (XLA:CPU ops)"] = cpu_ops
    return devices, host


def _label(gap, host_sorted) -> str:
    """The host event that covers most of `gap`, if one covers at least
    half of it."""
    gs, ge = gap
    best, best_ov = "host_unattributed", (ge - gs) / 2.0
    for name, s, e in host_sorted:
        if s >= ge:
            break
        ov = min(e, ge) - max(s, gs)
        if ov >= best_ov:
            best, best_ov = name, ov
    return best


def reduce_profile(profile, window_s: "float | None" = None) -> dict:
    """The numbers above from a ``jax.profiler.ProfileData``.  `window_s`
    is the traced window's length as the tracing process clocked it;
    without it, first event to last event."""
    devices, host = _device_lines(profile)
    if not devices:
        return {}
    host.sort(key=lambda x: x[1])
    first = min(s for evs in devices.values() for _n, s, _e in evs)
    last = max(e for evs in devices.values() for _n, _s, e in evs)
    window_ns = window_s * 1e9 if window_s else float(last - first)
    per_device = []
    by_cat = collections.Counter()
    by_op = collections.Counter()
    gaps = collections.Counter()
    coll_ns = exposed_ns = 0.0
    for name, evs in devices.items():
        busy = union([[s, e] for _n, s, e in evs])
        busy_ns = _length(busy)
        per_device.append({"name": name, "busy_s": busy_ns / 1e9,
                           "ops": len(evs)})
        coll, rest = [], []
        for op, s, e, self_ns in self_times(evs):
            cat = category(op)
            by_cat[cat] += self_ns
            by_op[op] += self_ns
            (coll if cat in COLLECTIVES else rest).append([s, e])
        coll = union(coll)
        coll_ns += _length(coll)
        exposed_ns += _length(_subtract(coll, union(rest)))
        inner = [[a[1], b[0]] for a, b in zip(busy, busy[1:])]
        for gap in sorted(inner, key=lambda g: g[0] - g[1])[:200]:
            gaps[_label(gap, host)] += gap[1] - gap[0]
    n = len(per_device)
    busy_s = sum(d["busy_s"] for d in per_device) / n
    return {
        "window_s": window_ns / 1e9,
        "busy_s": busy_s,
        "devices": per_device,
        "category_s": {c: v / 1e9 / n for c, v in by_cat.items()},
        "collective_s": coll_ns / 1e9 / n,
        "exposed_collective_s": exposed_ns / 1e9 / n,
        "device_ops": [[op, v / 1e9 / n]
                       for op, v in by_op.most_common(TOP)],
        "idle_gaps": [[what, v / 1e9 / n]
                      for what, v in gaps.most_common(TOP)],
    }


def describe(profile, limit: int = 6) -> list:
    """Plane / line names with a few events each: what to look at by
    hand before trusting the reduction on a new kind of device."""
    out = []
    for plane in profile.planes:
        for line in plane.lines:
            evs = list(line.events)
            out.append({
                "plane": plane.name, "line": line.name, "events": len(evs),
                "sample": [{"name": ev.name[:120],
                            "dur_ns": ev.duration_ns,
                            "stats": {str(k): str(v)[:80]
                                      for k, v in ev.stats}}
                           for ev in evs[:limit]]})
    return out


def reduce_file(path: str, window_s: "float | None" = None) -> dict:
    from jax.profiler import ProfileData
    return reduce_profile(ProfileData.from_file(path), window_s)


if __name__ == "__main__":
    # python -m benchmark.harness.trace_reduce <trace dir or .xplane.pb>
    import json
    import sys
    from jax.profiler import ProfileData
    target = sys.argv[1]
    if os.path.isdir(target):
        target = find_xplane(target)
    data = ProfileData.from_file(target)
    print(json.dumps({"file": target, "bytes": os.path.getsize(target),
                      "lines": describe(data),
                      "reduced": reduce_profile(data)}, indent=1))
