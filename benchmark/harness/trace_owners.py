"""Owners for what the device trace shows: a kernel name for every
operation's time, a layer of the program for every idle instant.

``trace_reduce`` reads the trace through ``jax.profiler.ProfileData``,
which shows an operation's HLO text and nothing of where the program
made it.  The program says that in two ways this file reads from the raw
``.xplane.pb`` (decoded here from the wire format; nothing but the
standard library):

* kernels: the traced bodies mark their stages with
  ``jax.named_scope("k_...")``; the scope is part of each HLO
  instruction's ``op_name``, which the trace carries as the ``tf_op``
  stat of the operation's event metadata and, whole, in the HLO module
  the profiler stores in the ``/host:metadata`` plane.  An operation's
  SELF time (``trace_reduce.self_times``) goes to the FIRST name of
  ``KERNELS`` in its ``op_name`` path, so a fusion goes to the scope of
  its root and nested scopes to the outermost.  An operation the
  compiler made without metadata (a ``reduce-window`` out of a cumsum, a
  relayout of a parameter) goes to the kernel of the nearest instruction
  that consumes its result, then of the nearest it consumes, then of the
  instruction that calls its computation; what still has no name is
  ``unnamed``.
* idle owners: the program's spans (``session/tracing.py``) are
  ``TraceAnnotation`` events on the host plane, on the same clock as the
  device lines.  Every instant at which a chip runs nothing goes to the
  innermost span open on any host thread at that instant (the latest
  started), through ``SPAN_OWNER``; with no span open it is
  ``outside_statement``.  Instants are taken from the first to the last
  thing the trace shows of the program (operation or span); the rest of
  the window the tracing process clocked (the harness waiting for the
  worker's hook at either end) is ``outside_statement`` too, so the
  owners sum to window minus busy.

A reader gets ``obs`` only, and ``obs.xplane`` is ``trace_reduce``'s
summary without the file's path: ``of(obs)`` finds the newest
``.xplane.pb`` under the run directories, accepts it only if its own busy
time is ``obs.xplane["busy_s"]`` to 0.1%, and parses once per run.  With
no trace, another trace, or a program that names nothing (the parent of
the PR that added the names), the readers return None.
"""

import bisect
import collections
import heapq
import os
import re
import struct
import sys

from . import trace_reduce

#: the program's kernel vocabulary (tidb_tpu/ops/device.py KERNEL_SCOPES;
#: a test holds the two together)
KERNELS = ("k_filter", "k_agg_sort", "k_agg_segment", "k_agg_gather",
           "k_join_build", "k_join_probe", "k_topk", "k_exchange")
UNNAMED = "unnamed"

#: program span -> who owns a device-idle instant under it
SPAN_OWNER = {
    "statement": "session",
    "session.plan_query": "session",
    "executor.build": "session",
    "device.dispatch": "dispatch",
    "scheduler.acquire": "dispatch",
    "compile.obtain": "dispatch",
    "supervisor.call": "device_call",
    "mpp.fragment": "device_call",
    "executor.run": "fetch_assemble",
    "upload.h2d": "fetch_assemble",
    "fetch.d2h": "fetch_assemble",
    "host.assemble": "fetch_assemble",
}
OUTSIDE = "outside_statement"
OWNERS = (OUTSIDE, "session", "dispatch", "device_call", "fetch_assemble")
TOP = 12


# -- protobuf wire format -----------------------------------------------------

def _varint(buf, i):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf, i=0, end=None):
    """(field number, wire type, value) of one message: ints for varint
    and fixed fields, (start, end) offsets into `buf` for length-delimited
    ones (a sub-message is decoded by another call over that range)."""
    end = len(buf) if end is None else end
    while i < end:
        key, i = _varint(buf, i)
        wt = key & 7
        if wt == 0:
            v, i = _varint(buf, i)
        elif wt == 2:
            n, i = _varint(buf, i)
            v = (i, i + n)
            i += n
        elif wt == 1:
            v = struct.unpack_from("<Q", buf, i)[0]
            i += 8
        elif wt == 5:
            v = struct.unpack_from("<I", buf, i)[0]
            i += 4
        else:
            raise ValueError(f"wire type {wt} at byte {i}")
        yield key >> 3, wt, v


def _text(buf, span):
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _signed(v):
    return v - (1 << 64) if v >= 1 << 63 else v


# -- xplane.proto, as far as it is needed ------------------------------------

class _Plane:
    """One XPlane: name, {line name: [(metadata id, start_ps, end_ps)]},
    {metadata id: (name, {stat name: value})}."""

    def __init__(self, buf, span):
        self.name = ""
        lines, emeta, smeta = [], [], {}
        for f, _wt, v in _fields(buf, *span):
            if f == 2:
                self.name = _text(buf, v)
            elif f == 3:
                lines.append(v)
            elif f == 4:
                emeta.append(v)
            elif f == 5:
                sid, sname = 0, ""
                for f2, _w, v2 in _fields(buf, *v):      # map entry
                    if f2 == 2:
                        for f3, _w3, v3 in _fields(buf, *v2):
                            if f3 == 1:
                                sid = v3
                            elif f3 == 2:
                                sname = _text(buf, v3)
                smeta[sid] = sname
        self._buf, self._lines, self._smeta = buf, lines, smeta
        self.meta = {}
        for span_ in emeta:
            for f2, _w, v2 in _fields(buf, *span_):
                if f2 == 2:
                    mid, name, stats = self._event_meta(v2)
                    self.meta[mid] = (name, stats)

    def _stat(self, span):
        """(stat name, value): strings as text, bytes as (start, end),
        a ref as the text it refers to."""
        name, val = "", None
        for f, _wt, v in _fields(self._buf, *span):
            if f == 1:
                name = self._smeta.get(v, "")
            elif f in (3, 4):
                val = v
            elif f == 5:
                val = _text(self._buf, v)
            elif f == 6:
                val = v
            elif f == 7:
                val = self._smeta.get(v, "")
        return name, val

    def _event_meta(self, span):
        mid, name, stats = 0, "", {}
        for f, _wt, v in _fields(self._buf, *span):
            if f == 1:
                mid = v
            elif f == 2:
                name = _text(self._buf, v)
            elif f == 5:
                k, val = self._stat(v)
                stats[k] = val
        return mid, name, stats

    def lines(self, want_stats=()):
        """(line name, [(metadata id, start_ps, end_ps, {stat: value})])
        per line; only the stats named in `want_stats` are decoded."""
        for span in self._lines:
            name, t0_ns, events = "", 0, []
            for f, _wt, v in _fields(self._buf, *span):
                if f == 2:
                    name = _text(self._buf, v)
                elif f == 3:
                    t0_ns = _signed(v)
                elif f == 4:
                    events.append(v)
            out = []
            for ev in events:
                mid = off = dur = 0
                stats = {}
                for f, _wt, v in _fields(self._buf, *ev):
                    if f == 1:
                        mid = v
                    elif f == 2:
                        off = v
                    elif f == 3:
                        dur = v
                    elif f == 4 and want_stats:
                        k, val = self._stat(v)
                        if k in want_stats:
                            stats[k] = val
                start = t0_ns * 1000 + off
                out.append((mid, start, start + dur, stats))
            yield name, out


def _planes(buf):
    return [_Plane(buf, v) for f, _wt, v in _fields(buf) if f == 1]


# -- hlo.proto, as far as it is needed ---------------------------------------

class _Module:
    """One HloModuleProto: every instruction's op_name, operands, users
    and the instruction that calls its computation."""

    def __init__(self, buf, span):
        self.op_name = {}     # instruction id -> op_name
        self.by_name = {}     # instruction name -> id
        self.operands = {}
        self.users = collections.defaultdict(list)
        self.comp_of = {}     # instruction id -> computation id
        self.caller = {}      # computation id -> calling instruction id
        for f, _wt, v in _fields(buf, *span):
            if f != 3:
                continue
            cid, instrs = 0, []
            for f2, _w, v2 in _fields(buf, *v):
                if f2 == 2:
                    instrs.append(v2)
                elif f2 == 5:
                    cid = v2
            for ins in instrs:
                iid, name, op_name, ops, calls = 0, "", "", [], []
                for f3, wt3, v3 in _fields(buf, *ins):
                    if f3 == 1:
                        name = _text(buf, v3)
                    elif f3 == 7:
                        for f4, _w4, v4 in _fields(buf, *v3):
                            if f4 == 2:
                                op_name = _text(buf, v4)
                    elif f3 == 35:
                        iid = v3
                    elif f3 in (36, 38):
                        dst = ops if f3 == 36 else calls
                        if wt3 == 2:      # packed
                            i, end = v3
                            while i < end:
                                x, i = _varint(buf, i)
                                dst.append(x)
                        else:
                            dst.append(v3)
                self.op_name[iid] = op_name
                self.by_name[name] = iid
                self.operands[iid] = ops
                self.comp_of[iid] = cid
                for o in ops:
                    self.users[o].append(iid)
                for c in calls:
                    self.caller.setdefault(c, iid)

    def kernel(self, name, names):
        """The kernel of the instruction called `name` (see the module's
        docstring for the order), or None."""
        iid = self.by_name.get(name)
        callers = set()
        while iid is not None and iid not in callers:
            for edges in (self.users, self.operands):
                queue, visited = collections.deque([iid]), {iid}
                while queue:
                    cur = queue.popleft()
                    k = kernel_of(self.op_name.get(cur, ""), names)
                    if k is not None:
                        return k
                    for nxt in edges.get(cur, ()):
                        if nxt not in visited:
                            visited.add(nxt)
                            queue.append(nxt)
            callers.add(iid)
            iid = self.caller.get(self.comp_of[iid])
        return None


def _modules(planes, buf):
    """{program id: _Module, module name: [_Module]} from the
    ``/host:metadata`` plane, whose event metadata are called
    ``<module name>(<program id>)`` and hold the HloProto in a bytes
    stat."""
    out = {}
    for plane in planes:
        if plane.name != "/host:metadata":
            continue
        for name, stats in plane.meta.values():
            m = re.fullmatch(r"(.*)\((\d+)\)", name)
            blob = next((v for v in stats.values()
                         if isinstance(v, tuple)), None)
            if not m or blob is None:
                continue
            for f, _wt, v in _fields(buf, *blob):
                if f == 1:       # HloProto.hlo_module
                    mod = _Module(buf, v)
                    out[int(m.group(2))] = mod
                    out.setdefault(m.group(1), []).append(mod)
    return out


def kernel_of(op_name: str, names=KERNELS) -> "str | None":
    """The first of `names` among the parts of an op_name path
    (``jit(pipeline_ks1)/k_agg_sort/jit(argsort)/sort``)."""
    for part in op_name.split(":", 1)[0].split("/"):
        if part in names:
            return part
    return None


# -- the reduction ------------------------------------------------------------

def _instr(event_name: str) -> str:
    """'%fusion.12 = f32[...] fusion(...)' -> 'fusion.12'."""
    return event_name.split(" = ", 1)[0].lstrip("%").strip()


def _device_ops(planes, modules, names):
    """{device: [((kernel or UNNAMED, op), start_ps, end_ps)]}: the
    ``XLA Ops`` line of every TPU plane, else (XLA:CPU) the host events
    that carry an ``hlo_op`` stat, as one device."""
    cache = {}

    def name_it(program, op, tf_op, module=None):
        key = (program, module, op)
        if key not in cache:
            k = kernel_of(tf_op or "", names)
            if k is None:
                # the operation's module by its program id; an executable
                # loaded from the compile cache can run under another id
                # than the trace files its module under (XLA:CPU), so
                # then by the module's name, if every module of that
                # name gives the same answer
                found = {m.kernel(op, names) for m in
                         ([modules[program]] if program in modules
                          else modules.get(module, ()))}
                k = found.pop() if len(found) == 1 else None
            cache[key] = k or UNNAMED
        return cache[key]

    devices = collections.OrderedDict()
    for plane in planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        lines = dict(plane.lines())
        # the program an operation ran in, by name: the "XLA Modules"
        # event ("<module name>(<program id>)") around it
        runs = sorted((s, e, plane.meta.get(mid, ("",))[0].rsplit("(", 1)[0])
                      for mid, s, e, _st in lines.get("XLA Modules", ()))
        starts = [r[0] for r in runs]
        ops = devices.setdefault(plane.name, [])
        for mid, s, e, _st in lines.get("XLA Ops", ()):
            name, stats = plane.meta.get(mid, ("", {}))
            op = _instr(name)
            i = bisect.bisect_right(starts, s) - 1
            module = runs[i][2] if i >= 0 and s < runs[i][1] else None
            ops.append(((name_it(stats.get("program_id"), op,
                                 stats.get("tf_op"), module), op), s, e))
    if devices:
        return devices
    cpu = []
    for plane in _host_planes(planes):
        for _line, events in plane.lines(("hlo_op", "program_id",
                                          "hlo_module")):
            for mid, s, e, st in events:
                if e > s and "hlo_op" in st:
                    op = plane.meta.get(mid, ("", {}))[0]
                    cpu.append(((name_it(st.get("program_id"), op, None,
                                         st.get("hlo_module")), op), s, e))
    return {"/host:CPU (XLA:CPU ops)": cpu} if cpu else {}


def _host_planes(planes):
    return [p for p in planes if p.name.startswith("/host:")
            and p.name != "/host:metadata"]


def _spans(planes):
    """[(start_ps, end_ps, owner)] of the program's spans on any host
    thread."""
    out = []
    for plane in _host_planes(planes):
        known = {mid: SPAN_OWNER[name]
                 for mid, (name, _st) in plane.meta.items()
                 if name in SPAN_OWNER}
        if not known:
            continue
        for _line, events in plane.lines():
            out += [(s, e, known[mid]) for mid, s, e, _st in events
                    if mid in known and e > s]
    return out


def owner_segments(spans, lo, hi):
    """[(start, end, owner)] covering [lo, hi): at every instant the
    owner of the latest-started span open then, OUTSIDE with none."""
    marks = sorted({lo, hi, *(t for s, e, _o in spans for t in (s, e)
                              if lo < t < hi)})
    by_start = sorted(spans)
    open_, out, j = [], [], 0       # heap of (-start, end, owner)
    for a, b in zip(marks, marks[1:]):
        while j < len(by_start) and by_start[j][0] <= a:
            s, e, o = by_start[j]
            heapq.heappush(open_, (-s, e, o))
            j += 1
        while open_ and open_[0][1] <= a:
            heapq.heappop(open_)
        # a span deeper in the heap may have ended: only the top matters,
        # and it is popped above as soon as it has
        owner = open_[0][2] if open_ else OUTSIDE
        if out and out[-1][2] == owner and out[-1][1] == a:
            out[-1][1] = b
        else:
            out.append([a, b, owner])
    return out


def _apportion(idle, segments):
    """{owner: length} of the merged `idle` intervals by `segments`."""
    out = collections.Counter()
    j = 0
    for s, e in idle:
        while j < len(segments) and segments[j][1] <= s:
            j += 1
        k = j
        while k < len(segments) and segments[k][0] < e:
            a, b, owner = segments[k]
            out[owner] += min(b, e) - max(a, s)
            k += 1
    return out


def reduce_xspace(buf, window_s: "float | None" = None,
                  names=KERNELS) -> dict:
    """Kernel and idle owners of one serialized XSpace.  Seconds, mean
    over the devices.  {} when no operation ran on a device."""
    buf = memoryview(buf)
    planes = _planes(buf)
    devices = _device_ops(planes, _modules(planes, buf), names)
    if not devices:
        return {}
    spans = _spans(planes)
    lo = min([s for ops in devices.values() for _ko, s, _e in ops]
             + [s for s, _e, _o in spans])
    hi = max([e for ops in devices.values() for _ko, _s, e in ops]
             + [e for _s, e, _o in spans])
    segments = owner_segments(spans, lo, hi)
    n = len(devices)
    kernel = collections.Counter()
    by_op = collections.Counter()
    idle = collections.Counter()
    busy_ps = 0
    for ops in devices.values():
        for ko, _s, _e, self_ps in trace_reduce.self_times(ops):
            kernel[ko[0]] += self_ps
            by_op[ko] += self_ps
        busy = trace_reduce.union([[s, e] for _ko, s, e in ops])
        busy_ps += sum(e - s for s, e in busy)
        idle.update(_apportion(
            trace_reduce._subtract([[lo, hi]], busy), segments))
    span_s = (hi - lo) / 1e12
    window = window_s if window_s else span_s
    idle_s = {o: idle[o] / 1e12 / n for o in OWNERS}
    # the window's ends, before the first and after the last thing the
    # program did: the harness and the worker's hook talking
    idle_s[OUTSIDE] += max(window - span_s, 0.0)
    return {
        "devices": n,
        "window_s": window,
        "busy_s": busy_ps / 1e12 / n,
        "kernel_s": {k: v / 1e12 / n for k, v in kernel.items()},
        "idle_s": idle_s,
        "named": any(k != UNNAMED for k in kernel),
        "spans": len(spans),
        "ops": [[k, op, v / 1e12 / n]
                for (k, op), v in by_op.most_common(TOP)],
    }


def reduce_file(path: str, window_s: "float | None" = None,
                names=KERNELS) -> dict:
    with open(path, "rb") as f:
        return reduce_xspace(f.read(), window_s, names)


# -- what the metric readers call ---------------------------------------------

_MEMO = []      # [(the xplane summary it was made for, the reduction)]


def of(obs) -> "dict | None":
    """The reduction of this run's trace, or None (module docstring)."""
    x = obs.xplane
    if not x:
        return None
    if _MEMO and _MEMO[0][0] is x:
        return _MEMO[0][1]
    out = None
    try:
        from .cell import CACHE_DIR
        path = trace_reduce.find_xplane(
            os.path.join(CACHE_DIR, "run", "*", "trace"))
        if path is not None:
            got = reduce_file(path, x.get("window_s"))
            if got and abs(got["busy_s"] - x["busy_s"]) \
                    <= 1e-3 * x["busy_s"]:
                got["requests"] = len(x.get("requests") or ())
                out = got
    except Exception as e:  # noqa: BLE001 -- a reader never fails a run
        # a trace this decoder cannot read is no trace: the metrics are
        # left out of the line, and the run's stderr says why
        print(f"benchmark: trace_owners: {type(e).__name__}: {e}",
              file=sys.stderr)
    _MEMO[:] = [(x, out)]
    return out


def kernel_ms(obs, kernel: str) -> "float | None":
    """Self time of `kernel` per traced request, ms, mean over the chips;
    None when the program names no kernel at all."""
    got = of(obs)
    if not got or not got["named"] or not got["requests"]:
        return None
    return 1e3 * got["kernel_s"].get(kernel, 0.0) / got["requests"]


def unnamed_share(obs) -> "float | None":
    got = of(obs)
    if not got or not got["named"] or not got["busy_s"]:
        return None
    return 100.0 * got["kernel_s"].get(UNNAMED, 0.0) / got["busy_s"]


def idle_ms(obs, owner: str) -> "float | None":
    """Device idle time under `owner` per traced request, ms, mean over
    the chips; None when the program's spans are not in the trace."""
    got = of(obs)
    if not got or not got["spans"] or not got["requests"]:
        return None
    return 1e3 * got["idle_s"][owner] / got["requests"]


if __name__ == "__main__":
    # python -m benchmark.harness.trace_owners <trace dir or .xplane.pb[.gz]>
    import gzip
    import json
    target = sys.argv[1]
    if os.path.isdir(target):
        target = trace_reduce.find_xplane(target)
    opener = gzip.open if target.endswith(".gz") else open
    with opener(target, "rb") as fh:
        print(json.dumps({"file": target,
                          "reduced": reduce_xspace(fh.read())}, indent=1))
