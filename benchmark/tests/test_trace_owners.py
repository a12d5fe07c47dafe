"""Kernel and idle owners from the raw trace (``harness/trace_owners.py``):
on hand-built XSpaces (encoded here, field by field), on PR 22's recorded
chip trace, whose program names nothing, and on short traced windows of
``tpch-sf1.q6`` recorded from the program that does (PR 24: on XLA:CPU; the
chip's is still to be recorded, ``record_short_trace.py``)."""

import gzip
import json
import os
import subprocess
import sys
from types import SimpleNamespace as NS

import pytest

from benchmark.harness import trace_owners as to
from benchmark.harness import trace_reduce as tr
from benchmark.harness.resolve import ROOT

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
#: window_s of PR 22's recorded trace, as its run's ``trace.done`` said
Q6_PR22_WINDOW_S = 1.1597481940000307


# -- a protobuf encoder, as small as the decoder under test -------------------

def _vint(n):
    n &= (1 << 64) - 1
    out = bytearray()
    while True:
        b, n = n & 0x7F, n >> 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _f(num, value):
    """One field: int -> varint, str/bytes -> length-delimited."""
    if isinstance(value, int):
        return _vint(num << 3) + _vint(value)
    if isinstance(value, str):
        value = value.encode()
    return _vint(num << 3 | 2) + _vint(len(value)) + value


def _stat(sid, val):
    """str -> str_value, bytes -> bytes_value, int -> uint64_value."""
    num = 3 if isinstance(val, int) else 6 if isinstance(val, bytes) else 5
    return _f(1, sid) + _f(num, val)


def _plane(name, lines, event_names, stat_names=(), meta_stats=None):
    """lines: {line name: [(metadata id, offset_ps, duration_ps[, [(stat
    id, value)]])]}; event_names: {metadata id: name}; meta_stats:
    {metadata id: [(stat id, value)]}."""
    out = _f(2, name)
    for lname, events in lines.items():
        body = _f(2, lname) + _f(3, 0)
        for mid, off, dur, *stats in events:
            ev = _f(1, mid) + _f(2, off) + _f(3, dur)
            for sid, val in (stats[0] if stats else ()):
                ev += _f(4, _stat(sid, val))
            body += _f(4, ev)
        out += _f(3, body)
    for mid, ename in event_names.items():
        meta = _f(1, mid) + _f(2, ename)
        for sid, val in (meta_stats or {}).get(mid, ()):
            meta += _f(5, _stat(sid, val))
        out += _f(4, _f(1, mid) + _f(2, meta))
    for sid, sname in enumerate(stat_names, 1):
        out += _f(5, _f(1, sid) + _f(2, _f(1, sid) + _f(2, sname)))
    return _f(1, out)


def _hlo(computations):
    """computations: {comp id: [(instr id, name, op_name, operand ids,
    called computation ids)]} -> serialized HloProto."""
    mod = b""
    for cid, instrs in computations.items():
        comp = _f(5, cid)
        for iid, name, op_name, operands, calls in instrs:
            ins = _f(1, name) + _f(35, iid)
            if op_name:
                ins += _f(7, _f(2, op_name))
            if operands:
                ins += _f(36, b"".join(_vint(o) for o in operands))
            for c in calls:
                ins += _f(38, c)          # unpacked, as proto2 writers do
            comp += _f(2, ins)
        mod += _f(3, comp)
    return _f(1, mod)


PROGRAM = 77
STATS = ("tf_op", "program_id", "Hlo Proto")


def _device_plane(ops, tf_ops=None):
    """ops: [(name, offset_ps, duration_ps)]."""
    ids = {name: i for i, name in
           enumerate(dict.fromkeys(n for n, _o, _d in ops), 1)}
    stats = {i: [(2, PROGRAM)] + ([(1, (tf_ops or {})[n])]
                                  if n in (tf_ops or {}) else [])
             for n, i in ids.items()}
    return _plane("/device:TPU:0",
                  {"XLA Modules": [], "XLA Ops": [(ids[n], o, d)
                                                  for n, o, d in ops]},
                  {i: f"%{n} = f32[8]{{0}} fusion(...)"
                   for n, i in ids.items()}, STATS, stats)


def _host_plane(threads):
    """threads: {line name: [(span name, start_ps, end_ps)]}."""
    names = dict.fromkeys(n for evs in threads.values() for n, _s, _e in evs)
    ids = {n: i for i, n in enumerate(names, 1)}
    return _plane("/host:CPU",
                  {ln: [(ids[n], s, e - s) for n, s, e in evs]
                   for ln, evs in threads.items()},
                  {i: n for n, i in ids.items()})


def _metadata_plane(computations):
    return _plane("/host:metadata", {}, {1: f"jit_pipeline_ks1({PROGRAM})"},
                  STATS, {1: [(3, _hlo(computations))]})


# -- hand-built traces --------------------------------------------------------

def test_two_overlapping_spans_on_two_threads_go_to_the_innermost():
    ms = 10 ** 9
    space = _device_plane([("fusion.1", 10 * ms, 10 * ms),
                           ("fusion.2", 60 * ms, 10 * ms)]) + \
        _host_plane({
            "conn-1": [("statement", 5 * ms, 75 * ms),
                       ("executor.run", 8 * ms, 72 * ms),
                       ("device.dispatch", 9 * ms, 40 * ms)],
            # a supervisor worker: opened later than device.dispatch, so
            # while both are open the gap is the worker's
            "device-supervisor-1": [("supervisor.call", 25 * ms, 38 * ms),
                                    ("fetch.d2h", 30 * ms, 35 * ms)]})
    out = to.reduce_xspace(space, window_s=0.100)
    assert out["busy_s"] == pytest.approx(0.020)
    idle = {k: round(v * 1e3, 6) for k, v in out["idle_s"].items()}
    # the gap 20..60 ms: dispatch 20-25, supervisor.call 25-30, fetch
    # 30-35, supervisor.call 35-38, dispatch 38-40, executor.run 40-60
    # the ends: statement 5-8, executor.run 8-9, dispatch 9-10,
    #           executor.run 70-72, statement 72-75, and 30 ms of the
    #           100 ms window that the trace does not span
    assert idle == {"outside_statement": 30.0, "session": 6.0,
                    "dispatch": 8.0, "device_call": 8.0,
                    "fetch_assemble": 28.0}
    assert sum(out["idle_s"].values()) == pytest.approx(0.100 - 0.020)
    assert out["spans"] == 5 and not out["named"]


def test_kernel_names_from_tf_op_and_through_the_hlo_graph():
    us = 10 ** 6
    ops = [("fusion.1", 0, 10 * us),           # own scope (a fusion: root's)
           ("sort.2", 10 * us, 20 * us),       # nested scopes: outermost
           ("reduce-window.3", 30 * us, 40 * us),   # none: its user's
           ("custom-call.4", 70 * us, 5 * us),      # a parameter's relayout
           ("copy.5", 75 * us, 5 * us),        # none, no users: operand's
           ("while.6", 80 * us, 20 * us),      # a loop and ...
           ("add.7", 85 * us, 10 * us),        # ... its body: the caller's
           ("iota.8", 100 * us, 1 * us)]       # nothing anywhere
    tf = {"fusion.1": "jit(pipeline_ks1)/k_agg_gather/jit(_take)/gather:",
          "sort.2": "jit(entry_ks1)/jit(shmap_body)/k_exchange/k_agg_sort/"
                    "jit(argsort)/sort:",
          "custom-call.4": "env[2][0]:",
          "while.6": "jit(pipeline_ks1)/k_agg_segment/jit(searchsorted)/"
                     "while:"}
    graph = {1: [
        (11, "fusion.1", tf["fusion.1"][:-1], [], []),
        (12, "sort.2", tf["sort.2"][:-1], [], []),
        (13, "reduce-window.3", "", [11], []),
        (14, "get-tuple-element.9", "", [13], []),
        (15, "fusion.10", "jit(pipeline_ks1)/k_agg_segment/sub", [14], []),
        (16, "custom-call.4", "env[2][0]", [], []),
        (17, "compare.11", "jit(pipeline_ks1)/k_filter/ge", [16], []),
        (18, "copy.5", "", [12], []),
        (19, "while.6", tf["while.6"][:-1], [], [2]),
        (21, "iota.8", "", [], [])],
        2: [(20, "add.7", "", [], [])]}
    space = _device_plane(ops, tf) + _metadata_plane(graph)
    out = to.reduce_xspace(space)
    got = {k: round(v * 1e6, 6) for k, v in out["kernel_s"].items()}
    assert got == {"k_agg_gather": 10.0, "k_exchange": 20.0 + 5.0,
                   "k_agg_segment": 40.0 + 10.0 + 10.0, "k_filter": 5.0,
                   "unnamed": 1.0}
    assert sum(out["kernel_s"].values()) == pytest.approx(out["busy_s"])
    assert out["named"] and out["spans"] == 0
    # without the HLO module only the operations' own tf_op names them
    bare = to.reduce_xspace(_device_plane(ops, tf))["kernel_s"]
    assert bare["unnamed"] * 1e6 == pytest.approx(40 + 5 + 5 + 10 + 1)


def test_xla_cpu_operations_find_their_module_by_name_when_ids_differ():
    """XLA:CPU (the rehearsal): operations are host events with an
    ``hlo_op`` stat.  An executable loaded from the compile cache runs
    under the program id it was compiled with while the trace files its
    module under a new one; the module's name still finds it."""
    us = 10 ** 6
    stats = ("hlo_op", "program_id", "hlo_module", "Hlo Proto")
    graph = {1: [(11, "sort.0", "jit(f_ks1)/k_agg_sort/jit(argsort)/sort",
                  [], []),
                 (12, "copy.1", "", [11], [])]}

    def space(event_program, filed_program):
        host = _plane(
            "/host:CPU",
            {"tf_XLAEigen/1": [
                (1, 0, 30 * us, [(1, "sort.0"), (2, event_program),
                                 (3, "jit_f_ks1")]),
                (2, 40 * us, 10 * us, [(1, "copy.1"), (2, event_program),
                                       (3, "jit_f_ks1")])],
             "python": [(3, 0, 60 * us)]},
            {1: "sort.0", 2: "copy.1", 3: "statement"}, stats)
        meta = _plane("/host:metadata", {},
                      {1: f"jit_f_ks1({filed_program})"}, stats,
                      {1: [(4, _hlo(graph))]})
        return host + meta
    for event_program, filed_program in ((7, 7), (7, 8)):
        out = to.reduce_xspace(space(event_program, filed_program))
        assert out["kernel_s"] == {"k_agg_sort": pytest.approx(40e-6)}
        assert out["idle_s"]["session"] == pytest.approx(20e-6)


def test_kernel_of_reads_the_first_vocabulary_name_of_a_path():
    assert to.kernel_of("jit(pipeline_ks1)/k_agg_sort/jit(argsort)/sort:"
                        ) == "k_agg_sort"
    assert to.kernel_of("jit(f)/jit(shmap_body)/k_exchange/k_agg_sort/x") \
        == "k_exchange"
    assert to.kernel_of("jit(pipeline)/jit(argsort)/sort:") is None
    assert to.kernel_of("jit(f)/not_k_filter/mul") is None
    assert to.kernel_of("") is None


# -- the recorded chip traces -------------------------------------------------

def _recorded(name):
    with gzip.open(os.path.join(DATA, name)) as f:
        return f.read()


def test_pr22_trace_names_nothing_and_reads_the_same_busy_time(tmp_path):
    """PR 22's program had no scopes and no annotations: every operation
    is unnamed, every idle instant outside a statement, and the readers
    would return None.  Busy time is trace_reduce's."""
    data = _recorded("tpu_q6.xplane.pb.gz")
    out = to.reduce_xspace(data, Q6_PR22_WINDOW_S)
    path = tmp_path / "q6.xplane.pb"
    path.write_bytes(data)
    ref = tr.reduce_file(str(path), Q6_PR22_WINDOW_S)
    assert out["busy_s"] == pytest.approx(ref["busy_s"], rel=1e-6)
    assert out["kernel_s"] == {"unnamed": pytest.approx(ref["busy_s"],
                                                        rel=1e-6)}
    assert not out["named"] and out["spans"] == 0
    # (this file adds picoseconds, ProfileData hands out float ns)
    assert out["idle_s"]["outside_statement"] == pytest.approx(
        ref["window_s"] - ref["busy_s"], abs=2e-6)
    assert [op for _k, op, _s in out["ops"][:4]] == \
        [op for op, _s in ref["device_ops"][:4]]


def test_pr22_trace_tf_op_is_the_hlo_modules_op_name():
    """The two places the scope can be read from agree, operation by
    operation, and with JAX primitive paths standing in for a vocabulary
    the metadata-less reduce-window lands where its result is used."""
    buf = memoryview(_recorded("tpu_q6.xplane.pb.gz"))
    planes = to._planes(buf)
    module, = [m for key, m in to._modules(planes, buf).items()
               if isinstance(key, int)]        # also filed by its name
    dev, = [p for p in planes if p.name == "/device:TPU:0"]
    checked = 0
    for name, stats in dev.meta.values():
        iid = module.by_name.get(to._instr(name))
        if iid is not None and "tf_op" in stats:
            assert stats["tf_op"].rsplit(":", 1)[0] == module.op_name[iid]
            checked += 1
    assert checked > 50
    names = ("jit(argsort)", "jit(_take)", "gather", "concatenate", "sub")
    out = to.reduce_xspace(buf, Q6_PR22_WINDOW_S, names)
    by_op = {op: k for k, op, _s in out["ops"]}
    assert by_op["sort.18"] == "jit(argsort)"
    assert by_op["fusion.12"] == "jit(_take)"
    assert by_op["reduce-window.4"] == "concatenate"   # cumsum -> [0, c]
    assert sum(out["kernel_s"].values()) == pytest.approx(out["busy_s"])


#: recorded traces of the program WITH scopes and annotations, one short
#: traced window of `tpch-sf1.q6` each (tests/record_short_trace.py):
#: `cpu_q6_named` on XLA:CPU at SF0.01 (CPU sandbox, PR 24); `tpu_q6_named`
#: on a v5e chip at SF1, which PR 24 could not record (no chip was free):
#: its cases skip until the files are there
NAMED = ("cpu_q6_named", "tpu_q6_named")


@pytest.fixture(scope="module", params=NAMED)
def named(request, tmp_path_factory):
    """(which, trace_owners' reduction, trace_reduce's, path)."""
    gz = os.path.join(DATA, request.param + ".xplane.pb.gz")
    if not os.path.exists(gz):
        pytest.skip(f"{request.param}: not recorded yet")
    with open(os.path.join(DATA, request.param + ".window_s.txt")) as f:
        window_s = float(f.read())
    data = _recorded(request.param + ".xplane.pb.gz")
    path = tmp_path_factory.mktemp(request.param) / "q6.xplane.pb"
    path.write_bytes(data)
    return (request.param, to.reduce_xspace(data, window_s),
            tr.reduce_file(str(path), window_s), str(path))


def test_named_trace_kernels_sum_to_busy_time(named):
    which, out, ref, _path = named
    assert out["devices"] == 1 and out["named"]
    assert out["busy_s"] == pytest.approx(ref["busy_s"], rel=1e-6)
    assert sum(out["kernel_s"].values()) == pytest.approx(ref["busy_s"],
                                                          rel=1e-6)
    assert set(out["kernel_s"]) <= set(to.KERNELS) | {to.UNNAMED}
    assert out["kernel_s"].get(to.UNNAMED, 0.0) < 0.05 * out["busy_s"]
    by_op = {op: k for k, op, _s in out["ops"]}
    if which == "cpu_q6_named":
        # XLA:CPU aggregates by scatter, all of it one scope
        assert set(out["kernel_s"]) == {"k_agg_segment"}
    else:
        # the chip's sort + segment kernel, under PR 22's op names
        assert by_op["sort.18"] == "k_agg_sort"
        assert by_op["fusion.12"] == "k_agg_gather"
        assert by_op["reduce-window.4"] == "k_agg_segment"


def test_named_trace_idle_owners_sum_to_window_minus_busy(named):
    _which, out, ref, _path = named
    assert out["spans"] >= 5 * 9          # nine spans a statement
    assert set(out["idle_s"]) == set(to.OWNERS)
    assert sum(out["idle_s"].values()) == pytest.approx(
        ref["window_s"] - ref["busy_s"], abs=2e-6)
    # every layer of the request owns some of the device's idle time
    assert all(out["idle_s"][o] > 0 for o in to.OWNERS)


def test_named_trace_gaps_are_labelled_by_program_spans(named):
    """trace_reduce's own labeller (unchanged) now finds the program's
    spans among the host events."""
    _which, _out, ref, _path = named
    assert {what for what, _s in ref["idle_gaps"]} & set(to.SPAN_OWNER)


# -- what the readers get -----------------------------------------------------

def _obs(path, busy_s, window_s, requests=5):
    return NS(xplane={"busy_s": busy_s, "window_s": window_s,
                      "requests": [None] * requests})


def test_readers_take_the_runs_trace_only_if_its_busy_time_matches(
        named, tmp_path, monkeypatch):
    _which, out, ref, path = named
    run = tmp_path / "run" / "tpch-sf1.q6" / "trace" / "plugins" / \
        "profile" / "2026_09_28"
    run.mkdir(parents=True)
    os.link(path, run / "host.xplane.pb")
    from benchmark.harness import cell
    monkeypatch.setattr(cell, "CACHE_DIR", str(tmp_path))
    to._MEMO.clear()
    obs = _obs(path, ref["busy_s"], ref["window_s"])
    assert to.kernel_ms(obs, "k_agg_segment") == pytest.approx(
        1e3 * out["kernel_s"]["k_agg_segment"] / 5)
    assert to.unnamed_share(obs) < 5.0
    assert sum(to.idle_ms(obs, o) for o in to.OWNERS) == pytest.approx(
        1e3 * (ref["window_s"] - ref["busy_s"]) / 5, abs=1e-3)
    assert to.kernel_ms(obs, "k_exchange") == 0.0
    # parsed once per run: the file can go, the answers stay
    os.remove(run / "host.xplane.pb")
    assert to.kernel_ms(obs, "k_filter") is not None
    # another run's summary: the newest file is not this run's trace
    os.link(path, run / "host.xplane.pb")
    other = _obs(path, ref["busy_s"] * 1.01, ref["window_s"])
    assert to.kernel_ms(other, "k_agg_sort") is None
    assert to.idle_ms(other, "session") is None
    # no trace at all (--trace 0), or no file
    assert to.unnamed_share(NS(xplane=None)) is None
    os.remove(run / "host.xplane.pb")
    assert to.unnamed_share(_obs(path, ref["busy_s"], 1.0)) is None
    to._MEMO.clear()


def test_readers_return_none_for_a_program_that_names_nothing(
        tmp_path, monkeypatch):
    """The parent of PR 24 through the new readers: nothing, not zeros."""
    run = tmp_path / "run" / "x" / "trace" / "plugins" / "profile" / "1"
    run.mkdir(parents=True)
    (run / "host.xplane.pb").write_bytes(_recorded("tpu_q6.xplane.pb.gz"))
    from benchmark.harness import cell
    monkeypatch.setattr(cell, "CACHE_DIR", str(tmp_path))
    to._MEMO.clear()
    ref = tr.reduce_file(str(run / "host.xplane.pb"), Q6_PR22_WINDOW_S)
    obs = _obs(None, ref["busy_s"], ref["window_s"])
    assert to.of(obs) is not None
    assert to.kernel_ms(obs, "k_agg_sort") is None
    assert to.unnamed_share(obs) is None
    assert to.idle_ms(obs, "session") is None
    to._MEMO.clear()


def test_vocabulary_is_the_programs():
    from tidb_tpu.ops.device import KERNEL_SCOPES
    assert to.KERNELS == KERNEL_SCOPES


# -- every cell, end to end, off the chip -------------------------------------

def _cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return [w["name"] for w in json.load(f)["workloads"]]


@pytest.mark.parametrize("trace", (0, 1))
@pytest.mark.parametrize("cell_name", _cells())
def test_every_cell_rehearses_with_the_new_readers(cell_name, trace):
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", cell_name, "--seed", "4100200300", "--seconds", "3",
         "--trace", str(trace), "--rehearse"],
        capture_output=True, text=True, cwd=ROOT, timeout=1200)
    assert p.returncode == 0, p.stderr[-2000:]
    lines = [json.loads(ln) for ln in p.stdout.strip().splitlines()]
    last = lines[-1]
    assert last["correct"] is True and last["failed"] == 0
    if not trace:
        return
    values = next(ln["values"] for ln in lines
                  if ln.get("metric") == "bench_rehearsal_values")
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        wanted = [m["name"] for m in json.load(f)["per_layer"]
                  if cell_name in m.get("workloads", [cell_name])
                  and m["name"].split(".")[0] in (
                      "kernel", "idle", "upload", "fetch", "assemble")
                  or m["name"] == "wire.parse_ms"]
    assert wanted and set(wanted) <= set(values) == set(last["metrics"])
    idle = sum(v for k, v in values.items() if k.startswith("idle."))
    assert idle > 0 and values["kernel.unnamed_share"] < 100.0
    # (kernels summing to busy time is held on the recorded chip trace:
    # XLA:CPU runs operations on several threads at once, and self times
    # of one merged line only approximate their union)
