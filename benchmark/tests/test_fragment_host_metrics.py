"""The six readers PR 35 brought for the host's work inside a fragment
(``join.index_build_ms``, ``join.index_builds_per_query``,
``fetch.device_wait_ms``, ``fetch.copy_ms``, ``fragment.reruns_per_query``,
``trace.spans_dropped``): their entries in ``BENCHMARK.json`` (looked up BY
NAME: a later PR appends), what each reads on a made observation, what
each finds in a program without the span or counter (the committed short
trace of the parent's shape, a status body without the keys), and what the
rehearsed join cell prints of them."""

import gzip
import json
import os
import subprocess
import sys

import pytest

from benchmark.harness import observe, trace_reduce
from benchmark.harness.observe import Request
from benchmark.harness.resolve import BENCH_DIR, ROOT, Cell, load_module

JOIN_CELLS = ["tpch-sf1.q3q5", "tpch-sf1-mpp4.q3", "ssb-sf10.flights",
              "tpch-sf1.q9q18"]
#: name -> (unit, source, layer, workloads or None for every cell)
ENTRIES = {
    "join.index_build_ms": ("ms", "program_span", "dispatch stack",
                            JOIN_CELLS),
    "join.index_builds_per_query": ("count", "program_counter",
                                    "dispatch stack", JOIN_CELLS),
    "fetch.device_wait_ms": ("ms", "program_span", "fetch + host assembly",
                             None),
    "fetch.copy_ms": ("ms", "program_span", "fetch + host assembly", None),
    "fragment.reruns_per_query": ("count", "program_counter",
                                  "dispatch stack", None),
    "trace.spans_dropped": ("count", "program_counter", "wire + session",
                            None),
}
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def _reader(name):
    return load_module(os.path.join(BENCH_DIR, "layer_metrics", name + ".py"),
                       "per_layer metric")


def _spec():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


# -- the entries --------------------------------------------------------------

@pytest.mark.parametrize("name", sorted(ENTRIES))
def test_the_entry_and_the_cells_that_report_it(name):
    unit, source, layer, cells = ENTRIES[name]
    spec = _spec()
    (entry,) = [m for m in spec["per_layer"] if m["name"] == name]
    want = {"name": name, "unit": unit, "better": "lower", "source": source,
            "layer": layer, "moves": "query_geomean_s"}
    if cells is not None:
        want["workloads"] = cells
    assert entry == want
    assert layer in {m["layer"] for m in spec["per_layer"]
                     if m["name"] not in ENTRIES}
    for w in spec["workloads"]:
        resolved = {m["name"]: mod for m, mod in Cell(w["name"]).per_layer}
        assert (name in resolved) == (cells is None or w["name"] in cells)
        if name in resolved:
            assert callable(resolved[name].read)


def test_they_are_appended_and_nothing_else_moved():
    spec = _spec()
    assert [m["name"] for m in spec["per_layer"][54:60]] == list(ENTRIES)
    assert len({m["name"] for m in spec["per_layer"]}) == \
        len(spec["per_layer"])


# -- the readers, on made observations ----------------------------------------

def _span(name, dur=None, tags=None, children=()):
    return {"name": name, "duration_s": dur, "tags": tags or {},
            "children": list(children)}


def _fetch(dur, wait=None, copy=None):
    kids = [] if wait is None else [
        _span("device.wait", wait),
        _span("fetch.copy", copy, {"arrays": 25, "bytes": 1000})]
    return _span("fetch.d2h", dur, {"bytes": 1000}, kids)


def _request(template, builds=(), fetches=(), split=True):
    """A join request's tree: `builds` under ``supervisor.call``'s self
    part, `fetches` as (wait, copy) pairs, 1 ms of slicing each."""
    call = _span("supervisor.call", 1.0, children=[
        *(_span("join.index_build", b, {"layout": "dense"}) for b in builds),
        *(_fetch(w + c + 0.001, *((w, c) if split else ()))
          for w, c in fetches)])
    return Request(template, 1.0, True, trace={"root": _span(
        "statement", 1.1, children=[_span("device.dispatch", 1.05,
                                          children=[call])])})


def _observation(requests, pipes0, pipes1, tracing0=None, tracing1=None,
                 xplane=None):
    return observe.Observation(
        requests=requests, setup={},
        status0={"device_pipelines": pipes0,
                 "device_tracing": tracing0 or {"spans_dropped": 0}},
        status1={"device_pipelines": pipes1,
                 "device_tracing": tracing1 or {"spans_dropped": 0}},
        templates={"q3": None, "q5": None}, rows={}, device={},
        hbm_bytes=None, peaks=None, xplane=xplane)


def _q3q5_window():
    """Q3, Q5, Q3, Q5, Q3: every request rebuilds `orders`' filtered table
    (the other's tag holds the column), Q5 `customer`'s too."""
    reqs = [
        _request("q3", [0.050], [(0.600, 0.004), (0.0, 0.002)]),
        _request("q5", [0.052, 0.010], [(0.700, 0.003), (0.0, 0.002)]),
        _request("q3", [0.050], [(0.610, 0.004), (0.0, 0.002)]),
        _request("q5", [0.052, 0.010], [(0.710, 0.003), (0.0, 0.002)]),
        _request("q3", [0.050], [(0.620, 0.004), (0.0, 0.002)])]
    return _observation(
        reqs, {"join_index_builds": 14, "capacity_reruns": 3},
        {"join_index_builds": 21, "capacity_reruns": 3},
        {"spans_dropped": 2}, {"spans_dropped": 2})


def test_the_readers_on_a_join_window():
    obs = _q3q5_window()
    # the MEAN a request: (3 x 50 + 2 x 62) / 5
    assert _reader("join.index_build_ms").read(obs) == pytest.approx(54.8)
    assert _reader("join.index_builds_per_query").read(obs) == \
        pytest.approx(7 / 5)
    # medians of a request's totals
    assert _reader("fetch.device_wait_ms").read(obs) == pytest.approx(620.0)
    assert _reader("fetch.copy_ms").read(obs) == pytest.approx(6.0)
    assert _reader("fragment.reruns_per_query").read(obs) == 0.0
    assert _reader("trace.spans_dropped").read(obs) == 0
    # the two parts and the slices' dispatch are the whole fetch
    d2h = _reader("fetch.d2h_ms").read(obs)
    assert d2h == pytest.approx(620.0 + 6.0 + 2.0)


def test_a_rebuild_every_other_request_is_half_of_itself():
    """Two templates, one of which finds its index cached: a median would
    read 0 or all of it; the mean reads what a request pays."""
    reqs = [_request("q9", [], [(1.1, 0.001)]),
            _request("q18", [0.008], [(3.2, 0.030)]),
            _request("q9", [], [(1.1, 0.001)]),
            _request("q18", [0.008], [(3.2, 0.030)])]
    obs = _observation(reqs, {"join_index_builds": 5, "capacity_reruns": 8},
                       {"join_index_builds": 7, "capacity_reruns": 10},
                       {"spans_dropped": 0}, {"spans_dropped": 3})
    assert _reader("join.index_build_ms").read(obs) == pytest.approx(4.0)
    assert _reader("join.index_builds_per_query").read(obs) == 0.5
    assert _reader("fragment.reruns_per_query").read(obs) == 0.5
    assert _reader("trace.spans_dropped").read(obs) == 3


def test_a_window_that_builds_nothing_reads_zero_not_nothing():
    """The mesh cell: Q3 only, one filter tag a column."""
    reqs = [_request("q3", [], [(0.19, 0.02)]) for _ in range(4)]
    obs = _observation(reqs, {"join_index_builds": 2, "capacity_reruns": 1},
                       {"join_index_builds": 2, "capacity_reruns": 1})
    assert _reader("join.index_build_ms").read(obs) == 0.0
    assert _reader("join.index_builds_per_query").read(obs) == 0.0
    assert _reader("fetch.copy_ms").read(obs) == pytest.approx(20.0)


# -- a program without the spans and counters ---------------------------------

@pytest.fixture(scope="module")
def short_trace(tmp_path_factory):
    """trace_reduce's summary of the committed short trace (`tpch-sf1.q6`
    on XLA:CPU, PR 24): a program of before this PR."""
    path = tmp_path_factory.mktemp("short") / "q6.xplane.pb"
    with gzip.open(os.path.join(DATA, "cpu_q6_named.xplane.pb.gz")) as src:
        path.write_bytes(src.read())
    with open(os.path.join(DATA, "cpu_q6_named.window_s.txt")) as f:
        return trace_reduce.reduce_file(str(path), float(f.read()))


@pytest.mark.parametrize("name", sorted(ENTRIES))
def test_the_parents_line_lacks_it(name, short_trace):
    """The parent under this PR's benchmark files: its trees hold
    ``fetch.d2h`` whole and no ``join.index_build``, its status body
    neither counter (``device_tracing.spans_dropped`` it has)."""
    assert short_trace["busy_s"] > 0
    reqs = [_request("q3", [], [(0.6, 0.004)], split=False)
            for _ in range(3)]
    obs = _observation(reqs, {"compiles": 5, "join_direct": 4},
                       {"compiles": 5, "join_direct": 9},
                       xplane=short_trace)
    assert _reader(name).read(obs) is None
    # the accepted reader beside them still reads its span
    assert _reader("fetch.d2h_ms").read(obs) == pytest.approx(605.0)


@pytest.mark.parametrize("name", sorted(ENTRIES))
def test_an_untraced_window_gives_the_span_readers_nothing(name):
    obs = _observation([Request("q3", 1.0, True)],
                       {"join_index_builds": 0, "capacity_reruns": 0},
                       {"join_index_builds": 2, "capacity_reruns": 1})
    got = _reader(name).read(obs)
    if ENTRIES[name][1] == "program_span":
        assert got is None
    else:
        assert got == {"join.index_builds_per_query": 2.0,
                       "fragment.reruns_per_query": 1.0,
                       "trace.spans_dropped": 0}[name]


# -- the cell, rehearsed ------------------------------------------------------

def test_the_rehearsed_join_cell_prints_all_six():
    """SF0.01 on XLA:CPU: Q3 and Q5 alternating rebuild `orders`' filtered
    index for each other, no fragment runs twice once warm, no span is
    dropped, and the two parts of the fetch stay inside it."""
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", "tpch-sf1.q3q5", "--seed", "3500200101",
         "--seconds", "4", "--trace", "1", "--rehearse"],
        capture_output=True, text=True, cwd=ROOT, timeout=900)
    assert p.returncode == 0, p.stderr[-2000:]
    lines = [json.loads(ln) for ln in p.stdout.strip().splitlines()]
    last = lines[-1]
    assert last["correct"] is True and last["failed"] == 0
    values = next(ln["values"] for ln in lines
                  if ln.get("metric") == "bench_rehearsal_values")
    for name in ENTRIES:
        assert values[name] is not None and name in last["metrics"], name
    assert values["join.index_builds_per_query"] >= 1
    assert values["join.index_build_ms"] > 0
    assert values["fragment.reruns_per_query"] == 0
    assert values["trace.spans_dropped"] == 0
    assert values["compile.window_compiles"] == 0
    assert 0 < values["fetch.device_wait_ms"] + values["fetch.copy_ms"] \
        <= values["fetch.d2h_ms"]
