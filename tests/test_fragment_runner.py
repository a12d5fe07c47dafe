"""One turn around compile_fragment for every path that runs a join
fragment's program (``device_join.FragmentRunner``).

The whole input (``device_join_agg``), the probe page by page
(``_paged_join_agg``) and the mesh's indexed path (``mpp_exec``) plan,
key, build, learn and count through the one runner.  Over the same star
and two executions: every program the path registers in the pipeline
cache is one the runner's ``program`` acquired, under a key of the
runner's layout with the path's own parts; the store learns what the
path's loop learns, under the fragment's signature and nothing else; the
second execution compiles nothing, reruns nothing and learns the same
values again.
"""

import json

import numpy as np
import pytest

import tidb_tpu.executor.device_join as dj
from tidb_tpu.executor import device_exec
from tidb_tpu.testkit import TestKit

_N = 4000                          # fact rows: a 4,096-row bucket
_PAGE = 1024                       # four pages of them
_LIVE = 512                        # f.v < 512: the probe leaf's live rows

#: 80 groups: more than the first capacity (64 a key without statistics),
#: so the first execution reruns its turn once and learns the count
_SQL = ("select d.k, count(*), sum(f.v), sum(d.w) from f join d "
        "on f.k = d.k where f.v < 512 group by d.k order by d.k")


def _values(rows):
    return ",".join("(" + ",".join(str(v) for v in r) + ")" for r in rows)


@pytest.fixture(scope="module")
def tk():
    """`f` (fact: id, k -> d.k, v) and `d` (k unique, every fifth key of
    f's missing)."""
    tk = TestKit()
    tk.must_exec("create table f (id bigint, k bigint, v bigint)")
    tk.must_exec("create table d (k bigint, w bigint, c bigint)")
    i = np.arange(_N)
    tk.must_exec("insert into f values " + _values(zip(i, (i * 7) % 100, i)))
    tk.must_exec("insert into d values " + _values(
        (k, k % 11, k % 4) for k in range(100) if k % 5 != 4))
    tk.must_exec("set tidb_device_dispatch_rows = 1")
    tk.must_exec("set tidb_result_cache = 'OFF'")
    tk.must_exec("set tidb_mpp_devices = 4")
    tk.must_exec("set tidb_executor_engine = 'host'")
    return tk


def _pipelines(tk):
    return json.loads(tk.must_query("DIAG STATUS").rows[0][0])[
        "device_pipelines"]


def _whole(monkeypatch):
    return "tpu"


def _paged(monkeypatch):
    monkeypatch.setattr(device_exec, "_SORTED_SCAN_MAX_ROWS", 1000)
    monkeypatch.setattr(dj, "_PROBE_PAGE_ROWS", _PAGE)
    return "tpu"


def _mesh(monkeypatch):
    return "tpu-mpp"


#: path -> (set-up returning the engine, what the store learns for the
#: fragment, the check of the path's own parts of the key)
_PATHS = {
    "whole": (_whole, {("live", -1), ("live", 0), "agg"},
              lambda key: key[-1] == ((-1, None), (0, None))),
    "paged": (_paged, {("live", -1), ("live", 0), "agg", "groups"},
              lambda key: key[-2:] == ("paged", ((-1, None), (0, None)))),
    "mesh": (_mesh, {"caps", "agg"},
             lambda key: key[0][0] == "mpp" and key[2] == ()),
}


class _Turns:
    """The keys FragmentRunner.program acquired, and the runners that
    planned them."""

    def __init__(self, monkeypatch):
        self.keys, self.sigs = [], set()
        program, acquire = dj.FragmentRunner.program, dj.acquire_pipeline
        inside = []

        def spy_program(run, *a, **kw):
            inside.append(run)
            try:
                return program(run, *a, **kw)
            finally:
                inside.pop()

        def spy_acquire(key, *a, **kw):
            if inside:
                self.keys.append(key)
                self.sigs.add(inside[-1].sig)
            return acquire(key, *a, **kw)
        monkeypatch.setattr(dj.FragmentRunner, "program", spy_program)
        monkeypatch.setattr(dj, "acquire_pipeline", spy_acquire)


def _run(tk, engine):
    tk.must_exec(f"set tidb_executor_engine = '{engine}'")
    try:
        return tk.must_query(_SQL).rows
    finally:
        tk.must_exec("set tidb_executor_engine = 'host'")


@pytest.mark.parametrize("path", [
    "whole", "paged", pytest.param("mesh", marks=pytest.mark.multichip)])
def test_every_path_turns_through_the_runner(tk, monkeypatch, path):
    setup, learns, own_parts = _PATHS[path]
    want = tk.must_query(_SQL).rows
    engine = setup(monkeypatch)
    dj._CAP_STORE.clear()
    device_exec._PIPE_CACHE.clear()
    turns = _Turns(monkeypatch)

    before = _pipelines(tk)
    assert _run(tk, engine) == want
    registered = set(device_exec._PIPE_CACHE)
    (sig,) = turns.sigs
    assert registered and registered == set(turns.keys)
    for key in registered:
        assert key[0] == sig and own_parts(key), key
    store = dict(dj._CAP_STORE)
    assert {s for s, _what in store} == {sig}
    assert {what for _s, what in store} == learns
    if ("live", -1) in learns:
        assert store[(sig, ("live", -1))] == _LIVE
    first = _pipelines(tk)
    assert first["compiles"] > before["compiles"]
    assert first["capacity_reruns"] > before["capacity_reruns"]

    # the second execution finds its program and learns the same values
    turns.keys.clear()
    assert _run(tk, engine) == want
    assert set(device_exec._PIPE_CACHE) == registered
    assert set(turns.keys) <= registered and turns.keys
    assert dict(dj._CAP_STORE) == store
    second = _pipelines(tk)
    assert [second[k] - first[k] for k in ("compiles", "capacity_reruns")
            ] == [0, 0]
