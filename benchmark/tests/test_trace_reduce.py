"""The reduction from a profiler trace to device numbers: on a small
recorded trace (XLA:CPU: five executions of a jitted sort, a 50 ms sleep
after the third) and on hand-built planes that look like a TPU's."""

import os
from types import SimpleNamespace as NS

import pytest

from benchmark.harness import trace_reduce as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def test_recorded_cpu_trace():
    out = tr.reduce_file(os.path.join(DATA, "cpu_sort.xplane.pb"))
    assert len(out["devices"]) == 1
    assert 0 < out["busy_s"] < out["window_s"]
    assert out["device_ops"][0][0].startswith("sort")
    assert out["category_s"]["sort"] / out["busy_s"] > 0.5
    assert out["collective_s"] == 0
    # the sleep is the longest gap, and no host event covers half of it
    assert out["idle_gaps"][0][0] == "host_unattributed"
    assert 0.04 < out["idle_gaps"][0][1] < 0.08
    assert sum(out["category_s"].values()) == pytest.approx(out["busy_s"],
                                                            rel=0.05)


def test_recorded_tpu_trace(tmp_path):
    """One second of the `tpch-sf1.q6` cell on a v5e chip (my chip run,
    PR 22): five executions of Q6, the device busy nearly all the time."""
    import gzip
    import shutil
    path = tmp_path / "tpu_q6.xplane.pb"
    with gzip.open(os.path.join(DATA, "tpu_q6.xplane.pb.gz")) as src, \
            open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    out = tr.reduce_file(str(path), window_s=1.1597481940000307)
    assert [d["name"] for d in out["devices"]] == ["/device:TPU:0"]
    assert out["devices"][0]["ops"] == 1495
    assert out["busy_s"] == pytest.approx(1.090206628)
    assert out["window_s"] == pytest.approx(1.159748194)
    assert out["device_ops"][0][0] == "fusion.2"
    assert out["device_ops"][3] == ["sort.18", pytest.approx(0.120324382)]
    assert sum(out["category_s"].values()) == pytest.approx(out["busy_s"])
    assert out["category_s"]["fusion"] / out["busy_s"] == \
        pytest.approx(0.7889, abs=1e-3)
    assert out["collective_s"] == 0
    assert out["idle_gaps"][0][0] == "host_unattributed"


def _ev(name, start, dur):
    return NS(name=name, start_ns=start, duration_ns=dur, stats=[])


def _profile():
    dev0 = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", events=[_ev("jit_run", 0, 1000)]),
        NS(name="XLA Ops", events=[
            _ev("%while.1 = while(...)", 0, 400),
            _ev("sort.3", 10, 200), _ev("fusion.7", 220, 100),
            _ev("all-to-all.2", 500, 100), _ev("copy.1", 550, 150),
            _ev("gather.9", 900, 100)])])
    dev1 = NS(name="/device:TPU:1", lines=[
        NS(name="XLA Ops", events=[_ev("sort.3", 0, 500)])])
    host = NS(name="/host:CPU", lines=[
        NS(name="main", events=[_ev("TransferToHost", 705, 190),
                                _ev("tiny", 401, 3)])])
    return NS(planes=[dev0, dev1, host])


def test_hand_built_tpu_planes():
    out = tr.reduce_profile(_profile(), window_s=2000e-9)
    d0, d1 = out["devices"]
    assert d0["busy_s"] == pytest.approx(700e-9)   # 0-400, 500-700, 900-1000
    assert d1["busy_s"] == pytest.approx(500e-9)
    assert out["busy_s"] == pytest.approx(600e-9)
    assert out["window_s"] == pytest.approx(2000e-9)
    cat = {k: v * 2e9 for k, v in out["category_s"].items()}  # sum, ns
    assert cat["sort"] == pytest.approx(200 + 500)
    assert cat["other"] == pytest.approx(100)      # the while's self time
    assert cat["fusion"] == pytest.approx(100)
    # an op that starts inside another takes the overlap: self times sum
    # to the busy time
    assert cat["all-to-all"] == pytest.approx(50)
    assert cat["copy"] == pytest.approx(150)
    assert sum(cat.values()) == pytest.approx(700 + 500)
    assert cat["gather"] == pytest.approx(100)
    # the collective ran 500-600, a copy overlapped it from 550
    assert out["collective_s"] * 2e9 == pytest.approx(100)
    assert out["exposed_collective_s"] * 2e9 == pytest.approx(50)
    gaps = dict(out["idle_gaps"])
    assert gaps["TransferToHost"] * 2e9 == pytest.approx(200)   # 700-900
    assert gaps["host_unattributed"] * 2e9 == pytest.approx(100)  # 400-500
    assert out["device_ops"][0] == ["sort.3", pytest.approx(350e-9)]


def test_no_device_events_reduce_to_nothing():
    assert tr.reduce_profile(NS(planes=[NS(name="/host:CPU", lines=[])])) \
        == {}


@pytest.mark.parametrize("name, want", [
    ("sort.12", "sort"), ("%fusion.3 = s64[8]{0} fusion(...)", "fusion"),
    ("all-gather-start.1", "all-gather"), ("all_gather.66", "all-gather"),
    ("all-to-all.4", "all-to-all"), ("gather.2", "gather"),
    ("scatter.1", "scatter"), ("copy.5", "copy"),
    ("dynamic-update-slice.1", "other"), ("all-reduce.1", "all-reduce"),
])
def test_categories(name, want):
    assert tr.category(tr._op_name(name)) == want
