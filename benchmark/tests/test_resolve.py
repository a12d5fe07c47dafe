"""Names in BENCHMARK.json resolve to files; a missing one is an error
that names the path.  Also: the file itself obeys the contract's limits
that are cheap to check here."""

import json
import os
import re
import shutil

import pytest

from benchmark.harness.resolve import BENCH_DIR, ROOT, Cell, ResolveError

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def _spec():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("cell", [w["name"] for w in _spec()["workloads"]])
def test_every_cell_resolves(cell):
    c = Cell(cell)
    assert any(m["name"] == "setup_s" for m, _mod in c.end_to_end)
    assert len(c.end_to_end) >= 2 and c.per_layer
    e2e = {m["name"] for m, _mod in c.end_to_end}
    assert all(m["moves"] in e2e for m, _mod in c.per_layer)
    for mod in c.templates.values():
        assert mod.SQL.strip() and callable(mod.reference)
        for table, cols in mod.READS.items():
            assert set(cols) <= set(c.config["tables"][table])


@pytest.mark.parametrize("entry", _spec()["configs"],
                         ids=lambda c: c["name"])
def test_config_installs_the_whole_schema(entry):
    """A deployment is the data set's schema, not what today's templates
    read: a later mix finds its columns without an edit to the config."""
    from benchmark.harness.resolve import load_dataset
    with open(os.path.join(ROOT, entry["file"])) as f:
        cfg = json.load(f)
    schema = load_dataset(cfg["dataset"]).SCHEMA
    assert cfg["tables"] == {t: list(cols) for t, cols in schema.items()}
    assert cfg["source"] == entry["source"]
    # what BENCHMARK.json calls reduced is a key of the file, explained there
    assert set(entry["reduced"]) == set(cfg["reduced"])
    assert all(k in cfg for k in entry["reduced"])


def test_unknown_workload():
    with pytest.raises(ResolveError, match="no workload 'nope'"):
        Cell("nope")


@pytest.mark.parametrize("drop, what", [
    ("traffic/power_q6.json", "traffic"),
    ("queries/q6.py", "query template"),
    ("layer_metrics/xla.sort_share.py", "per_layer metric"),
    ("end_to_end/setup_s.py", "end_to_end metric"),
    ("configs/tpch-sf1-1chip.json", "config"),
    ("datasets/tpch.py", "data set"),
])
def test_missing_file_is_named(tmp_path, drop, what):
    root = tmp_path / "checkout"
    shutil.copytree(BENCH_DIR, root / "benchmark",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__",
                                                  "tests"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    os.remove(root / "benchmark" / drop)
    with pytest.raises(ResolveError) as e:
        Cell("tpch-sf1.q6", bench_dir=str(root / "benchmark"))
    assert what in str(e.value) and drop in str(e.value)


def test_contract_limits():
    spec = _spec()
    assert set(spec) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert 1 <= spec["run_seconds"] <= 51
    names = [m["name"] for m in spec["end_to_end"] + spec["per_layer"]]
    assert len(names) == len(set(names))
    for entry in (spec["configs"] + spec["workloads"] + spec["end_to_end"]
                  + spec["per_layer"]):
        assert NAME.match(entry["name"]), entry["name"]
        for key in ("why", "layer", "source"):
            if key in entry:
                assert 1 <= len(entry[key]) <= 200 and "\n" not in entry[key]
    for m in spec["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in spec["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    four = [w for w in spec["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(spec["workloads"]) // 2)
    assert len(json.dumps(spec)) < 64 * 1024
