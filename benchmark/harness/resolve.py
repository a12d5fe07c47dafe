"""Resolve a cell of BENCHMARK.json to the files that define it.

Adding a cell is one entry in ``workloads`` plus, where new, one file
each under ``configs/``, ``datasets/``, ``traffic/``, ``queries/``,
``end_to_end/`` and ``layer_metrics/``.  Every name is resolved to a file
here, and a file that is missing is an error that names the path.
"""

import importlib
import importlib.util
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH_DIR = os.path.join(ROOT, "benchmark")


class ResolveError(Exception):
    """A name in BENCHMARK.json (or a file it leads to) has no file."""


def _need(path: str, what: str) -> str:
    if not os.path.isfile(path):
        raise ResolveError(f"{what}: missing file {os.path.relpath(path, ROOT)}")
    return path


def _json(path: str, what: str) -> dict:
    with open(_need(path, what)) as f:
        return json.load(f)


def load_module(path: str, what: str):
    _need(path, what)
    name = "benchmark_file_" + os.path.relpath(path, BENCH_DIR) \
        .replace(os.sep, "_").removesuffix(".py").replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_dataset(name: str, bench_dir: str = BENCH_DIR):
    """The data set a configuration names: ``datasets/<name>.py``, imported
    as ``benchmark.datasets.<name>`` so that the worker, the templates and
    the harness share one module.  It gives ``DB``, ``GEN_VERSION``,
    ``generate(seed, sf, want)`` and ``load(tk, tables, want, seeded,
    tag)``."""
    _need(os.path.join(bench_dir, "datasets", f"{name}.py"),
          f"data set {name!r}")
    return importlib.import_module(f"benchmark.datasets.{name}")


class Cell:
    """One entry of ``workloads`` with everything it names loaded."""

    def __init__(self, name: str, bench_dir: str = BENCH_DIR,
                 benchmark_json: "str | None" = None):
        spec = _json(benchmark_json or os.path.join(
            os.path.dirname(bench_dir), "BENCHMARK.json"), "the benchmark")
        cells = {w["name"]: w for w in spec["workloads"]}
        if name not in cells:
            raise ResolveError(f"no workload {name!r} in BENCHMARK.json; "
                               f"it has {sorted(cells)}")
        self.name = name
        self.spec = spec
        self.entry = cells[name]
        self.chips = int(self.entry["chips"])
        cfg_entry = next((c for c in spec["configs"]
                          if c["name"] == self.entry["config"]), None)
        if cfg_entry is None:
            raise ResolveError(f"workload {name!r} names config "
                               f"{self.entry['config']!r}, which "
                               "BENCHMARK.json does not list")
        self.config_name = cfg_entry["name"]
        self.config_path = os.path.join(os.path.dirname(bench_dir),
                                        cfg_entry["file"])
        self.config = _json(self.config_path,
                            f"config {self.config_name!r}")
        if "dataset" not in self.config:
            raise ResolveError(f"config {self.config_name!r} names no "
                               "'dataset' (a file under datasets/)")
        self.dataset = load_dataset(self.config["dataset"], bench_dir)
        self.traffic_name = self.entry["traffic"]
        self.traffic = _json(
            os.path.join(bench_dir, "traffic", self.traffic_name + ".json"),
            f"traffic {self.traffic_name!r}")
        self.templates = {
            t: load_module(os.path.join(bench_dir, "queries", t + ".py"),
                           f"query template {t!r}")
            for t in self.traffic["templates"]}
        self.end_to_end = self._metrics("end_to_end", bench_dir)
        self.per_layer = self._metrics("per_layer", bench_dir)

    def _metrics(self, kind: str, bench_dir: str) -> list:
        """[(entry, reader module)] of the metrics this cell reports."""
        folder = "end_to_end" if kind == "end_to_end" else "layer_metrics"
        out = []
        for m in self.spec[kind]:
            if "workloads" in m and self.name not in m["workloads"]:
                continue
            mod = load_module(
                os.path.join(bench_dir, folder, m["name"] + ".py"),
                f"{kind} metric {m['name']!r}")
            out.append((m, mod))
        return out
