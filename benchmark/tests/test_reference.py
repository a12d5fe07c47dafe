"""Each template's plain numpy ``reference()`` against the HOST engine's
rows through TestKit at SF0.01, two seeds; the same seed gives the same
answers, another seed other answers."""

import os

import pytest

from benchmark.datasets import tpch as tpch_gen
from benchmark.harness.resolve import BENCH_DIR, Cell, load_module

TEMPLATES = ("q1", "q3", "q5", "q6")
SF = 0.01


def _template(name):
    return load_module(os.path.join(BENCH_DIR, "queries", name + ".py"),
                       name)


@pytest.fixture(scope="module", params=(1, 2))
def loaded(request):
    from tidb_tpu.testkit import TestKit
    want = Cell("tpch-sf1.q6").config["tables"]   # every generated column
    tables = tpch_gen.generate(request.param, SF, want)
    tk = TestKit()
    tpch_gen.load(tk, tables, want, False, f"test/seed{request.param}")
    tk.must_exec("set tidb_executor_engine = 'host'")
    return tk, tables


@pytest.mark.parametrize("name", TEMPLATES)
def test_reference_equals_host_engine(loaded, name):
    tk, tables = loaded
    mod = _template(name)
    got = [tuple(r) for r in tk.must_query(mod.SQL).rows]
    assert got == mod.reference(tables)
    assert got, "an empty answer proves nothing"


@pytest.mark.parametrize("name", TEMPLATES)
def test_answers_follow_the_seed(name):
    mod = _template(name)
    a, b, c = (mod.reference(tpch_gen.generate(s, SF, mod.READS))
               for s in (3, 3, 4))
    assert a == b and a != c


@pytest.mark.parametrize("name", TEMPLATES)
def test_min_bytes_counts_every_column_once(name):
    mod = _template(name)
    rows = {t: 10 for t in tpch_gen.SCHEMA}
    cols = sum(len(c) for c in mod.READS.values())
    assert 4 * 10 * cols <= mod.min_bytes(rows) <= 8 * 10 * cols
