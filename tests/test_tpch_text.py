"""The data set ``tpch_text`` (ISSUE 37): ``tpch``'s eight tables with
``orders.o_comment``.  The 49 shared columns are ``tpch``'s value for
value, so every template that runs on ``tpch`` has the same reference
answer here; ``o_comment`` depends on the seed and the scale factor
alone; its lengths and the share ``'%special%requests%'`` matches are
what the module and the configuration state."""

import json
import pathlib
import re
import sys

import numpy as np
import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from benchmark.datasets import tpch, tpch_text  # noqa: E402
from benchmark.queries import q3, q13  # noqa: E402

CASES = [(7, 0.02), (3700000011, 0.01)]
CONFIG = (pathlib.Path(__file__).resolve().parents[1] / "benchmark"
          / "configs" / "tpch-sf1-joinkinds-1chip.json")


def _same(a, b):
    if isinstance(a, tuple):
        return (isinstance(b, tuple) and np.array_equal(a[0], b[0])
                and list(a[1]) == list(b[1]))
    return np.array_equal(a, b) and a.dtype == b.dtype


def test_the_schema_is_tpchs_with_one_column_more():
    added = {(t, c) for t, cols in tpch_text.SCHEMA.items() for c in cols} \
        - {(t, c) for t, cols in tpch.SCHEMA.items() for c in cols}
    assert added == {("orders", "o_comment")}
    assert tpch_text.SCHEMA["orders"]["o_comment"] == "varchar(79)"
    assert list(tpch_text.SCHEMA) == list(tpch.SCHEMA)
    assert sum(map(len, tpch_text.SCHEMA.values())) == 50
    assert "o_comment" not in tpch.SCHEMA["orders"]      # tpch.py untouched
    assert tpch_text.DB != tpch.DB
    assert tpch_text.SQL_TABLES == tpch.SQL_TABLES


@pytest.mark.parametrize("seed,sf", CASES)
def test_the_49_shared_columns_are_tpchs_value_for_value(seed, sf):
    ours = tpch_text.generate(seed, sf)
    theirs = tpch.generate(seed, sf)
    assert list(ours) == list(theirs)
    compared = 0
    for table, cols in theirs.items():
        assert [c for c in ours[table] if c != "o_comment"] == list(cols)
        for name, want in cols.items():
            assert _same(ours[table][name], want), (table, name)
            compared += 1
    assert compared == 49
    # so a template of the other data set has the same answer on this one
    assert q3.reference(ours) == q3.reference(theirs)


@pytest.mark.parametrize("seed,sf", CASES)
def test_o_comment_depends_on_seed_and_scale_alone(seed, sf):
    everything = tpch_text.generate(seed, sf)["orders"]["o_comment"]
    alone = tpch_text.generate(
        seed, sf, {"orders": ["o_comment"]})["orders"]["o_comment"]
    with_key = tpch_text.generate(
        seed, sf, {"customer": ["c_custkey"],
                   "orders": ["o_comment", "o_custkey"]})["orders"]
    assert list(with_key) == ["o_comment", "o_custkey"]
    assert _same(everything, alone) and _same(everything,
                                              with_key["o_comment"])
    other_seed = tpch_text.generate(
        seed + 1, sf, {"orders": ["o_comment"]})["orders"]["o_comment"]
    assert not _same(everything, other_seed)
    assert len(everything[0]) == tpch.sizes(sf)["orders"]


@pytest.mark.parametrize("seed,sf", CASES)
def test_o_comment_is_grammar_text_of_19_to_78_characters(seed, sf):
    codes, words = tpch_text.generate(
        seed, sf, {"orders": ["o_comment"]})["orders"]["o_comment"]
    assert codes.dtype == np.int32
    assert all(isinstance(w, bytes) for w in words[:100])
    assert words == sorted(set(words))         # a sorted, unique dictionary
    assert codes.min() == 0 and codes.max() == len(words) - 1
    lengths = np.array([len(w) for w in words])[codes]
    lo, hi = tpch_text.COMMENT_CHARS
    assert (lo, hi) == (19, 78)
    assert lengths.min() >= lo and lengths.max() <= hi
    assert 45 < lengths.mean() < 52            # uniform over the range
    # practically unique a row: the dictionary is as long as its table
    assert len(words) > 0.99 * len(codes)
    text = b" ".join(words[:2000])
    for word in (b"special", b"requests", b"packages", b"pending",
                 b"accounts", b"deposits", b"unusual", b"express",
                 b"furiously", b"the "):
        assert word in text, word
    assert re.fullmatch(rb"[A-Za-z',.;:?!\- ]+", text)


@pytest.mark.parametrize("seed", [7, 3700000011])
def test_the_matched_share_is_the_configurations(seed):
    t = tpch_text.generate(seed, 0.02, q13.READS)
    codes, words = t["orders"]["o_comment"]
    rx = re.compile(rb"special.*requests", re.S)
    hit = np.array([rx.search(w) is not None for w in words])[codes]
    assert abs(hit.mean() - tpch_text.MATCHED_SHARE) \
        <= tpch_text.MATCHED_SHARE_TOLERANCE
    assert 0.005 < tpch_text.MATCHED_SHARE < 0.02     # dbgen's is about 1%
    with open(CONFIG) as f:
        said = json.load(f)["assumed"]["matched_share"]
    assert f"{100 * tpch_text.MATCHED_SHARE:.2f}%" in said
    assert f"{100 * tpch_text.MATCHED_SHARE_TOLERANCE:.1f} points" in said


def test_an_unknown_column_is_refused_as_tpch_refuses_it():
    with pytest.raises(KeyError, match="free-text columns are not"):
        tpch_text.generate(7, 0.01, {"orders": ["o_comment"],
                                     "customer": ["c_comment"]})


def test_column_bytes_counts_the_codes():
    rows = {"orders": 1000, "customer": 10}
    assert tpch_text.column_bytes(q13.READS, rows) == 10 * 8 + 1000 * (
        8 + 8 + 4)
    shared = {"orders": ["o_orderkey", "o_orderdate"]}
    assert tpch_text.column_bytes(shared, rows) == tpch.column_bytes(
        shared, rows)
