"""The lowering the chip takes, run in tier-1.

The device executor has ONE lowering: ``ops/device.py``, ``device_exec.py``,
``device_join.py``, ``hybrid_join.py`` and ``mpp_exec.py`` never ask
``jax.default_backend()`` (held below), so what XLA:CPU traces, compiles
and answers here — each held to exact parity with the host engine — is
the program the chip runs: the dense arm for small packed key spaces
(Q1, Q6), the sort + segment kernel (``ops/device._agg_impl``) for the
rest and for every join fragment, at the fact length, and the in-kernel
``merge_partial_states`` fold of streamed partial states.
"""

import ast
import pathlib
import random
import sys

import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

import bench  # noqa: E402
from tidb_tpu.executor import device_exec, device_join  # noqa: E402
from tidb_tpu.ops import device as dev  # noqa: E402
from tidb_tpu.testkit import TestKit  # noqa: E402


_ONE_LOWERING = ("ops/device.py", "executor/device_exec.py",
                 "executor/device_join.py", "executor/hybrid_join.py",
                 "executor/mpp_exec.py")


@pytest.mark.parametrize("module", _ONE_LOWERING)
def test_the_device_executor_never_asks_for_the_backend(module):
    """No name or attribute `default_backend` anywhere in the modules
    that build and dispatch XLA programs: a branch on it is a program
    tier-1 runs and the chip does not (or the reverse)."""
    path = pathlib.Path(dev.__file__).resolve().parents[1] / module
    tree = ast.parse(path.read_text())
    asked = [node.lineno for node in ast.walk(tree)
             if (isinstance(node, ast.Attribute)
                 and node.attr == "default_backend")
             or (isinstance(node, ast.Name) and node.id == "default_backend")
             or (isinstance(node, ast.alias)
                 and node.name == "default_backend")]
    assert not asked, (module, asked)


def _drop_compiled():
    """Forget every fused pipeline (each is its own jit function held by
    _PIPE_CACHE), so that the next execution traces under the caller's
    spies whatever an earlier test of this worker compiled."""
    with device_exec._PIPE_LOCK:
        device_exec._PIPE_CACHE.clear()
        device_exec._TOPK_CACHE.clear()


@pytest.fixture(scope="module")
def tpch():
    tk = TestKit()
    bench.gen_all(tk, 0.02)    # 120k lineitem rows
    return tk


def _parity(tk, sql):
    tk.must_exec("set tidb_result_cache = 'OFF'")
    tk.must_exec("set tidb_executor_engine = 'tpu'")
    got = tk.must_query(sql).rows
    again = tk.must_query(sql).rows  # learned capacities: settled shapes
    plan = tk.must_query("explain analyze " + sql).rows
    tk.must_exec("set tidb_executor_engine = 'host'")
    want = tk.must_query(sql).rows
    assert want, "empty reference result proves nothing"
    assert got == want and again == want
    engines = [part for row in plan for part in row[2].split(", ")
               if part.startswith("engine:")]
    return engines


def test_small_packed_key_aggregate_reduces_densely(tpch, monkeypatch):
    """Q1: two dict-coded keys pack into 5 bits — 32 buckets, one masked
    reduction each."""
    arms = []
    orig = dev.agg_arm

    def spy(pack, agg_ops, gathered=False):
        arms.append(orig(pack, agg_ops, gathered))
        return arms[-1]
    monkeypatch.setattr(dev, "agg_arm", spy)
    _drop_compiled()
    assert _parity(tpch, bench.QUERIES["q1"]) == ["engine:tpu"]
    assert arms and set(arms) == {"dense"}


def test_join_aggregate_at_the_length_its_cuts_leave(tpch, monkeypatch):
    """Q3: the join fragment aggregates at the probe leaf's row bucket
    until its live counts are learned, then at the length the cuts of its
    probe path leave (device_join.compact_to: past `orders` at SF0.02)."""
    lengths = []
    orig = dev._agg_impl

    def spy(key_cols, key_nulls, val_cols, val_nulls, mask, **kw):
        lengths.append((mask.shape[0], kw.get("gathered", False)))
        return orig(key_cols, key_nulls, val_cols, val_nulls, mask, **kw)
    monkeypatch.setattr(dev, "_agg_impl", spy)
    _drop_compiled()
    assert _parity(tpch, bench.QUERIES["q3"]) == ["engine:tpu"]
    n_fact = int(tpch.must_query("select count(*) from lineitem").rows[0][0])
    assert lengths and all(g for _n, g in lengths)
    assert lengths[0][0] >= n_fact
    assert lengths[-1][0] * 4 <= lengths[0][0], (lengths, n_fact)


# -- the sort arm's group starts: one sort or a search a slot (ISSUE 36) ------

_ORDER_SUMS = ("select l_orderkey, sum(l_quantity) from lineitem "
               "group by l_orderkey having sum(l_quantity) > 250 "
               "order by l_orderkey")
_A_GROUP_A_ROW = ("select l_orderkey, l_partkey, l_suppkey, l_shipdate, "
                  "count(*), sum(l_quantity) from lineitem group by "
                  "l_orderkey, l_partkey, l_suppkey, l_shipdate "
                  "order by l_orderkey, l_partkey, l_suppkey, l_shipdate")


def test_learned_capacity_program_finds_its_groups_without_a_loop(
        tpch, monkeypatch):
    """Q18's subquery shape at SF0.02 (30,000 groups over the 131,072-row
    bucket): the program at the estimated capacity keeps the per-slot
    search (a `while` of dependent gathers; its text is the parent's,
    held by hash in tests/test_mpp_indexed.py), the one at the learned
    capacity of 32,768 sorts the flagged positions once and holds no
    `while` at all."""
    programs = []
    orig = dev.observed_jit

    def spy(fn, **jit_kw):
        run = orig(fn, **jit_kw)

        def call(*a, **k):
            programs.append(run.lower(*a, **k).as_text(debug_info=True))
            return run(*a, **k)
        return call
    monkeypatch.setattr(dev, "observed_jit", spy)
    _drop_compiled()
    tpch.must_exec("set tidb_result_cache = 'OFF'")
    tpch.must_exec("set tidb_executor_engine = 'tpu'")
    got = tpch.must_query(_ORDER_SUMS).rows
    _drop_compiled()
    tpch.must_exec("set tidb_executor_engine = 'host'")
    assert got and got == tpch.must_query(_ORDER_SUMS).rows
    first, learned = programs
    assert "tensor<32768xi64>" in learned and "tensor<32768xi64>" not in first
    assert "stablehlo.while" in first and "searchsorted" in first
    assert "stablehlo.while" not in learned and "searchsorted" not in learned
    # the one sort more is k_agg_segment's: positions as int32
    assert "k_agg_segment/jit(sort)" in learned
    assert "k_agg_segment/jit(sort)" not in first


@pytest.mark.parametrize("shape,sql,one_pass", [
    ("q18", bench.QUERIES["q18"], 1),          # its inner aggregate
    ("a_group_a_row", _A_GROUP_A_ROW, 1),      # capacity = the row bucket
    ("q5", bench.QUERIES["q5"], 0),            # 16 slots: the search
])
def test_one_pass_programs_answer_as_the_host_and_are_counted(
        tpch, shape, sql, one_pass):
    """Parity with the host engine on either side of dev.spans_one_pass,
    and DIAG STATUS device_pipelines.agg_spans_one_pass: one per settled
    execution of a fragment whose learned capacity sorts, none for a
    fragment that searches."""
    _drop_compiled()
    assert set(_parity(tpch, sql)) == {"engine:tpu"}
    tpch.must_exec("set tidb_executor_engine = 'tpu'")
    before = _device_pipelines(tpch)
    assert tpch.must_query(sql).rows
    after = _device_pipelines(tpch)
    tpch.must_exec("set tidb_executor_engine = 'host'")
    assert (after["agg_spans_one_pass"]
            - before["agg_spans_one_pass"]) == one_pass, shape


def test_streamed_aggregate_merges_in_kernel(monkeypatch):
    """A streamed scan-aggregate with a packable key folds its partial
    states through merge_partial_states' concat + sort kernel, not the
    numpy fold."""
    tk = TestKit()
    tk.must_exec("use test")
    tk.must_exec("create table s (cat varchar(8), d date, amount int)")
    random.seed(11)
    rows = [f"('c{i % 5}', '202{i % 3}-0{i % 9 + 1}-15', "
            f"{random.randrange(1000)})" for i in range(20_000)]
    for lo in range(0, len(rows), 2000):
        tk.must_exec("insert into s values " + ",".join(rows[lo:lo + 2000]))

    def no_host_fold(*a, **k):
        raise AssertionError("the streamed scan folded in numpy")
    monkeypatch.setattr(device_exec, "_merge_states_host", no_host_fold)
    packs = []
    orig = device_exec.merge_partial_states

    def spy(state, parts, merge_cap, n_keys, nvals, merge_ops, key_pack):
        packs.append(key_pack)
        return orig(state, parts, merge_cap, n_keys, nvals, merge_ops,
                    key_pack)
    monkeypatch.setattr(device_exec, "merge_partial_states", spy)
    # 7 blocks, flushed every 2: several folds onto a running state
    monkeypatch.setattr(device_exec, "_MERGE_BUDGET_ROWS", 2 * 16)
    tk.must_exec("set tidb_device_stream_rows = 3000")
    engines = _parity(
        tk, "select cat, d, count(*), sum(amount), min(amount), "
            "max(amount) from s where amount > 10 group by cat, d "
            "order by cat, d")
    assert engines == ["engine:tpu-stream"]
    assert len(packs) >= 4 and all(p is not None for p in packs)


# -- kernel names (ISSUE 24) --------------------------------------------------
#
# The traced bodies mark their stages with jax.named_scope from ONE
# vocabulary (ops/device.KERNEL_SCOPES); the benchmark sums device time by
# those names.  What the chip's programs are lowered from must carry every
# name that applies to its shape.

Q6 = """
select sum(l_extendedprice * l_discount) as revenue
from lineitem
where l_shipdate >= date '1994-01-01'
  and l_shipdate < date '1994-01-01' + interval '1' year
  and l_discount between 0.06 - 0.01 and 0.06 + 0.01
  and l_quantity < 24
"""

_SCOPES_BY_SHAPE = {
    # scan -> filter -> group by 32 / 2 buckets: the dense arm, under the
    # same names (key packing, aggregate inputs, bucket reductions)
    "q1": ("k_filter", "k_agg_sort", "k_agg_segment", "k_agg_gather"),
    "q6": ("k_filter", "k_agg_sort", "k_agg_segment", "k_agg_gather"),
    # host-indexed joins (no in-program build) under the aggregate
    "q3": ("k_filter", "k_join_probe", "k_agg_sort", "k_agg_segment",
           "k_agg_gather"),
    # order by/limit over more groups than one small fetch holds (at this
    # scale Q3's own groups fit one, and its top-k runs on the host)
    "topn": ("k_agg_sort", "k_topk"),
    # two devices, `orders` over both broadcast thresholds: radix
    # exchange, in-program sort join, partials merged
    "mpp_q3": ("k_filter", "k_exchange", "k_join_build", "k_join_probe",
               "k_agg_sort", "k_agg_segment", "k_agg_gather"),
    # two devices, as shipped: compile_fragment's body on every shard
    # (host-built direct indexes: no in-program build), partials merged
    "mpp_q3_indexed": ("k_filter", "k_exchange", "k_join_probe",
                       "k_agg_sort", "k_agg_segment", "k_agg_gather"),
}


@pytest.fixture(scope="module")
def lowered(tpch):
    """{shape: the debug-info text every program of one execution was
    lowered to}."""
    texts = {}
    current, programs = [], []
    orig = dev.observed_jit

    def spy(fn, **jit_kw):
        run = orig(fn, **jit_kw)

        def call(*a, **k):
            low = run.lower(*a, **k)
            current.append(low.as_text(debug_info=True))
            programs.append(low)
            return run(*a, **k)
        return call

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(dev, "observed_jit", spy)
        _drop_compiled()
        tpch.must_exec("set tidb_result_cache = 'OFF'")
        topn = ("select l_orderkey, sum(l_quantity) as q from lineitem "
                "group by l_orderkey order by q desc, l_orderkey limit 5")
        for shape, engine, sql in (
                ("q1", "tpu", bench.QUERIES["q1"]),
                ("q6", "tpu", Q6),
                ("q3", "tpu", bench.QUERIES["q3"]),
                ("q5", "tpu", bench.QUERIES["q5"]),
                ("topn", "tpu", topn),
                ("mpp_q3", "tpu-mpp", bench.QUERIES["q3"]),
                ("mpp_q3_indexed", "tpu-mpp", bench.QUERIES["q3"])):
            tpch.must_exec(f"set tidb_executor_engine = '{engine}'")
            tpch.must_exec("set tidb_mpp_devices = 2")
            # `orders` (30,000 rows) is over the row threshold; its direct
            # index keeps it broadcast unless its bytes are over theirs
            tpch.must_exec("set tidb_broadcast_join_threshold_size = "
                           f"{1 if shape == 'mpp_q3' else 104857600}")
            del current[:], programs[:]
            rows = tpch.must_query(sql).rows
            assert rows and current, shape
            texts[shape] = "\n".join(current)
            texts[shape, "programs"] = list(programs)
        _drop_compiled()
    tpch.must_exec("set tidb_executor_engine = 'tpu'")
    return texts


@pytest.mark.parametrize("shape,scope", [
    (shape, scope) for shape, scopes in _SCOPES_BY_SHAPE.items()
    for scope in scopes])
def test_lowered_programs_name_their_kernels(lowered, shape, scope):
    import re
    assert scope in dev.KERNEL_SCOPES
    # a part of a name-stack path: after a "/" or, where the text nests
    # its locations (under shard_map), at the start of one
    assert re.search(rf'["/]{scope}/', lowered[shape])


def _op_results(text, op):
    """Element counts of the results of every `op` in a StableHLO text."""
    import math
    import re
    out = []
    for m in re.finditer(
            rf'stablehlo\.{op}\b[^\n]*->\s*\(?tensor<([0-9x]*)[a-z]', text):
        out.append(math.prod(int(d) for d in m.group(1).split("x") if d))
    return out


def _fact_length(text):
    import re
    return max(int(n) for n in re.findall(r"tensor<(\d+)xi64>", text))


@pytest.mark.parametrize("shape", ["q1", "q6"])
def test_dense_programs_neither_sort_nor_gather_at_fact_length(lowered,
                                                              shape):
    """Q1 and Q6 aggregate by masked reductions: no sort, no scatter, and
    no gather or cumsum whose result is as long as the fact table."""
    text = lowered[shape]
    n = _fact_length(text)
    assert n >= 131072
    assert "stablehlo.sort" not in text
    assert "stablehlo.scatter" not in text
    for op in ("gather", "dynamic_gather", "reduce_window"):
        assert all(size < n for size in _op_results(text, op)), op
    assert _op_results(text, "reduce")      # the reductions are there


@pytest.mark.parametrize("shape", ["q3", "q5"])
def test_join_fragments_still_sort(lowered, shape):
    """Q3 groups by (l_orderkey, o_orderdate, o_shippriority), far past
    the dense bound; Q5 by n_name, under it, but its inputs come out of
    the probe's gather chain: both trace the program the parent traced."""
    text = lowered[shape]
    assert "stablehlo.sort" in text
    n = _fact_length(text)
    assert any(size >= n for size in _op_results(text, "gather")
               + _op_results(text, "dynamic_gather"))


@pytest.mark.parametrize("shape", ["q1", "q6"])
def test_dense_programs_leave_no_instruction_unnamed(lowered, shape):
    """Every instruction of the compiled dense programs falls to a
    KERNEL_SCOPES name by the rule the benchmark's trace reader applies
    (benchmark/harness/trace_owners.py: its own scope, else the nearest
    instruction around it that has one), so kernel.unnamed_share stays
    at 0 to the digits it is printed with."""
    import math
    import re
    from benchmark.harness import trace_owners
    assert trace_owners.KERNELS == dev.KERNEL_SCOPES
    programs = lowered[shape, "programs"]
    assert programs
    n = _fact_length(lowered[shape])
    checked = 0
    for low in programs:
        compiled = low.compile()
        sizes = {m.group(1): math.prod(
                     int(d) for d in m.group(2).split(",") if d)
                 for m in re.finditer(
                     r"%?([\w.-]+) = \w+\[([\d,]*)\]", compiled.as_text())}
        for hm in compiled.runtime_executable().hlo_modules():
            buf = hm.as_serialized_hlo_module_proto()
            mod = trace_owners._Module(buf, (0, len(buf)))
            unnamed = [name for name in mod.by_name
                       if mod.kernel(name, dev.KERNEL_SCOPES) is None]
            # what the compiler folds to a constant loses its scope: the
            # all-false NULL flags of COUNT, `capacity` booleans.  Nothing
            # at the fact length may go unnamed
            assert all(sizes[name] < n // 16 for name in unnamed), \
                (shape, unnamed[:8])
            assert len(unnamed) <= len(mod.by_name) // 50
            checked += len(mod.by_name)
    assert checked > 20


def test_lowered_program_names_carry_the_scopes_tag(lowered):
    """The persistent compile cache hashes a module without its debug
    info, so scopes alone would be served a cached, unnamed executable:
    the module names carry KERNEL_SCOPES_TAG."""
    import re
    for shape, text in lowered.items():
        if not isinstance(shape, str):
            continue
        names = set(re.findall(r"module @(\w+)", text))
        assert names and all(
            n.endswith("_" + dev.KERNEL_SCOPES_TAG) for n in names), \
            (shape, names)


def test_mesh_merge_counts_as_exchange(lowered):
    """Where scopes nest the outermost names the kernel: the final merge
    of the gathered partials is _agg_impl under k_exchange."""
    assert "k_exchange/k_agg_sort/" in lowered["mpp_q3"]
    assert "k_exchange/" not in lowered["q3"]


# -- which arm a fragment took, as the program reports it (ISSUE 26) ---------

def _device_pipelines(tk):
    import json
    rows = tk.must_query("DIAG STATUS").rows
    return json.loads(rows[0][0])["device_pipelines"]


def _agg_annotations(tk, sql):
    plan = tk.must_query("explain analyze " + sql).rows
    return [part for row in plan for part in row[2].split(", ")
            if part.startswith("agg:")]


@pytest.mark.parametrize("shape,sql,counter,arm", [
    ("q1", bench.QUERIES["q1"], "agg_dense", "dense"),
    ("q6", Q6, "agg_dense", "dense"),
    ("wide_key", "select l_orderkey, sum(l_quantity) from lineitem "
                 "group by l_orderkey order by l_orderkey limit 3",
     "agg_sorted", "sort"),
    ("q3", bench.QUERIES["q3"], "agg_sorted", "sort"),
    # n_name packs into 5 bits, but a join fragment's inputs are gathered
    ("q5", bench.QUERIES["q5"], "agg_sorted", "sort"),
])
def test_fragments_count_their_aggregate_arm(tpch, shape, sql, counter,
                                             arm):
    """DIAG STATUS device_pipelines.agg_dense / agg_sorted advance by one
    per dispatched aggregate fragment, on the side dev.agg_arm named, and
    EXPLAIN ANALYZE names the arm."""
    tpch.must_exec("set tidb_result_cache = 'OFF'")
    tpch.must_exec("set tidb_executor_engine = 'tpu'")
    before = _device_pipelines(tpch)
    assert tpch.must_query(sql).rows
    after = _device_pipelines(tpch)
    grew = {k: after[k] - before[k]
            for k in ("agg_dense", "agg_sorted")}
    assert grew == {"agg_dense": 0, "agg_sorted": 0, counter: 1}, shape
    assert _agg_annotations(tpch, sql) == [f"agg:{arm}"]
