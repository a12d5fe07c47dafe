"""Project lint engine (tidb_tpu/lint): synthetic-source fixtures per
rule (positive + negative + allowlisted), allowlist/baseline round-trip,
and the tier-1 full-repo run — CI fails on any new unallowlisted finding.
"""

import ast
import json
import subprocess
import sys

import pytest

import tidb_tpu.lint.rules  # noqa: F401 — populate the registry
from tidb_tpu.lint import (Allowlist, Context, RULES, run_repo, run_rules,
                           write_baseline)
from tidb_tpu.lint.engine import SourceFile


def make_ctx(files: dict, aux: dict | None = None) -> Context:
    """In-memory fixture tree: rel-path -> source text."""
    fs = [SourceFile(rel, rel, text, ast.parse(text))
          for rel, text in files.items()]
    fs += [SourceFile(rel, rel, text, ast.parse(text), aux=True)
           for rel, text in (aux or {}).items()]
    return Context(fs)


def run_one(rule: str, files: dict, aux: dict | None = None):
    return RULES[rule].run(make_ctx(files, aux))


# -- engine: allowlist + baseline ---------------------------------------------

class TestAllowlist:
    def test_reason_required(self, tmp_path):
        p = tmp_path / "al.txt"
        p.write_text("some-rule pat:* \n")
        with pytest.raises(ValueError):
            Allowlist.load(str(p))
        p.write_text("some-rule pat:* -- \n")
        with pytest.raises(ValueError):
            Allowlist.load(str(p))

    def test_match_suppresses_and_stale_reported(self, tmp_path):
        files = {"a.py": "try:\n    pass\nexcept Exception:\n    pass\n"}
        p = tmp_path / "al.txt"
        p.write_text(
            "exception-swallow a.py:swallow@* -- fixture reason\n"
            "exception-swallow never.py:* -- stale entry\n")
        al = Allowlist.load(str(p))
        report = run_rules(make_ctx(files), al,
                           rules=["exception-swallow"])
        assert not report.findings
        assert len(report.allowlisted) == 1
        assert report.allowlisted[0][1].reason == "fixture reason"
        assert len(report.stale) == 1
        assert not report.ok  # stale entries fail the run

    def test_stale_only_for_rules_that_ran(self, tmp_path):
        p = tmp_path / "al.txt"
        p.write_text("lock-order x:* -- other rule's entry\n")
        al = Allowlist.load(str(p))
        report = run_rules(make_ctx({"a.py": "x = 1\n"}), al,
                           rules=["exception-swallow"])
        assert report.ok  # the lock-order entry is not stale-checked

    def test_baseline_round_trip(self, tmp_path):
        files = {
            "a.py": "try:\n    pass\nexcept Exception:\n    pass\n",
            "b.py": "try:\n    pass\nexcept:\n    pass\n",
        }
        p = tmp_path / "al.txt"
        report = run_rules(make_ctx(files), Allowlist(),
                           rules=["exception-swallow"])
        assert len(report.findings) == 2
        write_baseline(report, str(p))
        al = Allowlist.load(str(p))
        report2 = run_rules(make_ctx(files), al,
                            rules=["exception-swallow"])
        assert report2.ok
        assert len(report2.allowlisted) == 2

    def test_identity_is_line_independent(self):
        src1 = "def f():\n    try:\n        pass\n" \
               "    except Exception:\n        pass\n"
        src2 = "# moved\n\n\n" + src1
        (f1,) = run_one("exception-swallow", {"a.py": src1})
        (f2,) = run_one("exception-swallow", {"a.py": src2})
        assert f1.key == f2.key
        assert f1.line != f2.line


# -- exception-swallow --------------------------------------------------------

SWALLOW = """
import logging
log = logging.getLogger("x")

def swallowed():
    try:
        work()
    except Exception:
        pass

def bare():
    try:
        work()
    except:
        return 0

def reraised():
    try:
        work()
    except Exception:
        raise

def logged():
    try:
        work()
    except Exception as e:
        log.warning("failed: %%s", e)

def classified():
    try:
        work()
    except Exception as e:
        label = classify(e)

def handed_on():
    try:
        work()
    except Exception as e:
        job.fail(str(e))

def handed_on_kw():
    try:
        work()
    except Exception as e:
        job.fail(error=str(e))

def typed():
    try:
        work()
    except ValueError:
        pass
"""


class TestExceptionSwallow:
    def test_positive_negative(self):
        out = run_one("exception-swallow", {"m.py": SWALLOW})
        idents = {f.ident for f in out}
        assert idents == {"swallow@swallowed", "swallow@bare"}

    def test_multiple_handlers_disambiguated(self):
        src = ("def f():\n"
               "    try:\n        a()\n    except Exception:\n"
               "        pass\n"
               "    try:\n        b()\n    except Exception:\n"
               "        pass\n")
        out = run_one("exception-swallow", {"m.py": src})
        assert {f.ident for f in out} == {"swallow@f", "swallow@f#1"}


# -- lock rules ---------------------------------------------------------------

CYCLE = """
import threading
_A = threading.Lock()
_B = threading.Lock()

def one():
    with _A:
        with _B:
            pass

def two():
    with _B:
        with _A:
            pass
"""

NO_CYCLE = """
import threading
_A = threading.Lock()
_B = threading.Lock()

def one():
    with _A:
        with _B:
            pass

def two():
    with _A:
        with _B:
            pass
"""

SELF_DEADLOCK = """
import threading
_A = threading.Lock()
_R = threading.RLock()

def bad():
    with _A:
        with _A:
            pass

def fine():
    with _R:
        with _R:
            pass
"""

CROSS_CALL_CYCLE = """
import threading
_A = threading.Lock()
_B = threading.Lock()

def takes_b():
    with _B:
        helper()

def helper():
    with _A:
        pass

def takes_a():
    with _A:
        with _B:
            pass
"""


class TestLockOrder:
    def test_cycle_detected(self):
        out = run_one("lock-order", {"m.py": CYCLE})
        assert len(out) == 1
        assert out[0].ident.startswith("cycle:")
        assert "m._A" in out[0].ident and "m._B" in out[0].ident

    def test_consistent_order_clean(self):
        assert run_one("lock-order", {"m.py": NO_CYCLE}) == []

    def test_self_deadlock_plain_lock_only(self):
        out = run_one("lock-order", {"m.py": SELF_DEADLOCK})
        assert [f.ident for f in out] == ["self-deadlock:m._A"]

    def test_cycle_through_call_graph(self):
        out = run_one("lock-order", {"m.py": CROSS_CALL_CYCLE})
        assert len(out) == 1 and out[0].ident.startswith("cycle:")

    def test_multi_item_with_orders(self):
        src = ("import threading\n"
               "_A = threading.Lock()\n_B = threading.Lock()\n"
               "def one():\n    with _A, _B:\n        pass\n"
               "def two():\n    with _B:\n        with _A:\n"
               "            pass\n")
        out = run_one("lock-order", {"m.py": src})
        assert len(out) == 1 and out[0].ident.startswith("cycle:")

    def test_uninventoried_self_lock_not_guessed(self):
        # class A's lock comes from a helper (not inventoried); its
        # nested with must NOT bind to class B's same-named plain Lock
        src = ("import threading\n"
               "class A:\n"
               "    def __init__(self):\n"
               "        self._mu = make_rlock()\n"
               "    def reenter(self):\n"
               "        with self._mu:\n"
               "            with self._mu:\n"
               "                pass\n"
               "class B:\n"
               "    def __init__(self):\n"
               "        self._mu = threading.Lock()\n")
        assert run_one("lock-order", {"m.py": src}) == []


BLOCKING = """
import threading
import time
_LOCK = threading.Lock()

def bad():
    with _LOCK:
        time.sleep(0.1)

def bad2(fn):
    with _LOCK:
        call_supervised(fn)

def fine():
    with _LOCK:
        x = 1
    time.sleep(0.1)

class C:
    def __init__(self):
        self._mu = threading.Lock()

    def inst_lock_ok(self):
        with self._mu:
            time.sleep(0.1)  # instance lock: out of scope for this rule
"""


class TestBlockingWhileLocked:
    def test_positive_negative(self):
        out = run_one("blocking-while-locked", {"m.py": BLOCKING})
        assert {f.ident for f in out} == {
            "blocking:sleep@bad", "blocking:call_supervised@bad2"}


# -- traced-value hazard ------------------------------------------------------

TRACED = """
import jax
from functools import partial

def body(x, n):
    if n > 0:
        return x
    return x * 2

_k = observed_jit(body)

def shaped(x):
    if x.shape[0] > 4:
        return x
    return int(x.shape[0]) + len(x)

_k2 = observed_jit(shaped)

@partial(jax.jit, static_argnames=("cap",))
def bucketed(x, cap):
    if cap > 8:
        return x
    return x

@jax.jit
def concretizes(x):
    return int(x)

def plain(x):
    if x > 0:
        return 1
    return 0
"""


class TestTracedValueHazard:
    def test_findings(self):
        out = run_one("traced-value-hazard", {"m.py": TRACED})
        idents = {f.ident for f in out}
        # body branches on traced n; concretizes int()s its arg; the
        # shape-derived branch, static_argnames branch and the un-jitted
        # plain() are all clean
        assert idents == {"branch@body", "concretize-int@concretizes"}

    def test_range_and_iteration(self):
        src = ("import jax\n"
               "@jax.jit\n"
               "def f(n, xs):\n"
               "    for i in range(n):\n"
               "        pass\n"
               "    for v in xs:\n"
               "        pass\n")
        out = run_one("traced-value-hazard", {"m.py": src})
        assert {f.ident for f in out} == {"iterate@f", "iterate@f#1"}


# -- taxonomy -----------------------------------------------------------------

ERRORS_OK = """
class ErrCode:
    BackoffExhausted = 9005
    DeviceHang = 9008

class BackoffExhaustedError(Exception):
    code = ErrCode.BackoffExhausted

class DeviceHangError(Exception):
    code = ErrCode.DeviceHang
"""

BACKOFF_OK = """
CLASS_HANG = "hang"
CLASS_OTHER = "other"

def classify(err):
    from ..errors import DeviceHangError
    if isinstance(err, DeviceHangError):
        return CLASS_HANG
    return CLASS_OTHER
"""


class TestTaxonomy:
    def test_clean(self):
        out = run_one("taxonomy-consistency",
                      {"errors.py": ERRORS_OK,
                       "utils/backoff.py": BACKOFF_OK})
        assert out == []

    def test_duplicate_engine_code(self):
        errors = ERRORS_OK + "\nclass OtherError(Exception):\n" \
            "    code = 9008\n"
        out = run_one("taxonomy-consistency",
                      {"errors.py": errors,
                       "utils/backoff.py": BACKOFF_OK})
        assert any(f.ident == "dup-code:9008" for f in out)

    def test_orphan_code(self):
        errors = ERRORS_OK.replace(
            "    DeviceHang = 9008",
            "    DeviceHang = 9008\n    Reserved = 9011")
        out = run_one("taxonomy-consistency",
                      {"errors.py": errors,
                       "utils/backoff.py": BACKOFF_OK})
        assert any(f.ident == "orphan-code:Reserved" for f in out)

    def test_dead_class_constant(self):
        backoff = BACKOFF_OK + '\nCLASS_GHOST = "ghost"\n'
        out = run_one("taxonomy-consistency",
                      {"errors.py": ERRORS_OK,
                       "utils/backoff.py": backoff})
        assert any(f.ident == "dead-class:CLASS_GHOST" for f in out)

    def test_unclassified_device_error(self):
        errors = ERRORS_OK + "\nclass DeviceGhostError(Exception):\n" \
            "    code = 9013\n"
        out = run_one("taxonomy-consistency",
                      {"errors.py": errors,
                       "utils/backoff.py": BACKOFF_OK})
        assert any(f.ident == "unclassified:DeviceGhostError"
                   for f in out)


# -- failpoint coverage -------------------------------------------------------

HARNESS = """
READ_FAULTS = {"known-point": ["panic"]}
WRITE_FAULTS = {"txn-point": ["1*panic"]}
THREADED_FAULTS = {"threaded-point": ["sleep(0.01)"]}
"""

INJECTS = """
from .utils import failpoint

def covered():
    failpoint.inject("known-point")
    failpoint.inject("txn-point")
    failpoint.inject("threaded-point")

def uncovered():
    failpoint.inject("ghost-point")

def nonliteral(name):
    failpoint.inject(name)
"""


class TestFailpointCoverage:
    def test_positive_negative(self):
        out = run_one("failpoint-coverage", {"m.py": INJECTS},
                      aux={"tests/chaos_harness.py": HARNESS})
        idents = {f.ident for f in out}
        assert idents == {"uncataloged:ghost-point",
                          "inject-nonliteral@nonliteral"}

    def test_no_harness_no_coverage_check(self):
        out = run_one("failpoint-coverage", {"m.py": INJECTS})
        assert {f.ident for f in out} == {"inject-nonliteral@nonliteral"}


# -- gauge consistency --------------------------------------------------------

GAUGE_STATUS = """
def _status(self):
    from ..executor import widget
    return {"device_widget": widget.snapshot()}
"""

GAUGE_WIDGET = """
STATS = {"widget_hits": 0, "widget_lost": 0}

def snapshot():
    return {"widget_hits": STATS["widget_hits"]}

def report_gauges():
    return {"widget_hits": STATS["widget_hits"]}

def _publish_gauges():
    vals = {"widget_hits": STATS["widget_hits"],
            "widget_lost": STATS["widget_lost"]}
    for obs in []:
        for k, v in vals.items():
            obs.set_gauge(k, v)
"""

GAUGE_EXEC = """
from . import widget

class Exec:
    def execute(self):
        self.annotate(**widget.report_gauges())
"""


class TestGaugeConsistency:
    def test_unsurfaced_found_surfaced_clean(self):
        out = run_one("gauge-consistency",
                      {"server/http_status.py": GAUGE_STATUS,
                       "executor/widget.py": GAUGE_WIDGET,
                       "executor/exec_select.py": GAUGE_EXEC})
        idents = {f.ident for f in out}
        # widget_hits reaches /status via snapshot() and EXPLAIN via the
        # report_gauges splat; widget_lost reaches neither
        assert idents == {"unsurfaced-status:widget_lost",
                          "unsurfaced-explain:widget_lost"}

    def test_annotate_kwarg_counts_as_surfaced(self):
        exec_src = GAUGE_EXEC + (
            "\n\ndef annotate_direct(self, n):\n"
            "    self.annotate(widget_lost=n)\n")
        status = GAUGE_STATUS.replace(
            '"device_widget": widget.snapshot()',
            '"device_widget": widget.snapshot(), "widget_lost": 0')
        out = run_one("gauge-consistency",
                      {"server/http_status.py": status,
                       "executor/widget.py": GAUGE_WIDGET,
                       "executor/exec_select.py": exec_src})
        assert out == []

    # -- the ISSUE 18 fleet-inventory extension: snapshot()-fed fields
    # pinned on both the publishing module and the /metrics side

    FLEET_PERF_SRC = ('def stats():\n'
                      '    return {"perf_notes": 1, "perf_merged": 2}\n')
    FLEET_STATUS_OK = ('FLEET_KEYS = ("perf_notes", "perf_merged")\n')

    def test_fleet_inventory_both_sides_clean(self):
        assert run_one("gauge-consistency",
                       {"server/http_status.py": self.FLEET_STATUS_OK,
                        "fabric/perf.py": self.FLEET_PERF_SRC}) == []

    def test_fleet_inventory_missing_status_side(self):
        out = run_one("gauge-consistency",
                      {"server/http_status.py":
                       'FLEET_KEYS = ("perf_notes",)\n',
                       "fabric/perf.py": self.FLEET_PERF_SRC})
        assert ({f.ident for f in out}
                == {"fleet-inventory-status:perf_merged"}), out

    def test_fleet_inventory_missing_source_side(self):
        out = run_one("gauge-consistency",
                      {"server/http_status.py": self.FLEET_STATUS_OK,
                       "fabric/perf.py":
                       'def stats():\n    return {"perf_notes": 1}\n'})
        assert ({f.ident for f in out}
                == {"fleet-inventory-source:perf_merged"}), out


# -- trace-coverage -----------------------------------------------------------

TRACE_COV_BAD = """
from ..ops.device import DeviceUnsupported
from ..session import tracing

def run_device(ctx, fn):
    if bad():
        raise DeviceUnsupported("degraded silently")

def _run_device_admitted(ctx):
    raise DeviceUnsupported("also silent")

def helper_not_audited(ctx):
    raise DeviceUnsupported("feature gap — out of scope")
"""

TRACE_COV_OK = """
from ..ops.device import DeviceUnsupported
from ..session import tracing

def run_device(ctx, fn):
    with tracing.span("device.dispatch"):
        if bad():
            raise DeviceUnsupported("span-wrapped")

def _run_device_admitted(ctx):
    if bad():
        tracing.event("host_degraded", reason="breaker_open")
        raise DeviceUnsupported("event precedes the raise")
    raise OtherError("not a degradation exception")
"""

TRACE_COV_EVENT_AFTER = """
from ..ops.device import DeviceUnsupported
from ..session import tracing

def run_device(ctx):
    if bad():
        raise DeviceUnsupported("event comes too late")
    tracing.event("host_degraded", reason="x")
"""


class TestTraceCoverage:
    def test_unmarked_degradation_found(self):
        out = run_one("trace-coverage",
                      {"executor/device_exec.py": TRACE_COV_BAD})
        assert len(out) == 2, out  # audited fns only, helper exempt
        assert all(f.ident.startswith("degrade@") for f in out)

    def test_span_wrap_and_event_comply(self):
        assert run_one("trace-coverage",
                       {"executor/device_exec.py": TRACE_COV_OK}) == []

    def test_event_after_raise_does_not_count(self):
        out = run_one("trace-coverage",
                      {"executor/device_exec.py": TRACE_COV_EVENT_AFTER})
        assert len(out) == 1

    def test_unaudited_file_ignored(self):
        assert run_one("trace-coverage",
                       {"executor/rogue.py": TRACE_COV_BAD}) == []


# -- span-chokepoints / kernel-scope-vocabulary -------------------------------

SPAN_CHOKE_OK = """
from ..session import tracing

def device_agg(plan):
    with tracing.span("upload.h2d") as usp:
        pass

def _stream_block(col_arrays, lo, hi, batch_rows):
    with tracing.span("upload.h2d") as sp:
        return {}

def _fetch(make_tree):
    with tracing.span("fetch.d2h") as sp:
        if sp is None:
            return make_tree()
        with tracing.span("device.wait"):
            tree = make_tree()
        with tracing.span("fetch.copy", arrays=1, bytes=8):
            return tree

def _assemble_agg(plan):
    with tracing.span("host.assemble", rows=1):
        return 1
"""

#: the wait and the copy back under one name again: two of three missing
SPAN_CHOKE_FETCH_WHOLE = SPAN_CHOKE_OK.replace(
    'with tracing.span("device.wait"):', 'if True:').replace(
    'with tracing.span("fetch.copy", arrays=1, bytes=8):', 'if True:')

JOIN_INDEX_OK = """
from ..session import tracing

def build_join_index(columns, mask_fn=None):
    with tracing.span("join.index_build") as sp:
        return _build_index(columns, mask_fn)
"""

JOIN_INDEX_BAD = """
from ..session import tracing

def build_join_index(columns, mask_fn=None):
    return _build_index(columns, mask_fn)

def _build_index(columns, mask_fn):
    with tracing.span("join.index_build"):   # a hit would open it too
        return None
"""

SPAN_CHOKE_BAD = """
from ..session import tracing

def device_agg(plan):
    with tracing.span("upload.hd2"):      # misspelt
        pass

def _fetch(make_tree):
    return make_tree()                    # the span is gone

def helper():
    with tracing.span("host.assemble"):   # right span, wrong function
        pass
"""

SCOPE_VOCAB = """
KERNEL_SCOPES = (
    "k_filter",     # comments between the names are fine
    "k_agg_sort",
)
"""

SCOPE_USES = """
import jax

def body(x, name):
    with jax.named_scope("k_filter"):
        x = x + 1
    with jax.named_scope("k_agg_srot"):
        x = x * 2
    with jax.named_scope(name):
        x = x - 1
    return x

@jax.named_scope("k_agg_sort")
def decorated(x):
    return x

@jax.named_scope("sort")
def collides_with_a_primitive(x):
    return x
"""


class TestSpanChokepoints:
    def test_named_functions_open_their_spans(self):
        assert run_one("span-chokepoints",
                       {"executor/device_exec.py": SPAN_CHOKE_OK}) == []

    def test_missing_misspelt_and_misplaced_found(self):
        out = run_one("span-chokepoints",
                      {"executor/device_exec.py": SPAN_CHOKE_BAD})
        assert sorted(f.ident for f in out) == [
            "span@_assemble_agg:host.assemble", "span@_fetch:device.wait",
            "span@_fetch:fetch.copy", "span@_fetch:fetch.d2h",
            "span@_stream_block:upload.h2d", "span@device_agg:upload.h2d"]

    def test_every_span_of_a_function_is_wanted(self):
        out = run_one("span-chokepoints",
                      {"executor/device_exec.py": SPAN_CHOKE_FETCH_WHOLE})
        assert sorted(f.ident for f in out) == [
            "span@_fetch:device.wait", "span@_fetch:fetch.copy"]

    @pytest.mark.parametrize("text,want", [
        (JOIN_INDEX_OK, []),
        (JOIN_INDEX_BAD, ["span@build_join_index:join.index_build"]),
    ])
    def test_join_index_build_opens_its_span(self, text, want):
        out = run_one("span-chokepoints",
                      {"executor/join_index.py": text})
        assert [f.ident for f in out] == want

    def test_tree_without_the_layer_is_skipped(self):
        assert run_one("span-chokepoints",
                       {"executor/rogue.py": SPAN_CHOKE_BAD}) == []


class TestKernelScopeVocabulary:
    def test_misspelt_computed_and_foreign_scopes_found(self):
        out = run_one("kernel-scope-vocabulary",
                      {"ops/device.py": SCOPE_VOCAB,
                       "executor/device_exec.py": SCOPE_USES})
        assert sorted(f.ident for f in out) == [
            "scope@body:None", "scope@body:k_agg_srot",
            "scope@collides_with_a_primitive:sort"]

    def test_no_vocabulary_no_check(self):
        assert run_one("kernel-scope-vocabulary",
                       {"executor/device_exec.py": SCOPE_USES}) == []

    def test_package_vocabulary_is_the_programs(self):
        """The rule reads the tuple from the AST; it must see exactly
        what the program imports."""
        from tidb_tpu.lint.rules.trace_cov import KernelScopeVocabulary
        from tidb_tpu.ops.device import KERNEL_SCOPES
        text = open(sys.modules["tidb_tpu.ops.device"].__file__).read()
        got = KernelScopeVocabulary._vocabulary(
            make_ctx({"ops/device.py": text}))
        assert got == set(KERNEL_SCOPES) and len(KERNEL_SCOPES) == 8


# -- codec-rpc-trace ----------------------------------------------------------

CODEC_RPC_BAD = """
from . import codec

def call(sock, req):
    codec.write_frame(sock, req)
    return codec.read_frame(sock)
"""

CODEC_RPC_OK = """
from . import codec
from ..session import tracing

def call(sock, req):
    ctx = tracing.wire_ctx()
    if ctx is not None:
        req["trace"] = ctx
    codec.write_frame(sock, req)
    resp = codec.read_frame(sock)
    tracing.attach_remote(resp.pop("_trace", None))
    return resp

def serve(sock, coord):
    req = codec.read_frame(sock)
    rtr = tracing.begin_remote(req.pop("trace", None), "op")
    codec.write_frame(sock, {"ok": True})
    return rtr
"""


class TestCodecRpcTrace:
    def test_unpropagated_rpc_found(self):
        out = run_one("codec-rpc-trace",
                      {"fabric/widget_net.py": CODEC_RPC_BAD})
        assert len(out) == 1 and out[0].ident.startswith("rpc@"), out

    def test_client_and_server_forms_comply(self):
        assert run_one("codec-rpc-trace",
                       {"fabric/widget_net.py": CODEC_RPC_OK}) == []

    def test_codec_transport_and_non_fabric_exempt(self):
        assert run_one("codec-rpc-trace",
                       {"fabric/codec.py": CODEC_RPC_BAD,
                        "executor/widget.py": CODEC_RPC_BAD}) == []


# -- guard inference + guarded-state ------------------------------------------

# fixtures live at an AUDITED rel path (rules/guards.py AUDITED) so the
# state inventory picks them up
GPATH = "executor/scheduler.py"

GUARDED = """
import threading
_LOCK = threading.Lock()
_CACHE = {}

def locked_read(k):
    with _LOCK:
        return _CACHE.get(k)

def locked_write(k, v):
    with _LOCK:
        _CACHE[k] = v

def locked_len():
    with _LOCK:
        return len(_CACHE)

def rogue_read(k):
    return _CACHE.get(k)

def rogue_write(k, v):
    _CACHE[k] = v
"""

GUARDED_CLEAN = """
import threading
_LOCK = threading.Lock()
_CACHE = {}

def locked_read(k):
    with _LOCK:
        return _CACHE.get(k)

def locked_write(k, v):
    with _LOCK:
        _CACHE[k] = v
"""

PROPAGATED = """
import threading
_LOCK = threading.Lock()
_STATS = {"n": 0}

def outer():
    with _LOCK:
        _bump_locked()

def outer2():
    with _LOCK:
        _STATS["n"] += 1

def _bump_locked():
    _STATS["n"] += 1
"""

MULTILOCK = """
import threading
_A = threading.Lock()
_B = threading.Lock()
_STATE = {}

def both(k, v):
    with _A, _B:
        _STATE[k] = v

def a_only(k):
    with _A:
        return _STATE.get(k)

def rogue(k):
    return _STATE.get(k)
"""

LOCAL_AND_INIT = """
import threading
_LOCK = threading.Lock()

class Svc:
    def __init__(self):
        self._mu = threading.Lock()
        self.table = {}

    def put(self, k, v):
        with self._mu:
            self.table[k] = v

    def get(self, k):
        with self._mu:
            return self.table.get(k)

def local_only():
    table = {}
    table["k"] = 1
    return table
"""

NO_MAJORITY = """
import threading
_LOCK = threading.Lock()
_FREE = {}

def locked_once(k):
    with _LOCK:
        return _FREE.get(k)

def free1(k):
    return _FREE.get(k)

def free2(k, v):
    _FREE[k] = v
"""


class TestGuardedState:
    def test_majority_vote_flags_minority_sites(self):
        out = run_one("guarded-state", {GPATH: GUARDED})
        assert {f.ident for f in out} == {
            "unguarded:_CACHE@rogue_read", "unguarded:_CACHE@rogue_write"}
        msgs = {f.ident: f.msg for f in out}
        assert "read of" in msgs["unguarded:_CACHE@rogue_read"]
        assert "write to" in msgs["unguarded:_CACHE@rogue_write"]

    def test_call_propagated_guard_counts(self):
        # _bump_locked's write runs under _LOCK at every resolved call
        # site, so it is guarded — no findings
        assert run_one("guarded-state", {GPATH: PROPAGATED}) == []

    def test_multi_lock_with_scope(self):
        out = run_one("guarded-state", {GPATH: MULTILOCK})
        assert [f.ident for f in out] == ["unguarded:_STATE@rogue"]

    def test_local_state_and_init_writes_exempt(self):
        assert run_one("guarded-state", {GPATH: LOCAL_AND_INIT}) == []

    def test_no_inference_without_majority(self):
        assert run_one("guarded-state", {GPATH: NO_MAJORITY}) == []

    def test_unaudited_file_ignored(self):
        assert run_one("guarded-state",
                       {"executor/rogue_module.py": GUARDED}) == []

    def test_cross_module_access_votes(self):
        clearer = (
            "from . import scheduler\n"
            "def clear_all():\n"
            "    scheduler._CACHE.clear()\n")
        out = run_one("guarded-state",
                      {GPATH: GUARDED_CLEAN,
                       "executor/supervisor.py": clearer})
        assert [f.ident for f in out] == ["unguarded:_CACHE@clear_all"]


# -- check-then-act -----------------------------------------------------------

CTA_BUG = """
import threading
_LOCK = threading.Lock()
_JOBS = {}

def submit(key, job):
    with _LOCK:
        in_flight = key in _JOBS
    if in_flight:
        return None
    with _LOCK:
        _JOBS[key] = job
    return job
"""

CTA_FIXED = CTA_BUG.replace(
    "    with _LOCK:\n        _JOBS[key] = job\n",
    "    with _LOCK:\n        if key in _JOBS:\n"
    "            return None\n        _JOBS[key] = job\n")

CTA_SAME_HOLD = """
import threading
_LOCK = threading.Lock()
_JOBS = {}

def submit(key, job):
    with _LOCK:
        if key in _JOBS:
            return None
        _JOBS[key] = job
    return job

def drain(key):
    with _LOCK:
        return _JOBS.pop(key, None)
"""

CTA_UNGUARDED_ACT = """
import threading
_LOCK = threading.Lock()
_JOBS = {}

def anchor(key):
    with _LOCK:
        return _JOBS.get(key)

def anchor2(key, v):
    with _LOCK:
        _JOBS[key] = v

def submit(key, job):
    with _LOCK:
        have = key in _JOBS
    if not have:
        _JOBS[key] = job
"""

CTA_SIBLING_RECHECK = """
import threading
_LOCK = threading.Lock()
_FLAG = [False]
_GEN = [0]

def fence_clear():
    with _LOCK:
        if not _FLAG[0]:
            return
        gen = _GEN[0]
    reinit()
    with _LOCK:
        if _GEN[0] == gen:
            _FLAG[0] = False

def arm():
    with _LOCK:
        _FLAG[0] = True
        _GEN[0] += 1
"""


class TestCheckThenAct:
    def test_split_check_and_act_flagged(self):
        out = run_one("check-then-act", {GPATH: CTA_BUG})
        assert [f.ident for f in out] == ["check-then-act:_JOBS@submit"]

    def test_recheck_in_acting_hold_clean(self):
        assert run_one("check-then-act", {GPATH: CTA_FIXED}) == []

    def test_check_and_act_in_one_hold_clean(self):
        assert run_one("check-then-act", {GPATH: CTA_SAME_HOLD}) == []

    def test_unguarded_act_after_check_flagged(self):
        out = run_one("check-then-act", {GPATH: CTA_UNGUARDED_ACT})
        assert [f.ident for f in out] == ["check-then-act:_JOBS@submit"]
        assert "no lock held" in out[0].msg

    def test_sibling_state_recheck_suppresses(self):
        # the _maybe_reinit pattern: the acting hold re-validates a
        # generation counter guarded by the same lock
        assert run_one("check-then-act", {GPATH: CTA_SIBLING_RECHECK}) == []


# -- locked-suffix-contract ---------------------------------------------------

LSC = """
import threading
_LOCK = threading.Lock()

def _drain_locked():
    pass

def good():
    with _LOCK:
        _drain_locked()

def bad():
    _drain_locked()
"""

LSC_PROPAGATED = """
import threading
_LOCK = threading.Lock()

def outer():
    with _LOCK:
        _middle_locked()

def _middle_locked():
    _inner_locked()

def _inner_locked():
    pass
"""

LSC_ACQUIRES = """
import threading
_LOCK = threading.Lock()

def _grab_locked():
    with _LOCK:
        pass

def caller():
    with _LOCK:
        _grab_locked()
"""


class TestLockedSuffixContract:
    def test_unlocked_call_flagged(self):
        out = run_one("locked-suffix-contract", {GPATH: LSC})
        assert [f.ident for f in out] == ["unlocked-call:_drain_locked@bad"]

    def test_call_propagated_lock_satisfies_contract(self):
        assert run_one("locked-suffix-contract",
                       {GPATH: LSC_PROPAGATED}) == []

    def test_acquiring_own_guard_flagged(self):
        out = run_one("locked-suffix-contract", {GPATH: LSC_ACQUIRES})
        assert any(f.ident == "acquires-guard:_grab_locked" for f in out)


# -- sysvar-scope -------------------------------------------------------------

SVS_DUAL_OK = """
def attach(ctx):
    dom = getattr(ctx, "domain", None)
    if dom is not None:
        budget = int(dom.global_vars.get("tidb_device_mem_budget", 0))
    else:
        budget = int(ctx.get_sysvar("tidb_device_mem_budget"))
    return budget
"""

SVS_SESSION_READ = """
def attach(ctx):
    return int(ctx.get_sysvar("tidb_device_mem_budget"))
"""

SVS_GLOBAL_READ = """
def group_of(dom):
    return dom.global_vars.get("tidb_resource_group", "default")
"""

SVS_DISPATCHER = """
def refresh(ctx):
    dom = getattr(ctx, "domain", None)
    if dom is not None:
        gv = dom.global_vars
        src = lambda n, d: gv.get(n, d)
    else:
        src = lambda n, d: ctx.get_sysvar(n)
    depth = src("tidb_device_sched_queue_depth", 64)
    grp = src("tidb_resource_group", "default")
    return depth, grp
"""

SVS_UNDECLARED = """
def f(ctx):
    return ctx.get_sysvar("tidb_device_mystery_knob")
"""


class TestSysvarScope:
    def test_dual_path_fallback_clean(self):
        assert run_one("sysvar-scope", {"ops/residency.py": SVS_DUAL_OK}) \
            == []

    def test_session_read_of_process_knob_flagged(self):
        out = run_one("sysvar-scope", {"ops/residency.py": SVS_SESSION_READ})
        assert [f.ident for f in out] == [
            "session-read:tidb_device_mem_budget@attach"]

    def test_global_read_of_session_knob_flagged(self):
        out = run_one("sysvar-scope", {"m.py": SVS_GLOBAL_READ})
        assert [f.ident for f in out] == [
            "global-read:tidb_resource_group@group_of"]

    def test_dual_dispatcher_scopes(self):
        out = run_one("sysvar-scope", {"m.py": SVS_DISPATCHER})
        # the process knob through the dual dispatcher is the sanctioned
        # discipline; the session knob through it reads global-first
        assert [f.ident for f in out] == [
            "global-read:tidb_resource_group@refresh"]

    def test_undeclared_serving_knob_flagged(self):
        out = run_one("sysvar-scope", {"m.py": SVS_UNDECLARED})
        assert [f.ident for f in out] == [
            "undeclared:tidb_device_mystery_knob@f"]

    def test_defining_modules_exempt(self):
        assert run_one("sysvar-scope",
                       {"session/session.py": SVS_GLOBAL_READ}) == []


# -- migrated confinement rules ----------------------------------------------

class TestConfinementRules:
    def test_jit_confinement(self):
        src = "import jax\n\ndef f(fn):\n    return jax.jit(fn)\n"
        out = run_one("jit-confinement", {"executor/rogue.py": src})
        assert [f.ident for f in out] == ["jax.jit@f"]
        # the sanctioned compile layer is rule config, not a finding
        assert run_one("jit-confinement",
                       {"executor/compile_service.py": src}) == []

    def test_jit_aot_chain(self):
        src = "import jax\nk = jax.jit(f).lower(x).compile()\n"
        out = run_one("jit-confinement", {"m.py": src})
        idents = {f.ident for f in out}
        assert "jax.jit@<module>" in idents
        assert any(i.startswith("jit-aot-") for i in idents)

    def test_device_slot_confinement(self):
        src = ("def f(col):\n    col._device = thing\n"
               "\ndef g(col):\n    col._device = None\n")
        out = run_one("device-slot-confinement", {"m.py": src})
        assert {f.ident for f in out} == {"_device@f", "_device=None@g"}
        assert run_one("device-slot-confinement",
                       {"ops/residency.py": src}) == []
        # chunk.py may None-init the slot but not otherwise touch it
        out = run_one("device-slot-confinement", {"utils/chunk.py": src})
        assert {f.ident for f in out} == {"_device@f"}

    def test_supervised_confinement(self):
        src = "def f(fn):\n    return call_supervised(fn, deadline_s=1)\n"
        out = run_one("supervised-confinement", {"m.py": src})
        assert [f.ident for f in out] == ["call_supervised@f"]
        assert run_one("supervised-confinement",
                       {"executor/scheduler.py": src}) == []

    def test_confinement_not_allowlistable(self, tmp_path):
        """An allowlist line can never quietly neutralize an
        architectural gate: the finding stays AND the entry is stale."""
        src = "import jax\n\ndef f(fn):\n    return jax.jit(fn)\n"
        p = tmp_path / "al.txt"
        p.write_text("jit-confinement executor/rogue.py:* -- nope\n")
        report = run_rules(make_ctx({"executor/rogue.py": src}),
                           Allowlist.load(str(p)),
                           rules=["jit-confinement"])
        assert len(report.findings) == 1
        assert len(report.stale) == 1
        assert not report.ok

    def test_run_device_shape(self):
        src = ("def a(ctx, fn):\n    return run_device(ctx, fn)\n"
               "\ndef b(ctx, fn):\n"
               "    return run_device(ctx, fn, shape='join')\n"
               "\ndef c(ctx, fn):\n"
               "    return x._with_pipe_stats(run_device, ctx, fn)\n")
        out = run_one("run-device-shape", {"m.py": src})
        assert {f.ident for f in out} == {
            "run_device@a", "_with_pipe_stats@c"}

    def test_shared_memory_confinement(self):
        """Every way of reaching multiprocessing.shared_memory outside
        tidb_tpu/fabric/ is a finding; the fabric package itself is the
        sanctioned layer (rule config, like the other confinements)."""
        imp_from = ("from multiprocessing import shared_memory\n"
                    "def f():\n"
                    "    return shared_memory.SharedMemory(name='x')\n")
        imp_mod = ("import multiprocessing.shared_memory\n"
                   "def g():\n"
                   "    return multiprocessing.shared_memory\n")
        ctor = ("def h():\n    return SharedMemory(name='x', create=True)\n")
        out = run_one("shared-memory-confinement",
                      {"executor/rogue.py": imp_from})
        assert any(f.ident.startswith("shm-import@") for f in out)
        assert any(f.ident.startswith("shm-ctor@") for f in out)
        out = run_one("shared-memory-confinement", {"ops/x.py": imp_mod})
        assert any(f.ident.startswith("shm-import@") for f in out)
        assert any(f.ident.startswith("shm-attr@") for f in out)
        out = run_one("shared-memory-confinement", {"session/y.py": ctor})
        assert [f.ident for f in out] == ["shm-ctor@h"]
        # the fabric package is the sanctioned coordination layer
        assert run_one("shared-memory-confinement",
                       {"fabric/coord.py": imp_from + imp_mod + ctor}) \
            == []


# -- the tier-1 gate: full-repo run is clean ----------------------------------

class TestFullRepo:
    def test_repo_clean(self):
        report = run_repo()
        assert len(report.rules_run) >= 10
        assert not report.findings, report.human()
        assert not report.stale, report.human()
        # the burn-down inventory is real: allowlisted findings exist and
        # every entry carries a reason
        assert report.allowlisted
        assert all(e.reason for _f, e in report.allowlisted)

    def test_cli_json_exit_zero(self):
        import os
        import tidb_tpu
        repo_root = os.path.dirname(os.path.dirname(
            os.path.abspath(tidb_tpu.__file__)))
        proc = subprocess.run(
            [sys.executable, "-m", "tidb_tpu.lint", "--json"],
            capture_output=True, text=True, timeout=300, cwd=repo_root,
            env={**os.environ, "JAX_PLATFORMS": "cpu"})
        assert proc.returncode == 0, proc.stdout + proc.stderr
        payload = json.loads(proc.stdout)
        assert payload["ok"] is True
        assert payload["counts"]["findings"] == 0
        assert payload["counts"]["allowlisted"] > 0
        # per-rule timings ride the JSON report (--stats data source);
        # shared-model fixpoints get their own row so no rule is
        # mischarged for building them
        assert set(payload["timings_s"]) - {"shared-models"} \
            == set(payload["rules"])
        assert "shared-models" in payload["timings_s"]

    def test_race_rules_registered_and_clean(self):
        """The ISSUE-11 zero-findings gate: the four race rules are in
        the registry and the repo is clean under each — any new
        unguarded access / split critical section / contract breach /
        mis-scoped sysvar read fails tier-1 here."""
        from tidb_tpu.lint import run_rule
        for rule in ("guarded-state", "check-then-act",
                     "locked-suffix-contract", "sysvar-scope"):
            assert rule in RULES
            findings = run_rule(rule)
            assert findings == [], "\n".join(
                f"{f.rel}:{f.line}: {f.msg}" for f in findings)

    def test_guarded_state_allowlist_entries_all_carry_reasons(self):
        """Every deliberate lock-free access is inventoried: the repo
        HAS guarded-state allowlist entries (the documentation of every
        GIL-atomic fast path), each with a reason."""
        report = run_repo(rules=["guarded-state"])
        assert report.allowlisted, "expected documented lock-free sites"
        for _f, e in report.allowlisted:
            assert e.reason

    def test_runtime_budget(self):
        """The merge gate stays cheap: a fresh full-repo run (parse +
        every rule, shared-model fixpoints included) under 20s on CPU.
        Min of two runs: a transient load spike on the CI box must not
        fail the budget, a real 2x regression fails both."""
        import time
        from tidb_tpu.lint.engine import (Allowlist as AL, collect,
                                          default_allowlist_path,
                                          run_rules as rr)
        best = float("inf")
        for _ in range(2):
            t0 = time.perf_counter()
            ctx = collect()  # fresh Context: no cached analysis models
            rr(ctx, AL.load(default_allowlist_path()))
            best = min(best, time.perf_counter() - t0)
            if best < 20.0:
                break
        assert best < 20.0, f"full-repo lint took {best:.1f}s (budget 20s)"

    def test_cli_rule_and_path_filters(self):
        import os
        import tidb_tpu
        repo_root = os.path.dirname(os.path.dirname(
            os.path.abspath(tidb_tpu.__file__)))
        env = {**os.environ, "JAX_PLATFORMS": "cpu"}
        proc = subprocess.run(
            [sys.executable, "-m", "tidb_tpu.lint", "--rule",
             "guarded-state", "--path", "executor/*", "--json"],
            capture_output=True, text=True, timeout=300, cwd=repo_root,
            env=env)
        assert proc.returncode == 0, proc.stdout + proc.stderr
        payload = json.loads(proc.stdout)
        assert payload["rules"] == ["guarded-state"]
        assert payload["counts"]["findings"] == 0
        # path-filtered: only executor/ allowlisted findings remain, and
        # the stale check is skipped (session/ entries would look stale)
        assert all(f["file"].startswith("executor/")
                   for f in payload["allowlisted"])
        assert payload["counts"]["stale_allowlist"] == 0
        # --stats renders the timing table on the human path
        proc = subprocess.run(
            [sys.executable, "-m", "tidb_tpu.lint", "--rule",
             "lock-order", "--stats"],
            capture_output=True, text=True, timeout=300, cwd=repo_root,
            env=env)
        assert proc.returncode == 0, proc.stdout + proc.stderr
        assert "lock-order" in proc.stdout and "ms" in proc.stdout

    def test_path_filter_in_engine_skips_stale(self, tmp_path):
        files = {"a.py": "try:\n    pass\nexcept Exception:\n    pass\n",
                 "b/c.py": "try:\n    pass\nexcept Exception:\n    pass\n"}
        p = tmp_path / "al.txt"
        p.write_text("exception-swallow a.py:* -- fixture\n")
        al = Allowlist.load(str(p))
        report = run_rules(make_ctx(files), al,
                           rules=["exception-swallow"], paths=["b/*"])
        assert [f.rel for f in report.findings] == ["b/c.py"]
        assert report.stale == []  # a.py's entry is filtered, not stale
