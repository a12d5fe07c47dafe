"""JAX/XLA device kernels — the TPU execution path.

Design (SURVEY.md §7 step 6 + hard parts):
- **Static shapes**: aggregation uses sort + segment_sum with a padded
  group capacity; joins are two-pass (count on device, host reads the total,
  expansion kernel with a static output size). This is the standard answer
  to XLA's no-dynamic-shapes rule.
- **Fusion**: a whole scan→filter→project→aggregate pipeline compiles into
  ONE jitted program, so lineitem never round-trips to the host between
  operators (the coprocessor-pushdown boundary of the reference becomes the
  host↔device boundary).
- **Exactness**: decimals stay scaled int64 end-to-end (x64 enabled);
  sums are exact; decimal division uses round-half-away integer math.
- **Strings**: dictionary codes (int32) computed host-side; equality /
  IN constants are translated to codes before tracing.

reference parity: executor/aggregate.go (hash agg) → sort-based segment
aggregation; executor/join.go + hash_table.go → sort + searchsorted join;
expression/*_vec.go → compile_expr tracing numpy-identical semantics.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..errors import TiDBError
from ..expression.core import (
    Column as ExprColumn, Constant, ScalarFunc, phys_kind,
    K_DATE, K_DEC, K_FLOAT, K_INT, K_STR,
)
from ..sqltypes import POW10, TYPE_DATETIME, TYPE_TIMESTAMP


class DeviceUnsupported(TiDBError):
    """Raised during compilation when an expression/type can't run on
    device; the executor falls back to the host kernels."""


def next_pow2(n: int) -> int:
    p = 1
    while p < n:
        p <<= 1
    return max(p, 8)


# ---------------------------------------------------------------------------
# shape canonicalization: geometric row buckets
# ---------------------------------------------------------------------------
#
# XLA programs are compiled per SHAPE: tracing against the exact row count
# means any delta append, different table, or different scale factor forces a
# full recompile — the dominant cost measured on a v5e (July 2026, SF1: Q3
# spent 378s compiling for 45s of compute). Device arrays are therefore
# padded up to a small set of geometric buckets (`bucket_rows`), with the
# live row count threaded through the jitted program as a TRACED scalar:
# padding rows carry null=True and are masked by `arange(n) < n_live` before
# any filter/join/aggregate, extending the existing "padding must not
# survive the scan filter" invariant of the paged path. A within-bucket
# delta then re-dispatches the already-compiled program.

import math as _math


def bucket_rows(n: int, per_double: int = 2) -> int:
    """Smallest geometric bucket >= n: `per_double` buckets per doubling
    (2 = powers of sqrt(2): 8, 12, 16, 23, 32, 46, 64, ...). per_double <= 0
    disables bucketing (exact shapes). Worst-case padding overhead is
    2^(1/per_double) - 1 (~41% at 1, ~19% at 2)."""
    if per_double <= 0 or n <= 0:
        return n
    b, k = 8, 0
    while b < n:
        k += 1
        b = _math.ceil(2 ** (3 + k / per_double))
    return b


def shape_buckets(ctx) -> int:
    """The session's bucket granularity (sysvar tidb_device_shape_buckets;
    default 2 buckets per doubling, 0 = exact shapes)."""
    try:
        return int(ctx.get_sysvar("tidb_device_shape_buckets"))
    except Exception:
        return 2


def pad_host(arr, n_to: int, null_pad: bool = False):
    """Pad a host array to `n_to` rows. Data pads with zeros (any value is
    fine — padding is masked), null masks pad with True (`null_pad`) so a
    padding row reads as NULL even before the n_live mask applies."""
    arr = np.asarray(arr)
    n = arr.shape[0]
    if n_to <= n:
        return arr
    if null_pad:
        out = np.ones(n_to, dtype=arr.dtype)
    else:
        out = np.zeros(n_to, dtype=arr.dtype)
    out[:n] = arr
    return out


# ---------------------------------------------------------------------------
# column transfer
# ---------------------------------------------------------------------------

class DeviceCol:
    """Device representation of one column: data + null mask (+ dictionary
    for strings; data holds int32 codes)."""

    __slots__ = ("data", "nulls", "dictionary", "reps", "ftype", "host_col")

    def __init__(self, data, nulls, ftype, dictionary=None, reps=None,
                 host_col=None):
        self.data = data
        self.nulls = nulls
        self.ftype = ftype
        # For _ci columns the dictionary holds the sorted collation sort
        # keys (constants are transformed before lookup) and reps holds a
        # representative original value per class for output decode.
        self.dictionary = dictionary
        self.reps = reps
        # backing utils.chunk.Column (when known): host min/max feed static
        # key-range packing in the agg planner (device_exec._key_pack)
        self.host_col = host_col

    def decode_dict(self):
        """The dictionary that maps codes back to OUTPUT strings."""
        return self.reps if self.reps is not None else self.dictionary


def to_device_col(col, bucket: int | None = None,
                  scoped: bool = False) -> DeviceCol:
    """utils.chunk.Column → DeviceCol. Strings are dict-encoded host-side.

    The device arrays are cached on the Column THROUGH the residency
    manager (ops/residency.py): a table's working set is uploaded to HBM
    once per columnar-cache version and reused across queries (for a
    resident working set the host→HBM transfer, not the kernel, would
    dominate), with every cached upload byte-accounted against
    `tidb_device_mem_budget`, LRU-evictable under pressure, and stamped
    with the device epoch so a fenced/restarted backend never serves a
    stale buffer.

    `bucket` (> len) pads the uploaded arrays to that static row count:
    padding rows carry null=True and zeroed data, and the consuming
    pipeline must mask them via its traced live-row count. One padded
    length is cached per column: a LONGER cached upload serves shorter
    requests as a device-side slice (no host re-transfer — an
    exact-shape consumer like the mpp path must not thrash a bucketed
    HBM-resident cache); only a grow evicts and re-uploads.

    `scoped`: the column is the running statement's own (a derived join
    build's, executor/device_join.py), published as such
    (`residency.publish`) for the statement to release."""
    from . import residency
    want = bucket if bucket is not None and bucket > len(col) else len(col)
    cached = residency.lookup(col, want)
    if cached is None:
        # chaos hook: a synthetic RESOURCE_EXHAUSTED at the upload
        # boundary (classified device OOM → run_device's evict-all →
        # retry → host-degradation ladder)
        from ..utils import failpoint
        failpoint.inject("device-upload-oom")
        if col.is_object():
            from ..sqltypes import TYPE_NEWDECIMAL
            if col.ftype.tp == TYPE_NEWDECIMAL:
                # wide decimals (precision > 18) are exact host bigints;
                # dict-encoding them as strings would break arithmetic
                raise DeviceUnsupported("wide-decimal column")
            from ..utils.collate import is_ci
            if is_ci(col.ftype.collate):
                # _ci columns encode as collation-class codes: ranks in
                # sort-key order, so code equality/ordering IS collation
                # semantics (utils/chunk.py dict_encode_ci)
                ci_codes, _kd, _reps = col.dict_encode_ci(col.ftype.collate)
                built = (jnp.asarray(pad_host(ci_codes, want)),
                         jnp.asarray(pad_host(col.nulls, want, True)))
            else:
                codes, _uniq = col.dict_encode()
                built = (jnp.asarray(pad_host(codes, want)),
                         jnp.asarray(pad_host(col.nulls, want, True)))
        else:
            built = (jnp.asarray(pad_host(col.data, want)),
                     jnp.asarray(pad_host(col.nulls, want, True)))
        # compare-and-keep publish under the residency lock: a racing
        # builder's loser arrays are accounted as immediately evicted,
        # never leaked outside the ledger
        cached = residency.publish(col, *built, scoped=scoped)
    data, nulls = cached
    if int(data.shape[0]) > want:
        # cached at a larger bucket: on-device slice (HBM-local, cheap)
        data, nulls = data[:want], nulls[:want]
    if col.is_object():
        from ..utils.collate import is_ci
        if is_ci(col.ftype.collate):
            _cc, key_dict, reps = col.dict_encode_ci(col.ftype.collate)
            return DeviceCol(data, nulls, col.ftype, dictionary=key_dict,
                             reps=reps, host_col=col)
        _codes, uniq = col.dict_encode()
        return DeviceCol(data, nulls, col.ftype, dictionary=uniq,
                         host_col=col)
    return DeviceCol(data, nulls, col.ftype, host_col=col)


def meta_device_col(col):
    """(DeviceCol with data=None, (host_data, host_nulls)) — the streamed/
    paged protocol: the DeviceCol carries only what the expression compiler
    reads (ftype, dictionaries, host_col for min/max packing); the host
    arrays are sliced into pages and uploaded per block by the caller.
    Never touches device memory, and never materializes a LazyDictColumn's
    object view (codes come straight off the memmap)."""
    if col.is_object():
        from ..sqltypes import TYPE_NEWDECIMAL
        if col.ftype.tp == TYPE_NEWDECIMAL:
            raise DeviceUnsupported("wide-decimal column")
        from ..utils.collate import is_ci
        if is_ci(col.ftype.collate):
            ci_codes, key_dict, reps = col.dict_encode_ci(col.ftype.collate)
            return (DeviceCol(None, None, col.ftype, dictionary=key_dict,
                              reps=reps, host_col=col),
                    (ci_codes, col.nulls))
        codes, uniq = col.dict_encode()
        return (DeviceCol(None, None, col.ftype, dictionary=uniq,
                          host_col=col),
                (codes, col.nulls))
    return (DeviceCol(None, None, col.ftype, host_col=col),
            (col.data, col.nulls))


# ---------------------------------------------------------------------------
# expression → jax compiler
# ---------------------------------------------------------------------------

#: longest IN list (a literal list, or a subquery's values folded by
#: device_join.collect_tree) that is compared with every row; past it
#: the list is binary-searched
_IN_SET_COMPARE_MAX = 256

#: the civil-date fields the device extracts, by their index in
#: _civil_from_days' result
_DATE_PARTS = {"year": 0, "month": 1, "day": 2}


def date_part(e):
    """(field, argument) when `e` takes the year, month or day of a
    temporal value, else None.  THE rule for what the device extracts:
    YEAR(d), EXTRACT(YEAR FROM d) and their month / day siblings are one
    expression to the compiler, to the key-bounds rule
    (device_exec._expr_bounds) and to the pipeline signature
    (device_exec._expr_sig), so a client's spelling never decides the
    engine or the program.  EXTRACT's other units (quarter, week, the
    time fields) have no lowering and stay DeviceUnsupported."""
    if not isinstance(e, ScalarFunc):
        return None
    if e.op == "extract":
        return (e.extra, e.args[1]) if e.extra in _DATE_PARTS else None
    op = "day" if e.op == "dayofmonth" else e.op
    return (op, e.args[0]) if op in _DATE_PARTS else None


def compile_expr(expr, cols: dict):
    """Build a traceable fn(env) -> (data, nulls) where env maps column idx
    → (jnp data, jnp nulls). `cols` maps idx → DeviceCol (for dictionaries
    and dtypes at compile time). Raises DeviceUnsupported when out of scope."""
    if isinstance(expr, ExprColumn):
        idx = expr.idx

        def f(env):
            return env[idx]
        return f
    if isinstance(expr, Constant):
        return _compile_const(expr, cols)
    if isinstance(expr, ScalarFunc):
        return _compile_func(expr, cols)
    raise DeviceUnsupported(f"cannot compile {type(expr).__name__} for device")


def _compile_const(expr: Constant, cols):
    """Constants trace as 0-d arrays so they broadcast against whichever
    column they meet — in a multi-table fragment the env holds arrays of
    several lengths, so sizing a constant from 'the first env entry' would
    be wrong. Consumers needing full-length arrays (group keys, aggregate
    inputs, join keys) broadcast explicitly via broadcast_1d."""
    v = expr.value
    if v is None:
        def f(env):
            return jnp.zeros((), dtype=jnp.int64), jnp.ones((), dtype=bool)
        return f
    k = phys_kind(expr.ftype)
    if k == K_STR:
        raise DeviceUnsupported("bare string constants only valid in eq/in")
    if k == K_FLOAT:
        val = float(v)
        dt = jnp.float64
    else:
        val = int(v)
        dt = jnp.int64 if k != K_DATE else jnp.int32

    def f(env):
        return jnp.asarray(val, dtype=dt), jnp.zeros((), dtype=bool)
    return f


def broadcast_1d(d, nl, n):
    """Expand 0-d (constant) results to length n where a full array is
    structurally required."""
    if d.ndim == 0:
        d = jnp.broadcast_to(d, (n,))
    if nl.ndim == 0:
        nl = jnp.broadcast_to(nl, (n,))
    return d, nl


def _dec_scale(e):
    return e.ftype.scale if phys_kind(e.ftype) == K_DEC else 0


def _to_common_numeric(sf, cols):
    """Compile both args of a binary numeric op to a common kind.
    Returns (kind, fa, fb, scale)."""
    a, b = sf.args
    ka, kb = phys_kind(a.ftype), phys_kind(b.ftype)
    fa = compile_expr(a, cols)
    fb = compile_expr(b, cols)
    # string equality via dictionary codes
    if ka == K_STR or kb == K_STR:
        raise DeviceUnsupported("string args only supported in eq/in paths")
    if K_FLOAT in (ka, kb):
        def wrap(f, e):
            sc = _dec_scale(e)

            def g(env):
                d, n = f(env)
                d = d.astype(jnp.float64)
                if sc:
                    d = d / POW10[sc]
                return d, n
            return g
        return K_FLOAT, wrap(fa, a), wrap(fb, b), 0
    if K_DEC in (ka, kb):
        s = max(_dec_scale(a), _dec_scale(b))

        def wrap(f, e):
            sc = _dec_scale(e)

            def g(env):
                d, n = f(env)
                d = d.astype(jnp.int64)
                if s > sc:
                    d = d * POW10[s - sc]
                return d, n
            return g
        return K_DEC, wrap(fa, a), wrap(fb, b), s
    # ints / dates / datetimes
    promote_a = ka == K_DATE and b.ftype.tp in (TYPE_DATETIME, TYPE_TIMESTAMP)
    promote_b = kb == K_DATE and a.ftype.tp in (TYPE_DATETIME, TYPE_TIMESTAMP)

    def wrap(f, promote):
        def g(env):
            d, n = f(env)
            d = d.astype(jnp.int64)
            if promote:
                d = d * 86_400_000_000
            return d, n
        return g
    return K_INT, wrap(fa, promote_a), wrap(fb, promote_b), 0


_CMP_OPS = {"eq": lambda a, b: a == b, "ne": lambda a, b: a != b,
            "lt": lambda a, b: a < b, "le": lambda a, b: a <= b,
            "gt": lambda a, b: a > b, "ge": lambda a, b: a >= b}


def _compile_func(sf: ScalarFunc, cols):
    """Dispatch with a dictionary-pushdown fallback: a numeric function
    of one dict-encoded string column that the direct compiler declines
    (LENGTH, casts, string arithmetic coercions, …) host-evaluates over
    the dictionary into a LUT instead of falling back to the host path."""
    try:
        return _compile_func_direct(sf, cols)
    except DeviceUnsupported:
        f = _try_str_numeric_lut(sf, cols)
        if f is not None:
            return f
        raise


def _compile_func_direct(sf: ScalarFunc, cols):
    op = sf.op
    if op in _CMP_OPS:
        # string vs constant → dictionary code comparison (eq/ne only)
        a, b = sf.args
        if phys_kind(a.ftype) == K_STR or phys_kind(b.ftype) == K_STR:
            return _compile_str_cmp(sf, cols)
        kind, fa, fb, _s = _to_common_numeric(sf, cols)
        cmp = _CMP_OPS[op]

        def f(env):
            da, na = fa(env)
            db, nb = fb(env)
            return cmp(da, db).astype(jnp.int64), na | nb
        return f
    if op in ("add", "sub", "mul"):
        out_k = phys_kind(sf.ftype)
        if out_k == K_DEC and op == "mul":
            fa = _compile_scaled(sf.args[0], cols, _dec_scale(sf.args[0]))
            fb = _compile_scaled(sf.args[1], cols, _dec_scale(sf.args[1]))

            def f(env):
                da, na = fa(env)
                db, nb = fb(env)
                return da * db, na | nb
            return f
        if out_k == K_DEC:
            s = sf.ftype.scale
            fa = _compile_scaled(sf.args[0], cols, s)
            fb = _compile_scaled(sf.args[1], cols, s)
            fn = jnp.add if op == "add" else jnp.subtract

            def f(env):
                da, na = fa(env)
                db, nb = fb(env)
                return fn(da, db), na | nb
            return f
        kind, fa, fb, _s = _to_common_numeric(sf, cols)
        fn = {"add": jnp.add, "sub": jnp.subtract, "mul": jnp.multiply}[op]

        def f(env):
            da, na = fa(env)
            db, nb = fb(env)
            return fn(da, db), na | nb
        return f
    if op == "div":
        out_k = phys_kind(sf.ftype)
        if out_k == K_FLOAT:
            _k, fa, fb, _s = _to_common_numeric(sf, cols)

            def f(env):
                da, na = fa(env)
                db, nb = fb(env)
                zero = db == 0
                safe = jnp.where(zero, 1.0, db)
                return da / safe, na | nb | zero
            return f
        s1 = _dec_scale(sf.args[0])
        s2 = _dec_scale(sf.args[1])
        sr = sf.ftype.scale
        fa = _compile_scaled(sf.args[0], cols, s1)
        fb = _compile_scaled(sf.args[1], cols, s2)
        shift = POW10[sr + s2 - s1]

        def f(env):
            da, na = fa(env)
            db, nb = fb(env)
            zero = db == 0
            num = da * shift
            den = jnp.where(zero, 1, db)
            sign = jnp.where((num < 0) != (den < 0), -1, 1)
            q = (2 * jnp.abs(num) + jnp.abs(den)) // (2 * jnp.abs(den))
            return sign * q, na | nb | zero
        return f
    if op in ("and", "or"):
        fa = compile_expr(sf.args[0], cols)
        fb = compile_expr(sf.args[1], cols)
        if op == "and":
            def f(env):
                da, na = fa(env)
                db, nb = fb(env)
                ta = (da != 0) & ~na
                tb = (db != 0) & ~nb
                fa_ = (da == 0) & ~na
                fb_ = (db == 0) & ~nb
                res = ta & tb
                nulls = ~(fa_ | fb_) & (na | nb)
                return res.astype(jnp.int64), nulls
            return f

        def f(env):
            da, na = fa(env)
            db, nb = fb(env)
            ta = (da != 0) & ~na
            tb = (db != 0) & ~nb
            res = ta | tb
            nulls = ~res & (na | nb)
            return res.astype(jnp.int64), nulls
        return f
    if op == "not":
        fa = compile_expr(sf.args[0], cols)

        def f(env):
            d, n = fa(env)
            return (d == 0).astype(jnp.int64), n
        return f
    if op == "isnull":
        fa = compile_expr(sf.args[0], cols)

        def f(env):
            _d, n = fa(env)
            return n.astype(jnp.int64), jnp.zeros_like(n)
        return f
    if op == "neg":
        fa = compile_expr(sf.args[0], cols)

        def f(env):
            d, n = fa(env)
            return -d, n
        return f
    if op == "in_set":
        target = sf.args[0]
        values, has_null = sf.extra
        if phys_kind(target.ftype) == K_STR:
            return _compile_str_in(sf, cols)
        fa = compile_expr(target, cols)
        if len(values) == 0:
            # empty IN list (e.g. a HAVING-filtered subquery with no
            # qualifying rows): constant FALSE, NULL if the list's only
            # content was NULL — gathering from a 0-length array is a
            # trace error
            def f(env):
                d, n = fa(env)
                hit = jnp.zeros_like(d, dtype=jnp.int64)
                return hit, n | bool(has_null)
            return f
        sorted_vals = jnp.asarray(np.sort(np.asarray(values)))
        # a short list is compared with every row in one fused pass (no
        # gather, no loop); a long one is binary-searched, a dependent
        # gather a step.  The bound keeps the time a smooth function of
        # the list's length: on the chip the search was 1.4 ms over 56
        # values and 1,244 ms over 68 (the compiler turns a gather from
        # at most 64 entries into selects), so Q18's time followed how
        # many large orders the data held (PERF.md, PR 33)
        compare_all = len(values) <= _IN_SET_COMPARE_MAX

        def f(env):
            d, n = fa(env)
            if compare_all:
                hit = (d[..., None] == sorted_vals).any(-1)
            else:
                pos = jnp.searchsorted(sorted_vals, d)
                pos = jnp.clip(pos, 0, len(sorted_vals) - 1)
                hit = sorted_vals[pos] == d
            nulls = n | (~hit & bool(has_null))
            return hit.astype(jnp.int64), nulls
        return f
    if op == "case":
        return _compile_case(sf, cols)
    if op == "if":
        return _compile_case(ScalarFunc("case", sf.args, sf.ftype), cols)
    if op == "cast":
        return _compile_cast(sf, cols)
    if op == "coalesce":
        fs = [compile_expr(a, cols) for a in sf.args]
        tk = phys_kind(sf.ftype)
        if tk == K_STR:
            raise DeviceUnsupported("string coalesce")

        def f(env):
            out_d, out_n = fs[0](env)
            out_d = _coerce_kind(out_d, sf.args[0], sf.ftype)
            for fx, ax in zip(fs[1:], sf.args[1:]):
                d, n = fx(env)
                d = _coerce_kind(d, ax, sf.ftype)
                out_d = jnp.where(out_n, d, out_d)
                out_n = out_n & n
            return out_d, out_n
        return f
    part = date_part(sf)
    if part is not None:
        field, arg = part
        fa = compile_expr(arg, cols)
        ak = phys_kind(arg.ftype)
        is_dt = arg.ftype.tp in (TYPE_DATETIME, TYPE_TIMESTAMP)
        if ak != K_DATE and not is_dt:
            raise DeviceUnsupported(f"{field}() on non-temporal for device")
        which = _DATE_PARTS[field]

        def f(env):
            d, n = fa(env)
            days = (jnp.floor_divide(d.astype(jnp.int64), 86_400_000_000)
                    if is_dt else d.astype(jnp.int64))
            return _civil_from_days(days)[which], n
        return f
    if op == "abs":
        fa = compile_expr(sf.args[0], cols)

        def f(env):
            d, n = fa(env)
            return jnp.abs(d), n
        return f
    if op in ("like", "regexp"):
        return _compile_str_pattern(sf, cols)
    raise DeviceUnsupported(f"scalar op {op} not available on device")


# ---------------------------------------------------------------------------
# string-VALUED expressions: everything compiles to CODES into a sorted key
# dictionary (dictionary pushdown, generalized). A derived string expression
# — CASE over strings, SUBSTRING, UPPER, CONCAT with constants — either
# merges its arms' dictionaries (branches) or is evaluated host-side ONCE
# per distinct dictionary entry and becomes a device code-LUT. The per-
# distinct-value cost beats per-row for real data, and the device sees only
# int codes (reference: the coprocessor evaluates these per row over raw
# bytes — expression/builtin_string.go; per-distinct is the columnar win).
# ---------------------------------------------------------------------------

_IMPURE_OPS = frozenset({"rand", "uuid", "sleep"})


def compile_str_expr(expr, cols):
    """Compile a string-valued expression → (fn, key_dict, reps): fn(env)
    yields codes into the sorted `key_dict`; `reps` decodes codes back to
    output strings. Raises DeviceUnsupported outside the language."""
    if isinstance(expr, ExprColumn):
        dc = cols.get(expr.idx)
        if dc is None or dc.dictionary is None:
            raise DeviceUnsupported("no dictionary for string column")
        return compile_expr(expr, cols), dc.dictionary, dc.decode_dict()
    if isinstance(expr, Constant):
        if expr.value is None:
            e = np.array([b""], dtype=object)

            def f(env):
                return (jnp.zeros((), dtype=jnp.int64),
                        jnp.ones((), dtype=bool))
            return f, e, e
        v = (expr.value if isinstance(expr.value, bytes)
             else str(expr.value).encode())
        from ..utils.collate import is_ci, sort_key
        key = (sort_key(v, expr.ftype.collate)
               if is_ci(expr.ftype.collate) else v)

        def f(env):
            return (jnp.zeros((), dtype=jnp.int64),
                    jnp.zeros((), dtype=bool))
        return (f, np.array([key], dtype=object),
                np.array([v], dtype=object))
    if isinstance(expr, ScalarFunc) and expr.op in ("case", "if",
                                                    "coalesce"):
        return _compile_str_branch(expr, cols)
    if isinstance(expr, ScalarFunc):
        return _compile_str_dict_pushdown(expr, cols)
    raise DeviceUnsupported(
        f"{type(expr).__name__} string expression on device")


def _compile_str_branch(sf, cols):
    """String-valued CASE/IF/COALESCE: arms compile to their own code
    spaces, merged into one union dictionary via static remap tables."""
    from ..utils.collate import is_ci
    args = sf.args
    if is_ci(sf.ftype.collate) or any(
            is_ci(a.ftype.collate) for a in args
            if phys_kind(a.ftype) == K_STR):
        # arm key spaces would mix raw bytes with per-collation sort keys
        raise DeviceUnsupported("_ci string branches on device")
    if sf.op == "coalesce":
        conds = None
        arms = list(args)
    else:
        has_else = len(args) % 2 == 1
        pairs = (len(args) - (1 if has_else else 0)) // 2
        conds = [compile_expr(args[2 * p], cols) for p in range(pairs)]
        arms = [args[2 * p + 1] for p in range(pairs)]
        if has_else:
            arms.append(args[-1])
    compiled = [compile_str_expr(a, cols) for a in arms]
    all_keys = np.concatenate([kd for _f, kd, _r in compiled])
    all_reps = np.concatenate([r for _f, _kd, r in compiled])
    key_dict, first = np.unique(all_keys, return_index=True)
    reps = all_reps[first]
    remaps = [jnp.asarray(np.searchsorted(key_dict, kd).astype(np.int64))
              for _f, kd, _r in compiled]
    sizes = [len(kd) for _f, kd, _r in compiled]

    def arm(i, env):
        d, n = compiled[i][0](env)
        d = remaps[i][jnp.clip(d.astype(jnp.int64), 0, sizes[i] - 1)]
        return d, n

    if sf.op == "coalesce":
        def f(env):
            out_d, out_n = arm(0, env)
            for i in range(1, len(compiled)):
                d, n = arm(i, env)
                out_d = jnp.where(out_n, d, out_d)
                out_n = out_n & n
            return out_d, out_n
        return f, key_dict, reps

    n_conds = len(conds)

    def f(env):
        out = jnp.zeros((), dtype=jnp.int64)
        out_n = jnp.ones((), dtype=bool)
        decided = jnp.zeros((), dtype=bool)
        for p in range(n_conds):
            cd, cn = conds[p](env)
            cond = (cd != 0) & ~cn & ~decided
            rd, rn = arm(p, env)
            out = jnp.where(cond, rd, out)
            out_n = jnp.where(cond, rn, out_n)
            decided = decided | cond
        if len(arms) > n_conds:  # ELSE
            rd, rn = arm(len(arms) - 1, env)
            out = jnp.where(decided, out, rd)
            out_n = jnp.where(decided, out_n, rn)
        return out, out_n
    return f, key_dict, reps


def _single_str_col(expr, cols):
    """The one dict-encoded string column an expression reads, or raise."""
    used: set = set()
    expr.columns_used(used)
    if len(used) != 1:
        raise DeviceUnsupported(
            "dictionary pushdown needs exactly one column input")
    idx = next(iter(used))
    dc = cols.get(idx)
    if dc is None or dc.dictionary is None or phys_kind(dc.ftype) != K_STR:
        raise DeviceUnsupported("dictionary pushdown needs a string column")
    return idx, dc


def _host_eval_over_dict(expr, dc):
    """Evaluate `expr` host-side once per distinct dictionary entry PLUS
    one NULL input row → (values, nulls) of length len(dict)+1, where the
    last slot is the expression's output FOR NULL INPUT. Null-handling
    subexpressions (COALESCE/IFNULL/CASE) may map NULL to a value, so the
    LUT must carry the null slot instead of blindly propagating input
    nulls."""
    def check(e):
        if isinstance(e, ScalarFunc):
            if e.op in _IMPURE_OPS:
                raise DeviceUnsupported(f"impure {e.op} on device")
            for a in e.args:
                check(a)
    check(expr)
    from ..utils.chunk import Chunk as HChunk, Column as HColumn
    src = dc.decode_dict()
    n = len(src)
    data = np.empty(n + 1, dtype=object)
    data[:n] = np.asarray(src, dtype=object)
    data[n] = b""
    nulls = np.zeros(n + 1, dtype=bool)
    nulls[n] = True
    col = HColumn(dc.ftype, data, nulls)
    local = expr.transform_columns(lambda c: ExprColumn(0, c.ftype))
    return local.eval(HChunk([col]))


def _compile_str_dict_pushdown(sf, cols):
    """String→string function of one dict column: host-evaluate over the
    dictionary, build the output dictionary, device op = code LUT."""
    from ..utils.collate import is_ci
    if is_ci(sf.ftype.collate):
        raise DeviceUnsupported("_ci derived string on device")
    idx, dc = _single_str_col(sf, cols)
    data, nulls = _host_eval_over_dict(sf, dc)
    vals = np.array([v if isinstance(v, bytes) else str(v).encode()
                     for v in data], dtype=object)
    key_dict, inv = np.unique(vals, return_inverse=True)
    code_map = jnp.asarray(inv.astype(np.int64))
    null_lut = jnp.asarray(np.asarray(nulls, dtype=bool))
    nd = len(dc.dictionary)

    def f(env):
        d, n = env[idx]
        # NULL input rows read the null slot (index nd) — the expression
        # may map NULL to a value (COALESCE etc.)
        c = jnp.where(n, nd, jnp.clip(d.astype(jnp.int64), 0, nd - 1))
        return code_map[c], null_lut[c]
    return f, key_dict, key_dict


def _try_str_numeric_lut(sf, cols):
    """Numeric-valued function of one dict string column (LENGTH, casts,
    string→number …): host-evaluate over the dictionary → numeric LUT.
    Returns None when the shape doesn't apply."""
    k = phys_kind(sf.ftype)
    if k == K_STR:
        return None
    try:
        idx, dc = _single_str_col(sf, cols)
    except DeviceUnsupported:
        return None
    data, nulls = _host_eval_over_dict(sf, dc)
    if k == K_FLOAT:
        arr = np.asarray(data, dtype=np.float64)
    else:
        arr = np.asarray(data).astype(np.int64)
    lut = jnp.asarray(arr)
    null_lut = jnp.asarray(np.asarray(nulls, dtype=bool))
    nd = len(dc.dictionary)

    def f(env):
        d, n = env[idx]
        c = jnp.where(n, nd, jnp.clip(d.astype(jnp.int64), 0, nd - 1))
        return lut[c], null_lut[c]
    return f


def _compile_str_pattern(sf, cols):
    """LIKE / REGEXP on a dict-encoded string column against a constant
    pattern: evaluate the predicate HOST-SIDE over the (small, distinct)
    dictionary once, then the device op is a boolean table lookup by code
    — dictionary pushdown (the reference evaluates LIKE per row over raw
    bytes, expression/builtin_like.go; per distinct value beats per row)."""
    from ..expression.core import like_to_regex
    import re as _re
    target, pat = sf.args[0], sf.args[1]
    if phys_kind(target.ftype) != K_STR:
        raise DeviceUnsupported(f"{sf.op} target must be a string value")
    if not isinstance(pat, Constant):
        raise DeviceUnsupported(f"{sf.op} pattern must be a constant")
    ft, key_dict, _reps = compile_str_expr(target, cols)
    if pat.value is None:
        def f(env):
            return jnp.zeros((), dtype=jnp.int64), jnp.ones((), dtype=bool)
        return f
    from ..utils.collate import is_ci
    ci = is_ci(target.ftype.collate)
    pv = (pat.value if isinstance(pat.value, bytes)
          else str(pat.value).encode())
    if sf.op == "like":
        if ci:
            # _ci dictionary holds sort keys: match the sort-keyed pattern
            # (same as the host ci path, which also uses the default
            # escape — core.py _eval_like)
            rx = like_to_regex(_expr_const_key(target, pv))
        else:
            # sf.extra carries the escape-aware regex the builder compiled
            # (LIKE ... ESCAPE '!'); rebuilding here would drop the escape
            rx = sf.extra if sf.extra is not None else like_to_regex(pv)
        match = rx.match
    else:
        if ci:
            raise DeviceUnsupported("regexp on _ci column")
        rx = _re.compile(pv)
        match = rx.search
    nd = len(key_dict)
    bits = np.zeros(nd, dtype=bool)
    for i, v in enumerate(key_dict):
        b = v if isinstance(v, bytes) else str(v).encode()
        bits[i] = match(b) is not None
    lut = jnp.asarray(bits)

    def f(env):
        d, n = ft(env)
        hit = lut[jnp.clip(d.astype(jnp.int64), 0, nd - 1)]
        return hit.astype(jnp.int64), n
    return f


def _civil_from_days(z):
    """days-since-epoch → (y, m, d). Howard Hinnant's civil_from_days,
    branch-free — pure integer ops, MXU-adjacent friendly."""
    z = z + 719468
    era = jnp.where(z >= 0, z, z - 146096) // 146097
    doe = z - era * 146097
    yoe = (doe - doe // 1460 + doe // 36524 - doe // 146096) // 365
    y = yoe + era * 400
    doy = doe - (365 * yoe + yoe // 4 - yoe // 100)
    mp = (5 * doy + 2) // 153
    d = doy - (153 * mp + 2) // 5 + 1
    m = jnp.where(mp < 10, mp + 3, mp - 9)
    y = jnp.where(m <= 2, y + 1, y)
    return y, m, d


def _compile_scaled(e, cols, target_scale):
    f = compile_expr(e, cols)
    sc = _dec_scale(e)
    k = phys_kind(e.ftype)
    if k in (K_FLOAT, K_STR):
        raise DeviceUnsupported("float→decimal on device")

    def g(env):
        d, n = f(env)
        d = d.astype(jnp.int64)
        if target_scale > sc:
            d = d * POW10[target_scale - sc]
        return d, n
    return g


def _coerce_kind(d, e, out_ft):
    k, ok = phys_kind(e.ftype), phys_kind(out_ft)
    if ok == K_FLOAT:
        d = d.astype(jnp.float64)
        if k == K_DEC:
            d = d / POW10[e.ftype.scale]
        return d
    if ok == K_DEC:
        d = d.astype(jnp.int64)
        sc = _dec_scale(e)
        if out_ft.scale > sc:
            d = d * POW10[out_ft.scale - sc]
        return d
    return d.astype(jnp.int64)


def _compile_case(sf, cols):
    args = sf.args
    has_else = len(args) % 2 == 1
    pairs = (len(args) - (1 if has_else else 0)) // 2
    if phys_kind(sf.ftype) == K_STR:
        raise DeviceUnsupported("string CASE on device")
    fs = [compile_expr(a, cols) for a in args]

    def f(env):
        # scalar seeds broadcast up against whichever condition/result
        # array they meet (constants are 0-d — see _compile_const)
        dt = jnp.float64 if phys_kind(sf.ftype) == K_FLOAT else jnp.int64
        out = jnp.zeros((), dtype=dt)
        out_n = jnp.ones((), dtype=bool)
        decided = jnp.zeros((), dtype=bool)
        for p in range(pairs):
            cd, cn = fs[2 * p](env)
            cond = (cd != 0) & ~cn & ~decided
            rd, rn = fs[2 * p + 1](env)
            rd = _coerce_kind(rd, args[2 * p + 1], sf.ftype)
            out = jnp.where(cond, rd, out)
            out_n = jnp.where(cond, rn, out_n)
            decided = decided | cond
        if has_else:
            rd, rn = fs[-1](env)
            rd = _coerce_kind(rd, args[-1], sf.ftype)
            out = jnp.where(decided, out, rd)
            out_n = jnp.where(decided, out_n, rn)
        return out, out_n
    return f


def _compile_cast(sf, cols):
    src = sf.args[0]
    f = compile_expr(src, cols)
    sk, tk = phys_kind(src.ftype), phys_kind(sf.ftype)
    if K_STR in (sk, tk):
        raise DeviceUnsupported("string casts on device")

    def g(env):
        d, n = f(env)
        if tk == K_FLOAT:
            d = d.astype(jnp.float64)
            if sk == K_DEC:
                d = d / POW10[src.ftype.scale]
            return d, n
        if tk == K_DEC:
            if sk == K_DEC:
                diff = sf.ftype.scale - src.ftype.scale
                if diff >= 0:
                    return d.astype(jnp.int64) * POW10[diff], n
                den = POW10[-diff]
                sign = jnp.where(d < 0, -1, 1)
                q = (2 * jnp.abs(d) + den) // (2 * den)
                return sign * q, n
            if sk == K_FLOAT:
                return jnp.round(d * POW10[sf.ftype.scale]).astype(jnp.int64), n
            return d.astype(jnp.int64) * POW10[sf.ftype.scale], n
        # int target
        if sk == K_DEC:
            den = POW10[src.ftype.scale]
            sign = jnp.where(d < 0, -1, 1)
            q = (2 * jnp.abs(d) + den) // (2 * den)
            return sign * q, n
        if sk == K_FLOAT:
            return jnp.round(d).astype(jnp.int64), n
        return d.astype(jnp.int64), n
    return g


def _compile_str_cmp(sf, cols):
    a, b = sf.args
    # ordering comparisons on dictionary codes are valid because every key
    # dictionary is sorted (np.unique bytes / sort-key classes for _ci)
    if isinstance(b, Constant) and not isinstance(a, Constant):
        lhs, const = a, b
    elif isinstance(a, Constant) and not isinstance(b, Constant):
        lhs, const = b, a
        # flip comparison direction
        sf = ScalarFunc({"lt": "gt", "gt": "lt", "le": "ge", "ge": "le"}.get(
            sf.op, sf.op), [b, a], sf.ftype)
    else:
        return _compile_str_cmp_exprs(sf, cols)
    fl, key_dict, _reps = compile_str_expr(lhs, cols)
    if const.value is None:
        def f(env):
            return (jnp.zeros((), dtype=jnp.int64),
                    jnp.ones((), dtype=bool))
        return f
    v = _expr_const_key(lhs, const.value)
    code = _key_code_for(key_dict, v)
    exact = code >= 0
    pos = code if exact else int(np.searchsorted(key_dict, v))
    if not exact:
        code = pos - 0.5  # between codes for range compares
    op = sf.op
    cmp = _CMP_OPS[op]

    def f(env):
        d, n = fl(env)
        res = cmp(d.astype(jnp.float64), code) if not exact else cmp(d, pos)
        return res.astype(jnp.int64), n
    return f


def _expr_const_key(expr, const_val):
    """A bytes constant in a string EXPRESSION's key space (its collation
    decides whether the key is the raw bytes or the sort key)."""
    from ..utils.collate import is_ci, sort_key
    v = const_val if isinstance(const_val, bytes) else str(const_val).encode()
    if is_ci(expr.ftype.collate):
        v = sort_key(v, expr.ftype.collate)
    return v


def _key_code_for(key_dict, key):
    """Exact code of `key` in a sorted key dictionary, or -2 (never
    matches: codes are >= 0)."""
    pos = int(np.searchsorted(key_dict, key))
    if pos < len(key_dict) and key_dict[pos] == key:
        return pos
    return -2


def _compile_str_cmp_exprs(sf, cols):
    """expr-vs-expr string comparison (col=col included): both sides map
    into the UNION of their key dictionaries, where code order is value
    order for both — then it's an int compare."""
    from ..utils.collate import is_ci
    a, b = sf.args
    ca, cb = a.ftype.collate, b.ftype.collate
    if (is_ci(ca) or is_ci(cb)) and ca != cb:
        # different sort-key spaces cannot union consistently
        raise DeviceUnsupported("mixed-collation string compare on device")
    fa, kda, _ra = compile_str_expr(a, cols)
    fb, kdb, _rb = compile_str_expr(b, cols)
    union = np.unique(np.concatenate([kda, kdb]))
    mapa = jnp.asarray(np.searchsorted(union, kda).astype(np.int64))
    mapb = jnp.asarray(np.searchsorted(union, kdb).astype(np.int64))
    na, nb = len(kda), len(kdb)
    cmp = _CMP_OPS[sf.op]

    def f(env):
        da, nla = fa(env)
        db, nlb = fb(env)
        ua = mapa[jnp.clip(da.astype(jnp.int64), 0, na - 1)]
        ub = mapb[jnp.clip(db.astype(jnp.int64), 0, nb - 1)]
        return cmp(ua, ub).astype(jnp.int64), nla | nlb
    return f


def _compile_str_in(sf, cols):
    target = sf.args[0]
    values, has_null = sf.extra
    ft, key_dict, _reps = compile_str_expr(target, cols)

    codes = sorted(set(
        c for c in (_key_code_for(key_dict, _expr_const_key(target, v))
                    for v in values) if c >= 0))
    code_arr = jnp.asarray(np.asarray(codes, dtype=np.int64)) if codes else None

    def f(env):
        d, n = ft(env)
        if code_arr is None:
            hit = jnp.zeros(d.shape[0], dtype=bool)
        else:
            pos = jnp.clip(jnp.searchsorted(code_arr, d), 0, len(codes) - 1)
            hit = code_arr[pos] == d
        nulls = n | (~hit & bool(has_null))
        return hit.astype(jnp.int64), nulls
    return f


# ---------------------------------------------------------------------------
# fused aggregation pipeline
# ---------------------------------------------------------------------------

def _seg_running(comb_val, is_new, z):
    """Segmented running reduction: resets at every True in is_new. Classic
    (flag, value) associative-scan operator — log-depth, fully vectorized,
    no scatter (scatters serialize on TPU)."""
    def comb(a, b):
        fa, va = a
        fb, vb = b
        return fa | fb, jnp.where(fb, vb, comb_val(va, vb))
    _f, run = jax.lax.associative_scan(comb, (is_new, z))
    return run


#: spans_one_pass's one price: what a searched slot-step (one dependent
#: gather into the input-length group-id array) is charged, in sorted
#: rows.  Set from a sweep on the v5e (standalone, n = 8,388,608 flags;
#: PERF.md section 6, PR 36): the search 1,454.4 ms at 2,097,152 slots,
#: 189.3 at 262,144, 8.4 at 16,384 (30 ns a slot-step of its 23, 22 at
#: the small end); the sort of int32 positions 5.7 ms at any capacity
#: (0.7 ns a row): the two are even at a price of 45.  At 4 the sort is
#: taken where it wins by eleven times or more: every shape that flips is
#: a new program text (a cold compile of tens of seconds for a whole
#: fragment), so a program within that margin (Q3's 16,384 slots: 8.4
#: against 5.7 ms; the mesh's 2,097,152-row shard) keeps its own until a
#: change claims it in its cell.
_SPANS_SEARCH_PRICE = 4

#: inputs shorter than this keep the search whatever their capacity: the
#: sort saves 8.7 ms at 131,072 rows and 32,768 slots (9.6 against 0.9)
#: and 4.1 ms at 65,536 and 16,384 (5.0 against 0.9; same sweep), and the
#: folds of partial states (device_exec.merge_partial_states, the mesh's
#: _merge_partials: pages x capacity rows at the same capacity) keep the
#: program texts they had.
_SPANS_ONE_PASS_MIN_ROWS = 1 << 17


def spans_one_pass(capacity, n) -> bool:
    """Which side of _group_spans turns the boundary flags of an `n`-row
    sort-arm aggregate into its `capacity` group starts: True = one sort
    at input length, False = a binary search per output slot.
    Host-callable (the dispatchers count programs by it:
    device_exec.note_agg_spans) and what _group_spans itself asks at
    trace time; it reads its two arguments only, both static shapes, so
    every backend traces the program the chip runs.

    The search costs capacity x ceil(log2 n) gathered rows, the sort n
    sorted rows; the sort is taken from _SPANS_ONE_PASS_MIN_ROWS rows up
    where a gathered row at _SPANS_SEARCH_PRICE sorted ones makes the
    search the dearer: TPC-H Q3's 16,384 slots over 8,388,608 rows
    search, Q18's 2,097,152 slots over the same rows sort."""
    n = int(n)
    steps = max(n - 1, 1).bit_length()          # ceil(log2 n)
    return (n >= _SPANS_ONE_PASS_MIN_ROWS
            and int(capacity) * steps * _SPANS_SEARCH_PRICE >= n)


def _group_spans(is_new, kept, n, capacity):
    """Group boundary arithmetic shared by the single-chip kernel and the
    MPP partial/final stages: starts[g] = the position of the g-th set
    flag of `is_new` (n past the last group), end_g = next start (or kept
    for the last group). Returns (starts, ends, end_idx, span_sum) where
    span_sum(z) = per-group sums of z via exclusive prefix sums (exact
    for ints — two's-complement differences cancel; float sums must use
    _seg_running instead to keep rounding error group-local).

    Two ways to the same `starts`, chosen by spans_one_pass(capacity, n)
    from the static shapes alone:

    - one pass: the first `capacity` entries of the ascending sort of
      where(is_new, position, n), positions as int32 while n fits (a
      capacity above n pads with n). One single-operand sort at input
      length, no group id, no per-slot search.
    - search: searchsorted over the running group id (cumsum of is_new),
      one binary search per output slot: ceil(log2 n) DEPENDENT gathers
      into an int64 array of n rows each. gid is non-decreasing by
      construction, so `starts[g] = first row with gid ≥ g` is exact, and
      slots past the last group return n.

    On the v5e at n = 8,388,608 and 2,097,152 slots (PERF.md section 6,
    PR 36) the search is 1,454 ms (in TPC-H Q18's inner aggregate: two
    fusions of its `while` body, the `u32` halves of the int64 group id,
    1,031 + 433 ms of a 1,868 ms program) and the sort 5.7 ms (13.7 ms
    as a stable sort, which carries a second operand). Neither a scatter
    nor jnp.nonzero(size=...) comes near: scattering the flagged
    positions to their group id is 41.7 ms at any capacity,
    `unique_indices` / `indices_are_sorted` or not, a scatter-min over
    the sorted group id 76.2 ms, nonzero 573-577 ms."""
    if spans_one_pass(capacity, n):
        pos_dt = jnp.int32 if n < (1 << 31) else jnp.int64
        # not stable: equal entries are all the fill n, and a stable sort
        # carries a second operand (13.7 ms against 5.7, 15-20 s of
        # compile against 4-6)
        flagged = jnp.sort(jnp.where(is_new, jnp.arange(n, dtype=pos_dt),
                                     jnp.asarray(n, dtype=pos_dt)),
                           stable=False)
        starts = flagged[:capacity].astype(jnp.int64)
        if capacity > n:
            starts = jnp.concatenate(
                [starts, jnp.full(capacity - n, n, dtype=jnp.int64)])
    else:
        gid = jnp.cumsum(is_new) - 1
        starts = jnp.searchsorted(gid, jnp.arange(capacity), side="left"
                                  ).astype(jnp.int64)
    ends = jnp.minimum(jnp.concatenate(
        [starts[1:], jnp.full(1, n, dtype=starts.dtype)]), kept)
    end_idx = jnp.clip(ends - 1, 0, jnp.maximum(n - 1, 0))

    def span_sum(z):
        c = jnp.concatenate([jnp.zeros(1, dtype=z.dtype), jnp.cumsum(z)])
        return c[ends] - c[jnp.minimum(starts, n)]

    return starts, ends, end_idx, span_sum


def _packed_bucket(key_cols, key_nulls, pack):
    """(bucket id per row, bucket count B) of the dense arm: the
    statically packed group key as the sort arm packs it (NULL is 0, a
    value shifts by offset + 1), int64, clipped into [0, B)."""
    B = 1 << sum(b for b, _o in pack)
    bucket = jnp.zeros(key_cols[0].shape[0], dtype=jnp.int64)
    for i, (bits, offset) in enumerate(pack):
        shifted = (key_cols[i].astype(jnp.int64)
                   + jnp.asarray(offset + 1, dtype=jnp.int64))
        v = jnp.where(key_nulls[i], jnp.zeros((), dtype=jnp.int64), shifted)
        bucket = (bucket << bits) | v
    return jnp.clip(bucket, 0, B - 1), B


#: dense-arm bound: packed key spaces of at most this many buckets
#: aggregate by one masked reduction per bucket. Work grows as buckets x
#: reductions x rows; the sort arm costs the same whatever the bucket
#: count. Set from a sweep on the v5e through the served path (6M-row
#: lineitem, sum + count(*); PERF.md section 6, PR 26): dense 17 ms at
#: 256 buckets, 100 ms at 2,048, 215 ms at 4,096 against the sort arm's
#: 375-407 ms. 2,048 still wins 3.8x there, but only 1.3x at Q1's ten
#: value rows (Q1 scaled from its 32 buckets: 484 against 642 ms); at
#: 1,024 both shapes win by more than 2x.
_DENSE_AGG_BUCKETS = 1024

#: aggregates the dense arm computes: integer sums wrap the same in any
#: order, min/max/first do not depend on it. A float sum in another order
#: is another answer, and COUNT(DISTINCT) needs the sorted runs.
_DENSE_AGG_OPS = frozenset({"count", "sum_i", "min", "max", "first"})


def agg_arm(pack, agg_ops, gathered=False) -> str:
    """Which arm of _agg_impl aggregates a fragment with this static key
    packing and these ops: "dense" | "sort". Host-callable (the
    dispatchers count fragments by it) and what _agg_impl itself asks at
    trace time. It reads its arguments only, so every backend traces the
    program the chip runs.

    - dense: the packed key space holds at most _DENSE_AGG_BUCKETS
      buckets, every op is in _DENSE_AGG_OPS and the inputs are not
      `gathered`.
    - sort: the rest (no static packing, wide keys, sum_f, cnt_dist).

    gathered: the aggregate's inputs come out of a join's per-row gather
    chain in the same program (device_join.compile_fragment, the mesh
    body). Those fragments keep the sort arm: on the v5e TPC-H Q5's
    aggregate (32 buckets) fell by 0.23 s under the dense arm and the
    compiler then ran the probe chain's same gathers 1.35 s slower
    (PERF.md section 6, PR 26)."""
    if pack is None:
        return "sort"
    bits = sum(b for b, _o in pack)
    if (not gathered and (1 << bits) <= _DENSE_AGG_BUCKETS
            and all(op in _DENSE_AGG_OPS for op in agg_ops)):
        return "dense"
    return "sort"


def _agg_dense_impl(key_cols, key_nulls, val_cols, val_nulls, mask,
                    agg_ops, capacity, pack):
    """Dense masked reduction: bucket id = the statically packed group
    key (as the sort arm packs it: NULL is 0, values shift by offset + 1);
    per bucket b and aggregate input, ONE reduction of
    where(bucket == b, v, identity) over the row axis. The TPU compiler
    fuses the compare and the select into the reduction and stores
    nothing of shape (B, n); XLA:CPU stores the select (1,024 buckets
    over 2M rows: 55 GB), which keeps this arm to test sizes there. No
    argsort, no gather through a permutation, no scatter and no cumsum at
    the fact length.

    Same contract as the sort arm: groups in packed-key order, the
    representative row of a group (keys, `first`) is its first kept row,
    integer sums are the same int64 two's-complement sums, result_null =
    no non-null kept row. Live buckets compact in bucket order into the
    capacity-sized outputs; n_groups may exceed capacity (caller
    retries)."""
    n = mask.shape[0]
    with jax.named_scope("k_agg_sort"):
        packed, B = _packed_bucket(key_cols, key_nulls, pack)
        # filtered-out rows get bucket B, which no reduction selects
        bucket = jnp.where(mask, packed, B).astype(jnp.int32)
        onehot = bucket[None, :] == jnp.arange(B, dtype=jnp.int32)[:, None]
    # row positions and counts fit 32 bits (64-bit ALU ops are emulated
    # pairs on TPU); counts widen to int64 after the reduction
    cnt_dt = jnp.int32 if n < (1 << 31) - 1 else jnp.int64

    # jnp.sum alone would widen an int32 count before it reduces
    row_sum = functools.partial(jnp.sum, promote_integers=False)

    def reduce_rows(red, v, identity, keep=None):
        sel = onehot if keep is None else onehot & keep[None, :]
        return red(jnp.where(sel, v[None, :], identity), axis=1,
                   initial=identity)

    with jax.named_scope("k_agg_segment"):
        rep = reduce_rows(jnp.min, jnp.arange(n, dtype=cnt_dt),
                          jnp.asarray(n, dtype=cnt_dt))
        live = rep < n
        n_groups = jnp.sum(live)
        # output slot g <- the g-th live bucket: the first bucket whose
        # inclusive live count exceeds g (a (slots, B) compare, no sort).
        # Slots past n_groups hold garbage, as in the sort arm
        slots = min(capacity, B)
        src = jnp.minimum(jnp.sum(
            jnp.cumsum(live)[None, :] <= jnp.arange(slots)[:, None],
            axis=1), B - 1)
        rep_out = jnp.clip(rep[src], 0, max(n - 1, 0))

        def compact(arr_B):
            return jnp.pad(arr_B[src], (0, capacity - slots))

        def first_row(col):
            return jnp.pad(col[rep_out], (0, capacity - slots))

        key_out = tuple(first_row(k) for k in key_cols)
        key_null_out = tuple(first_row(kn) for kn in key_nulls)

    # non-null kept rows per bucket, once per distinct null array (avg =
    # sum + count over one column share it)
    nn_by_src = {}

    def nonnull_counts(j):
        hit = nn_by_src.get(id(val_nulls[j]))
        if hit is None:
            with jax.named_scope("k_agg_segment"):
                hit = compact(reduce_rows(
                    row_sum, jnp.ones(n, dtype=cnt_dt),
                    jnp.asarray(0, dtype=cnt_dt), ~val_nulls[j]
                ).astype(jnp.int64))
            nn_by_src[id(val_nulls[j])] = hit
        return hit

    results = []
    result_nulls = []
    for j, opn in enumerate(agg_ops):
        if opn == "first":
            with jax.named_scope("k_agg_segment"):
                results.append(first_row(val_cols[j]))
                result_nulls.append(first_row(val_nulls[j]))
            continue
        nn = nonnull_counts(j)
        if opn == "count":
            results.append(nn)
            with jax.named_scope("k_agg_segment"):
                result_nulls.append(jnp.zeros(capacity, dtype=bool))
            continue
        v = val_cols[j]
        if opn == "sum_i":
            with jax.named_scope("k_agg_gather"):
                z = jnp.where(val_nulls[j], 0, v.astype(jnp.int64))
            with jax.named_scope("k_agg_segment"):
                acc = reduce_rows(row_sum, z, jnp.int64(0))
        elif opn in ("min", "max"):
            if jnp.issubdtype(v.dtype, jnp.floating):
                lo, hi = -jnp.inf, jnp.inf
            else:
                lo, hi = jnp.iinfo(v.dtype).min, jnp.iinfo(v.dtype).max
            red, ident = (jnp.min, hi) if opn == "min" else (jnp.max, lo)
            with jax.named_scope("k_agg_segment"):
                acc = reduce_rows(red, v, jnp.asarray(ident, dtype=v.dtype),
                                  ~val_nulls[j])
        else:
            raise ValueError(opn)
        with jax.named_scope("k_agg_segment"):
            results.append(compact(acc))
            result_nulls.append(nn == 0)
    with jax.named_scope("k_agg_segment"):
        valid = jnp.arange(capacity) < n_groups
    return (key_out, key_null_out, tuple(results), tuple(result_nulls),
            n_groups, valid)


def _agg_impl(key_cols, key_nulls, val_cols, val_nulls, mask,
              n_keys, agg_ops, capacity, pack=None, gathered=False):
    """One fused kernel: filter mask + group-by + aggregate, in one of
    two arms chosen at trace time by agg_arm(pack, agg_ops, gathered):

    - dense (_agg_dense_impl): small packed key spaces whose inputs are
      not `gathered` — one masked reduction per bucket, no sort, gather
      or scatter at the fact length (TPC-H Q6's one group, Q1's four).
    - sort (below): the rest. Sort-based grouping + boundary arithmetic —
      the XLA/TPU-native answer to the reference's hash tables
      (executor/aggregate.go): static shapes, no data-dependent control
      flow, and NO scatters (XLA lowers scatter-adds to a serialized loop
      on TPU; sort + cumsum + gather are all parallel). Per aggregate:
      exclusive-prefix-sum, then sum over a group = csum[end] -
      csum[start]; min/max via segmented associative scan.

    Every arm detects groups beyond `capacity` (n_groups > capacity) and
    the caller retries with a bigger static capacity — one extra compile,
    never wrong results.

    key_cols: tuple of int64 arrays (dict codes / ints). agg_ops: tuple of
    ("sum_i"|"sum_f"|"count"|"min"|"max"|"first"|"cnt_dist") aligned with
    val_cols.

    pack: optional static tuple of (bits, offset) per key when every key's
    value range fits a known bit width (dict codes, dates). In the sort
    arm all keys, their null flags, and the filter mask then fold into ONE
    sort key — int32 when it fits (64-bit ALU ops are emulated pairs on
    TPU) — giving one argsort instead of 2·n_keys+1. NULL packs as 0 (its
    own group); filtered-out rows pack as the dtype max and sort last.

    gathered: static; the caller says its inputs come out of a join's
    gather chain in this program (see agg_arm).
    """
    arm = agg_arm(pack, agg_ops, gathered)
    if arm == "dense":
        return _agg_dense_impl(key_cols, key_nulls, val_cols, val_nulls,
                               mask, agg_ops, capacity, pack)
    n = mask.shape[0]
    with jax.named_scope("k_agg_segment"):
        kept = jnp.sum(mask)
        pos = jnp.arange(n)
        in_range = pos < kept
    if pack is not None:
        with jax.named_scope("k_agg_sort"):
            total_bits = sum(b for b, _o in pack)
            dt = jnp.int32 if total_bits < 31 else jnp.int64
            packed = jnp.zeros(n, dtype=dt)
            for i, (bits, offset) in enumerate(pack):
                # add the offset BEFORE narrowing: a large-valued key with
                # a small span (decimals, sparse ids) overflows int32 if
                # cast first; the shifted value always fits `bits`
                shifted = (key_cols[i].astype(jnp.int64)
                           + jnp.asarray(offset + 1, dtype=jnp.int64)
                           ).astype(dt)
                v = jnp.where(key_nulls[i], jnp.zeros((), dtype=dt), shifted)
                packed = (packed << bits) | v
            sort_val = jnp.where(mask, packed, jnp.iinfo(dt).max)
            order = jnp.argsort(sort_val, stable=True)
            sv = sort_val[order]
        with jax.named_scope("k_agg_segment"):
            prev = jnp.concatenate([sv[:1], sv[:-1]])
            is_new = (jnp.zeros(n, dtype=bool).at[0].set(n > 0)
                      | (sv != prev))
            is_new = is_new & in_range
    else:
        # combined sort: minor-to-major stable argsort over keys, then
        # kept-first. Each key is the compound (null_flag, masked value) —
        # null is its own most-significant bit so a NULL never collides
        # with any real value (NULL ≠ -1; GROUP BY groups NULLs apart from
        # values). The value is NULL-MASKED to 0: NULL rows carry
        # arbitrary raw data (join-gather garbage), and sorting by it
        # would interleave rows of distinct groups that differ only in
        # minor keys, splintering the group blocks.
        with jax.named_scope("k_agg_sort"):
            order = jnp.arange(n)
            for i in range(n_keys - 1, -1, -1):
                mk = jnp.where(key_nulls[i], 0, key_cols[i])
                order = order[jnp.argsort(mk[order], stable=True)]
                order = order[jnp.argsort(key_nulls[i][order], stable=True)]
            order = order[jnp.argsort(~mask[order], stable=True)]
        # boundary flags on the sorted, kept prefix
        with jax.named_scope("k_agg_segment"):
            is_new = jnp.zeros(n, dtype=bool).at[0].set(n > 0)
        for i in range(n_keys):
            with jax.named_scope("k_agg_sort"):
                k = key_cols[i][order]
                kn = key_nulls[i][order]
            with jax.named_scope("k_agg_segment"):
                prev = jnp.concatenate([k[:1], k[:-1]])
                prev_n = jnp.concatenate([kn[:1], kn[:-1]])
                changed = jnp.where(kn | prev_n, kn != prev_n, k != prev)
                is_new = is_new | changed
        with jax.named_scope("k_agg_segment"):
            is_new = is_new & in_range
    with jax.named_scope("k_agg_segment"):
        n_groups = jnp.sum(is_new)
        # slots past n_groups hold garbage — callers slice [:n_groups] /
        # mask with `valid`
        starts, ends, end_idx, span_sum = _group_spans(is_new, kept, n,
                                                       capacity)
        # representative row (first of group in sort order = first in
        # original order for equal keys, since the sorts are stable)
        rep_safe = jnp.clip(
            order[jnp.clip(starts, 0, jnp.maximum(n - 1, 0))],
            0, jnp.maximum(n - 1, 0))
        key_out = tuple(k[rep_safe] for k in key_cols)
        key_null_out = tuple(kn[rep_safe] for kn in key_nulls)
    # -- batched count/sum_i path: ALL integer sums and their non-null
    # counters fold into ONE (m, n) matrix — one axis-1 gather by `order`,
    # one 2D cumsum, one boundary subtraction. Per-slot gathers+cumsums
    # were the kernel's dominant cost (~135ms/slot at 6M rows vs ~30ms
    # batched; measured on v5e over the serving fabric).
    batch_rows = []          # rows of the (m, n) matrix, pre-sort order
    slot_plan = {}           # j -> ("count", nn_row) | ("sum_i", nn_row, v_row)
    nn_rows_by_src = {}      # id(val_nulls[j]) -> row (avg = sum+count over
    #                          the same column: share one indicator row)
    for j, opn in enumerate(agg_ops):
        if opn not in ("count", "sum_i"):
            continue
        nn_row = nn_rows_by_src.get(id(val_nulls[j]))
        with jax.named_scope("k_agg_gather"):
            if nn_row is None:
                nn_row = len(batch_rows)
                batch_rows.append(
                    (~(val_nulls[j] | ~mask)).astype(jnp.int64))
                nn_rows_by_src[id(val_nulls[j])] = nn_row
            if opn == "count":
                slot_plan[j] = ("count", nn_row)
            else:
                v64 = val_cols[j].astype(jnp.int64)
                v_row = len(batch_rows)
                batch_rows.append(jnp.where(val_nulls[j] | ~mask, 0, v64))
                slot_plan[j] = ("sum_i", nn_row, v_row)
    spans2d = None
    if batch_rows:
        with jax.named_scope("k_agg_gather"):
            M = jnp.stack(batch_rows, axis=0)          # (m, n)
            SM = jnp.take(M, order, axis=1)            # one gather
        with jax.named_scope("k_agg_segment"):
            C = jnp.concatenate(
                [jnp.zeros((M.shape[0], 1), dtype=jnp.int64),
                 jnp.cumsum(SM, axis=1)], axis=1)
            spans2d = C[:, ends] - C[:, jnp.minimum(starts, n)]

    results = []
    result_nulls = []
    for j, opn in enumerate(agg_ops):
        if opn == "first":
            # first row's own value AND null flag (mirrors host first_row;
            # a NULL in the representative row must stay NULL)
            with jax.named_scope("k_agg_segment"):
                results.append(val_cols[j][rep_safe])
                result_nulls.append(val_nulls[j][rep_safe])
            continue
        if opn == "cnt_dist":
            # COUNT(DISTINCT v): re-sort with the value as the MINOR key
            # — the group blocks land on the SAME positional spans (equal
            # multiset of group keys, stable order), so the order-1 span
            # machinery applies unchanged; distinct = run starts among
            # kept non-null rows (NULLs sort last per group and never
            # start a run). Reference: executor/aggfuncs count distinct
            # via a per-group hash set; sorted runs are the static-shape
            # equivalent.
            with jax.named_scope("k_agg_sort"):
                v64 = val_cols[j].astype(jnp.int64)
                if pack is not None:
                    order2 = jnp.lexsort((v64, val_nulls[j], sort_val))
                else:
                    order2 = jnp.arange(n)
                    order2 = order2[jnp.argsort(v64[order2], stable=True)]
                    order2 = order2[jnp.argsort(val_nulls[j][order2],
                                                stable=True)]
                    for i in range(n_keys - 1, -1, -1):
                        # NULL-MASKED key: a NULL group's rows carry
                        # garbage raw key values; sorting by them would
                        # cluster the group internally and restart value
                        # runs at every cluster boundary (overcounting
                        # distinct). Masking to 0 keeps the whole null
                        # group one value-sorted block; the null-flag
                        # stage still separates it from a real 0-keyed
                        # group.
                        mk = jnp.where(key_nulls[i], 0, key_cols[i])
                        order2 = order2[jnp.argsort(mk[order2],
                                                    stable=True)]
                        order2 = order2[jnp.argsort(key_nulls[i][order2],
                                                    stable=True)]
                    order2 = order2[jnp.argsort(~mask[order2], stable=True)]
            with jax.named_scope("k_agg_gather"):
                v2 = v64[order2]
                vn2 = val_nulls[j][order2]
            with jax.named_scope("k_agg_segment"):
                prev_v2 = jnp.concatenate([v2[:1], v2[:-1]])
                new_run = is_new | (v2 != prev_v2)
                live = ~vn2 & in_range & mask[order2]
                results.append(span_sum(jnp.where(live & new_run, 1, 0)
                                        .astype(jnp.int64)))
                result_nulls.append(jnp.zeros(capacity, dtype=bool))
            continue
        if opn == "count":
            _tag, nn_row = slot_plan[j]
            with jax.named_scope("k_agg_segment"):
                results.append(spans2d[nn_row])
                result_nulls.append(jnp.zeros(capacity, dtype=bool))
            continue
        if opn == "sum_i":
            _tag, nn_row, v_row = slot_plan[j]
            with jax.named_scope("k_agg_segment"):
                results.append(spans2d[v_row])
                result_nulls.append(spans2d[nn_row] == 0)
            continue
        if opn not in ("sum_f", "min", "max"):
            raise ValueError(opn)
        with jax.named_scope("k_agg_gather"):
            v = val_cols[j][order]
            vn = val_nulls[j][order] | ~in_range
        with jax.named_scope("k_agg_segment"):
            nonnull = span_sum((~vn).astype(jnp.int64))
            if opn == "sum_f":
                # segmented scan, NOT prefix-sum differences:
                # c[end]-c[start] carries the whole column's magnitude
                # into each group's rounding error (catastrophic
                # cancellation); the scan resets per group so error stays
                # group-local
                run = _seg_running(
                    jnp.add, is_new,
                    jnp.where(vn, 0.0, v.astype(jnp.float64)))
            elif opn == "min":
                big = (jnp.inf if jnp.issubdtype(v.dtype, jnp.floating)
                       else jnp.iinfo(v.dtype).max)
                run = _seg_running(jnp.minimum, is_new,
                                   jnp.where(vn, big, v))
            else:
                small = (-jnp.inf if jnp.issubdtype(v.dtype, jnp.floating)
                         else jnp.iinfo(v.dtype).min)
                run = _seg_running(jnp.maximum, is_new,
                                   jnp.where(vn, small, v))
            results.append(run[end_idx])
            result_nulls.append(nonnull == 0)
    with jax.named_scope("k_agg_segment"):
        valid = jnp.arange(capacity) < n_groups
    return (key_out, key_null_out, tuple(results), tuple(result_nulls),
            n_groups, valid)


#: compile observability hooks, installed by executor.device_exec at
#: import so the standalone kernels below (join match, topk, graft agg
#: entry) meter retraces and compile seconds into the same pipe-cache
#: stats as the fused pipelines. All None → unobserved plain jit.
_trace_cb = None        # () -> None, called once per retrace
_tls_traces = None      # () -> this thread's trace count
_charge_compile = None  # seconds -> None


def _note_trace():
    if _trace_cb is not None:
        _trace_cb()


#: THE vocabulary of device kernel names: every traced body marks its
#: stages with ``jax.named_scope("<one of these>")``, the scope lands in
#: each HLO instruction's ``op_name`` and so in the profiler's trace, and
#: the benchmark sums device time by it (``kernel.*`` metrics; what falls
#: under no scope is reported as ``kernel.unnamed_share``).  The ``k_``
#: prefix keeps them apart from the JAX primitive names that share those
#: paths (``sort``, ``gather``).  Where scopes nest, the OUTERMOST names
#: the kernel (the mesh's final merge is ``k_exchange`` although it runs
#: ``_agg_impl``).  The ``kernel-scope-vocabulary`` lint holds every
#: ``named_scope`` literal in the package to this tuple.  PERF.md §3 says
#: what each covers.
KERNEL_SCOPES = (
    "k_filter",        # scan predicates, live/padding and null masks
    "k_agg_sort",      # group-key expressions, packing, the grouping
                       # argsort(s) and the sorted keys (dense arm: the
                       # packing alone, nothing sorts)
    "k_agg_segment",   # group boundaries, prefix/segment reductions,
                       # compaction to `capacity` (dense arm: the
                       # per-bucket masked reductions)
    "k_agg_gather",    # aggregate-input expressions and their gather
                       # through the sort permutation (dense arm: the
                       # expressions alone, nothing is gathered)
    "k_join_build",    # in-program build side: key folding, build sort
    "k_join_probe",    # probe, expansion, the lazy per-row gather chain
    "k_topk",          # order-by/limit over the aggregate's groups
    "k_exchange",      # mesh: radix bucketing, all_to_all / all_gather,
                       # the merge of received partials
)

#: suffix of every jitted query program's name.  jax's persistent
#: compilation cache hashes the module with its debug info stripped
#: (jax/_src/cache_key.py), and a name scope IS debug info: a program
#: that differs from a cached one only in its scopes would be served the
#: cached executable, whose instructions carry the OLD op_names.  The
#: module's name does take part in the key, so the programs' names carry
#: this tag: bump it whenever a scope is added, moved or renamed.  Price:
#: one cold compile per program in a cache directory an older tag filled.
KERNEL_SCOPES_TAG = "ks1"


def observed_jit(fn, **jit_kw):
    """jax.jit + compile accounting (mirror of device_exec._timed_jit for
    kernels living below the executor layer): the body must call
    _note_trace(); a dispatch whose trace count moved charges its wall
    time as compile seconds.  The program is named ``<fn>_<scopes tag>``
    (see KERNEL_SCOPES_TAG)."""
    import time as _time

    @functools.wraps(fn)
    def tagged(*args, **kw):
        return fn(*args, **kw)
    tagged.__name__ = f"{fn.__name__}_{KERNEL_SCOPES_TAG}"
    jfn = jax.jit(tagged, **jit_kw)

    def run(*args, **kw):
        if _tls_traces is None:
            return jfn(*args, **kw)
        before = _tls_traces()
        t0 = _time.perf_counter()
        out = jfn(*args, **kw)
        if _tls_traces() > before and _charge_compile is not None:
            _charge_compile(_time.perf_counter() - t0)
        return out
    run.lower = jfn.lower   # the program as dispatched, for inspection
    return run


def _agg_entry(key_cols, key_nulls, val_cols, val_nulls, mask,
               n_keys, agg_ops, capacity, pack=None):
    # thin wrapper: _agg_impl itself also traces INSIDE fused pipelines,
    # which count their own traces — only the standalone entry notes here
    _note_trace()
    return _agg_impl(key_cols, key_nulls, val_cols, val_nulls, mask,
                     n_keys=n_keys, agg_ops=agg_ops, capacity=capacity,
                     pack=pack)


#: jitted standalone entry (graft entry / direct kernel callers); the SQL
#: executor instead traces _agg_impl inside its own fused pipeline jit
_agg_kernel = observed_jit(
    _agg_entry, static_argnames=("n_keys", "agg_ops", "capacity", "pack"))

# ---------------------------------------------------------------------------
# two-pass sort join kernels
# ---------------------------------------------------------------------------

def _join_count_impl(build_key, probe_key, build_null, probe_null):
    """Pass 1: sort build side, count matches per probe row."""
    _note_trace()
    with jax.named_scope("k_join_build"):
        order = jnp.argsort(build_key, stable=True)
        sb = build_key[order]
    with jax.named_scope("k_join_probe"):
        lo = jnp.searchsorted(sb, probe_key, side="left")
        hi = jnp.searchsorted(sb, probe_key, side="right")
        cnt = jnp.where(probe_null, 0, hi - lo)
    return order, sb, lo, cnt


_join_count_kernel = observed_jit(_join_count_impl)


def _join_expand_impl(order, lo, cnt, build_null, total):
    """Pass 2 (static total): expand match pairs."""
    _note_trace()
    with jax.named_scope("k_join_probe"):
        cum = jnp.cumsum(cnt)
        pos = jnp.arange(total, dtype=jnp.int64)
        probe_idx = jnp.searchsorted(cum, pos, side="right")
        base = jnp.where(probe_idx > 0,
                         cum[jnp.clip(probe_idx - 1, 0, None)], 0)
        within = pos - base
        safe_probe = jnp.clip(probe_idx, 0, lo.shape[0] - 1)
        bpos = lo[safe_probe] + within
        build_idx = order[jnp.clip(bpos, 0, order.shape[0] - 1)]
        keep = ~build_null[build_idx]
    return probe_idx, build_idx, keep


_join_expand_kernel = observed_jit(_join_expand_impl,
                                   static_argnames=("total",))


def device_join_match(build_keys, probe_keys):
    """Mirror of ops.host.join_match with device kernels. build_keys /
    probe_keys: [(np data int64, np nulls)] — pre-combined single key column
    (caller combines multi-column keys via host factorization for now).
    Returns numpy (probe_idx, build_idx)."""
    bk, bn = build_keys
    pk, pn = probe_keys
    order, _sb, lo, cnt = _join_count_kernel(
        jnp.asarray(bk), jnp.asarray(pk), jnp.asarray(bn), jnp.asarray(pn))
    total = int(jnp.sum(cnt))
    if total == 0:
        return np.zeros(0, dtype=np.int64), np.zeros(0, dtype=np.int64)
    probe_idx, build_idx, keep = _join_expand_kernel(
        order, lo, cnt, jnp.asarray(bn), total)
    keep = np.asarray(keep)
    return np.asarray(probe_idx)[keep], np.asarray(build_idx)[keep]
