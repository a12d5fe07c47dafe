"""ANALYZE TABLE (reference: executor/analyze.go + statistics/builder.go).

Builds, per column: null count, NDV, min/max, TopN (most frequent values
with exact counts — reference statistics/cmsketch.go:503 TopN), and an
equal-depth histogram (bucket upper bounds + cumulative counts —
reference statistics/histogram.go:50). The whole pass is vectorized
numpy over the columnar cache (the reference samples per region; here
the column is already materialized host-side)."""

from __future__ import annotations

import numpy as np

from ..meta import Meta

HIST_BUCKETS = 64
TOPN_SIZE = 8
CM_DEPTH = 4
CM_WIDTH = 512

#: version of the sketch's hash, stored in every sketch: a blob built
#: under another hash (the blake2b sketches of version 1 were bare
#: depth x width lists) answers 0, and the caller estimates from the NDV
CM_VERSION = 2

_U64 = np.uint64
_INT64_LO, _INT64_HI = float(-2 ** 63), float(2 ** 63)


def _mix64(x):
    """splitmix64's finalizer over a uint64 array (wraps modulo 2**64)."""
    x = (x ^ (x >> _U64(30))) * _U64(0xBF58476D1CE4E5B9)
    x = (x ^ (x >> _U64(27))) * _U64(0x94D049BB133111EB)
    return x ^ (x >> _U64(31))


def _hash_numbers(vals) -> np.ndarray:
    """uint64 hash per number. A float with an integral value hashes as
    that integer, so int 2 and float 2.0 collide deliberately: query
    constants may arrive as either type."""
    vals = np.asarray(vals)
    if vals.dtype.kind == "f":
        vals = vals.astype(np.float64)
        whole = ((vals == np.floor(vals)) & (vals >= _INT64_LO)
                 & (vals < _INT64_HI))
        bits = np.where(whole,
                        np.where(whole, vals, 0).astype(np.int64)
                        .view(_U64),
                        vals.view(_U64) ^ _U64(0x9E3779B97F4A7C15))
    else:
        bits = vals.astype(np.int64).view(_U64)
    return _mix64(bits + _U64(0x9E3779B97F4A7C15))


def _hash_bytes(vals) -> np.ndarray:
    """uint64 hash per byte string, one pass of array operations per
    eight bytes of the longest value. All-zero words add nothing, so the
    hash does not depend on how far the others pad a value."""
    fixed = np.asarray(vals, dtype=np.bytes_)
    n, width = fixed.shape[0], max(fixed.dtype.itemsize, 1)
    words = -(-width // 8)
    buf = np.zeros((n, words * 8), dtype=np.uint8)
    buf[:, :width] = fixed.view(np.uint8).reshape(n, width)
    buf = buf.view("<u8")
    acc = np.zeros(n, dtype=_U64)
    for j in range(words):
        w = buf[:, j]
        acc += np.where(w != 0, _mix64(w ^ _U64((j + 1) * 0xD6E8FEB86659FD93
                                                & 0xFFFFFFFFFFFFFFFF)),
                        _U64(0))
    return _mix64(acc ^ _U64(0xA0761D6478BD642F))


def _cm_hash(values) -> np.ndarray:
    """uint64 hash of every value of a column's (or a query's) keys:
    numeric arrays and all-bytes object arrays hash as arrays; any other
    object array (wide decimals held as Python ints, TopN keys read
    back as str) goes value by value through the same two hashes."""
    vals = np.asarray(values)
    if vals.dtype.kind in "iufb":
        return _hash_numbers(vals)
    if vals.dtype.kind == "S":
        return _hash_bytes(vals)
    flat = vals.ravel()
    if all(isinstance(v, (bytes, bytearray)) for v in flat):
        return _hash_bytes(flat)
    out = np.empty(len(flat), dtype=_U64)
    for i, v in enumerate(flat):
        if isinstance(v, (bool, np.bool_)):
            v = int(v)
        if isinstance(v, (int, np.integer)) and -2 ** 63 <= v < 2 ** 63 \
                or isinstance(v, (float, np.floating)):
            out[i] = _hash_numbers(np.array([v]))[0]
        else:
            out[i] = _hash_bytes([
                bytes(v) if isinstance(v, (bytes, bytearray))
                else str(v).encode("utf-8", "surrogateescape")])[0]
    return out


def _cm_indices(values) -> list:
    """Per sketch row, the column index of every value: one 64-bit hash
    per value, the rows derive from two mixes of it (reference:
    cmsketch.go hashes once with murmur128 and mixes h1 + i*h2). The high
    half of each row's hash is scaled into [0, CM_WIDTH) by multiply and
    shift; a 64-bit modulo costs ten times the hash."""
    h = _cm_hash(values)
    step = _mix64(h ^ _U64(0xE7037ED1A0B428DB)) | _U64(1)
    out = []
    for _d in range(CM_DEPTH):
        out.append((((h >> _U64(32)) * _U64(CM_WIDTH)) >> _U64(32))
                   .astype(np.int64))
        h = h + step
    return out


def build_cmsketch(values, counts) -> dict:
    """Count-min sketch over (distinct value, count) pairs (reference:
    statistics/cmsketch.go:46): depth×width counters; lookup takes the
    min across rows — an overestimate, never an underestimate."""
    idx = _cm_indices(values)
    weights = np.asarray(counts, dtype=np.float64)
    rows = [np.bincount(idx[d], weights=weights, minlength=CM_WIDTH)
            .astype(np.int64).tolist() for d in range(CM_DEPTH)]
    return {"v": CM_VERSION, "rows": rows}


def cm_query(cm, key) -> int:
    """Point estimate of `key`; 0 (no estimate: the caller falls back to
    the NDV average) for a sketch built under another CM_VERSION."""
    if not isinstance(cm, dict) or cm.get("v") != CM_VERSION:
        return 0
    idx = _cm_indices(np.array([key], dtype=object))
    return min(row[int(i[0])] for row, i in zip(cm["rows"], idx))


def _val_key(v):
    """JSON-able representation of an internal value for TopN matching."""
    if isinstance(v, (bytes, bytearray)):
        return v.decode("utf-8", "surrogateescape")
    if isinstance(v, (np.integer, int)):
        return int(v)
    return float(v)


def _distinct_counts(col, nn):
    """(sorted distinct non-null values, their counts), as
    ``np.unique(col.data[nn], return_counts=True)`` gives them (`nn`:
    the non-null rows, None when every row is one). A string
    column is counted from its dictionary codes (cached on the column;
    bulk loaders install them) and never sorted as Python objects; an
    integer column whose values span little more than its rows is
    counted by ``np.bincount`` instead of a sort."""
    if col.is_object():
        codes, uniques = col.dict_encode()
        codes = np.asarray(codes)
        counts = np.bincount(codes if nn is None else codes[nn],
                             minlength=len(uniques))
        seen = counts > 0
        return np.asarray(uniques, dtype=object)[seen], counts[seen]
    data = col.data if nn is None else col.data[nn]
    if data.dtype.kind in "iu" and len(data):
        lo, hi = int(data.min()), int(data.max())
        if hi - lo < max(2 * len(data), 1 << 16):
            counts = np.bincount((data - data.dtype.type(lo))
                                 .astype(np.int64, copy=False))
            seen = np.flatnonzero(counts)
            return (seen + lo).astype(data.dtype), counts[seen]
    return np.unique(data, return_counts=True)


def _column_stats(col):
    n_null = int(col.nulls.sum())
    cs = {"null_count": n_null}
    if n_null == len(col.nulls):
        cs["ndv"] = 0
        return cs
    uniques, counts = _distinct_counts(col, ~col.nulls if n_null else None)
    cs["ndv"] = int(len(uniques))
    # TopN: exact counts for the most frequent values
    k = min(TOPN_SIZE, len(uniques))
    top = np.argpartition(counts, -k)[-k:]
    top = top[np.argsort(counts[top])[::-1]]
    cs["topn"] = [[_val_key(uniques[i]), int(counts[i])] for i in top]
    # CMSketch over the non-TopN remainder: point estimates for values the
    # TopN missed (reference: cmsketch.go TopN+CMSketch split)
    rest = np.ones(len(uniques), dtype=bool)
    rest[top] = False
    if rest.any():
        cs["cmsketch"] = build_cmsketch(uniques[rest], counts[rest])
    if uniques.dtype != object:
        vals = uniques.astype(np.float64)
        cs["min"] = float(vals[0])
        cs["max"] = float(vals[-1])
        # equal-depth histogram over the sorted column, read off the
        # distinct values and their running counts: bucket upper bounds
        # at quantile positions + cumulative counts
        nb = min(HIST_BUCKETS, len(uniques))
        if nb >= 2:
            running = np.cumsum(counts)
            total = int(running[-1])
            pos = ((np.arange(1, nb + 1) * total) // nb) - 1
            bounds = vals[np.searchsorted(running, pos, side="right")]
            # two integers past 2**53 may share one float: the count runs
            # to the last distinct value that equals the bound
            cum = running[np.searchsorted(vals, bounds, side="right") - 1]
            cs["hist"] = {"bounds": [float(b) for b in bounds],
                          "cum": [int(c) for c in cum]}
    return cs


def _index_stats(info, cols, chunk):
    """Per-index prefix NDVs (reference: index stats built by ANALYZE in
    statistics/builder.go; consumed by access-path and join cardinality).
    prefix_ndv[k] = NDV of the first k+1 index columns as a tuple, with
    NULL counting as one distinct value. Computed by iterative
    code-densification so intermediate keys never overflow int64."""
    from ..model import SchemaState
    name2pos = {ci.name: i for i, ci in enumerate(cols)}
    out = {}
    n = chunk.num_rows
    for idx in info.indexes:
        if idx.state != SchemaState.PUBLIC:
            continue
        combined = np.zeros(n, dtype=np.int64)
        prefix_ndv = []
        ok = True
        for icol in idx.columns:
            pos = name2pos.get(icol.name)
            if pos is None:
                ok = False
                break
            col = chunk.columns[pos]
            if n:
                u, inv = np.unique(col.data, return_inverse=True)
                inv = inv.astype(np.int64) + 1
                inv[col.nulls] = 0
                combined = combined * (len(u) + 2) + inv
                _, combined = np.unique(combined, return_inverse=True)
                prefix_ndv.append(int(combined.max()) + 1)
            else:
                prefix_ndv.append(0)
        if ok and prefix_ndv:
            out[str(idx.id)] = {"name": idx.name, "prefix_ndv": prefix_ndv}
    return out


#: paged tables larger than this are analyzed from evenly-spaced sample
#: blocks (reference: ANALYZE samples per region rather than full-scanning
#: — statistics/builder.go; a 600M-row memmap must not be np.unique'd)
SAMPLE_CAP = 1 << 22
_SAMPLE_BLOCKS = 16


def _sampled_chunk(chunk, cap):
    """Evenly-spaced contiguous blocks totaling ~cap rows: contiguous
    slices read whole memmap pages (sequential IO), and spacing the blocks
    over the file keeps generation-order skew out of the sample."""
    from ..utils.chunk import concat_chunks
    n = chunk.num_rows
    block = max(cap // _SAMPLE_BLOCKS, 1)
    stride = max(n // _SAMPLE_BLOCKS, block)
    parts = []
    for b in range(_SAMPLE_BLOCKS):
        lo = min(b * stride, n)
        hi = min(lo + block, n)
        if hi > lo:
            parts.append(chunk.slice(lo, hi))
    return concat_chunks(parts)


def _rescale_column_stats(cs, factor, n):
    """Scale sampled per-column stats to the full table. NDV scaling uses
    the key-vs-category heuristic: a sample whose values are mostly
    distinct extrapolates linearly (key-like); a saturated small domain
    stays as observed."""
    if factor <= 1.0:
        return cs
    sample_nonnull = cs.pop("_sample_rows", None)
    cs["null_count"] = int(cs["null_count"] * factor)
    ndv = cs.get("ndv", 0)
    if sample_nonnull and ndv > 0.1 * sample_nonnull:
        cs["ndv"] = min(int(ndv * factor), n)
    if "topn" in cs:
        cs["topn"] = [[v, int(c * factor)] for v, c in cs["topn"]]
    if "hist" in cs:
        cs["hist"]["cum"] = [int(c * factor) for c in cs["hist"]["cum"]]
    return cs


def analyze_table(session, info):
    cache = session.columnar_cache()
    cols = info.public_columns()
    entry = cache.get(info, session.store.begin())
    if entry is not None:
        chunk = cache.project(entry, cols, info)
    else:  # unreachable with a fresh snapshot, but never skip ANALYZE
        from ..table import Table
        chunk = Table(info, session.store.begin()).scan_columnar(
            col_infos=cols)
    n = chunk.num_rows
    from ..storage.paged import chunk_is_paged
    factor = 1.0
    if n > SAMPLE_CAP and chunk_is_paged(chunk):
        chunk = _sampled_chunk(chunk, SAMPLE_CAP)
        factor = n / max(chunk.num_rows, 1)
    stats = {"row_count": int(n), "columns": {}}
    if factor > 1.0:
        stats["sampled_rows"] = int(chunk.num_rows)
    for ci, col in zip(cols, chunk.columns):
        cs = _column_stats(col)
        cs["_sample_rows"] = chunk.num_rows - cs["null_count"]
        stats["columns"][str(ci.id)] = _rescale_column_stats(
            cs, factor, int(n))
        stats["columns"][str(ci.id)].pop("_sample_rows", None)
    stats["indexes"] = _index_stats(info, cols, chunk)
    if factor > 1.0:
        # index prefix NDVs share the column key-vs-category extrapolation
        # (a unique index's sampled NDV ~= sample size must scale to the
        # table, or per-key row estimates inflate by the sample factor)
        sample_n = chunk.num_rows
        for ix in stats["indexes"].values():
            ix["prefix_ndv"] = [
                min(int(v * factor), int(n)) if v > 0.1 * sample_n else v
                for v in ix["prefix_ndv"]]
    txn = session.store.begin()
    try:
        m = Meta(txn)
        m.set_stats(info.id, stats)
        txn.commit()
    except Exception:
        txn.rollback()
        raise
    session.domain.stats[info.id] = stats
    session.domain.stats_version += 1  # invalidate cached plans
    return stats
