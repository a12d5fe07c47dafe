"""Host-built join indexes for the device join path.

The reference probes a hash table built per query execution
(executor/join.go:192 build workers, hash_table.go). On XLA that design
loses twice: hash tables need data-dependent shapes, and the sort-based
replacement re-sorts the build side on EVERY execution. But a join whose
build side is a BASE TABLE scan has a data-dependent part that only
changes when the table version changes — so the expensive part (ordering
the build rows by key) moves to the host, runs ONCE per table version in
numpy, and is cached on the Column exactly like the HBM upload
(utils/chunk.py Column._device). The device-side lookup degenerates to
gathers and searchsorteds — no sort in the compiled program at all.

Two layouts, told apart by how a probe key finds its build rows:
- ``dense``: the key IS the address. A table over the packed key span,
  read with one gather per probe row and no search. A UNIQUE build
  side's table holds the row id itself, or -1 where no row has that key
  (a gap, a NULL key, a row the pushed-down filter removed, the
  quantized slack): ``slots``, one gather a lookup. A non-unique one is
  CSR (``starts`` of span + 1 entries, ``rows`` listing row ids in key
  order): two gathers and an expansion.
- ``sorted``: row ids argsorted by packed key + the sorted key array;
  a lookup is a binary search.  Where the host sees that it pays
  (`_bucket_prefix`), the search starts from an address too: a
  ``prefix`` table over the key's HIGH bits (``packed >> shift``, about
  as many buckets as the index has padded rows, admitted by
  `direct_table_fits` like any direct table) holds where each bucket's
  keys start and end in the sorted array, side by side, so a probe row
  reads its bucket's two ends in ONE gather and bisects the few keys
  between them: ``steps`` dependent gathers, from the largest bucket's
  population, instead of ceil(log2(n)).  All keys of one bucket share
  their high bits, so the device holds and compares only the low
  ``shift`` bits (``low_keys``, one 32-bit gather a step).  Without a
  prefix (a hot key that fills a bucket, a span whose buckets are wider
  than 32 bits, a partitioned build) the device holds the int64 keys and
  every step is an emulated 64-bit compare over the whole array.

The layout is chosen from the table's BYTES (`direct_table_fits`): the
table is direct-addressed when it fits the device budget the residency
ledger reports and stays under `_DIRECT_MAX_BYTES`; the uploaded arrays
enter that ledger (`JoinIndex.device_arrays`). Rows do not enter the
rule: TPC-H's keys are NOT dense 1..N (cl. 4.2.3: ``o_orderkey`` uses 8
of every 32 values, so its span is 4.4x its rows, 24 MB of table at SF1
and 243 MB at SF10; Q5's (``c_nationkey``, ``c_custkey``) pair spans 26x
its rows in 16 MB), and a search at fact length cost the chip 18-20
dependent gathers where an address costs one (PERF.md §6, PR 28). What stays
``sorted`` is a composite key space like partsupp's (``ps_partkey``,
``ps_suppkey``): 2e9 slots, 8 GB at SF1, its 800,000 rows spread evenly
over them: a prefix of 1,064,960 buckets leaves one key a bucket and one
step of the twenty-one (PERF.md §6, PR 34).

Either layout knows whether the (non-null) build keys are UNIQUE. A
unique build side makes the join output shape the PROBE side's shape —
the expansion pass, its output capacity, and the overflow/recompile
machinery all disappear (TPC-H joins are fact⋈dim = FK⋈unique-PK, so
this is the common case on every north-star query).

Multi-column keys fold into one int64 by range packing with host-known
(min, span) per column — unlike the device-side data-dependent packing
(device_join._combined_join_keys), these are static at trace time.

Version tolerance (ROADMAP "version-tolerant pack"): the per-column
(min, span) is QUANTIZED to a geometric grid (`_quantize_range`) instead
of being exact.  The packs are baked into compiled-fragment signatures
and the dense tables' shapes (`JoinIndex.sig`, `JoinIndex.slots` /
`starts`), so with exact bounds ANY dimension-table delta that
nudged a key's min/max — one UPDATE widening a range by 1 — changed the
signature and forced a full XLA recompile.  With ~1/16-of-magnitude
slack on each end, a delta that stays inside the widened range rebuilds
only the (cheap, numpy) host index and re-uses the compiled fragment:
the lookup arrays are passed as runtime arguments, so same shapes ⇒ same
program.  A prefixed search bakes in two more numbers, both quantized:
the shift (from the packed span and the padded row count) and the number
of bisection steps (the largest bucket's population up to the next
2^steps - 1): a build-side INSERT that keeps every bucket under that
bound compiles nothing, one past it recompiles once.  Correctness is
unaffected — probe keys in the slack region simply find zero matches,
exactly like any other unmatched key.

Bucketed shapes + traced n_valid (ROADMAP item 1, the LAST recompile
trigger): the row-id array (and the sorted-key array) pads to a
geometric bucket (ops/device.py bucket_rows) and the live entry count
``n_valid`` rides to the device as a TRACED scalar in the ``jidx``
runtime arguments — never baked into the compiled program.  A build-side
INSERT that stays inside the bucket (and inside the quantized pack
range) rebuilds only this cheap numpy index: same array shapes, same
fragment signature, same compiled executable, zero new XLA compiles.
A unique dense table has no such array at all: its length is the
quantized span, and a new row lands in a slot that read -1.
Padding is inert by construction: ``rows`` pads with 0 (only reachable
behind a ``cnt`` guard that is 0 there) and ``sorted_keys`` pads with
int64 max (sorts after every real key, so probe searchsorted results
for real keys are unchanged and the ``lo < n_valid`` guard kills the
sentinel region).
"""

from __future__ import annotations

import math

import numpy as np

from ..ops.device import bucket_rows

#: the most bytes one direct-address table may take, whatever the device
#: holds: 6% of a v5e's HBM.  It is what decides on the in-process CPU
#: backend, whose budget is unlimited, so that tests take the side the
#: chip takes; on a 16 GB chip it decides too (the budget admits 12.8 GB)
_DIRECT_MAX_BYTES = 1 << 30

#: pack quantization: grid = 2^(bit_length(span)-1-SLACK_BITS) ≈ span/16
#: (min floors to the grid, max ceils) — ≤ ~12.5% span overshoot buys
#: signature stability across small dimension-table range drifts
_PACK_SLACK_BITS = 4


def _quantize_range(mn: int, mx: int) -> tuple[int, int]:
    """Widen [mn, mx] to a geometric grid so slightly-shifted bounds from
    a future table version land on the SAME packed range."""
    span = mx - mn + 1
    g = 1 << max((span - 1).bit_length() - _PACK_SLACK_BITS, 0)
    mn_q = (mn // g) * g                # floor toward -inf
    mx_q = ((mx // g) + 1) * g - 1      # ceil to the next grid edge - 1
    return mn_q, mx_q


def direct_table_fits(table_bytes: int) -> bool:
    """Whether a direct-address table of `table_bytes` is affordable: it
    stays resident beside the columns, so it has to fit the device budget
    as a resident scan's input does (`residency.scan_fits_resident`,
    against the whole budget: the index is cached per table version and
    serves every tenant), and under `_DIRECT_MAX_BYTES`."""
    from ..ops import residency
    return (table_bytes <= _DIRECT_MAX_BYTES
            and residency.scan_fits_resident(
                False, table_bytes, residency.effective_budget()))


class JoinIndex:
    """Host index over one ordered key-column tuple of a base chunk."""

    __slots__ = ("kind", "packs", "unique", "filtered", "n_rows",
                 "n_valid", "span", "slots", "starts", "rows",
                 "sorted_keys", "avg_cnt", "max_cnt", "rows_len", "_owner",
                 "prefix", "shift", "steps", "low_keys", "_prefix_owner",
                 "scoped")

    def __init__(self):
        self.slots = None
        self.filtered = False
        self._owner = None
        # over a statement's own build (a derived leaf): its device
        # arrays are published scoped and released with the statement
        self.scoped = False
        # a `sorted` index's direct-address front (_bucket_prefix)
        self.prefix = self.low_keys = None
        self.shift = self.steps = 0
        self._prefix_owner = None

    def sig(self) -> str:
        """What a compiled fragment bakes in of this index: the layout,
        the packs (hence a dense table's length), uniqueness, and the
        bucketed length and dtype of the row-id array.  n_valid is a
        TRACED runtime input, so a within-bucket build-side INSERT
        rebuilds the cheap numpy index and reuses the compiled program;
        a slot table has no row-id array (rows_len 0), and whether it
        was built under the leaf's filter decides whether the program
        still reads the build mask.  A prefixed search adds its shift
        and its number of steps (`_bucket_prefix`)."""
        ids = self.slots if self.slots is not None else self.rows
        sig = (f"{self.kind}/{self.packs}/{int(self.unique)}/"
               f"{int(self.filtered)}/{self.rows_len}/{ids.dtype}")
        if self.prefix is not None:
            # the prefix's length follows from packs and rows_len
            sig += f"/prefix{self.shift}.{self.steps}"
        return sig

    def device_arrays(self):
        """The (a0, a1, n_valid) lookup tuple the compiled fragment takes
        as runtime arguments: the slot table (a1 None) / the CSR starts /
        the sorted keys (their low bits under a prefix), the
        bucket-padded row ids, and the live entry count as a TRACED
        scalar (np.int64, the n_lives convention) — a same-shape index
        refresh re-dispatches the compiled program without retracing.  A
        prefixed `sorted` index, and no other, passes a fourth element:
        the prefix table.  The arrays are uploaded once and cached
        through the residency ledger like a column's (`direct_table_fits`
        admitted their bytes against its budget): counted, evictable,
        and dropped at a device epoch bump."""
        from ..ops import residency
        if self._owner is None:
            self._owner = residency.CacheOwner()
            self._prefix_owner = residency.CacheOwner()
        a0, a1 = _resident(self._owner, *self.host_arrays(), self.scoped)
        if self.prefix is None:
            return a0, a1, np.int64(self.n_valid)
        return (a0, a1, np.int64(self.n_valid),
                _resident(self._prefix_owner, self.prefix, None,
                          self.scoped)[0])

    def host_arrays(self):
        """The numpy (a0, a1) behind `device_arrays`, for a caller that
        places them itself (the mesh replicates them over its devices,
        mpp_exec._place_index)."""
        if self.slots is not None:
            return self.slots, None
        if self.kind == "dense":
            return self.starts, self.rows
        return (self.sorted_keys if self.prefix is None else self.low_keys,
                self.rows)

    def host_bytes(self) -> int:
        """Bytes of the arrays `device_arrays` places: what the next
        ``upload.h2d`` sends of this index."""
        return sum(a.nbytes for a in (*self.host_arrays(), self.prefix)
                   if a is not None)


def _resident(owner, a0, a1, scoped=False):
    """`owner`'s (a0, a1) on the device, through the residency ledger."""
    import jax.numpy as jnp
    from ..ops import residency
    dev = residency.lookup(owner, len(a0))
    if dev is None:
        dev = residency.publish(owner, jnp.asarray(a0),
                                None if a1 is None else jnp.asarray(a1),
                                scoped=scoped)
    return dev


def release(col) -> None:
    """Forget the index `col` caches and drop its device arrays from the
    residency ledger: a statement's own build (a derived leaf) is done
    with it, and no later statement may be served it."""
    cached, col._join_index = col._join_index, None
    idx = cached[1] if cached is not None else None
    if idx is not None and idx._owner is not None:
        from ..ops import residency
        residency.release((idx._owner, idx._prefix_owner))


def _bucket_prefix(sk, span, pad_len, row_dt):
    """The direct-address front of a `sorted` index over the valid sorted
    keys `sk`: (shift, steps, prefix), or None where a prefixed search
    would not pay.  Everything is what the host observes:

    - ``shift``: log2 of the packed span over the padded row count,
      rounded, so the table has as many entries as the index has rows to
      within a factor of sqrt(2) and is no larger than the arrays the
      index uploads anyway (partsupp at SF1: span 2,181,038,080 over
      1,048,576 rows, shift 11, 1,064,960 buckets);
    - ``prefix``: CSR starts over ``sk >> shift`` and the same starts one
      bucket on, as the two columns of one (buckets, 2) table: bucket
      b's keys are ``sk[prefix[b, 0]:prefix[b, 1]]``, the last end reads
      n_valid.  Each start is stored twice so that a probe row reads
      both ends in one gather of an 8-byte row: on the chip the lookup
      at Q9's shapes took 159 ms against 254 ms with two gathers of a
      (buckets + 1) array (PERF.md §6, PR 34);
    - ``steps``: bit_length of the largest bucket's population, the
      STATIC number of bisection steps that finds any position among a
      bucket's at most 2^steps - 1 keys (every population up to that
      bound shares one compiled program).

    None when the prefix's two values and the steps would not come to
    less than the full search's ``pad_len.bit_length()`` steps (a hot key
    with thousands of rows, a table of a few rows), when a bucket's low
    bits do not fit the 32 the device compares, or when
    `direct_table_fits` refuses the table."""
    shift = max(round(math.log2(span / pad_len)), 0)
    n_buckets = -(-span // (1 << shift))
    if shift > 32 or not direct_table_fits(
            2 * n_buckets * np.dtype(row_dt).itemsize):
        return None
    counts = np.bincount(sk >> shift, minlength=n_buckets)
    steps = max(int(counts.max()), 1).bit_length()
    if steps + 2 >= pad_len.bit_length():
        return None
    starts = np.zeros(n_buckets + 1, dtype=row_dt)
    np.cumsum(counts, out=starts[1:])
    return shift, steps, np.stack([starts[:-1], starts[1:]], axis=1)


def _pack_host(datas, valid, packs):
    """Fold key columns into one int64 per row (valid rows only are
    meaningful; invalid rows fold to arbitrary in-range values)."""
    packed = np.zeros(len(datas[0]), dtype=np.int64)
    for d, (mn, span) in zip(datas, packs):
        v = d.astype(np.int64) - mn
        np.clip(v, 0, span - 1, out=v)
        packed = packed * span + v
    return packed


def build_join_index(columns, mask_fn=None, cache_tag="", packs=None,
                     force_sorted=False, pad_rows=None,
                     scoped=False) -> "JoinIndex | None":
    """Index over `columns` (utils.chunk.Column tuple, int-kinded numpy
    data), cached on columns[0]. None when the keys can't range-pack into
    int64 (caller falls back to the device-side sort join).

    mask_fn/cache_tag: optional build-side FILTER — the leaf's pushed-down
    predicates evaluated host-side (lazily, only on cache miss). A
    filtered index drops non-qualifying rows from the CSR counts, so an
    expansion join's capacity tracks the SELECTED rows, not the raw table
    (TPC-H Q5's orders⋈customer leg shrinks ~7x: the date filter keeps
    15% of orders but an unfiltered count expands all of them). The tag
    keys the cache per predicate set; one Column can hold one index at a
    time (queries alternating predicate sets rebuild — numpy, cheap).
    `mask_fn` is the leaf's WHOLE filter: a slot table built under it
    (`JoinIndex.filtered`) is all a probe reads, its -1 stands for a
    filtered row as for a gap, and the compiled fragment does not gather
    the build side's mask again.

    packs / force_sorted / pad_rows override the shape-determining
    choices for PARTITIONED builds (executor/hybrid_join.py): every radix
    partition of one hybrid join must carry the SAME per-column (min,
    span) packs, the same layout kind (with no prefix: `_bucket_prefix`'s
    bound is one build's own) and the same padded array length —
    otherwise each partition would bake its own shapes into the fragment
    signature and the zero-recompile invariant would die P ways.  `packs`
    are the whole-table quantized ranges (probe keys outside a
    partition's narrower true range simply find no match); force_sorted
    skips the dense layout (a per-partition table spans the WHOLE key
    range — P copies of it would dwarf the data); `pad_rows`
    floors the bucket so all partitions pad to the largest one's.

    scoped: the columns are a statement's own (a derived build leaf):
    the index's device arrays are published for the statement to
    `release` (`JoinIndex.scoped`)."""
    host = columns[0]
    # the cached tuple PINS the column objects: a live reference can never
    # share its id with a newly allocated Column, which is what makes the
    # id()-keyed composite lookup sound (same convention as the pipeline
    # cache's dict_refs, executor/device_exec.py)
    cache_key = (tuple(id(c) for c in columns), cache_tag, packs,
                 force_sorted, pad_rows)
    cached = getattr(host, "_join_index", None)
    if cached is not None and cached[0] == cache_key:
        # a hit says nothing: the plan walk may ask for one index more
        # than once a fragment, and a count of hits would measure the walk
        return cached[1]
    from ..session import tracing
    from .device_exec import note_join_index_build
    note_join_index_build()
    with tracing.span("join.index_build") as sp:
        idx, nb, n_valid = _build_index(columns, mask_fn, packs,
                                        force_sorted, pad_rows)
        if idx is not None:
            idx.scoped = scoped
        # the negative entry must pin the columns too — id() keys are
        # only sound while the referenced objects stay alive
        host._join_index = (cache_key, idx, tuple(columns))
        if sp is not None:
            sp.tags.update(
                layout="none" if idx is None else idx.kind, rows=nb,
                kept=n_valid, bytes=0 if idx is None else idx.host_bytes(),
                filtered=mask_fn is not None,
                prefix=idx is not None and idx.prefix is not None)
    return idx


def _build_index(columns, mask_fn, packs, force_sorted, pad_rows):
    """`build_join_index`'s miss: (the index or None, the build side's
    rows, the rows its keys' NULLs and `mask_fn` keep), all in numpy."""
    datas = [c.data for c in columns]
    nulls = columns[0].nulls
    for c in columns[1:]:
        nulls = nulls | c.nulls
    valid = ~nulls
    if mask_fn is not None:
        m = mask_fn()
        if m is not None:
            valid = valid & m
    nb = len(datas[0])
    n_valid = int(valid.sum())

    # a partitioned build keeps the plain search: its partitions share
    # one signature, and a bound of its own each would end that
    partitioned = packs is not None or force_sorted
    if packs is not None:
        total_span = 1.0
        for _mn, span in packs:
            total_span *= span
        packs = list(packs)
    else:
        packs = []
        total_span = 1.0
        for d in datas:
            dv = d[valid]
            if dv.size == 0:
                mn, mx = 0, 0
            else:
                mn, mx = int(dv.min()), int(dv.max())
            # slack-quantized range: within-slack deltas keep the pack —
            # and therefore the fragment signature and compiled program —
            # stable
            mn, mx = _quantize_range(mn, mx)
            span = mx - mn + 1
            total_span *= span
            packs.append((mn, span))
    if total_span > 2.0**62:
        return None, nb, n_valid

    idx = JoinIndex()
    idx.filtered = mask_fn is not None
    idx.packs = tuple(packs)
    idx.n_rows = nb
    idx.n_valid = n_valid
    span_total = int(total_span)
    idx.span = span_total
    packed = _pack_host(datas, valid, packs)

    row_dt = np.int32 if nb < (1 << 31) else np.int64
    # geometric BUCKET for the row-id (and sorted-key) array shapes: a
    # within-bucket build delta keeps every traced shape — the default
    # granularity (2 buckets per doubling) is fixed here because the
    # index is cached per table version, not per session
    pad_len = bucket_rows(max(n_valid, pad_rows or 1, 1))
    idx.rows_len = pad_len

    def _pad_rows(arr):
        out = np.zeros(pad_len, dtype=row_dt)
        out[:len(arr)] = arr
        return out

    if not force_sorted and direct_table_fits(
            (span_total + 1) * np.dtype(row_dt).itemsize):
        idx.kind = "dense"
        idx.sorted_keys = None
        keys = packed[valid]
        ids = np.flatnonzero(valid).astype(row_dt)
        slots = np.full(span_total, -1, dtype=row_dt)
        slots[keys] = ids
        # a key two rows share kept the later one's id
        idx.unique = bool((slots[keys] == ids).all())
        if idx.unique:
            idx.slots = slots
            idx.starts = idx.rows = None
            idx.rows_len = 0
            idx.max_cnt = min(n_valid, 1)
            idx.avg_cnt = 1.0
        else:
            counts = np.bincount(keys, minlength=span_total)
            starts = np.empty(span_total + 1, dtype=row_dt)
            starts[0] = 0
            np.cumsum(counts, out=starts[1:])
            idx.starts = starts
            # row ids grouped by key (stable: in row order within a key)
            idx.rows = _pad_rows(ids[np.argsort(keys, kind="stable")])
            idx.max_cnt = int(counts.max(initial=0))
            idx.avg_cnt = n_valid / max(int(np.count_nonzero(counts)), 1)
    else:
        idx.kind = "sorted"
        sort_key = np.where(valid, packed, np.iinfo(np.int64).max)
        order = np.argsort(sort_key, kind="stable")
        sk = sort_key[order[:n_valid]] if n_valid else np.zeros(
            0, dtype=np.int64)
        # int64-max sentinels on the pad tail sort after every real key:
        # probe searchsorted positions for real keys are unchanged, and
        # the traced lo < n_valid guard excludes the sentinel region
        idx.sorted_keys = np.concatenate(
            [sk, np.full(pad_len - n_valid, np.iinfo(np.int64).max,
                         dtype=np.int64)])
        idx.rows = _pad_rows(order[:n_valid])
        idx.starts = None
        front = (None if partitioned
                 else _bucket_prefix(sk, span_total, pad_len, row_dt))
        if front is not None:
            idx.shift, idx.steps, idx.prefix = front
            # one bucket's keys differ in their low `shift` bits only; the
            # pad tail's sentinels lie past every bucket
            idx.low_keys = (idx.sorted_keys
                            & ((1 << idx.shift) - 1)).astype(np.uint32)
        idx.unique = bool(n_valid <= 1 or not np.any(sk[1:] == sk[:-1]))
        n_distinct = (1 + int(np.count_nonzero(sk[1:] != sk[:-1]))
                      if n_valid else 1)
        idx.avg_cnt = n_valid / max(n_distinct, 1)
        if n_valid:
            # longest equal-key run = the hottest key's row count
            bounds = np.flatnonzero(np.concatenate(
                ([True], sk[1:] != sk[:-1], [True])))
            idx.max_cnt = int(np.diff(bounds).max())
        else:
            idx.max_cnt = 0
    return idx, nb, n_valid
