"""Columnar batch format — the engine's unit of data flow.

Plays the role of the reference's ``util/chunk/chunk.go`` (Arrow-like
Chunk/Column with null bitmaps), redesigned for TPU friendliness: fixed-width
columns are numpy arrays that transfer to device as-is (int64/float64/float32/
int32), nulls are boolean masks (not packed bitmaps — XLA wants bool vectors),
and strings live host-side as object arrays of ``bytes`` with helpers to
produce device encodings (dictionary codes, padded u8 matrices, or 64-bit
order-preserving prefixes).

Executors stream these batches Volcano-style (reference: executor/executor.go
Next(ctx, *chunk.Chunk)); device operators consume/produce the array parts.
"""

from __future__ import annotations

import numpy as np

from ..sqltypes import (
    FieldType, INT_TYPES, FLOAT_TYPES, STRING_TYPES,
    TYPE_NEWDECIMAL, TYPE_DATE, TYPE_NEWDATE, TYPE_DATETIME, TYPE_TIMESTAMP,
    TYPE_DURATION, TYPE_FLOAT, TYPE_NULL, TYPE_JSON, format_value,
)

#: default rows per chunk flowing through the host pipeline
#: (reference: sessionctx/variable DefMaxChunkSize=1024; larger here because
#: device dispatch overhead favors bigger batches)
DEFAULT_CHUNK_SIZE = 65536


def null_fill_value(ft: FieldType):
    """Sentinel stored in an object array's NULL slots: 0 for wide
    decimals (bigint arithmetic runs over masked slots too), b"" for
    everything byte-like. ONE definition — every object-array producer
    must use it."""
    return 0 if ft.tp == TYPE_NEWDECIMAL else b""


def np_dtype_for(ft: FieldType):
    """numpy physical dtype for a field type; object means host-only bytes.

    Wide decimals (precision > 18 digits — reference types/mydecimal.go
    holds 81 digits) don't fit a scaled int64: they materialize as object
    arrays of arbitrary-precision Python ints (SURVEY §7's int128-pair
    plan, realized as exact bigints host-side; the device path declines
    and falls back)."""
    tp = ft.tp
    if tp == TYPE_NEWDECIMAL:
        if ft.flen is not None and ft.flen > 18:
            return object
        return np.int64
    if tp in INT_TYPES or tp == TYPE_DURATION:
        return np.int64
    if tp == TYPE_FLOAT:
        return np.float32
    if tp in FLOAT_TYPES:
        return np.float64
    if tp in (TYPE_DATE, TYPE_NEWDATE):
        return np.int32
    if tp in (TYPE_DATETIME, TYPE_TIMESTAMP):
        return np.int64
    if tp in STRING_TYPES or tp == TYPE_JSON:
        return object
    if tp == TYPE_NULL:
        # NULL literals: all-null int64 vector, coercible to any numeric kind
        return np.int64
    return object


def dict_content_sig(uniques) -> str:
    """Stable content hash of a sorted dictionary (bytes / sort keys):
    equal content → equal signature, across re-encodes and processes."""
    import hashlib
    h = hashlib.blake2b(digest_size=12)
    h.update(str(len(uniques)).encode())
    for v in uniques:
        b = v if isinstance(v, bytes) else str(v).encode()
        h.update(len(b).to_bytes(4, "little"))
        h.update(b)
    return h.hexdigest()


class Column:
    """One column: `data` (numpy array) + `nulls` (bool mask, True = NULL)."""

    # __weakref__: the HBM residency manager (ops/residency.py) holds a
    # weak back-reference per cached device upload so a collected Column
    # releases its bytes from the ledger
    __slots__ = ("ftype", "data", "nulls", "_dict", "_dict_ci", "_device",
                 "_join_index", "_minmax", "_has_nulls", "_dict_sig",
                 "__weakref__")

    def __init__(self, ftype: FieldType, data: np.ndarray, nulls: np.ndarray | None = None):
        self.ftype = ftype
        self.data = data
        if nulls is None:
            nulls = np.zeros(len(data), dtype=bool)
        self.nulls = nulls
        self._dict = None    # cached (codes, uniques) for device encoding
        self._dict_ci = None  # cached (collation, ci encoding) for _ci cols
        self._device = None  # HBM-resident cache slot; ALL access goes
        #                      through ops/residency.py (epoch-stamped,
        #                      byte-accounted, evictable — AST-linted)
        self._join_index = None  # cached host join index (executor/join_index)
        self._minmax = None  # cached (min, max) over non-null int rows
        self._has_nulls = None  # cached nulls.any()
        self._dict_sig = None  # cached content hash of the dictionary

    def __len__(self):
        return len(self.data)

    # -- pickling (fabric result pages, tidb_tpu/fabric/dedup.py) ----------
    # Only the material survives: ftype + data + nulls.  Every other slot
    # is a PROCESS-LOCAL cache — above all the `_device` HBM slot, whose
    # handle must never ship to another process (its bytes are accounted
    # in THIS process's residency ledger), plus the join-index/dict/ci/
    # minmax caches, which the consumer rebuilds lazily.  setattr-by-name
    # below is the Column constructor's None slot init in pickle form.

    _PICKLE_SLOTS = ("ftype", "data", "nulls")

    def __getstate__(self):
        return {s: getattr(self, s) for s in self._PICKLE_SLOTS}

    def __setstate__(self, st):
        for s in ("ftype", "data", "nulls", "_dict", "_dict_ci", "_device",
                  "_join_index", "_minmax", "_has_nulls", "_dict_sig"):
            setattr(self, s, st.get(s))

    @classmethod
    def from_values(cls, ftype: FieldType, values) -> "Column":
        """Build from python values (None = NULL)."""
        dt = np_dtype_for(ftype)
        n = len(values)
        nulls = np.fromiter((v is None for v in values), dtype=bool, count=n)
        if dt is object:
            decimal = ftype.tp == TYPE_NEWDECIMAL
            data = np.empty(n, dtype=object)
            for i, v in enumerate(values):
                if v is None:
                    data[i] = 0 if decimal else b""
                elif decimal:
                    data[i] = int(v)   # wide decimal: exact Python int
                elif isinstance(v, str):
                    data[i] = v.encode("utf-8")
                else:
                    data[i] = bytes(v)
        else:
            data = np.zeros(n, dtype=dt)
            for i, v in enumerate(values):
                if v is not None:
                    data[i] = v
        return cls(ftype, data, nulls)

    def value_at(self, i: int):
        """Internal python value at row i (None for NULL)."""
        if self.nulls[i]:
            return None
        v = self.data[i]
        if isinstance(v, np.generic):
            return v.item()
        return v

    def take(self, idx: np.ndarray) -> "Column":
        return Column(self.ftype, self.data[idx], self.nulls[idx])

    def slice(self, start: int, end: int) -> "Column":
        return Column(self.ftype, self.data[start:end], self.nulls[start:end])

    def is_device_friendly(self) -> bool:
        return self.data.dtype != object

    def is_object(self) -> bool:
        """String/wide-decimal physical layout? (LazyDictColumn answers
        without materializing its object view — use this instead of
        ``col.data.dtype == object`` anywhere a paged column may flow.)"""
        return self.data.dtype == object

    def minmax(self):
        """(min, max) over non-null rows of an integer-kinded column, cached
        (feeds static key-range packing in the device agg/join planners).
        None for empty/all-null/non-integer columns."""
        if self._minmax is None:
            if (self.data.dtype == object
                    or not np.issubdtype(self.data.dtype, np.integer)):
                self._minmax = (None,)
            else:
                d = self.data[~self.nulls] if self.has_nulls() else self.data
                if d.size == 0:
                    self._minmax = (None,)
                else:
                    self._minmax = (int(d.min()), int(d.max()))
        return None if self._minmax[0] is None else self._minmax

    def has_nulls(self) -> bool:
        """Does any row hold a NULL?  Read from the data (a schema without
        NOT NULL says nothing) and cached like minmax(): a Column's arrays
        never change in place, a write installs new Columns.  It scans the
        whole mask, so a paged (memmap) column is never asked."""
        if self._has_nulls is None:
            self._has_nulls = bool(self.nulls.any())
        return self._has_nulls

    # -- string device encodings -------------------------------------------

    def dict_encode(self):
        """Factorize a bytes column → (codes int32, uniques object array).

        Dictionary encoding is how string group-by/join keys reach the TPU:
        the kernel sees int32 codes; the dictionary stays host-side. Cached —
        bulk loaders install the encoding directly via set_dict().
        """
        if self._dict is None:
            uniques, codes = np.unique(self.data.astype(object),
                                       return_inverse=True)
            self._dict = (codes.astype(np.int32), uniques)
        return self._dict

    def set_dict(self, codes: np.ndarray, uniques: np.ndarray):
        """Install a pre-computed dictionary encoding (bulk-load path).

        The dictionary MUST be sorted ascending: device string compare/IN/
        min/max (ops/device.py) rely on code order == byte order, exactly
        what np.unique produces. Reject anything else loudly."""
        if len(uniques) > 1:
            u = np.asarray(uniques, dtype=object)
            if not all(u[i] < u[i + 1] for i in range(len(u) - 1)):
                raise ValueError("set_dict requires a sorted, deduplicated "
                                 "dictionary (np.unique order)")
        self._dict = (codes.astype(np.int32), uniques)

    def dict_encode_ci(self, collation: str):
        """Collation-class dictionary encoding for _ci columns →
        (ci_codes int32, key_dict, reps).

        Distinct values are grouped by their collation sort key
        (utils/collate.py); ci_codes are ranks in sort-key order, so device
        equality/ordering/group-by over the codes IS collation-correct.
        key_dict holds the sorted unique sort keys (constants are looked up
        here after the same transform); reps[i] is a representative
        original value for class i, used to decode group keys back to
        strings (reference: the collator's RestoreData role)."""
        if self._dict_ci is None or self._dict_ci[0] != collation:
            from .collate import sort_key
            codes, uniq = self.dict_encode()
            sk = np.empty(len(uniq), dtype=object)
            for i, u in enumerate(uniq):
                sk[i] = sort_key(u if isinstance(u, bytes) else
                                 str(u).encode(), collation)
            key_dict, first, inv = np.unique(sk, return_index=True,
                                             return_inverse=True)
            reps = uniq[first]
            ci_codes = inv.astype(np.int32)[codes]
            self._dict_ci = (collation, (ci_codes, key_dict, reps))
        return self._dict_ci[1]

    def dict_sig(self) -> str:
        """Content hash of the column's key dictionary (sort keys for _ci
        columns, byte uniques otherwise) — the compiled-fragment cache key
        component. id()-based keys can never survive a delta: the merged
        view re-encodes into NEW dictionary objects whose CONTENT is
        usually identical, and a compiled program's baked code LUTs stay
        valid exactly when the content matches. Cached per column."""
        if self._dict_sig is None:
            from .collate import is_ci
            if is_ci(self.ftype.collate):
                _codes, key_dict, _reps = self.dict_encode_ci(
                    self.ftype.collate)
            else:
                _codes, key_dict = self.dict_encode()
            self._dict_sig = dict_content_sig(key_dict)
        return self._dict_sig

    def prefix64(self) -> np.ndarray:
        """Order-preserving uint64 of the first 8 bytes of each value —
        enough to sort/compare most real keys on device; ties are broken
        host-side."""
        n = len(self.data)
        out = np.zeros(n, dtype=np.uint64)
        for i in range(n):
            b = self.data[i][:8]
            out[i] = int.from_bytes(b.ljust(8, b"\0"), "big")
        return out


class _PageRemapCodes:
    """Sliceable view `remap[codes[...]]` evaluated per access: the
    collation-class codes of a paged string column, without ever holding
    the full remapped array in RAM. Whole-array use (__array__) is the
    resident-dim path, bounded by the caller's budget check."""

    __slots__ = ("codes", "remap")

    def __init__(self, codes, remap):
        self.codes = codes
        self.remap = remap

    def __len__(self):
        return len(self.codes)

    @property
    def shape(self):
        return (len(self.codes),)

    @property
    def dtype(self):
        return self.remap.dtype

    def __getitem__(self, sl):
        return self.remap[np.asarray(self.codes[sl], dtype=np.int64)]

    def __array__(self, dtype=None, copy=None):
        out = self.remap[np.asarray(self.codes, dtype=np.int64)]
        return out if dtype is None else out.astype(dtype)


def false_nulls(n: int) -> np.ndarray:
    """An all-False null mask backed by ONE byte (np.broadcast_to view):
    paged tables would otherwise pay n bytes of RAM per column just to say
    'no NULLs'. Read-only; slicing/indexing yields normal views."""
    return np.broadcast_to(np.zeros(1, dtype=bool), (n,))


class LazyDictColumn(Column):
    """Dictionary-encoded string column whose object `data` materializes
    only on first host access.

    The paged store keeps string columns as int32 code files + a sorted
    dictionary sidecar (storage/paged.py). Device paths consume the codes
    via dict_encode() without ever touching `data`; the object-array view
    (`uniques[codes]`) is built lazily for host-side row access and then
    cached. slice()/take() stay in code space so host streaming over a
    paged table materializes only the rows it touches."""

    __slots__ = ("_mat",)

    def __init__(self, ftype: FieldType, codes: np.ndarray, uniques,
                 nulls: np.ndarray | None = None):
        # bypass Column.__init__: `data` is a property here
        self.ftype = ftype
        self.nulls = nulls if nulls is not None else false_nulls(len(codes))
        self._dict = (codes, np.asarray(uniques, dtype=object))
        self._dict_ci = None
        self._device = None
        self._join_index = None
        self._minmax = (None,)
        self._has_nulls = None
        self._dict_sig = None
        self._mat = None

    @property
    def data(self) -> np.ndarray:
        if self._mat is None:
            codes, uniques = self._dict
            self._mat = uniques[np.asarray(codes, dtype=np.int64)]
        return self._mat

    # pickling: the codes+dictionary ARE the material here (`data` is a
    # derived view — serializing it would materialize the whole object
    # array); same process-local-cache exclusions as Column.__getstate__

    def __getstate__(self):
        return {"ftype": self.ftype, "nulls": self.nulls,
                "_dict": (np.asarray(self._dict[0]), self._dict[1])}

    def __setstate__(self, st):
        self.ftype = st["ftype"]
        self.nulls = st["nulls"]
        self._dict = st["_dict"]
        for s in ("_dict_ci", "_device", "_join_index", "_has_nulls",
                  "_dict_sig", "_mat"):
            setattr(self, s, None)
        self._minmax = (None,)

    def __len__(self):
        return len(self._dict[0])

    def is_device_friendly(self) -> bool:
        return False

    def is_object(self) -> bool:
        return True

    def minmax(self):
        return None

    def dict_encode(self):
        return self._dict

    def dict_encode_ci(self, collation: str):
        """Collation-class encoding WITHOUT materializing a table-sized
        ci_codes array: returns a _PageRemapCodes view that applies the
        uniq→class remap per requested slice, so paged streaming reads
        stay page-bounded (Column.dict_encode_ci would fancy-index the
        whole memmap into RAM)."""
        if self._dict_ci is None or self._dict_ci[0] != collation:
            from .collate import sort_key
            codes, uniq = self._dict
            sk = np.empty(len(uniq), dtype=object)
            for i, u in enumerate(uniq):
                sk[i] = sort_key(u if isinstance(u, bytes) else
                                 str(u).encode(), collation)
            key_dict, first, inv = np.unique(sk, return_index=True,
                                             return_inverse=True)
            reps = uniq[first]
            lazy = _PageRemapCodes(codes, inv.astype(np.int32))
            self._dict_ci = (collation, (lazy, key_dict, reps))
        return self._dict_ci[1]

    def take(self, idx: np.ndarray) -> "LazyDictColumn":
        return LazyDictColumn(self.ftype, np.asarray(self._dict[0])[idx],
                              self._dict[1], np.asarray(self.nulls)[idx])

    def slice(self, start: int, end: int) -> "LazyDictColumn":
        return LazyDictColumn(self.ftype, self._dict[0][start:end],
                              self._dict[1], self.nulls[start:end])


class Chunk:
    """A batch of rows in columnar layout."""

    __slots__ = ("columns",)

    def __init__(self, columns: list[Column]):
        self.columns = columns

    @property
    def num_rows(self) -> int:
        return len(self.columns[0]) if self.columns else 0

    @property
    def num_cols(self) -> int:
        return len(self.columns)

    def __len__(self):
        return self.num_rows

    @classmethod
    def from_rows(cls, ftypes: list[FieldType], rows) -> "Chunk":
        cols = []
        for ci, ft in enumerate(ftypes):
            cols.append(Column.from_values(ft, [r[ci] for r in rows]))
        return cls(cols)

    @classmethod
    def empty(cls, ftypes: list[FieldType]) -> "Chunk":
        return cls([Column.from_values(ft, []) for ft in ftypes])

    def row(self, i: int) -> tuple:
        return tuple(c.value_at(i) for c in self.columns)

    def to_rows(self) -> list[tuple]:
        return [self.row(i) for i in range(self.num_rows)]

    def take(self, idx: np.ndarray) -> "Chunk":
        return Chunk([c.take(idx) for c in self.columns])

    def slice(self, start: int, end: int) -> "Chunk":
        return Chunk([c.slice(start, end) for c in self.columns])

    def filter(self, mask: np.ndarray) -> "Chunk":
        idx = np.nonzero(mask)[0]
        return self.take(idx)

    def mem_bytes(self) -> int:
        """Approximate resident bytes (reference: chunk.Chunk MemoryUsage —
        feeds the memory tracker and EXPLAIN ANALYZE's memory column)."""
        total = 0
        for c in self.columns:
            if isinstance(c, LazyDictColumn):
                # codes + dictionary, NOT the (possibly unmaterialized)
                # object view — and memmap codes are disk, not RAM
                codes, uniques = c.dict_encode()
                if not isinstance(codes, np.memmap):
                    total += codes.nbytes
                total += sum(len(v) + 49 for v in uniques)
                if c.nulls.strides != (0,):
                    total += c.nulls.nbytes
                continue
            if c.data.dtype == object:
                # bytes + obj header; wide-decimal bigints ~60B each
                total += sum(
                    (len(v) + 49) if isinstance(v, (bytes, bytearray, str))
                    else 60 for v in c.data)
            elif not isinstance(c.data, np.memmap):
                # memmap columns are disk pages, not query RAM (the
                # reference likewise keeps block-cache bytes outside the
                # query quota)
                total += c.data.nbytes
            if c.nulls.strides != (0,):  # stride-0 = broadcast false mask
                total += c.nulls.nbytes
        return total

    def to_display_rows(self) -> list[tuple]:
        """Rows rendered as MySQL text protocol strings (None for NULL)."""
        out = []
        for i in range(self.num_rows):
            out.append(tuple(
                format_value(c.value_at(i), c.ftype) for c in self.columns
            ))
        return out


def concat_chunks(chunks: list[Chunk]) -> Chunk:
    """Concatenate non-empty list of chunks with identical schemas."""
    if len(chunks) == 1:
        return chunks[0]
    first = chunks[0]
    cols = []
    for ci in range(first.num_cols):
        datas = [c.columns[ci].data for c in chunks]
        nulls = [c.columns[ci].nulls for c in chunks]
        cols.append(Column(first.columns[ci].ftype,
                           np.concatenate(datas), np.concatenate(nulls)))
    return Chunk(cols)
