"""Disk-backed paged column storage — the larger-than-memory scan path.

The reference streams arbitrarily large operands through cop paging
(reference kv/kv.go:349-350 Paging{MinPagingSize,MaxPagingSize}) and
chunk spill files (reference util/chunk/disk.go:34 ListInDisk); its scans
never require a table to fit in RAM. This engine's analog: a table's
columns live in append-only binary files on disk, readers map them with
``np.memmap`` (read-only), and the device pipelines slice fixed-size row
pages out of the maps — each slice reads only its file pages, the OS page
cache owns residency, and peak query RSS is bounded by
``pages_in_flight x page_bytes`` instead of the table size.

Write path (bulk load / datagen, the Lightning physical-import role):
``PagedTableWriter`` appends page batches column-by-column; ``finalize``
installs memmap-backed Columns into the columnar cache, so every existing
executor (host or device) sees an ordinary ``_View`` — paging is a
storage property, not a new executor protocol.

String columns are stored dictionary-encoded (int32 code files + a
dictionary sidecar) and surface as ``LazyDictColumn``: device paths read
the codes directly; only a host-side row access materializes bytes.
"""

from __future__ import annotations

import itertools
import json
import os
import pickle
import threading

import numpy as np

from ..utils.chunk import Column, LazyDictColumn, false_nulls, np_dtype_for

#: default rows per page streamed through the device pipeline — 4M rows
#: x ~40B/row ~ 160MB per in-flight block: big enough to amortize the
#: per-dispatch overhead, small enough that double-buffered transfer +
#: partial-agg state stays far under one chip's HBM.
DEFAULT_PAGE_ROWS = 1 << 22


class _ColWriter:
    __slots__ = ("path", "dtype", "f", "n")

    def __init__(self, path: str, dtype):
        self.path = path
        self.dtype = np.dtype(dtype)
        self.f = open(path, "wb")
        self.n = 0

    def append(self, arr: np.ndarray):
        a = np.ascontiguousarray(arr, dtype=self.dtype)
        a.tofile(self.f)
        self.n += len(a)

    def close(self):
        self.f.close()


class PagedTableWriter:
    """Append page batches for one table; finalize into memmap Columns.

    Usage::

        w = PagedTableWriter(dir, info)            # schema from TableInfo
        w.append({"l_orderkey": arr, ...})         # one page at a time
        w.set_dictionary("l_returnflag", [b"A", b"N", b"R"])  # str cols
        columns, handles = w.finalize()            # memmap-backed

    String columns append int32 CODES into their (sorted, deduplicated)
    dictionary — exactly the Column.set_dict contract, so device
    compare/IN/min-max over codes stays order-faithful.
    """

    def __init__(self, root: str, info):
        os.makedirs(root, exist_ok=True)
        self.root = root
        self.info = info
        self._cols = {}      # name -> ColumnInfo
        self._writers = {}   # name -> _ColWriter
        self._dicts = {}     # name -> np.ndarray(object), sorted
        for c in info.public_columns():
            self._cols[c.name] = c

    def _writer(self, name: str) -> _ColWriter:
        w = self._writers.get(name)
        if w is None:
            c = self._cols[name]
            dt = np_dtype_for(c.ftype)
            if dt is object:
                dt = np.int32  # dictionary codes
            w = _ColWriter(os.path.join(self.root, f"{name}.bin"), dt)
            self._writers[name] = w
        return w

    def set_dictionary(self, name: str, values):
        u = np.asarray(values, dtype=object)
        if len(u) > 1 and not all(u[i] < u[i + 1] for i in range(len(u) - 1)):
            raise ValueError("paged string dictionary must be sorted "
                             "and deduplicated")
        self._dicts[name] = u

    def append(self, data: dict):
        """One page: {col_name: np array} — codes for string columns."""
        for name, arr in data.items():
            self._writer(name).append(arr)

    def finalize(self):
        """Close files, write the manifest, and return
        ({col_id: Column}, handles) ready for install_bulk. Handles are a
        lazily-materialized 1..N range (row ids are dense by
        construction in the bulk-load path)."""
        n = None
        manifest = {"columns": {}}
        for name, w in self._writers.items():
            w.close()
            if n is None:
                n = w.n
            elif w.n != n:
                raise ValueError(
                    f"paged column {name} has {w.n} rows, expected {n}")
            if (np_dtype_for(self._cols[name].ftype) is object
                    and name not in self._dicts):
                # codes without a dictionary would silently surface as
                # integers on every read path — refuse at load time
                raise ValueError(
                    f"string column {name} was appended without "
                    f"set_dictionary()")
            manifest["columns"][name] = {"dtype": w.dtype.str, "rows": w.n}
        n = n or 0
        for name, u in self._dicts.items():
            with open(os.path.join(self.root, f"{name}.dict"), "wb") as f:
                pickle.dump(u, f)
        with open(os.path.join(self.root, "MANIFEST.json"), "w") as f:
            json.dump(manifest, f)
        return open_paged_columns(self.root, self.info), _range_handles(n)


class LazyRangeHandles:
    """Dense 1..n handle vector that materializes only when numpy touches
    it (writes/tombstones/_tidb_rowid access — never a plain scan). A
    600M-row bulk load must not pin a 4.8GB arange just to exist."""

    __slots__ = ("n", "_arr")

    def __init__(self, n: int):
        self.n = n
        self._arr = None

    def __len__(self):
        return self.n

    def _mat(self) -> np.ndarray:
        if self._arr is None:
            self._arr = np.arange(1, self.n + 1, dtype=np.int64)
        return self._arr

    def __array__(self, dtype=None, copy=None):
        a = self._mat()
        return a if dtype is None else a.astype(dtype)

    def __getitem__(self, i):
        return self._mat()[i]

    @property
    def dtype(self):
        return np.dtype(np.int64)


def _range_handles(n: int):
    return LazyRangeHandles(n)


def open_paged_columns(root: str, info) -> dict:
    """{col_id: Column} over the table's on-disk column files (read-only
    memmaps; zero bytes resident until a page is touched)."""
    with open(os.path.join(root, "MANIFEST.json")) as f:
        manifest = json.load(f)
    out = {}
    for c in info.public_columns():
        spec = manifest["columns"].get(c.name)
        if spec is None:
            continue
        mm = np.memmap(os.path.join(root, f"{c.name}.bin"), mode="r",
                       dtype=np.dtype(spec["dtype"]), shape=(spec["rows"],))
        dict_path = os.path.join(root, f"{c.name}.dict")
        if os.path.exists(dict_path):
            with open(dict_path, "rb") as f:
                uniques = pickle.load(f)
            out[c.id] = LazyDictColumn(c.ftype, mm, uniques)
        else:
            out[c.id] = Column(c.ftype, mm, false_nulls(spec["rows"]))
    return out


# ---------------------------------------------------------------------------
# hybrid-join spill pages (executor/hybrid_join.py)
# ---------------------------------------------------------------------------

#: process-wide registry of open spill sets: the chaos invariant is that
#: this drains to ZERO after every query — a fence/OOM/injected fault
#: mid-probe must not leak partition pages on disk (tests/chaos_harness
#: asserts spill_outstanding() between seeds)
_SPILL_LOCK = threading.Lock()
_SPILL_OPEN: dict[int, "SpillSet"] = {}
_SPILL_SEQ = itertools.count(1)

SPILL_STATS = {
    "spill_sets_opened": 0,   # lifetime SpillSets created
    "spill_writes": 0,        # partition pages written
    "spill_bytes_written": 0,  # lifetime bytes through the spill path
}


class SpillSet:
    """Host columnar pages for the hybrid hash join's OVERFLOW build
    partitions: the radix partitions that do not fit the residency
    ledger's free share are gathered column-by-column into per-partition
    binary page files (one compact sequential file per column — a
    memmap-backed fact's random partition rows become sequential reads
    for the host probe pass) and read back as read-only memmaps.

    Dictionary-encoded string columns spill their int CODES (the caller
    keeps the dictionary — same contract as the paged table format
    above).  ``close()`` deletes every page and unregisters the set; the
    drained invariant (spill_outstanding) is chaos-checked."""

    def __init__(self, tag: str = ""):
        import tempfile
        self.root = tempfile.mkdtemp(prefix=f"tidb-hj-spill-{tag}-")
        self.token = next(_SPILL_SEQ)
        self.bytes = 0
        self._parts: dict[int, dict] = {}  # pid -> {key: (path, dtype, n)}
        self._closed = False
        with _SPILL_LOCK:
            _SPILL_OPEN[self.token] = self
            SPILL_STATS["spill_sets_opened"] += 1

    def write(self, pid: int, arrays: dict):
        """Spill one partition: arrays maps a caller key (the leaf-local
        column index) -> (data, nulls) numpy arrays (codes for dict
        columns — object arrays are a caller bug and refused)."""
        from ..utils import failpoint
        # chaos hook: a `spill-fail` action models a disk-full / IO error
        # mid-spill — the join must abort classified with pages drained
        failpoint.inject("device-join-spill")
        part = self._parts.setdefault(pid, {})
        written = 0
        for key, (data, nulls) in arrays.items():
            d = np.ascontiguousarray(data)
            if d.dtype == object:
                raise ValueError(
                    "object array reached the spill writer (dictionary "
                    "columns must spill their codes)")
            nl = np.ascontiguousarray(nulls, dtype=bool)
            dp = os.path.join(self.root, f"p{pid}c{key}.bin")
            npth = os.path.join(self.root, f"p{pid}c{key}.null")
            d.tofile(dp)
            nl.tofile(npth)
            part[key] = (dp, npth, d.dtype.str, len(d))
            written += d.nbytes + nl.nbytes
        self.bytes += written
        with _SPILL_LOCK:
            SPILL_STATS["spill_writes"] += 1
            SPILL_STATS["spill_bytes_written"] += written

    def read(self, pid: int) -> dict:
        """{key: (data, nulls)} read-only memmaps of one spilled
        partition's pages."""
        out = {}
        for key, (dp, npth, dt, n) in self._parts.get(pid, {}).items():
            d = np.memmap(dp, mode="r", dtype=np.dtype(dt), shape=(n,))
            nl = np.memmap(npth, mode="r", dtype=np.bool_, shape=(n,))
            out[key] = (d, nl)
        return out

    def close(self):
        """Delete every page and unregister (idempotent).  Called from
        the hybrid join's ``finally`` so an abort at ANY point — fence,
        OOM, injected spill failure, kill — drains the pages."""
        if self._closed:
            return
        self._closed = True
        import shutil
        shutil.rmtree(self.root, ignore_errors=True)
        self._parts.clear()
        with _SPILL_LOCK:
            _SPILL_OPEN.pop(self.token, None)


def spill_outstanding() -> dict:
    """{"open_sets": n, "open_bytes": b} — the drained invariant reads
    zero/zero between queries."""
    with _SPILL_LOCK:
        sets = list(_SPILL_OPEN.values())
    return {"open_sets": len(sets),
            "open_bytes": sum(s.bytes for s in sets)}


def is_paged(col: Column) -> bool:
    """True when the column's backing array is a disk memmap (scans must
    stream pages rather than materialize/transfer the whole column)."""
    d = col._dict[0] if isinstance(col, LazyDictColumn) else col.data
    return isinstance(d, np.memmap)


def chunk_is_paged(chunk) -> bool:
    return any(is_paged(c) for c in chunk.columns)
