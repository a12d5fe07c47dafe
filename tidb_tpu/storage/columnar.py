"""Per-table columnar snapshots with incremental delta maintenance.

Scans are the hot read path of the analytical engine; decoding rows per query
would drown the device in host work. The cache materializes a table once into
column arrays (plus the handle column) and then keeps the snapshot fresh by
applying each commit's row mutations as a delta — appended row versions plus
tombstones over older ones — compacting periodically. This is the TiFlash
delta-tree role (stable layer + delta layer + background merge) rather than
the rebuild-on-version-bump v1: a single-row write no longer re-decodes the
table.

Concurrency: readers receive an immutable ``_View`` (copy-on-write row set);
``apply_delta`` never mutates arrays a view references — it builds the next
view and swaps it in. A reader that obtained a view before a commit keeps
reading exactly its row set, closing the get→project window that an
in-place delta would leak post-snapshot rows through.

Bulk loaders (the Lightning role) can still install columns directly,
bypassing row encode/decode entirely.
"""

from __future__ import annotations

import threading

import numpy as np

from ..model import TableInfo
from ..sqltypes import TYPE_LONGLONG, FieldType
from ..table import Table, rows_to_chunk
from ..utils.chunk import Chunk, Column

#: compact when the delta exceeds this many rows or this fraction of the base
_COMPACT_MIN = 4096
_COMPACT_FRAC = 8  # base_n // _COMPACT_FRAC


class _Seg:
    """One commit's appended row versions (the delta layer)."""

    __slots__ = ("handles", "live", "columns")

    def __init__(self, handles, live, columns):
        self.handles = handles    # np.int64
        self.live = live          # np.bool (False = superseded later)
        self.columns = columns    # {col_id: Column}


class _View:
    """An immutable row-set snapshot: base layer + delta segments. The only
    mutable state is the lazily-built merge cache, guarded by its own lock
    (merging twice is harmless; mutating rows a reader holds is not)."""

    __slots__ = ("columns", "handles", "base_live", "segs", "nrows",
                 "lock", "_merged", "_merged_handles", "_base_idx")

    def __init__(self, columns, handles, base_live, segs, nrows):
        self.columns = columns      # base layer {col_id: Column}
        self.handles = handles      # base handles, ASCENDING (KV scan order)
        self.base_live = base_live  # bool mask or None (= all live)
        self.segs = segs            # tuple[_Seg]
        self.nrows = nrows          # live rows across base + delta
        self.lock = threading.Lock()
        self._merged = {}
        self._merged_handles = None
        self._base_idx = None

    def delta_rows(self) -> int:
        return sum(len(s.handles) for s in self.segs)

    def _base_indices(self):
        if self.base_live is None:
            return None  # whole base
        if self._base_idx is None:
            self._base_idx = np.nonzero(self.base_live)[0]
        return self._base_idx

    def merged_column(self, col_id: int) -> Column | None:
        """Column over live rows: base[live] ++ seg0[live] ++ ... Cached, so
        repeated scans after one write are zero-decode AND zero-copy."""
        with self.lock:
            col = self._merged.get(col_id)
            if col is not None:
                return col
            base = self.columns.get(col_id)
            if base is None:
                return None
            if not self.segs and self.base_live is None:
                self._merged[col_id] = base
                return base
            idx = self._base_indices()
            datas = [base.data if idx is None else base.data[idx]]
            nulls = [base.nulls if idx is None else base.nulls[idx]]
            for s in self.segs:
                sc = s.columns[col_id]
                if s.live.all():
                    datas.append(sc.data)
                    nulls.append(sc.nulls)
                else:
                    li = np.nonzero(s.live)[0]
                    datas.append(sc.data[li])
                    nulls.append(sc.nulls[li])
            col = Column(base.ftype, np.concatenate(datas),
                         np.concatenate(nulls))
            self._carry_dictionary(base, col, idx, col_id)
            self._merged[col_id] = col
            return col

    def _carry_dictionary(self, base: Column, col: Column, idx, col_id):
        """Re-key the merged string column against the BASE dictionary when
        no delta row introduced a new value (the overwhelmingly common
        case): base codes slice + per-segment searchsorted beats a full
        np.unique over the merged object array, and the dictionary OBJECT
        (and its content signature) stays identical — which is what lets
        the compiled-fragment cache survive a delta append."""
        if base._dict is None or not base.is_object():
            return
        from ..sqltypes import TYPE_NEWDECIMAL
        if base.ftype.tp == TYPE_NEWDECIMAL:
            return
        codes, uniq = base._dict
        if len(uniq) == 0:
            return  # empty base dictionary: any delta value is new
        parts = [np.asarray(codes) if idx is None
                 else np.asarray(codes)[idx]]
        for s in self.segs:
            sc = s.columns.get(col_id)
            if sc is None:
                return
            vals = (sc.data if s.live.all()
                    else sc.data[np.nonzero(s.live)[0]])
            if len(vals):
                pos = np.clip(np.searchsorted(uniq, vals), 0,
                              len(uniq) - 1)
                # vectorized membership check (object-array equality runs
                # in C): this guards the hot per-delta merge path
                if not np.all(uniq[pos] == np.asarray(vals, dtype=object)):
                    return  # new distinct value: let dict_encode re-unique
                parts.append(pos.astype(np.int32))
        # bypass set_dict's O(dict) sortedness re-check: `uniq` is the
        # base's already-validated np.unique output, reused as-is
        col._dict = (np.concatenate(parts) if len(parts) > 1 else parts[0],
                     uniq)
        col._dict_sig = base._dict_sig

    def merged_handles(self) -> np.ndarray:
        with self.lock:
            if self._merged_handles is not None:
                return self._merged_handles
            if not self.segs and self.base_live is None:
                self._merged_handles = self.handles
                return self.handles
            idx = self._base_indices()
            parts = [self.handles if idx is None else self.handles[idx]]
            for s in self.segs:
                parts.append(s.handles if s.live.all()
                             else s.handles[np.nonzero(s.live)[0]])
            self._merged_handles = np.concatenate(parts)
            return self._merged_handles


class _Entry:
    """Cache slot for one table: the current (version, view) pair + apply
    bookkeeping. The pair is published as ONE tuple reference (`vv`): a
    reader loading it can never observe a new view with the old version —
    that mismatch would pass get()'s version check while leaking the next
    commit's rows."""

    __slots__ = ("vv", "col_sig", "lock", "delta_pos", "bulk")

    def __init__(self, version, col_sig, view, bulk=False):
        self.vv = (version, view)        # atomic ref swap on publish
        self.col_sig = col_sig
        self.bulk = bulk                 # install_bulk: base rows not in KV
        self.lock = threading.Lock()     # serializes apply/compact
        self.delta_pos: dict[int, tuple[int, int]] = {}  # handle->(seg,pos)

    @property
    def version(self):
        return self.vv[0]

    @property
    def view(self):
        return self.vv[1]

    # passthroughs kept for tests/introspection
    @property
    def handles(self):
        return self.view.handles

    @property
    def segs(self):
        return self.view.segs

    @property
    def nrows(self):
        return self.view.nrows

    def delta_rows(self):
        return self.view.delta_rows()


class ColumnarCache:
    def __init__(self, storage):
        self.storage = storage
        self._lock = threading.Lock()
        self._entries: dict[int, _Entry] = {}
        self._bulk_tags: dict[int, str] = {}

    def invalidate(self, table_id: int):
        with self._lock:
            self._entries.pop(table_id, None)

    def get(self, info: TableInfo, snapshot) -> _View | None:
        """The table's materialized row set at the current write watermark,
        as an immutable view. `snapshot` must be a kv read view with .scan
        (Snapshot or Transaction).

        Returns None when the reader's snapshot ts predates the last commit
        the cache reflects (an explicit txn holding an old read view after
        another session committed): serving the cache would leak post-
        snapshot rows, so the caller must scan through its own snapshot."""
        tid = info.id
        reader_ts = getattr(snapshot, "ts", None)
        if reader_ts is None:
            reader_ts = getattr(snapshot, "start_ts", 0)
        version, last_commit_ts = self.storage.mvcc.table_version_info(tid)
        if reader_ts < last_commit_ts:
            return None
        col_sig = tuple(c.id for c in info.public_columns())
        with self._lock:
            e = self._entries.get(tid)
            if e is not None:
                ever, eview = e.vv  # one load: version+view are consistent
                if ever == version and e.col_sig == col_sig:
                    return eview
        # build from the caller's snapshot: reader_ts >= last_commit_ts, so
        # it sees exactly the content of `version` (a commit racing in is
        # invisible to this ts; if the version counter advanced meanwhile,
        # apply_delta's version chain check heals by idempotent re-apply
        # or drop-and-rebuild)
        e = self._build(info, snapshot, version, col_sig)
        with self._lock:
            cur = self._entries.get(tid)
            # a concurrent apply_delta may have advanced the entry past our
            # snapshot — never clobber a newer entry with an older build
            if cur is None or cur.version <= e.version:
                self._entries[tid] = e
            else:
                e = cur
        return e.view

    def _build(self, info, snapshot, version, col_sig) -> _Entry:
        tbl = Table(info, snapshot)
        cols = info.public_columns()
        handles = []
        rowdicts = []
        for handle, row in tbl.iter_rows():
            handles.append(handle)
            rowdicts.append(row)
        chunk = rows_to_chunk(info, cols, handles, rowdicts)
        columns = {c.id: chunk.columns[i] for i, c in enumerate(cols)}
        view = _View(columns, np.array(handles, dtype=np.int64),
                     None, (), len(handles))
        return _Entry(version, col_sig, view)

    # -- delta maintenance (reference analog: TiFlash delta tree;
    #    v1 behavior was rebuild-on-invalidate) ------------------------------

    def apply_delta(self, info: TableInfo, muts, new_version: int):
        """Apply one committed txn's record mutations by building the next
        view copy-on-write (readers holding the old view are unaffected).

        muts: [(handle, encoded_row_bytes | None)] — None is a delete.
        new_version: the table version this commit produced; the entry must
        be exactly one behind, otherwise it is stale (a concurrent commit's
        delta was missed) and is dropped for rebuild-on-next-read."""
        tid = info.id
        col_sig = tuple(c.id for c in info.public_columns())
        with self._lock:
            e = self._entries.get(tid)
        if e is None:
            return
        with e.lock:
            if e.version != new_version - 1 or e.col_sig != col_sig:
                self.invalidate(tid)
                return
            try:
                new_view = self._next_view(e, info, muts)
            except Exception:
                self.invalidate(tid)
                return
            if new_view.delta_rows() > max(_COMPACT_MIN,
                                           len(new_view.handles)
                                           // _COMPACT_FRAC):
                new_view = self._compact(new_view, col_sig)
                e.delta_pos = {}
            e.vv = (new_version, new_view)  # atomic publish

    def _next_view(self, e: _Entry, info: TableInfo, muts) -> _View:
        from .. import tablecodec
        v = e.view
        base_live = v.base_live
        base_copied = False
        segs = list(v.segs)
        seg_copied: set[int] = set()
        nrows = v.nrows

        def tombstone(h: int):
            nonlocal base_live, base_copied, nrows
            pos = e.delta_pos.pop(h, None)
            if pos is not None:
                si, i = pos
                if segs[si].live[i]:
                    if si not in seg_copied:
                        s = segs[si]
                        segs[si] = _Seg(s.handles, s.live.copy(), s.columns)
                        seg_copied.add(si)
                    segs[si].live[i] = False
                    nrows -= 1
                    return
            i = int(np.searchsorted(v.handles, h))
            if i < len(v.handles) and v.handles[i] == h:
                if base_live is None:
                    base_live = np.ones(len(v.handles), dtype=bool)
                    base_copied = True
                elif not base_copied:
                    base_live = base_live.copy()
                    base_copied = True
                if base_live[i]:
                    base_live[i] = False
                    nrows -= 1

        up_handles, up_rows = [], []
        for h, val in muts:
            tombstone(h)
            if val is not None:
                up_handles.append(h)
                up_rows.append(tablecodec.decode_row(val))
        if up_handles:
            cols = info.public_columns()
            chunk = rows_to_chunk(info, cols, up_handles, up_rows)
            seg_cols = {c.id: chunk.columns[i] for i, c in enumerate(cols)}
            segs.append(_Seg(np.array(up_handles, dtype=np.int64),
                             np.ones(len(up_handles), dtype=bool), seg_cols))
            si = len(segs) - 1
            for i, h in enumerate(up_handles):
                e.delta_pos[h] = (si, i)
            nrows += len(up_handles)
        return _View(v.columns, v.handles, base_live, tuple(segs), nrows)

    @staticmethod
    def _compact(view: _View, col_sig) -> _View:
        """Merge delta into a new handle-sorted base (memcpy-level: no row
        decode). Restores the sorted-handles invariant tombstone relies on."""
        handles = view.merged_handles()
        order = np.argsort(handles, kind="stable")
        new_cols = {}
        for cid in col_sig:
            col = view.merged_column(cid)
            if col is None:
                continue  # base predates this column; project() defaults it
            new_cols[cid] = Column(col.ftype, col.data[order],
                                   col.nulls[order])
        return _View(new_cols, handles[order], None, (), len(handles))

    def install_bulk(self, info: TableInfo, columns: dict, handles: np.ndarray,
                     content_tag: "str | None" = None):
        """Bulk-load path (the Lightning physical-import role): install
        column arrays directly and mark the table version as current.

        ``content_tag`` is the caller's declaration of the installed
        CONTENT's identity (e.g. "tpch/lineitem/sf0.002/v1" for a
        fixed-seeded generator).  Bulk columns are process-local — they
        never travel through the shared log — so the fleet result cache
        (executor/agg_cache.py) only caches a never-SQL-written bulk
        table when a tag vouches for cross-worker content identity, and
        folds the tag into the cache key: two fleets (or two workers)
        installing different content can never share a page.  None
        (default) keeps such tables cache-ineligible."""
        tid = info.id
        version = self.storage.mvcc.table_version(tid)
        col_sig = tuple(c.id for c in info.public_columns())
        e = _Entry(version, col_sig,
                   _View(columns, handles, None, (), len(handles)),
                   bulk=True)
        with self._lock:
            self._entries[tid] = e
            if content_tag is not None:
                self._bulk_tags[tid] = str(content_tag)
        return e.view

    def is_bulk(self, table_id: int) -> bool:
        """True while the table's rows are a bulk install: they live in
        this cache only, so a KV seek (point get, index-lookup join)
        finds nothing and readers must scan the view."""
        with self._lock:
            e = self._entries.get(table_id)
        return e is not None and e.bulk

    def bulk_tag(self, table_id: int) -> "str | None":
        """The content_tag a bulk install declared for this table, if
        any (see install_bulk)."""
        with self._lock:
            return self._bulk_tags.get(table_id)

    def project(self, view: _View, col_infos, info: TableInfo) -> Chunk:
        out = []
        for c in col_infos:
            col = view.merged_column(c.id)
            if col is None:
                # column added after materialization: all default/null
                col = _default_column(c, view.nrows)
            out.append(col)
        return Chunk(out)

    def handle_column(self, view: _View) -> Column:
        h = view.merged_handles()
        return Column(FieldType(tp=TYPE_LONGLONG),
                      h, np.zeros(len(h), dtype=bool))


def _default_column(c, n: int) -> Column:
    from ..utils.chunk import np_dtype_for
    dt = np_dtype_for(c.ftype)
    if c.default_value is not None:
        if dt is object:
            data = np.full(n, c.default_value, dtype=object)
        else:
            data = np.full(n, c.default_value, dtype=dt)
        nulls = np.zeros(n, dtype=bool)
    else:
        data = (np.full(n, b"", dtype=object) if dt is object
                else np.zeros(n, dtype=dt))
        nulls = np.ones(n, dtype=bool)
    return Column(c.ftype, data, nulls)
