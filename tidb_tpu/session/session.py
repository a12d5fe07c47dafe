"""Session + Domain (reference: session/session.go ExecuteStmt loop,
domain/domain.go per-process runtime singleton).

The Domain owns the store, the schema cache, the columnar cache and the DDL
executor; Sessions own variables, the current txn and the statement loop:
parse → plan → optimize → execute, with lazy autocommit transactions
(reference: session/txn.go LazyTxn)."""

from __future__ import annotations

import datetime as _dt
import json
import random as _random
import threading
import time

import numpy as np

from ..errors import (ErrCode, SchemaError, TiDBError, WriteConflictError)
from ..errors import SchemaChangedError as _SchemaChangedError
from ..infoschema import InfoSchema, build_infoschema
from ..meta import Meta
from ..model import DBInfo
from ..parser import Parser, ast, digest as sql_digest
from ..planner import PlanBuilder, optimize
from ..planner.logical import explain_tree
from ..sqltypes import (TYPE_LONGLONG, TYPE_VARCHAR, FieldType, format_value)
from ..utils.chunk import Chunk
from . import sysvars as sv
from . import tracing


class Domain:
    """reference: domain/domain.go — schema cache + background machinery."""

    def __init__(self, store):
        from ..storage import ColumnarCache
        from .observe import Observability
        self.store = store
        self.columnar_cache = ColumnarCache(store)
        self._schema_lock = threading.Lock()
        from ..utils.rwgate import RWGate
        self.schema_gate = RWGate()  # commits(shared) vs publication(excl)
        self._infoschema: InfoSchema | None = None
        self.global_vars: dict[str, str] = {}
        self.stats: dict[int, dict] = {}      # table_id -> stats blob
        self.stats_version = 0                # bumped per stats change
        #                                       (invalidates cached plans)
        self.ddl_lock = threading.RLock()     # single-owner DDL (owner role)
        self.observe = Observability()        # slow log + stmt summary + metrics
        # conn_id -> live session, weakly: embedded users who never close()
        # must not leak ghost processlist rows (the server path still calls
        # Session.close() for prompt removal)
        import weakref
        self.sessions = weakref.WeakValueDictionary()
        from ..ddl_worker import DDLWorker
        self.ddl_worker = DDLWorker(self)   # async online-DDL owner worker
        from ..privilege import PrivManager
        self.priv = PrivManager(self)       # grant-table cache (RBAC)
        from ..statistics.worker import StatsWorker
        self.stats_worker = StatsWorker(self)  # auto-analyze loop
        from ..kv.gcworker import GCWorker
        self.gc_worker = GCWorker(self)        # MVCC safepoint GC
        self.reload_schema()
        from ..bindinfo import BindHandle
        from ..coordinator import Coordinator
        self.coordinator = Coordinator()       # PD/etcd role (TSO, election,
        #                                        registry, safepoints, watch)
        # infinite TTL for the embedded single-process deployment: nothing
        # would heartbeat an idle embedded domain, and a registry that
        # forgets its only server after 60s idle is wrong there. Server
        # mode keeps liveness real: the stats worker loop heartbeats, so a
        # wedged process still ages out of a (future) shared registry.
        self.coordinator.register_server(
            "tidb-0", {"version": "8.0.11-tpu-htap", "status_port": 10080},
            ttl_s=float("inf"))
        self.bind_handle = BindHandle(self)    # global plan bindings
        self.capture_counts: dict[str, int] = {}  # baseline capture tally
        from ..plugin import PluginRegistry
        self.plugins = PluginRegistry(self)    # audit/auth plugin SPI
        from ..telemetry import Telemetry
        self.telemetry = Telemetry(self)       # local-only usage collector
        from ..topsql import TopSQL
        self.topsql = TopSQL(self)             # per-SQL CPU attribution
        # LOCK TABLES state (reference: ddl/table_lock.go, held in-memory
        # per domain): (db, table) -> {"mode": read|write, conn_id: mode}
        self.table_locks: dict[tuple, dict] = {}
        self.table_locks_mu = threading.Lock()
        # compile-service prewarm (executor/compile_service.py): globals
        # are in-memory only, so at Domain start the opt-in is the
        # TIDB_TPU_COMPILE_PREWARM env var — recipes survive Domain
        # churn, so a re-created embedded Domain starts its ladder warm;
        # the sysvar path kicks from SET GLOBAL tidb_compile_prewarm
        from ..executor import compile_service
        compile_service.maybe_prewarm_on_start(self)
        # durable-store hookups (kv/wal.py + kv/shared_store.py): the
        # WAL reads its fsync policy from GLOBAL scope through this
        # domain, and the schema LEASE window bounds how stale this
        # worker's infoschema may run behind the fleet's published
        # schema-version cell before a statement triggers a reload
        wal = getattr(self.store.mvcc, "wal", None)
        if wal is not None:
            gv = self.global_vars
            wal.policy_source = lambda: gv.get("tidb_wal_fsync", "commit")
        if hasattr(self.store.mvcc, "on_freshness_wait"):
            # every fleet ts acquisition lands in the freshness
            # histogram (p99 is the paper's measured consistency cost;
            # /metrics renders the buckets, bench_oltp reports it)
            obs = self.observe
            self.store.mvcc.on_freshness_wait = (
                lambda s: obs.observe_hist("freshness_wait_seconds", s))
        self._schema_lease_next = 0.0

    #: seconds an infoschema may serve past the fleet's published
    #: version before the lease check re-reads the cell (the
    #: reference's schema-lease staleness bound, scaled to the segment)
    SCHEMA_LEASE_S = 0.05

    def maybe_reload_schema(self, force: bool = False):
        """Fleet schema lease: when the coordination segment's
        schema-version cell is ahead of this worker's infoschema, catch
        up the log tail (the DDL's meta writes ride it) and reload.
        One attribute check when the store has no fleet cell; at most
        one cell read per SCHEMA_LEASE_S otherwise."""
        fleet_v = getattr(self.store.mvcc, "fleet_schema_version", None)
        if fleet_v is None:
            return
        now = time.monotonic()
        if not force and now < self._schema_lease_next:
            return
        self._schema_lease_next = now + self.SCHEMA_LEASE_S
        v = fleet_v()
        if v and v > self.infoschema().version:
            self.reload_schema()

    def reload_schema(self):
        """reference: domain.Reload — full load on version change. The
        exclusive gate drains in-flight [schema-check → commit] sections
        first, so a commit can never validate against the old schema and
        land after the new one publishes (rwgate.py)."""
        txn = self.store.begin()
        try:
            m = Meta(txn)
            infos = build_infoschema(m)
        finally:
            txn.rollback()
        with self.schema_gate.exclusive():
            with self._schema_lock:
                self._infoschema = infos

    def infoschema(self) -> InfoSchema:
        with self._schema_lock:
            return self._infoschema

    def load_stats(self):
        txn = self.store.begin()
        try:
            m = Meta(txn)
            for db in m.list_databases():
                for t in m.list_tables(db.id):
                    s = m.stats(t.id)
                    if s:
                        self.stats[t.id] = s
                        self.stats_version += 1
        finally:
            txn.rollback()


def _schema_names(plan):
    """Output column names for a plan's schema (anonymous → col_i)."""
    return [r.name or f"col_{i}" for i, r in enumerate(plan.schema.refs)]


class Result:
    """Query result: column names + the result chunk."""

    def __init__(self, names=None, chunk: Chunk | None = None, affected=0,
                 last_insert_id=0, warnings=None):
        self.names = names or []
        self.chunk = chunk
        self.affected = affected
        self.last_insert_id = last_insert_id
        self.warnings = warnings or []

    @property
    def internal_rows(self):
        return self.chunk.to_rows() if self.chunk is not None else []

    @property
    def rows(self):
        """Display rows (MySQL text protocol strings)."""
        return self.chunk.to_display_rows() if self.chunk is not None else []

    @property
    def ftypes(self):
        return [c.ftype for c in self.chunk.columns] if self.chunk is not None else []


class _TempSchema:
    """InfoSchema overlay: session temporary tables shadow same-named
    catalog tables (reference: infoschema TemporaryTableAttachedInfoSchema)."""

    def __init__(self, base: InfoSchema, temp: dict):
        self._base = base
        self._temp = temp

    def table_by_name(self, db, table):
        t = self._temp.get((db.lower(), table.lower()))
        if t is not None:
            return t
        return self._base.table_by_name(db, table)

    def has_table(self, db, table):
        if (db.lower(), table.lower()) in self._temp:
            return True
        return self._base.has_table(db, table)

    def table_by_id(self, tid):
        for (db, _name), t in self._temp.items():
            if t.id == tid:
                return (self._base.schema_by_name(db), t)
        return self._base.table_by_id(tid)

    def tables_in_schema(self, db):
        out = {t.name.lower(): t for t in self._base.tables_in_schema(db)}
        for (d, name), t in self._temp.items():
            if d == db.lower():
                out[name] = t
        return sorted(out.values(), key=lambda t: t.name)

    def __getattr__(self, name):
        return getattr(self._base, name)


class _ExprCtx:
    """Context handed to ExprBuilder (sysvars, subqueries, time)."""

    def __init__(self, session):
        self.session = session
        self.params = None

    def eval_subquery(self, select, limit_one=False, outer=None):
        # mid-statement nested execution: the inner build_executor resets
        # the statement-scoped READ_FROM_STORAGE pin on the (shared)
        # session, so restore the OUTER statement's pin afterwards —
        # fragments built after the first subquery evaluation must still
        # honor the outer hint
        saved = getattr(self.session, "stmt_engine_hint", None)
        try:
            res = self.session.run_query(select, outer=outer)
        finally:
            self.session.stmt_engine_hint = saved
        fts = res.ftypes
        rows = res.internal_rows
        if limit_one:
            rows = rows[:1]
        return rows, fts

    def eval_built_plan(self, plan, limit_one=False):
        """Execute an already-built logical plan (uncorrelated subquery
        whose analysis plan is reusable)."""
        saved = getattr(self.session, "stmt_engine_hint", None)
        try:
            res = self.session.run_built_query(plan)
        finally:
            self.session.stmt_engine_hint = saved
        rows = res.internal_rows
        if limit_one:
            rows = rows[:1]
        return rows, res.ftypes

    def analyze_subquery(self, select, scope):
        """Build (and discard) the subquery's logical plan with `scope` as
        the outer name-resolution scope; correlation is recorded in
        scope.used. Returns the plan (for output types)."""
        builder = PlanBuilder(self, outer=scope)
        return builder.build(select)

    def get_sysvar(self, name, scope):
        return self.session.get_sysvar(name, scope)

    def get_uservar(self, name):
        return self.session.user_vars.get(name)

    def set_uservar(self, name, value):
        self.session.user_vars[name] = value

    def current_db(self):
        return self.session.current_db()

    def current_user(self):
        return self.session.user

    def now(self):
        return _dt.datetime.now()

    # planner hooks
    def infoschema(self):
        return self.session.infoschema()

    def mem_table(self, db, name):
        from .memtables import mem_table
        return mem_table(self.session, db, name)

    def table_rows(self, table_id):
        s = self.session.domain.stats.get(table_id)
        if s:
            return s.get("row_count", 1000)
        entry = self.session.domain.columnar_cache._entries.get(table_id)
        if entry is not None:
            return max(entry.nrows, 1)
        return 1000

    def table_stats(self, table_id):
        """ANALYZE statistics blob for CBO (planner/access.py,
        join-reorder cardinality), or None before ANALYZE."""
        return self.session.domain.stats.get(table_id)


class Session:
    """reference: session.session — one connection's state."""

    _next_conn_id = [1]
    #: the wire server creates Sessions from per-connection threads, so
    #: the read-increment below must be atomic — an unguarded `x[0] += 1`
    #: lets two simultaneous handshakes mint the SAME id, colliding in
    #: server.connections and misrouting KILL
    _conn_id_lock = threading.Lock()
    #: fleet-unique conn ids (tidb_tpu/fabric): a fabric worker sets its
    #: slot base — ``(slot + 1) << CONN_SLOT_SHIFT`` — so two serving
    #: processes can NEVER mint the same id.  KILL, processlist and
    #: slow-log attribution all resolve by conn id; with a per-process
    #: counter alone, "KILL 7" on worker B could name worker A's session.
    _conn_id_base = [0]

    @classmethod
    def set_conn_id_base(cls, base: int):
        cls._conn_id_base[0] = int(base)

    def __init__(self, domain: Domain):
        self.domain = domain
        self.store = domain.store
        self._db = "test"
        self.session_vars: dict[str, str] = {}
        self.user_vars: dict[str, object] = {}
        self.txn = None            # explicit or statement txn
        self.explicit_txn = False
        self._stmt_as_of_ts = None  # statement-level AS OF TIMESTAMP
        self._txn_as_of_ts = None   # stale READ ONLY txn's historical ts
        self.killed = False  # KILL / max_execution_time watchdog flag
        self.kill_conn = False  # KILL CONNECTION: refuse further stmts
        self.txn_read_only = False  # START TRANSACTION READ ONLY
        self.txn_stmt_history = []  # DML asts for optimistic-commit retry
        self._in_txn_retry = False
        self.session_bindings: dict[str, dict] = {}  # SESSION plan bindings
        self.binding_used = None   # normalized sql of the last matched binding
        self.bindings_version = 0  # session-binding change counter
        from ..planner.plan_cache import SessionPlanCache
        self.plan_cache = SessionPlanCache()  # prepared-plan cache
        self.plan_builds = 0       # full plan builds (test observability)
        # session-local temporary tables: (db, name) -> TableInfo
        # (reference: table/temptable)
        self.temp_tables: dict[tuple, object] = {}
        self.temp_tables_version = 0  # bumped per create/drop (plan cache)
        self.seq_lastval: dict[int, int] = {}  # sequence id -> LASTVAL
        self.seq_cache: dict[int, tuple] = {}  # sequence id -> (next, left)
        self.user = "root@%"
        self.parser = Parser()
        self.last_insert_id = 0
        self.affected_rows = 0
        self.warnings: list[str] = []
        self.prepared: dict[str, str] = {}
        with Session._conn_id_lock:
            self.conn_id = (Session._conn_id_base[0]
                            + Session._next_conn_id[0])
            Session._next_conn_id[0] += 1
        self._expr_ctx = _ExprCtx(self)
        from ..ddl import DDLExecutor
        self.ddl = DDLExecutor(self)
        self.current_sql: str | None = None   # processlist info
        self.stmt_start = 0.0
        self.mem_tracker = None               # per-statement quota tracker
        self._internal = 0                    # >0: internal SQL, skip priv
        domain.sessions[self.conn_id] = self

    def close(self):
        """Drop the session from the domain registry (processlist) and
        clean up session-local temporary tables."""
        for key in list(self.temp_tables):
            try:
                self.drop_temp_table(key)
            except Exception:
                pass
        try:
            self.unlock_tables()
        except Exception:
            pass
        self.domain.sessions.pop(self.conn_id, None)

    def drop_temp_table(self, key):
        info = self.temp_tables.pop(key, None)
        self.temp_tables_version += 1
        if info is not None:
            self.ddl._delete_table_data(info)

    # -- LOCK TABLES (reference: ddl/table_lock.go + executor lock checks) --

    def lock_tables(self, items):
        """items: [(db, name, mode)]. All-or-nothing acquisition; an
        existing foreign WRITE lock (or a foreign READ when WRITE is
        wanted) rejects with 'Table is locked' (reference error 8020)."""
        dom = self.domain
        with dom.table_locks_mu:
            for db, name, mode in items:
                holders = dom.table_locks.get((db, name), {})
                for cid, m in holders.items():
                    if cid == self.conn_id:
                        continue
                    if m == "write" or mode == "write":
                        raise TiDBError(
                            f"Table '{name}' is locked by another session",
                            code=ErrCode.TableLocked)
            self._release_locks_locked()
            for db, name, mode in items:
                dom.table_locks.setdefault((db, name), {})[
                    self.conn_id] = mode

    def unlock_tables(self):
        with self.domain.table_locks_mu:
            self._release_locks_locked()

    def _release_locks_locked(self):
        dom = self.domain
        for key in list(dom.table_locks):
            dom.table_locks[key].pop(self.conn_id, None)
            if not dom.table_locks[key]:
                del dom.table_locks[key]

    def _held_locks(self):
        with self.domain.table_locks_mu:
            return {k: v[self.conn_id]
                    for k, v in self.domain.table_locks.items()
                    if self.conn_id in v}

    def check_table_locks(self, stmt):
        """Statement-level LOCK TABLES enforcement (reference:
        executor/adapter.go checkLockTables + MySQL semantics): a session
        holding locks may only touch locked tables (writes need WRITE);
        other sessions are blocked from WRITE-locked tables entirely and
        from writing READ-locked ones."""
        if not self.domain.table_locks:
            return
        from ..priv_check import _collect_tables
        # only the DML/DDL TARGET is a write; source tables of
        # INSERT...SELECT / subqueries are reads (MySQL semantics)
        write_keys = set()
        targets = []
        if isinstance(stmt, (ast.InsertStmt, ast.TruncateTableStmt)):
            targets = [stmt.table]
        elif isinstance(stmt, (ast.UpdateStmt, ast.DeleteStmt)):
            if isinstance(stmt.table, ast.TableName) and not getattr(
                    stmt, "targets", None):
                targets = [stmt.table]
            else:
                # multi-table form: resolve target aliases to base tables
                from ..priv_check import _alias_map
                amap = _alias_map(self, stmt.table)
                if isinstance(stmt, ast.DeleteStmt):
                    for tn in stmt.targets:
                        key = (tn.as_name or tn.name).lower()
                        if key in amap:
                            db, name = amap[key]
                            write_keys.add((db.lower(), name.lower()))
                else:
                    from ..priv_check import _update_targets
                    for db, name in _update_targets(self, stmt, amap):
                        write_keys.add((db.lower(), name.lower()))
        elif isinstance(stmt, ast.DropTableStmt):
            targets = list(stmt.tables)
        elif isinstance(stmt, (ast.AlterTableStmt, ast.CreateIndexStmt,
                               ast.DropIndexStmt)):
            targets = [stmt.table]
        elif isinstance(stmt, ast.RenameTableStmt):
            targets = [old for old, _new in stmt.pairs]
        for tn in targets:
            write_keys.add(((tn.schema or self.current_db()).lower(),
                            tn.name.lower()))
        tabs = []
        _collect_tables(stmt, tabs)
        held = self._held_locks()
        infos = self.infoschema()
        for tn in tabs:
            db = (tn.schema or self.current_db()).lower()
            name = tn.name.lower()
            if not db or not infos.has_table(db, tn.name):
                continue
            key = (db, name)
            write = key in write_keys
            with self.domain.table_locks_mu:
                holders = dict(self.domain.table_locks.get(key, {}))
            mine = holders.pop(self.conn_id, None)
            foreign_write = any(m == "write" for m in holders.values())
            foreign_read = bool(holders)
            if foreign_write or (write and foreign_read):
                raise TiDBError(f"Table '{tn.name}' is locked by another "
                                "session", code=ErrCode.TableLocked)
            if held:
                if mine is None:
                    raise TiDBError(
                        f"Table '{tn.name}' was not locked with LOCK "
                        "TABLES", code=ErrCode.TableNotLocked)
                if write and mine != "write":
                    raise TiDBError(
                        f"Table '{tn.name}' was locked with a READ lock "
                        "and can't be updated",
                        code=ErrCode.TableNotLockedForWrite)

    # -- variables ----------------------------------------------------------

    def get_sysvar(self, name, scope="session"):
        reg = sv.get_registry().get(name)
        if scope == "global":
            if name in self.domain.global_vars:
                return self.domain.global_vars[name]
        else:
            if name in self.session_vars:
                return self.session_vars[name]
            if name in self.domain.global_vars:
                return self.domain.global_vars[name]
        if reg is None:
            raise TiDBError(f"Unknown system variable '{name}'",
                            code=ErrCode.UnknownSystemVariable)
        return reg.default

    def set_sysvar(self, name, value, scope="session"):
        reg = sv.get_registry().get(name)
        if reg is None:
            raise TiDBError(f"Unknown system variable '{name}'",
                            code=ErrCode.UnknownSystemVariable)
        v = reg.validate(value) if value is not None else reg.default
        if name == "tidb_snapshot" and v:
            # reject an unparseable snapshot NOW — accepting it would
            # wedge every later read behind cast errors (the reference
            # validates at SET time too, variable/varsutil.go)
            try:
                self._datetime_to_ts(v)
            except Exception:
                raise TiDBError(
                    f"Incorrect argument type to variable 'tidb_snapshot'"
                    f": '{v}'")
        if scope == "global":
            self.domain.global_vars[name] = v
            if (name == "tidb_compile_prewarm"
                    and str(v).upper() in ("ON", "1")):
                # globals are in-memory only, so the Domain-start hook
                # reads an empty dict on every boot — SET GLOBAL is the
                # moment the operator's intent actually exists; kick the
                # background prewarm NOW (executor/compile_service.py)
                from ..executor import compile_service
                compile_service.maybe_prewarm_on_start(self.domain)
        else:
            self.session_vars[name] = v

    def autocommit(self) -> bool:
        return self.get_sysvar("autocommit") == "ON"

    def current_db(self) -> str:
        return self._db

    def infoschema(self) -> InfoSchema:
        base = self.domain.infoschema()
        if not self.temp_tables:
            return base
        return _TempSchema(base, self.temp_tables)

    def expr_ctx(self):
        return self._expr_ctx

    # -- txn management (reference: session/txn.go LazyTxn) ------------------

    def txn_for_read(self):
        ts = self.stale_read_ts()
        if ts is not None:
            # stale read (reference: sessiontxn/interface.go:48 stale-read
            # providers): a historical snapshot, never the live txn
            return self.store.get_snapshot(ts)
        if self.txn is not None and self.txn.valid:
            return self.txn
        # read-only statement txn: snapshot view, nothing to commit
        return self.store.begin()

    def txn_for_write(self):
        if self.stale_read_ts() is not None or self.txn_read_only:
            raise TiDBError(
                "can not execute write statement in a read-only "
                "transaction or stale read ('tidb_snapshot'/AS OF)",
                code=ErrCode.CantExecuteInReadOnlyTxn)
        if self.txn is None or not self.txn.valid:
            self.txn = self.store.begin()
            if not self.explicit_txn and not self.autocommit():
                self.explicit_txn = True
        return self.txn

    def stale_read_ts(self):
        """The active historical read ts, or None. Priority (reference:
        sessiontxn staleness providers): statement-level AS OF TIMESTAMP >
        stale READ ONLY txn > tidb_snapshot sysvar > tidb_read_staleness."""
        if self._stmt_as_of_ts is not None:
            return self._stmt_as_of_ts
        if self._txn_as_of_ts is not None:
            return self._txn_as_of_ts
        try:
            snap = self.get_sysvar("tidb_snapshot")
        except Exception:
            snap = ""
        if snap:
            return self._datetime_to_ts(snap)
        try:
            stale_s = int(self.get_sysvar("tidb_read_staleness"))
        except Exception:
            stale_s = 0
        if stale_s < 0:
            import time as _time
            return (int((_time.time() + stale_s) * 1000) << 18) | 0x3ffff
        return None

    def set_stmt_as_of(self, expr_ast):
        """Statement-scoped AS OF TIMESTAMP from a table factor (cleared
        by run_query's finally). Mixing with an explicit txn is an error,
        like the reference."""
        if (self.txn is not None and self.txn.valid) or self.explicit_txn:
            raise TiDBError("as of timestamp can't be set in transaction",
                            code=ErrCode.AsOfInTxn)
        ts = self._eval_as_of_ts(expr_ast)
        if self._stmt_as_of_ts is not None and self._stmt_as_of_ts != ts:
            raise TiDBError(
                "can not set different time in the as of",
                code=ErrCode.AsOfInTxn)
        self._stmt_as_of_ts = ts

    def _eval_as_of_ts(self, expr_ast) -> int:
        from ..expression.builder import ExprBuilder, Schema
        b = ExprBuilder(Schema([]), self._expr_ctx)
        v = b.build(expr_ast).eval_scalar()
        if v is None:
            raise TiDBError("invalid AS OF TIMESTAMP value")
        if isinstance(v, (bytes, bytearray)):
            v = v.decode()
        return self._datetime_to_ts(v)

    def _datetime_to_ts(self, v) -> int:
        """Datetime (string or internal micros) → TSO upper bound for that
        wall instant (PD layout: unix-ms << 18 | logical)."""
        from ..sqltypes import TYPE_DATETIME, FieldType
        from ..table import cast_value
        if isinstance(v, str):
            v = cast_value(v, FieldType(tp=TYPE_DATETIME, decimal=6))
        micros = int(v)
        ms = micros // 1000
        return (ms << 18) | 0x3ffff

    def txn_dirty(self, table_id) -> bool:
        """True if the current txn holds uncommitted writes for this table
        (forces the union-scan read path)."""
        if self.txn is None or not self.txn.valid:
            return False
        if table_id in self.txn.touched_tables:
            return True
        if len(self.txn.membuf) == 0:
            return False
        from .. import tablecodec
        start, end = tablecodec.table_range(table_id)
        return bool(self.txn.membuf.range_items(start, end))

    def finish_dml(self):
        """Autocommit boundary after a DML statement."""
        if self.explicit_txn:
            return
        if self.autocommit() and self.txn is not None and self.txn.valid:
            self._commit_txn()

    def _commit_txn(self):
        txn, self.txn = self.txn, None
        from .. import tablecodec
        cache = self.domain.columnar_cache
        # capture per-table record mutations BEFORE commit (the membuffer
        # survives commit, but collecting first keeps failure paths simple)
        deltas: dict[int, list] | None = {}
        try:
            for tid in txn.touched_tables:
                pre = tablecodec.record_prefix(tid)
                muts = []
                for k, v in txn.membuf.range_items(pre, pre + b"\xff" * 9):
                    try:
                        _t, h = tablecodec.decode_record_key(k)
                    except ValueError:
                        continue
                    muts.append((h, v))
                deltas[tid] = muts
        except Exception:
            deltas = None
        if txn.schema_fps:
            # fleet half of the schema lease: a sibling worker's DDL
            # published a newer schema-version cell — reload FIRST
            # (outside the shared gate: reload takes the exclusive
            # side), then let the fingerprint check below decide whether
            # this txn's tables actually moved (ErrInfoSchemaChanged,
            # retriable) or the DDL was elsewhere (commit proceeds)
            self.domain.maybe_reload_schema(force=True)
            # F1 schema-lease guard (reference: the commit-time schema
            # check behind ErrInfoSchemaChanged + schema_amender.go's
            # role): mutations built against a table whose column/index
            # states advanced may lack maintenance the new state requires
            # (e.g. removing a delete-only index's entry) — fail the
            # commit retriably instead of corrupting the index. The
            # shared gate keeps [check → commit] atomic w.r.t. schema
            # publication (reload_schema holds the exclusive side).
            from ..errors import SchemaChangedError
            from ..table import schema_fp
            with self.domain.schema_gate.shared():
                infos_now = self.domain.infoschema()
                for tid, fp in txn.schema_fps.items():
                    info, _stats_tid = self._resolve_physical(infos_now, tid)
                    if info is None or (
                            schema_fp(info) != fp
                            and not self._try_amend_schema(txn, tid, fp,
                                                           info)):
                        txn.rollback()
                        raise SchemaChangedError(
                            "Information schema is changed during the "
                            "execution of the statement (for example, "
                            "table definition may be updated by other DDL "
                            "ran in parallel). Try again later")
                commit_ts = txn.commit()
        else:
            commit_ts = txn.commit()
        import json as _json
        # readonly observability var (reference: tidb_last_txn_info)
        self.session_vars["tidb_last_txn_info"] = _json.dumps(
            {"txn_scope": "global", "start_ts": txn.start_ts,
             "commit_ts": commit_ts})
        # commit succeeded: maintain the columnar cache incrementally
        # (reference analog: TiFlash applies raft log deltas, not rebuilds)
        infos = self.infoschema()
        for tid in txn.touched_tables:
            newv = txn.committed_versions.get(tid)
            info, stats_tid = self._resolve_physical(infos, tid)
            if deltas is not None and tid in deltas:
                # stats modify-count feed (reference: handle/update.go)
                self.domain.stats_worker.record_delta(stats_tid,
                                                      len(deltas[tid]))
            if deltas is None or info is None or newv is None:
                cache.invalidate(tid)
                continue
            try:
                cache.apply_delta(info, deltas[tid], newv)
            except Exception:
                cache.invalidate(tid)

    def _try_amend_schema(self, txn, tid, old_fp, new_info) -> bool:
        """Schema amender for the dominant mid-txn DDL case (reference:
        session/schema_amender.go, 704 LoC — amendOperationAddIndex):
        when the only schema delta on a written table is NON-UNIQUE
        indexes gaining write visibility (ADD INDEX reaching write-only/
        write-reorg/public while this optimistic txn was open), patch the
        membuffer with the missing index mutations — delete the entry the
        backfill may have written for the pre-txn row, insert the entry
        for the new row — and let the commit proceed instead of failing
        8028. Anything else (column changes, dropped/regressed indexes,
        unique additions whose duplicate check needs a global scan) keeps
        the fingerprint gate's retriable abort. Returns True when the
        txn's mutations now satisfy the CURRENT schema."""
        from .. import tablecodec
        from ..model import SchemaState
        from ..table import Table, schema_fp
        new_fp = schema_fp(new_info)
        if old_fp[0] != new_fp[0]:
            return False  # column layout moved: row encodings may be stale
        old_idx = {t[0]: t for t in old_fp[1]}
        to_amend = []
        for ix in new_info.indexes:
            prev = old_idx.pop(ix.id, None)
            prev_state = prev[1] if prev is not None else None
            if prev is not None and (prev[2] != ix.unique
                                     or ix.state < prev_state):
                return False  # changed definition or regressing state
            prev_writes = (prev_state is not None
                           and prev_state > SchemaState.DELETE_ONLY)
            if prev_writes or ix.state <= SchemaState.DELETE_ONLY:
                continue  # puts already maintained, or none required yet
            if ix.unique:
                return False
            to_amend.append(ix)
        if old_idx:
            return False  # an index this txn maintained no longer exists
        if to_amend:
            pre = tablecodec.record_prefix(tid)
            items = list(txn.membuf.range_items(pre, pre + b"\xff" * 9))
            tbl = Table(new_info, txn)

            def entry_key(ix, row, h):
                # to_amend is non-unique only: the entry key always
                # carries the handle (table.py _index_put layout)
                return tablecodec.index_key(
                    new_info.id, ix.id, tbl._index_values(ix, row), handle=h)

            for k, v in items:
                try:
                    _t, h = tablecodec.decode_record_key(k)
                except ValueError:
                    continue
                r_new = tablecodec.decode_row(v) if v is not None else None
                old_val = txn.snapshot.get(k)
                r_old = (tablecodec.decode_row(old_val)
                         if old_val is not None else None)
                for ix in to_amend:
                    if r_old is not None:
                        # the reorg backfill (running at a later snapshot)
                        # indexes the pre-txn row; our commit replaces it.
                        # Amended keys skip the prewrite ts-conflict check
                        # — the backfill's later commit on exactly these
                        # keys is the expected interleaving, not a race
                        key = entry_key(ix, r_old, h)
                        txn.delete(key)
                        txn.amend_keys.add(key)
                    if r_new is not None:
                        key = entry_key(ix, r_new, h)
                        txn.put(key, tablecodec.INDEX_VALUE_MARKER)
                        txn.amend_keys.add(key)
        txn.schema_fps[tid] = new_fp
        return True

    def _resolve_physical(self, infos, tid):
        """tid → (TableInfo view, stats table id): logical tables resolve
        directly; partition physical ids resolve to a partition view with
        stats rolling up to the logical table. (None, tid) when dropped."""
        found = infos.table_by_id(tid)
        if found is not None:
            return found[1], tid
        part = infos.partition_by_id(tid)
        if part is not None:
            from ..partition import partition_view
            _db, logical, pdef = part
            return partition_view(logical, pdef), logical.id
        return None, tid

    def _implicit_commit(self):
        """DDL and account-management statements implicitly commit the
        active transaction first (reference: MySQL implicit commit;
        session.go runs DDL outside the user txn)."""
        self.explicit_txn = False
        if self.txn is not None and self.txn.valid:
            self._commit_txn()
        else:
            self.txn = None

    def begin(self):
        if self.txn is not None and self.txn.valid:
            self._commit_txn()
        self._txn_as_of_ts = None
        self.txn = self.store.begin()
        self.explicit_txn = True
        self.txn_stmt_history = []

    def commit(self):
        self.explicit_txn = False
        self._txn_as_of_ts = None
        self.txn_read_only = False
        history, self.txn_stmt_history = self.txn_stmt_history, []
        if self.txn is not None and self.txn.valid:
            from ..errors import SchemaChangedError
            try:
                self._commit_txn()
            except (WriteConflictError, SchemaChangedError):
                # both are retriable by statement replay: the fresh attempt
                # re-resolves tables under the new schema (reference:
                # doCommitWithRetry, session.go:797)
                if self._txn_retry_disabled() or not history:
                    raise
                self._retry_txn(history)
        else:
            self.txn = None

    def _txn_retry_disabled(self) -> bool:
        try:
            v = str(self.get_sysvar("tidb_disable_txn_auto_retry"))
        except Exception:
            return True
        return v.upper() in ("ON", "1", "TRUE")

    def _retry_limit(self) -> int:
        try:
            return max(int(self.get_sysvar("tidb_retry_limit")), 0)
        except Exception:
            return 10

    def _retry_txn(self, history):
        """Optimistic-txn retry: replay the statement history on a fresh
        snapshot and re-commit (reference: session.go:797 doCommitWithRetry
        → retry with schema check).  Retries draw from the session's
        unified backoff budget (utils/backoff.Backoffer): bounded attempts
        with jittered sleeps between replays, interruptible by KILL."""
        from ..errors import BackoffExhaustedError
        from ..utils.backoff import Backoffer
        limit = max(self._retry_limit(), 1)
        bo = Backoffer.for_session(self)
        last = None
        for attempt in range(limit):
            self.txn = self.store.begin()
            self._in_txn_retry = True
            self.explicit_txn = True  # replayed DML must not autocommit
            try:
                for stmt in history:
                    self._dispatch(stmt)
                self.explicit_txn = False
                self._commit_txn()
                return
            except (WriteConflictError, _SchemaChangedError) as e:
                last = e
                if self.txn is not None and self.txn.valid:
                    self.txn.rollback()
                self.txn = None
                if attempt + 1 < limit:
                    try:
                        bo.backoff("txnRetry", e)
                    except BackoffExhaustedError as be:
                        last = be
                        break
                continue
            except Exception:
                if self.txn is not None and self.txn.valid:
                    self.txn.rollback()
                self.txn = None
                raise
            finally:
                self._in_txn_retry = False
                self.explicit_txn = False
        raise last if last is not None else TiDBError(
            "transaction retry failed", code=ErrCode.TxnRetryable)

    def rollback(self):
        self.explicit_txn = False
        self._txn_as_of_ts = None
        self.txn_read_only = False
        self.txn_stmt_history = []
        if self.txn is not None and self.txn.valid:
            self.txn.rollback()
        self.txn = None

    def _meta_txn_retry(self, body, exhaust_msg: str):
        """Run one independent meta txn (autoid/sequence allocation —
        outside the user txn) with unified conflict retry: WriteConflict
        backs off through the session's budget ("autoid" curve) and
        exhaustion surfaces as a NAMED classified error.  `body(txn)`
        commits (or rolls back a no-op) itself and returns the result."""
        from ..errors import BackoffExhaustedError
        from ..utils.backoff import Backoffer
        bo = Backoffer.for_session(self)
        while True:
            txn = self.store.begin()
            try:
                return body(txn)
            except WriteConflictError as e:
                txn.rollback()
                try:
                    bo.backoff("autoid", e)
                except BackoffExhaustedError as be:
                    raise TiDBError(exhaust_msg,
                                    code=ErrCode.BackoffExhausted) from be
            except Exception:
                txn.rollback()
                raise

    def alloc_autoid(self, table_id, n=1) -> int:
        """Independent meta txn for id allocation
        (reference: meta/autoid — batched, outside the user txn)."""
        def body(txn):
            base, _end = Meta(txn).alloc_autoid_batch(table_id, n)
            txn.commit()
            return base
        return self._meta_txn_retry(body, "autoid allocation conflict")

    def seq_next(self, info) -> int:
        """NEXTVAL: serve from the session's cached batch; refill with one
        independent meta txn per CACHE values (reference: meta/autoid
        SequenceAllocator — outside the user txn)."""
        inc = info.sequence.get("increment", 1) or 1
        st = self.seq_cache.get(info.id)
        if st is None or st[1] <= 0:
            k = max(int(info.sequence.get("cache", 1) or 1), 1)

            def body(txn):
                first, count = Meta(txn).sequence_next_batch(
                    info.id, info.sequence, k)
                txn.commit()
                return (first, count)
            st = self._meta_txn_retry(body, "sequence allocation conflict")
        v, remaining = st
        self.seq_cache[info.id] = (v + inc, remaining - 1)
        self.seq_lastval[info.id] = v
        return v

    def seq_setval(self, info, v: int) -> int:
        self.seq_cache.pop(info.id, None)  # cached batch is now stale

        def body(txn):
            Meta(txn).set_sequence_value(info.id, int(v))
            txn.commit()
            return int(v)
        return self._meta_txn_retry(body, "sequence setval conflict")

    def rebase_autoid(self, table_id, new_base: int):
        def body(txn):
            m = Meta(txn)
            if m.autoid(table_id) < new_base:
                m.set_autoid(table_id, new_base)
                txn.commit()
            else:
                txn.rollback()
        self._meta_txn_retry(body, "autoid rebase conflict")

    # -- columnar cache accessor used by executors ---------------------------

    def columnar_cache(self):
        return self.domain.columnar_cache

    # -- statement loop ------------------------------------------------------

    def execute(self, sql: str) -> list[Result]:
        """reference: session.ExecuteStmt (session.go:1637)."""
        # DIAG <kind> (session/diag.py): the direct-port diagnostics op
        # behind the cluster memtables — a diagnostics verb, not SQL
        # grammar, so it intercepts before the parser
        if sql.lstrip()[:4].upper() == "DIAG":
            from . import diag
            r = diag.maybe_handle(self, sql)
            if r is not None:
                return [r]
        # fleet schema lease (no-op outside a durable shared store): a
        # sibling worker's DDL must be visible before this statement
        # plans against the local infoschema
        self.domain.maybe_reload_schema()
        t0 = time.perf_counter()
        stmts = self.parser.parse(sql)
        if not self._internal:
            self.domain.observe.note_parse(time.perf_counter() - t0,
                                           len(stmts))
        return [self._execute_stmt(s) for s in stmts]

    def prepare(self, sql: str):
        """Binary-protocol PREPARE: parse once, return (stmt_ast,
        param_count) — '?' markers are real ParamMarker nodes, so the count
        follows SQL lexing (comments/identifiers/strings excluded).
        reference: server/driver_tidb.go Prepare."""
        stmts = self.parser.parse(sql)
        if len(stmts) != 1:
            raise TiDBError("prepared statement must be a single statement")
        return stmts[0], self.parser.param_count

    def prepared_schema(self, stmt_ast, n_params: int = 0):
        """Best-effort output schema (names, ftypes) for a prepared
        statement, derived by planning with NULL-bound parameters — the
        COM_STMT_PREPARE response must advertise the real column count
        (reference: server/conn_stmt.go writePrepare). Returns ([], [])
        for non-resultset statements or when planning needs real values."""
        if not isinstance(stmt_ast, (ast.SelectStmt, ast.SetOprStmt)):
            return [], []
        self._expr_ctx.params = [None] * n_params
        try:
            plan = self.plan_query(stmt_ast)
            return _schema_names(plan), [r.ftype for r in plan.schema.refs]
        except Exception:
            return [], []
        finally:
            self._expr_ctx.params = None

    def execute_prepared(self, stmt_ast, params: list) -> Result:
        """Binary-protocol EXECUTE over a pre-parsed statement with bound
        parameters (reference: server/conn_stmt.go handleStmtExecute)."""
        self._expr_ctx.params = list(params)
        try:
            return self._execute_stmt(stmt_ast)
        finally:
            self._expr_ctx.params = None

    def _execute_stmt(self, stmt) -> Result:
        self.warnings = []
        self.killed = False  # a KILL targets the CURRENT statement only
        if self.kill_conn:
            raise TiDBError("connection was killed",
                            code=ErrCode.QueryInterrupted)
        # a previous statement that only PLANNED (EXPLAIN, CTAS) may have
        # pinned a stale-read ts without a run_query finally to clear it
        self._stmt_as_of_ts = None
        # expensive-query watchdog (reference: util/expensivequery/
        # expensivequery.go:34,69 + MySQL semantics: TOP-LEVEL read-only
        # SELECTs only — a DML's embedded SELECT must not arm it)
        timer = None
        if isinstance(stmt, (ast.SelectStmt, ast.SetOprStmt)):
            try:
                timeout_ms = int(self.get_sysvar("max_execution_time"))
            except Exception:
                timeout_ms = 0
            if timeout_ms > 0:
                import threading as _threading
                timer = _threading.Timer(timeout_ms / 1000.0, self.kill)
                timer.daemon = True
                timer.start()
        # span tracing (session/tracing.py): sample this statement's
        # lifecycle per tidb_trace_sampling_rate (TRACE statements force
        # their own trace in _exec_trace).  Sampling off costs exactly
        # this one sysvar read + branch; no Trace is ever allocated.
        tr = None
        if not self._internal and tracing.active() is None:
            try:
                rate = float(self.get_sysvar("tidb_trace_sampling_rate"))
            except (TiDBError, ValueError, TypeError):
                rate = 0.0
            if rate > 0 and (rate >= 1.0 or _random.random() < rate):
                tr = tracing.begin("statement", origin="sampled",
                                   conn_id=self.conn_id,
                                   stmt=type(stmt).__name__)
        t0 = time.perf_counter()
        try:
            sql = stmt.restore()
        except Exception:
            sql = type(stmt).__name__
        self.current_sql = sql
        self.stmt_start = time.time()
        # advisory-lock owner identity: per-SESSION, not per-thread (an
        # in-process embedding serves many sessions on one thread)
        from ..expression.builtins_ext import set_lock_owner
        set_lock_owner(id(self))
        # per-statement memory quota (reference: stmtctx MemTracker under
        # the session tracker; tidb_mem_quota_query)
        from ..utils.memory import MemTracker
        try:
            quota = int(self.get_sysvar("tidb_mem_quota_query"))
        except Exception:
            quota = 0
        self.mem_tracker = MemTracker(f"conn{self.conn_id}", quota)
        self._expr_ctx.cte_results = {}  # recursive-CTE cache, per stmt
        res = None
        # audit plugins observe every statement (reference: the audit hook
        # in connection dispatch, server/conn.go:1094)
        if self.domain.plugins.list():
            from ..plugin import EVENT_STMT
            self.domain.plugins.audit_general(self, sql, EVENT_STMT)
        try:
            res = self._dispatch(stmt)
            if isinstance(stmt, (ast.SelectStmt, ast.SetOprStmt,
                                 ast.ExplainStmt, ast.TraceStmt,
                                 ast.ShowStmt)):
                # read-only statements: a kill landing after the last
                # operator checkpoint still cancels (result discarded).
                # Write statements are exempt — their txn may already be
                # committed, and "interrupted" after a commit would lie
                self.check_killed()
            return res
        except Exception:
            # statement-level rollback of the autocommit txn — ANY escaping
            # exception must not leave a stale txn dangling on the session
            if not self.explicit_txn and self.txn is not None and self.txn.valid:
                self.txn.rollback()
                self.txn = None
            raise
        finally:
            if timer is not None:
                timer.cancel()
            self.current_sql = None
            el = time.perf_counter() - t0
            try:
                if tr is not None:
                    tracing.finish(tr, succ=res is not None)
                thr_ms = int(self.get_sysvar("tidb_slow_log_threshold"))
                rows = (res.affected if res is not None and res.chunk is None
                        else (res.chunk.num_rows if res is not None else 0))
                # a sampled statement crossing the slow threshold keeps
                # its rendered span tree on the SlowQueryItem — the
                # causal timeline lands NEXT TO the slow entry instead
                # of needing a separate trace lookup
                trace_text = ""
                if tr is not None and el >= thr_ms / 1000.0:
                    trace_text = tracing.render_tree(tr)
                try:
                    slow_file = str(
                        self.get_sysvar("tidb_slow_query_file")).strip()
                except TiDBError:
                    slow_file = ""
                self.domain.observe.observe_stmt(
                    user=self.user, db=self._db, sql=sql,
                    digest=sql_digest(sql), latency_s=el, rows=rows,
                    succ=res is not None, slow_threshold_s=thr_ms / 1000.0,
                    trace=trace_text, slow_query_file=slow_file)
                self.domain.observe.observe_hist(
                    "statement_duration_seconds", el)
            except Exception:
                pass  # observability must never fail the statement

    def _dispatch(self, stmt) -> Result:
        if self.domain.priv.enabled and not self._internal:
            from ..priv_check import check_stmt_privileges
            check_stmt_privileges(self, stmt)
        if isinstance(stmt, (ast.CreateUserStmt, ast.DropUserStmt,
                             ast.AlterUserStmt, ast.GrantStmt,
                             ast.RevokeStmt)):
            # implicit commit: the grant-table writes and the cache reload
            # must see committed state, not the open txn's snapshot
            self._implicit_commit()
            from ..executor import priv_exec
            fn = {ast.CreateUserStmt: priv_exec.create_user,
                  ast.DropUserStmt: priv_exec.drop_user,
                  ast.AlterUserStmt: priv_exec.alter_user,
                  ast.GrantStmt: priv_exec.grant,
                  ast.RevokeStmt: priv_exec.revoke}[type(stmt)]
            fn(self, stmt)
            return Result()
        if isinstance(stmt, (ast.LockTablesStmt, ast.UnlockTablesStmt)):
            self._implicit_commit()  # LOCK/UNLOCK TABLES commit (MySQL)
            if isinstance(stmt, ast.UnlockTablesStmt):
                self.unlock_tables()
                return Result()
            items = []
            infos = self.infoschema()
            for tn, mode in stmt.items:
                db = tn.schema or self.current_db()
                infos.table_by_name(db, tn.name)  # must exist
                items.append((db.lower(), tn.name.lower(), mode))
            self.lock_tables(items)
            return Result()
        if isinstance(stmt, (ast.SelectStmt, ast.SetOprStmt, ast.InsertStmt,
                             ast.UpdateStmt, ast.DeleteStmt,
                             ast.TruncateTableStmt, ast.DropTableStmt,
                             ast.AlterTableStmt, ast.CreateIndexStmt,
                             ast.DropIndexStmt, ast.RenameTableStmt)):
            self.check_table_locks(stmt)
        if isinstance(stmt, (ast.SelectStmt, ast.SetOprStmt)):
            if (getattr(stmt, "for_update", False)
                    and (self.explicit_txn or not self.autocommit())):
                return self._run_select_for_update(stmt)
            return self.run_query(stmt)
        if isinstance(stmt, ast.InsertStmt):
            from ..executor.dml import InsertExec
            r = self._exec_dml(stmt, lambda: InsertExec(self, stmt).execute())
            self.last_insert_id = r.last_insert_id or self.last_insert_id
            return Result(affected=r.affected, last_insert_id=r.last_insert_id)
        if isinstance(stmt, ast.UpdateStmt):
            from ..executor.dml import UpdateExec
            r = self._exec_dml(stmt, lambda: UpdateExec(self, stmt).execute())
            return Result(affected=r.affected)
        if isinstance(stmt, ast.DeleteStmt):
            from ..executor.dml import DeleteExec
            r = self._exec_dml(stmt, lambda: DeleteExec(self, stmt).execute())
            return Result(affected=r.affected)
        if isinstance(stmt, ast.UseStmt):
            virtual = stmt.db.lower() in ("information_schema",
                                          "performance_schema",
                                          "metrics_schema")
            if not virtual and \
                    self.infoschema().schema_by_name(stmt.db) is None:
                raise SchemaError(f"Unknown database '{stmt.db}'",
                                  code=ErrCode.BadDB)
            self._db = stmt.db
            return Result()
        if isinstance(stmt, ast.SetStmt):
            return self._exec_set(stmt)
        if isinstance(stmt, ast.BeginStmt):
            self.txn_read_only = stmt.read_only
            if stmt.as_of is not None:
                # stale READ ONLY txn: a pinned historical read view,
                # no write txn at all (reference: sessiontxn staleness
                # provider for START TRANSACTION READ ONLY AS OF)
                if self.txn is not None and self.txn.valid:
                    self._commit_txn()
                self._txn_as_of_ts = self._eval_as_of_ts(stmt.as_of)
                self.explicit_txn = True
                self.txn_stmt_history = []
                return Result()
            self.begin()
            return Result()
        if isinstance(stmt, ast.CommitStmt):
            self.commit()
            return Result()
        if isinstance(stmt, ast.RollbackStmt):
            self.rollback()
            return Result()
        if isinstance(stmt, (ast.CreateDatabaseStmt, ast.DropDatabaseStmt,
                             ast.CreateTableStmt, ast.DropTableStmt,
                             ast.TruncateTableStmt, ast.CreateIndexStmt,
                             ast.DropIndexStmt, ast.AlterTableStmt,
                             ast.RenameTableStmt, ast.CreateViewStmt,
                             ast.CreateSequenceStmt, ast.DropSequenceStmt)):
            # DDL implicitly commits (MySQL rule) — EXCEPT CREATE/DROP
            # TEMPORARY TABLE, which MySQL exempts explicitly
            if not getattr(stmt, "temporary", False):
                self._implicit_commit()
        if isinstance(stmt, ast.ShowStmt):
            from .show import exec_show
            return exec_show(self, stmt)
        if isinstance(stmt, ast.ExplainStmt):
            return self._exec_explain(stmt)
        if isinstance(stmt, ast.CreateDatabaseStmt):
            self.ddl.create_database(stmt)
            return Result()
        if isinstance(stmt, ast.DropDatabaseStmt):
            self.ddl.drop_database(stmt)
            if self._db.lower() == stmt.name.lower():
                self._db = ""
            return Result()
        if isinstance(stmt, ast.CreateTableStmt):
            self.ddl.create_table(stmt)
            return Result()
        if isinstance(stmt, ast.CreateViewStmt):
            self.ddl.create_view(stmt)
            return Result()
        if isinstance(stmt, ast.CreateSequenceStmt):
            self.ddl.create_sequence(stmt)
            return Result()
        if isinstance(stmt, ast.DropSequenceStmt):
            self.ddl.drop_sequence(stmt)
            return Result()
        if isinstance(stmt, ast.RecoverTableStmt):
            self._implicit_commit()
            self.ddl.recover_table(stmt)
            return Result()
        if isinstance(stmt, ast.CreateBindingStmt):
            from ..bindinfo import make_binding
            key, rec = make_binding(stmt.original, stmt.hinted,
                                    db=self.current_db())
            if stmt.is_global:
                self.domain.bind_handle.create(key, rec)
            else:
                self.session_bindings[key] = rec
                self.bindings_version += 1
            return Result()
        if isinstance(stmt, ast.DropBindingStmt):
            from ..bindinfo import binding_key, normalized_sql
            key = binding_key(self.current_db(),
                              normalized_sql(stmt.original))
            if stmt.is_global:
                self.domain.bind_handle.drop(key)
            else:
                self.session_bindings.pop(key, None)
                self.bindings_version += 1
            return Result()
        if isinstance(stmt, ast.DropTableStmt):
            self.ddl.drop_table(stmt)
            return Result()
        if isinstance(stmt, ast.TruncateTableStmt):
            self.ddl.truncate_table(stmt)
            return Result()
        if isinstance(stmt, ast.CreateIndexStmt):
            self.ddl.create_index(stmt)
            return Result()
        if isinstance(stmt, ast.DropIndexStmt):
            self.ddl.drop_index(stmt)
            return Result()
        if isinstance(stmt, ast.AlterTableStmt):
            self.ddl.alter_table(stmt)
            return Result()
        if isinstance(stmt, ast.RenameTableStmt):
            self.ddl.rename_table(stmt)
            return Result()
        if isinstance(stmt, ast.AnalyzeTableStmt):
            return self._exec_analyze(stmt)
        if isinstance(stmt, ast.AdminStmt):
            return self._exec_admin(stmt)
        if isinstance(stmt, ast.PrepareStmt):
            sql = stmt.sql
            if isinstance(sql, ast.VariableExpr):
                v = self.user_vars.get(sql.name)
                sql = v.decode() if isinstance(v, bytes) else str(v or "")
            self.prepared[stmt.name] = sql
            return Result()
        if isinstance(stmt, ast.ExecuteStmt):
            return self._exec_execute(stmt)
        if isinstance(stmt, ast.DeallocateStmt):
            self.prepared.pop(stmt.name, None)
            return Result()
        if isinstance(stmt, ast.FlushStmt):
            return Result()
        if isinstance(stmt, (ast.CreatePlacementPolicyStmt,
                             ast.DropPlacementPolicyStmt)):
            # placement policies persist in meta; tables reference them by
            # name (reference: ddl/placement_policy.go). With ONE embedded
            # store the constraints are catalog state — the scheduler role
            # needs multiple stores — but the DDL surface round-trips.
            self._implicit_commit()
            return self._exec_placement_policy(stmt)
        if isinstance(stmt, ast.KillStmt):
            target = self.domain.sessions.get(stmt.conn_id)
            if target is None:
                raise TiDBError(f"Unknown thread id: {stmt.conn_id}",
                                code=ErrCode.NoSuchThread)
            target.kill(query_only=stmt.query_only)
            return Result()
        if isinstance(stmt, ast.BRIEStmt):
            self._implicit_commit()
            from .. import br
            from ..sqltypes import TYPE_LONGLONG, TYPE_VARCHAR
            if stmt.kind == "backup":
                meta = (br.physical_backup_database
                        if stmt.mode == "physical"
                        else br.backup_database)(self, stmt.db, stmt.path)
            else:
                # mode auto-detects from backupmeta; an explicit MODE
                # must match what the backup actually is
                bm = json.loads(br.open_storage(
                    stmt.path).read_text("backupmeta.json"))
                physical = bm.get("mode") == "physical"
                if stmt.mode and (stmt.mode == "physical") != physical:
                    raise TiDBError(
                        f"backup at '{stmt.path}' is "
                        f"{'physical' if physical else 'logical'}, not "
                        f"{stmt.mode}")
                meta = (br.physical_restore_database if physical
                        else br.restore_database)(
                    self, stmt.path, stmt.db, meta=bm)
            ft_s = FieldType(tp=TYPE_VARCHAR)
            ft_i = FieldType(tp=TYPE_LONGLONG)
            rows = [(t["name"].encode(), t.get("rows", t.get("kv", 0)))
                    for t in meta["tables"]]
            return Result(names=["table", "rows"],
                          chunk=Chunk.from_rows([ft_s, ft_i], rows))
        if isinstance(stmt, ast.TraceStmt):
            return self._exec_trace(stmt)
        if isinstance(stmt, ast.PlanReplayerStmt):
            return self._exec_plan_replayer(stmt)
        raise TiDBError(f"unsupported statement {type(stmt).__name__}")

    # -- DML execution with retry (reference: session.go:797
    #    doCommitWithRetry + executor/adapter.go:435 pessimistic retry) -----

    def _exec_dml(self, stmt, run):
        """Run a DML executor with the transaction-mode-appropriate
        conflict handling:
        - explicit pessimistic txn: lock written keys per statement,
          blocking on foreign locks; re-execute on a fresh for-update
          snapshot when a conflicting commit slipped in;
        - autocommit (implicit txn): retry the whole statement on commit
          conflict up to tidb_retry_limit;
        - explicit optimistic txn: record the statement for commit-time
          replay (see _retry_txn)."""
        if self.explicit_txn or not self.autocommit():
            # explicit txn OR implicit txn (autocommit=0): the first DML
            # must take the same path as the rest of the transaction
            mode = ""
            try:
                mode = str(self.get_sysvar("tidb_txn_mode")).lower()
            except Exception:
                pass
            if mode != "optimistic":
                return self._exec_dml_pessimistic(run)
            r = run()
            if not self._in_txn_retry:
                self.txn_stmt_history.append(stmt)
            return r
        from ..errors import (BackoffExhaustedError, LockedError,
                              SchemaChangedError)
        from ..utils.backoff import Backoffer
        try:
            wait_s = float(self.get_sysvar("innodb_lock_wait_timeout"))
        except Exception:
            wait_s = 50.0
        # wall-clock Backoffer: innodb_lock_wait_timeout is a hard user-
        # facing deadline — tidb_backoff_weight must not stretch it and
        # slow statement re-executions count against it, not just sleeps
        bo = Backoffer(budget_ms=wait_s * 1000, wall_clock=True,
                       check_killed=self.check_killed)
        last = None
        attempts = 0
        while True:
            try:
                return run()
            except (WriteConflictError, SchemaChangedError) as e:
                # schema change mid-statement retries like a conflict: the
                # fresh attempt re-resolves the table and rebuilds the
                # mutations under the new column/index states
                last = e
                attempts += 1
                if attempts > max(self._retry_limit(), 0):
                    raise
            except LockedError as e:
                # a pessimistic txn holds the key: wait it out through the
                # budgeted lock-wait curve (reference: client-go boTxnLock)
                last = e
                try:
                    bo.backoff("txnLock", e)
                except BackoffExhaustedError:
                    raise TiDBError(
                        "Lock wait timeout exceeded; try restarting "
                        "transaction", code=ErrCode.LockWaitTimeout)
            if self.txn is not None and self.txn.valid:
                self.txn.rollback()
            self.txn = None

    def _exec_dml_pessimistic(self, run):
        """Pessimistic statement execution: read at a fresh for_update_ts,
        buffer writes, then acquire pessimistic locks on the write set —
        waiting out foreign locks; when a conflicting commit landed after
        our for_update_ts, undo the statement's buffered writes and
        re-execute on a newer snapshot (reference: adapter.go:435
        handlePessimisticDML + UpdateForUpdateTS)."""
        from ..errors import BackoffExhaustedError, LockedError
        from ..kv.store import Snapshot
        from ..utils.backoff import Backoffer
        txn = self.txn_for_write()
        try:
            wait_s = float(self.get_sysvar("innodb_lock_wait_timeout"))
        except Exception:
            wait_s = 50.0
        orig_snapshot = txn.snapshot
        # hard wall-clock deadline, not weight-scaled (see _exec_dml)
        bo = Backoffer(budget_ms=wait_s * 1000, wall_clock=True,
                       check_killed=self.check_killed)
        last = None
        try:
            while True:
                sp = txn.membuf.savepoint()
                # frontier-fresh, not a raw TSO tick: the shared oracle
                # orders a raw ts ABOVE a peer's commit_ts even when the
                # local replica has not applied that commit yet, so a
                # raw-ts for-update read would compute from the stale
                # value while has_commit_after(for_update_ts) stays
                # silent — a cross-worker lost update.  fresh_read_ts
                # blocks until the applied LSN covers every live peer's
                # durable frontier <= ts (kv/shared_store.fresh_read_ts)
                for_update_ts = self.store._fresh_read_ts()
                txn.snapshot = Snapshot(self.store, for_update_ts,
                                        own_start_ts=txn.start_ts)
                try:
                    r = run()
                except LockedError as e:
                    # a foreign txn is mid-commit (prewrite locks visible
                    # to our read): wait it out like the lock-wait path
                    last = e
                    txn.membuf.rollback_to(sp)
                    try:
                        bo.backoff("txnLock", e)
                    except BackoffExhaustedError:
                        raise TiDBError(
                            "Lock wait timeout exceeded; try restarting "
                            "transaction", code=ErrCode.LockWaitTimeout)
                    continue
                except Exception:
                    txn.membuf.rollback_to(sp)
                    raise
                keys = txn.membuf.keys_since(sp)
                try:
                    txn.lock_keys_wait(
                        keys, for_update_ts,
                        timeout_s=max(bo.remaining_ms() / 1000, 0.001))
                    return r
                except WriteConflictError as e:
                    last = e
                    txn.membuf.rollback_to(sp)
                    try:
                        bo.backoff("txnRetry", e)
                    except BackoffExhaustedError:
                        raise e
                    continue
                except Exception:
                    # lock-wait timeout / deadlock: the statement failed —
                    # its buffered writes must not survive to commit
                    txn.membuf.rollback_to(sp)
                    raise
        finally:
            txn.snapshot = orig_snapshot

    def _run_select_for_update(self, stmt):
        """SELECT ... FOR UPDATE (reference: executor SelectLockExec):
        read on a fresh for-update snapshot, pessimistically lock the
        scanned rows of every base table (a conservative superset when
        filters could not be pushed to the scan), and execute on that same
        snapshot so the returned rows are the latest committed versions.
        Retries with a newer snapshot when a conflicting commit slips
        between snapshot and lock."""
        from .. import tablecodec
        from ..executor import build_executor
        from ..executor.exec_select import eval_conds_mask
        from ..kv.store import Snapshot
        from ..planner.logical import DataSource
        from ..table import Table
        txn = self.txn_for_write()
        plan = self.plan_query(stmt)
        try:
            wait_s = float(self.get_sysvar("innodb_lock_wait_timeout"))
        except Exception:
            wait_s = 50.0
        orig_snapshot = txn.snapshot
        last = None
        try:
            for _attempt in range(max(self._retry_limit(), 1)):
                # frontier-fresh for the same reason as
                # _exec_dml_pessimistic: FOR UPDATE promises the latest
                # committed versions, which in a fleet means waiting out
                # peers' durable frontiers, not just minting a ts
                for_update_ts = self.store._fresh_read_ts()
                txn.snapshot = Snapshot(self.store, for_update_ts,
                                        own_start_ts=txn.start_ts)
                keys = []

                def walk(p):
                    if isinstance(p, DataSource):
                        tbl = Table(p.table_info, txn, parts=p.partitions)
                        if (p.access is not None
                                and p.table_info.partition is None):
                            # drive from the chosen access path instead of
                            # a full scan (reference: SelectLockExec locks
                            # the reader's returned row keys)
                            from ..executor.exec_select import (
                                resolve_access_handles)
                            handles = resolve_access_handles(tbl, p.access)
                            for h in handles:
                                keys.append(tablecodec.record_key(
                                    p.table_info.id, int(h)))
                        else:
                            pts = (tbl.partition_tables()
                                   if p.table_info.partition is not None
                                   else [tbl])
                            for pt in pts:
                                chunk = pt.scan_columnar(
                                    col_infos=p.col_infos, with_handle=True)
                                handles = chunk.columns[-1].data
                                if p.pushed_conds:
                                    data = type(chunk)(chunk.columns[:-1])
                                    mask = eval_conds_mask(p.pushed_conds,
                                                           data)
                                    handles = handles[mask]
                                for h in handles:
                                    keys.append(tablecodec.record_key(
                                        pt.info.id, int(h)))
                    for c in p.children:
                        walk(c)
                walk(plan)
                try:
                    txn.lock_keys_wait(keys, for_update_ts,
                                       timeout_s=wait_s)
                except WriteConflictError as e:
                    last = e
                    continue
                # rows are locked: execute on the same snapshot
                exe = build_executor(plan, self._exec_ctx())
                chunk = exe.execute()
                return Result(names=_schema_names(plan), chunk=chunk)
        finally:
            txn.snapshot = orig_snapshot
        raise last if last is not None else TiDBError(
            "select-for-update retry failed", code=ErrCode.TxnRetryable)

    # -- query path ----------------------------------------------------------

    def plan_query(self, stmt, outer=None):
        undo = None
        if outer is None and isinstance(stmt, (ast.SelectStmt,
                                               ast.SetOprStmt)):
            undo = self._apply_binding(stmt)
        try:
            self.plan_builds += 1
            builder = PlanBuilder(self._expr_ctx, outer=outer)
            plan = builder.build(stmt)
            plan = optimize(plan, self._expr_ctx)
            if outer is None and isinstance(stmt, ast.SelectStmt):
                self._maybe_capture_baseline(stmt, plan)
            return plan
        finally:
            if undo:
                from ..bindinfo import undo_hints
                # restore the AST: prepared statements re-plan the same
                # object, and a dropped binding must stop applying
                undo_hints(undo)

    def _maybe_capture_baseline(self, stmt, plan):
        """Plan-baseline auto capture (reference: bindinfo/handle.go:749
        via the statement summary): with tidb_capture_plan_baselines on, a
        SELECT planned twice gets a GLOBAL binding recording the plan's
        synthesized hint set, so the choice survives restarts and stats
        drift."""
        try:
            if self._internal or self.binding_used is not None:
                return
            if str(self.get_sysvar(
                    "tidb_capture_plan_baselines")).upper() not in (
                        "ON", "1"):
                return
            if stmt.from_ is None:
                return
            from ..bindinfo import binding_key, normalized_sql, plan_hints
            norm = normalized_sql(stmt)
            key = binding_key(self.current_db(), norm)
            if self.domain.bind_handle.match(key) is not None:
                return
            seen = self.domain.capture_counts
            if len(seen) > 4096 and key not in seen:
                seen.clear()  # bounded tally; a cleared count just delays
                #               a capture by one extra planning
            seen[key] = seen.get(key, 0) + 1
            if seen[key] < 2:  # reference captures on the second execution
                return
            hints = plan_hints(plan)
            if not hints:
                return
            orig_text = stmt.restore()
            saved = stmt.hints
            try:  # render the bind text WITH the captured hints
                stmt.hints = hints
                bind_text = stmt.restore()
            finally:
                stmt.hints = saved
            rec = {"original": orig_text, "bind": bind_text,
                   "db": self.current_db().lower(),
                   "hints": [], "sql_hints": [[n, list(a)]
                                              for n, a in hints],
                   "created": time.strftime("%Y-%m-%d %H:%M:%S"),
                   "status": "enabled", "source": "capture"}
            self.domain.bind_handle.create(key, rec)
        except Exception:
            pass  # capture must never fail the statement

    def _apply_binding(self, stmt):
        """Plan-binding match at optimize time (reference:
        planner/optimize.go:147-207): transplant the matched binding's
        index hints onto the statement. Returns the undo list."""
        from ..bindinfo import (apply_hints, binding_key, hints_from_record,
                                normalized_sql)
        self.binding_used = None
        try:
            if self.get_sysvar("tidb_use_plan_baselines").upper() not in (
                    "ON", "1"):
                return None  # baselines disabled for this session
        except Exception:
            pass
        try:
            key = binding_key(self.current_db(), normalized_sql(stmt))
        except Exception:
            return None
        rec = self.session_bindings.get(key)
        if rec is None:
            rec = self.domain.bind_handle.match(key)
        if rec is not None and rec.get("status") == "enabled":
            self.binding_used = key
            from ..bindinfo import sql_hints_from_record
            return apply_hints(stmt, hints_from_record(rec),
                               sql_hints_from_record(rec))
        return None

    def run_built_query(self, logical_plan) -> Result:
        from ..executor import build_executor
        plan = optimize(logical_plan, self._expr_ctx)
        exe = build_executor(plan, self._exec_ctx())
        chunk = exe.execute()
        names = _schema_names(plan)
        return Result(names=names, chunk=chunk)

    def run_query(self, stmt, outer=None) -> Result:
        from ..executor import build_executor
        try:
            plan = cache_key = None
            if (outer is None and self._expr_ctx.params is not None
                    and isinstance(stmt, (ast.SelectStmt, ast.SetOprStmt))):
                plan, cache_key = self._cached_plan(stmt)
            if plan is None:
                with tracing.span("session.plan_query"):
                    plan = self.plan_query(stmt, outer=outer)
                if cache_key is not None:
                    from ..planner.plan_cache import collect_param_consts
                    try:
                        cap = int(self.get_sysvar(
                            "tidb_prepared_plan_cache_size"))
                    except Exception:
                        cap = 0
                    self.plan_cache.put(cache_key, plan,
                                        collect_param_consts(plan), cap)
            # when this statement is traced, wire a runtime-stats
            # collector through the executor tree so per-operator times
            # land in the span tree as events (the TRACE statement's
            # operator rows; reference: executor/trace.go reading the
            # runtime stats back into the span collector)
            coll = None
            if outer is None and tracing.active() is not None:
                from ..executor.execdetails import RuntimeStatsColl
                coll = RuntimeStatsColl()
            with tracing.span("executor.build"):
                exe = build_executor(plan, self._exec_ctx(), stats=coll)
            with tracing.span("executor.run"):
                chunk = exe.execute()
            if coll is not None:
                from ..planner.logical import explain_nodes
                for name, _info, node in explain_nodes(plan):
                    if coll.has(node):
                        st = coll.get(node)
                        tracing.event(
                            "operator." + name.strip().replace("└─", ""),
                            time_s=round(st.time_s, 6), rows=st.rows)
            # a kill that landed after the LAST operator checkpoint still
            # cancels the statement (the result is discarded) — without
            # this, a kill during the final operator's long tail is
            # silently swallowed at the next statement's flag reset
            self.check_killed()
            names = _schema_names(plan)
            return Result(names=names, chunk=chunk)
        finally:
            if outer is None:
                # a table factor's AS OF TIMESTAMP scopes to its
                # STATEMENT: a nested subquery run must not un-pin the
                # outer statement's historical read view mid-flight
                self._stmt_as_of_ts = None

    def _cached_plan(self, stmt):
        """Prepared-plan cache lookup (reference: planner/core/
        common_plans.go Execute.getPhysicalPlan). Returns (plan|None,
        key|None): a key without a plan means 'cacheable — store after
        planning'. On a hit, the new params are rebound into the cached
        plan's tagged constants and the value-dependent physical stages
        re-run (the rebuildRange analog, planner/plan_cache.py)."""
        from ..planner import plan_cache as pc
        try:
            enabled = str(self.get_sysvar(
                "tidb_enable_prepared_plan_cache")).upper() in ("ON", "1")
        except Exception:
            enabled = False
        if not enabled:
            return None, None
        # the prepared AST is immutable between executions: memoize the
        # cacheability walk and the digest on it (the text-protocol EXECUTE
        # path re-parses, so a fresh AST just re-memoizes)
        cacheable = getattr(stmt, "_pc_cacheable", None)
        if cacheable is None:
            cacheable = pc.is_cacheable(stmt)
            stmt._pc_cacheable = cacheable
        if not cacheable:
            return None, None
        digest = getattr(stmt, "_pc_digest", None)
        if digest is None:
            digest = sql_digest(stmt.restore())
            stmt._pc_digest = digest
        params = self._expr_ctx.params
        # the digest deliberately strips /*+ ... */ (bindings match the
        # unhinted form), so the cache key must carry the hint set
        # explicitly — otherwise a hinted and an unhinted prepared
        # statement share one entry and the hint leaks across them
        hint_fp = tuple(
            (n, tuple(a)) for n, a in getattr(stmt, "hints", []) or [])
        key = (digest, self._db, hint_fp,
               self.infoschema().version, self.domain.stats_version,
               self.domain.bind_handle.version, self.bindings_version,
               self.temp_tables_version, pc.param_kinds(params))
        ent = self.plan_cache.get(key)
        if ent is None:
            return None, key
        plan, consts = ent
        if not pc.rebind_params(consts, params):
            # a recorded refinement doesn't apply to these param values
            # (e.g. unparseable date string): re-plan WITHOUT overwriting
            # the good refined entry — the unrefined plan would downgrade
            # every later execution under the same key
            return None, None
        pc.reprune(plan, self._expr_ctx)
        return plan, key

    def _exec_ctx(self):
        return self

    # -- kill / watchdog (reference: util/expensivequery + the KILL
    #    dispatch in server/conn.go) ----------------------------------------

    def kill(self, query_only: bool = True):
        """Interrupt the in-flight statement; executors poll check_killed
        at their entry points and long loops. KILL CONNECTION also marks
        the session dead — further statements are refused and the wire
        server drops the connection."""
        self.killed = True
        if not query_only:
            self.kill_conn = True

    def check_killed(self):
        if self.killed:
            from ..errors import QueryInterruptedError
            raise QueryInterruptedError(
                "Query execution was interrupted")

    # -- misc statements -----------------------------------------------------

    def _exec_placement_policy(self, stmt) -> Result:
        from ..meta import Meta
        txn = self.store.begin()
        try:
            m = Meta(txn)
            rec = m.get_placement_policy(stmt.name)
            if isinstance(stmt, ast.DropPlacementPolicyStmt):
                if rec is None:
                    if stmt.if_exists:
                        txn.rollback()
                        return Result()
                    raise TiDBError(
                        f"Unknown placement policy '{stmt.name}'",
                        code=ErrCode.PlacementPolicyNotExists)
                m.drop_placement_policy(stmt.name)
            else:
                if rec is not None and not stmt.or_alter:
                    if stmt.if_not_exists:
                        txn.rollback()
                        return Result()
                    raise TiDBError(
                        f"Placement policy '{stmt.name}' already exists",
                        code=ErrCode.PlacementPolicyExists)
                if stmt.or_alter and rec is None:
                    raise TiDBError(
                        f"Unknown placement policy '{stmt.name}'",
                        code=ErrCode.PlacementPolicyNotExists)
                display = (rec or {}).get("display") if stmt.or_alter \
                    else None
                m.set_placement_policy(stmt.name, stmt.options,
                                       display=display)
            txn.commit()
        except Exception:
            if txn.valid:
                txn.rollback()
            raise
        return Result()

    def _exec_set(self, stmt: ast.SetStmt) -> Result:
        from ..expression import ExprBuilder, Schema
        b = ExprBuilder(Schema([]), self._expr_ctx)
        for scope, name, node in stmt.items:
            if scope == "user":
                self.user_vars[name] = b.build(node).eval_scalar()
                continue
            if name == "names":
                continue
            if isinstance(node, ast.DefaultExpr):
                self.set_sysvar(name, None, scope)
                continue
            if isinstance(node, ast.ColumnName) and not node.table:
                # SET var = bare_word — MySQL treats the identifier as a
                # string value (SET tidb_partition_prune_mode = dynamic)
                v = node.name
            else:
                # eval_scalar is scale-faithful (decimals come back as
                # decimal.Decimal), so decimal literals and expressions
                # need no special case
                v = b.build(node).eval_scalar()
            if isinstance(v, bytes):
                v = v.decode()
            self.set_sysvar(name, v, scope)
        return Result()

    def _exec_opt_trace(self, inner) -> Result:
        """TRACE FORMAT='opt' SELECT ... — the optimizer trace: one row per
        logical/physical rule with the plan after that rule (reference:
        planner/core/optimizer.go:93-126 step tracer, dumped over
        /optimize_trace/dump there; a resultset here)."""
        trace: list = []
        undo = None
        if isinstance(inner, (ast.SelectStmt, ast.SetOprStmt)):
            undo = self._apply_binding(inner)
        try:
            self.plan_builds += 1
            builder = PlanBuilder(self._expr_ctx)
            plan = builder.build(inner)
            optimize(plan, self._expr_ctx, trace=trace)
        finally:
            if undo:
                from ..bindinfo import undo_hints
                undo_hints(undo)
        ft = FieldType(tp=TYPE_VARCHAR)
        rows = []
        for i, (rule, rendered) in enumerate(trace):
            for line in rendered.splitlines():
                rows.append((str(i).encode(), rule.encode(), line.encode()))
        return Result(names=["step", "rule", "plan"],
                      chunk=Chunk.from_rows([ft, ft, ft], rows))

    def _exec_plan_replayer(self, stmt: ast.PlanReplayerStmt) -> Result:
        """PLAN REPLAYER DUMP EXPLAIN <stmt> (reference:
        executor/plan_replayer.go): capture everything needed to reproduce
        the plan offline — schemas, ANALYZE stats, session/global vars,
        the SQL, EXPLAIN output and engine version — into one zip; the
        result row carries the token (file path)."""
        import io
        import json
        import os
        import tempfile
        import zipfile

        inner = stmt.stmt
        if not isinstance(inner, (ast.SelectStmt, ast.SetOprStmt)):
            raise TiDBError("PLAN REPLAYER supports SELECT statements")
        # referenced base tables (walk TableName nodes in the AST)
        import dataclasses as _dc
        tables = []
        stack = [inner]
        while stack:
            n = stack.pop()
            if isinstance(n, (list, tuple)):
                stack.extend(n)
                continue
            if isinstance(n, ast.TableName):
                tables.append((n.schema or self.current_db(), n.name))
            if _dc.is_dataclass(n) and isinstance(n, ast.Node):
                for f in _dc.fields(n):
                    stack.append(getattr(n, f.name))
        infos = self.infoschema()
        schema_sql, stats = [], {}
        seen = set()
        for db, name in tables:
            key = (db.lower(), name.lower())
            if key in seen:
                continue
            seen.add(key)
            try:
                info = infos.table_by_name(db, name)
            except Exception:
                continue
            from .show import render_create_table
            schema_sql.append(f"USE `{db}`;\n" + render_create_table(info))
            s = self.domain.stats.get(info.id)
            if s:
                stats[f"{db}.{name}"] = s
        explain_rows = self._exec_explain(
            ast.ExplainStmt(stmt=inner)).rows
        sysvars = {"session": dict(self.session_vars),
                   "global": dict(self.domain.global_vars)}
        buf = io.BytesIO()
        with zipfile.ZipFile(buf, "w", zipfile.ZIP_DEFLATED) as z:
            z.writestr("sql/sql_meta.toml", f"sql = '''{inner.restore()}'''\n")
            z.writestr("schema/schema.sql", ";\n".join(schema_sql) + ";\n")
            z.writestr("stats/stats.json", json.dumps(stats, default=str))
            z.writestr("variables.json", json.dumps(sysvars))
            z.writestr("explain.txt", "\n".join(
                " | ".join(str(c) for c in r) for r in explain_rows))
            z.writestr("meta.txt", "tpu-htap plan replayer v1\n")
        token = f"replayer_{sql_digest(inner.restore())[:16]}_" \
                f"{int(time.time())}.zip"
        d = os.path.join(tempfile.gettempdir(), "tidb_tpu_replayer")
        os.makedirs(d, exist_ok=True)
        path = os.path.join(d, token)
        with open(path, "wb") as fh:
            fh.write(buf.getvalue())
        ft = FieldType(tp=TYPE_VARCHAR)
        return Result(names=["File_token"],
                      chunk=Chunk.from_rows([ft], [(path.encode(),)]))

    def _exec_explain(self, stmt: ast.ExplainStmt) -> Result:
        inner = stmt.stmt
        if not isinstance(inner, (ast.SelectStmt, ast.SetOprStmt)):
            raise TiDBError("EXPLAIN supports SELECT statements only for now")
        plan = self.plan_query(inner)
        ft = FieldType(tp=TYPE_VARCHAR)
        if not stmt.analyze:
            if stmt.format in ("verbose", "cost"):
                # cost column: the physical chooser's estimate for the
                # chosen operator variant plus the candidate set it
                # compared (reference: EXPLAIN FORMAT='verbose' prints
                # estCost, planner/core/explain.go)
                from ..planner.logical import explain_nodes
                rows = []
                for name, info, node in explain_nodes(plan):
                    # one currency end-to-end: every node carries the
                    # DP's accumulated cost (planner/physical.py
                    # _best_cost); candidate sets show the alternatives
                    # the chooser compared at that node
                    cost = getattr(node, "cost", None)
                    if cost is None:
                        cost = getattr(node, "join_cost", None)
                    cands = getattr(node, "cost_candidates", None)
                    if cost is not None and cands:
                        ctext = (f"{cost:g} "
                                 + "{" + ", ".join(
                                     f"{k}:{v:g}" for k, v in
                                     sorted(cands.items())) + "}")
                    elif cost is not None:
                        ctext = f"{cost:g}"
                    else:
                        ctext = "-"
                    rows.append((name.encode(), ctext.encode(),
                                 info.encode()))
                return Result(names=["id", "estCost", "info"],
                              chunk=Chunk.from_rows([ft, ft, ft], rows))
            rows = [(name.encode(), info.encode())
                    for name, info in explain_tree(plan)]
            return Result(names=["id", "info"],
                          chunk=Chunk.from_rows([ft, ft], rows))
        # EXPLAIN ANALYZE: run with a RuntimeStatsColl wired through the
        # executor tree (reference: util/execdetails + executor/explain.go)
        from ..executor import build_executor
        from ..executor.execdetails import RuntimeStatsColl, _fmt_bytes
        from ..planner.logical import explain_nodes
        coll = RuntimeStatsColl()
        exe = build_executor(plan, self._exec_ctx(), stats=coll)
        exe.execute()
        rows = []
        for name, info, node in explain_nodes(plan):
            if coll.has(node):
                st = coll.get(node)
                act = str(st.rows) if st.loops else "-"
                einfo = st.exec_info()
                mem = _fmt_bytes(st.mem_bytes) if st.mem_bytes else "N/A"
            else:
                act, einfo, mem = "-", "-", "N/A"
            rows.append((name.encode(), act.encode(), einfo.encode(),
                         info.encode(), mem.encode()))
        out = Chunk.from_rows([ft] * 5, rows)
        return Result(names=["id", "actRows", "execution info",
                             "operator info", "memory"], chunk=out)

    def _exec_trace(self, stmt: ast.TraceStmt) -> Result:
        """TRACE [FORMAT='row'|'json'] <stmt> — run the statement under a
        FORCED lifecycle trace (session/tracing.py, sampling-independent)
        and render its span tree: the statement root, plan/build/run,
        and every resilience-layer chokepoint the execution crossed —
        admission, compile service (with mode), supervisor deadline,
        device dispatch, backoff sleeps, residency evictions (reference:
        executor/trace.go:50 + util/tracing).  FORMAT='opt' keeps the
        optimizer rule trace."""
        inner = stmt.stmt
        if stmt.format == "opt" and isinstance(
                inner, (ast.SelectStmt, ast.SetOprStmt)):
            return self._exec_opt_trace(inner)
        tr = tracing.active()
        if tr is None:
            # always-on: a TRACE statement never depends on the sampler
            tr = tracing.begin("statement", origin="trace_stmt",
                               conn_id=self.conn_id,
                               stmt=type(inner).__name__)
        succ = False
        try:
            with tracing.span("statement.dispatch"):
                self._dispatch(inner)
            succ = True
        finally:
            # finish UNCONDITIONALLY before rendering: when the sampler
            # already traced this TRACE statement, rendering the live
            # trace would show a '-' root duration and a succ flag that
            # can never be false.  finish() is idempotent, so the
            # statement loop's own finish in _execute_stmt stays a no-op
            tracing.finish(tr, succ=succ)
        ft = FieldType(tp=TYPE_VARCHAR)
        if stmt.format == "json":
            payload = json.dumps(tr.to_dict(), default=str)
            return Result(names=["trace"],
                          chunk=Chunk.from_rows([ft], [(payload.encode(),)]))
        rows = [(op.encode(), start.encode(), dur.encode())
                for op, start, dur in tracing.tree_rows(tr)]
        return Result(names=["operation", "startTS", "duration"],
                      chunk=Chunk.from_rows([ft, ft, ft], rows))

    def _exec_analyze(self, stmt: ast.AnalyzeTableStmt) -> Result:
        """Collect basic stats (reference: executor/analyze.go; histograms
        and sketches land with the stats module)."""
        from ..statistics import analyze_table
        for tn in stmt.tables:
            db = tn.schema or self.current_db()
            info = self.infoschema().table_by_name(db, tn.name)
            analyze_table(self, info)
        return Result()

    def _exec_admin(self, stmt: ast.AdminStmt) -> Result:
        if stmt.kind == "show_telemetry":
            # what WOULD be reported; collection never egresses (reference:
            # ADMIN SHOW TELEMETRY, executor/telemetry.go)
            from .. import telemetry as _tel
            ft_s = FieldType(tp=TYPE_VARCHAR)
            payload = self.domain.telemetry.preview()
            status = b"enabled" if _tel.enabled(self.domain) else b"disabled"
            return Result(names=["TRACKING_ID", "LAST_STATUS", "DATA_PREVIEW"],
                          chunk=Chunk.from_rows(
                              [ft_s, ft_s, ft_s],
                              [(b"local-only", status, payload.encode())]))
        if stmt.kind == "checksum_table":
            # order-independent table checksum over record KVs (reference:
            # distsql.Checksum + executor/checksum.go; XOR of per-kv crcs
            # commutes, so partition/scan order never matters)
            import zlib
            from .. import tablecodec
            ft_s = FieldType(tp=TYPE_VARCHAR)
            ft_i = FieldType(tp=TYPE_LONGLONG)
            rows = []
            txn = self.store.begin()
            try:
                for tn in stmt.tables:
                    db = tn.schema or self.current_db()
                    info = self.infoschema().table_by_name(db, tn.name)
                    phys = ([d.id for d in info.partition.defs]
                            if info.partition is not None else [info.id])
                    acc = 0
                    n_kvs = 0
                    n_bytes = 0
                    for pid in phys:
                        start, end = tablecodec.table_range(pid)
                        for k, v in txn.scan(start, end):
                            acc ^= zlib.crc32(v, zlib.crc32(k))
                            n_kvs += 1
                            n_bytes += len(k) + len(v)
                    rows.append((db.encode(), tn.name.encode(), acc,
                                 n_kvs, n_bytes))
            finally:
                txn.rollback()
            return Result(names=["Db_name", "Table_name", "Checksum_crc64_xor",
                                 "Total_kvs", "Total_bytes"],
                          chunk=Chunk.from_rows(
                              [ft_s, ft_s, ft_i, ft_i, ft_i], rows))
        if stmt.kind == "show_ddl_jobs":
            txn = self.store.begin()
            try:
                m = Meta(txn)
                jobs = m.history_jobs()[-20:]
                jobs.reverse()
            finally:
                txn.rollback()
            from ..model import JobState, SchemaState
            ft_i = FieldType(tp=TYPE_LONGLONG)
            ft_s = FieldType(tp=TYPE_VARCHAR)
            rows = [(j.id, j.type.encode(),
                     SchemaState.NAMES.get(j.schema_state, "?").encode(),
                     j.schema_id, j.table_id, j.row_count,
                     JobState.NAMES.get(j.state, "?").encode())
                    for j in jobs]
            chunk = Chunk.from_rows([ft_i, ft_s, ft_s, ft_i, ft_i, ft_i, ft_s], rows)
            return Result(names=["job_id", "job_type", "schema_state",
                                 "schema_id", "table_id", "row_count", "state"],
                          chunk=chunk)
        if stmt.kind == "check_table":
            from ..executor.admin import check_table
            for tn in stmt.tables:
                db = tn.schema or self.current_db()
                info = self.infoschema().table_by_name(db, tn.name)
                check_table(self, info)
            return Result()
        if stmt.kind == "check_index":
            from ..executor.admin import check_index
            tn = stmt.tables[0]
            db = tn.schema or self.current_db()
            info = self.infoschema().table_by_name(db, tn.name)
            check_index(self, info, stmt.index_name)
            return Result()
        if stmt.kind == "compile":
            # ADMIN COMPILE: background-compile the geometric bucket
            # ladder for every hot fragment recipe and WAIT, so the
            # statement returns a final count (executor/compile_service)
            from ..executor import compile_service
            rep = compile_service.prewarm(ctx=self, wait=True)
            ft_i = FieldType(tp=TYPE_LONGLONG)
            return Result(
                names=["submitted", "prewarmed", "failed"],
                chunk=Chunk.from_rows(
                    [ft_i, ft_i, ft_i],
                    [(rep["submitted"], rep["prewarmed"], rep["failed"])]))
        raise TiDBError(f"unsupported ADMIN {stmt.kind}")

    def _exec_execute(self, stmt: ast.ExecuteStmt) -> Result:
        sql = self.prepared.get(stmt.name)
        if sql is None:
            raise TiDBError(f"Unknown prepared statement handler ({stmt.name})")
        params = []
        for uv in stmt.using:
            params.append(self.user_vars.get(uv))
        inner = self.parser.parse(sql)
        if len(inner) != 1:
            raise TiDBError("prepared statement must be a single statement")
        self._expr_ctx.params = params
        try:
            return self._dispatch(inner[0])
        finally:
            self._expr_ctx.params = None


BOOTSTRAP_VERSION = 3  # v2: grant tables; v3: mysql.db grant_priv column


def bootstrap_domain(store=None) -> Domain:
    """reference: session.BootstrapSession (session.go:2566) — creates system
    databases, the grant tables + root user, and marks the bootstrap
    version (versioned like bootstrap.go's upgrade chain)."""
    from ..kv import new_store
    if store is None:
        store = new_store()
    txn = store.begin()
    m = Meta(txn)
    ver = m.bootstrapped()
    if ver >= BOOTSTRAP_VERSION:
        txn.rollback()
        d = Domain(store)
        d.priv.load()
        return d
    if ver < 1:
        for db_name in ("mysql", "test"):
            db = DBInfo(id=m.gen_global_id(), name=db_name)
            m.create_database(db)
        m.bump_schema_version()
        # mark v1 with the same txn: a crash before v2 completes must not
        # re-run this step (create_database dedups by id, not name)
        m.set_bootstrapped(1)
    txn.commit()
    d = Domain(store)
    if ver < 2:
        # grant tables + root@% with all privileges (bootstrap.go:1739).
        # The bootstrap version is only marked AFTER this succeeds: a crash
        # mid-way re-runs the (idempotent) step instead of permanently
        # skipping it and silently disabling the privilege system
        from ..privilege import BOOTSTRAP_SQL, ROOT_ROW
        s = Session(d)
        s._internal = 1
        try:
            for sql in BOOTSTRAP_SQL:
                s.execute(sql)
            if not s.execute("select 1 from mysql.user where user = 'root'"
                             )[-1].rows:
                s.execute(ROOT_ROW)
        finally:
            s.close()
    elif ver < 3:
        # v3 upgrade: db-scoped grant option column (versioned upgrade
        # chain, reference: bootstrap.go upgradeToVerNN)
        s = Session(d)
        s._internal = 1
        try:
            info = d.infoschema().table_by_name("mysql", "db")
            if info is not None and info.find_column("grant_priv") is None:
                s.execute("alter table mysql.db add column "
                          "grant_priv varchar(1) default 'N'")
        finally:
            s.close()
    txn = store.begin()
    try:
        Meta(txn).set_bootstrapped(BOOTSTRAP_VERSION)
        txn.commit()
    except Exception:
        txn.rollback()
        raise
    d.priv.load()
    d.load_stats()
    return d


def new_session(domain: Domain | None = None) -> Session:
    if domain is None:
        domain = bootstrap_domain()
    return Session(domain)
