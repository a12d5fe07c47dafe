"""Centralized error classes with MySQL error codes.

Mirrors the role of the reference's ``errno/`` + ``util/dbterror``
(reference: errno/errcode.go, util/dbterror/terror.go): every user-visible
error carries a MySQL errno + SQL state so the protocol layer and tests can
match on codes, not strings.
"""


class ErrCode:
    # Subset of MySQL error codes used across the engine (reference: errno/errcode.go).
    DupEntry = 1062
    NoSuchTable = 1146
    PluginIsNotLoaded = 1524
    BadDB = 1049
    DBCreateExists = 1007
    DBDropExists = 1008
    TableExists = 1050
    BadTable = 1051
    BadField = 1054
    NonUniq = 1052
    ParseError = 1064
    UnknownSystemVariable = 1193
    WrongValueCountOnRow = 1136
    BadNull = 1048
    NoDefaultValue = 1364
    DataTooLong = 1406
    DataOutOfRange = 1264
    TruncatedWrongValue = 1292
    DivisionByZero = 1365
    LockWaitTimeout = 1205
    DeadlockDetected = 1213
    WrongFieldSpec = 1063
    DupKeyName = 1061
    KeyDoesNotExist = 1176
    CantDropFieldOrKey = 1091
    UnknownTable = 1109
    NoPermission = 1142
    TableaccessDenied = 1142
    DBaccessDenied = 1044
    AccessDenied = 1045
    CannotUser = 1396
    WrongDBName = 1102
    WrongTableName = 1103
    WrongColumnName = 1166
    InvalidGroupFuncUse = 1111
    MixOfGroupFuncAndFields = 1140
    FieldNotInGroupBy = 1055
    UnknownColumn = 1054
    OperandColumns = 1241
    SubqueryMoreThan1Row = 1242
    WrongNumberOfColumnsInSelect = 1222
    CantReopenTable = 1137
    WrongAutoKey = 1075
    MultiplePriKey = 1068
    TooManyKeys = 1069
    UnsupportedDDL = 8214
    PlacementPolicyExists = 8238
    PlacementPolicyNotExists = 8239
    CantExecuteInReadOnlyTxn = 1792
    AsOfInTxn = 8135
    InfoSchemaExpired = 8027
    InfoSchemaChanged = 8028
    WriteConflict = 9007
    TxnRetryable = 8002
    TiKVServerTimeout = 9002
    BackoffExhausted = 9005  # reference: ErrRegionUnavailable family —
    #                          the budgeted Backoffer ran out of retries
    DeviceHang = 9008  # reserved next to 9005: a supervised device call
    #                    blew its wall-clock deadline (the backend hung)
    DeviceAdmission = 9009  # the serving scheduler refused a fragment a
    #                         device slot (queue full / wait timed out)
    DeviceCompile = 9010  # the compile service could not build a device
    #                       executable (remote-compile RPC/transport
    #                       failure, injected compile fault, retry budget
    #                       exhausted) — the fragment degrades to host
    FreshnessWaitTimeout = 9011  # a snapshot's fleet-frontier wait blew
    #                              its budget: the read is REFUSED loudly
    #                              (never silently served stale), and the
    #                              lagging origin's freshness breaker
    #                              trips so one wedged worker cannot
    #                              freeze fleet reads (kv/shared_store)
    LazyUniquenessCheckFailure = 8147
    ResolveLockTimeout = 9004
    GCTooEarly = 9006
    UnsupportedType = 8003
    QueryInterrupted = 1317
    NoSuchThread = 1094
    MemExceedThreshold = 8001
    OOMKill = 8175
    # partitioned tables (MySQL partition error numbers)
    PartitionsMustBeDefined = 1492
    RangeNotIncreasing = 1493
    SameNamePartition = 1517
    DropLastPartition = 1508
    DropPartitionNonExistent = 1507
    NoPartitionForGivenValue = 1526
    PartitionMgmtOnNonpartitioned = 1505
    UniqueKeyNeedAllFieldsInPf = 1503
    PartitionRequiresValues = 1479
    WrongObject = 1347
    ViewRecursive = 1462
    ViewInvalid = 1356
    ViewWrongList = 1353
    NonInsertableTable = 1471
    NonUpdatableTable = 1288
    DupFieldName = 1060
    SequenceRunOut = 4135
    WrongObjectSequence = 1347
    TableLocked = 8020
    TableNotLocked = 1100
    TableNotLockedForWrite = 1099
    OptOnCacheTable = 8242
    RowDoesNotMatchPartition = 1737
    PartitionFunctionIsNotAllowed = 1564
    UnknownPartition = 1735
    OnlyOnRangeListPartition = 1512


class TiDBError(Exception):
    """Base error: carries MySQL errno + sqlstate for the wire protocol."""

    code = 1105  # ER_UNKNOWN_ERROR
    sqlstate = "HY000"

    def __init__(self, msg="", code=None):
        super().__init__(msg)
        if code is not None:
            self.code = code
        self.msg = msg

    def __str__(self):
        return self.msg or self.__class__.__name__


class ParseError(TiDBError):
    code = ErrCode.ParseError
    sqlstate = "42000"


class SchemaError(TiDBError):
    code = ErrCode.NoSuchTable
    sqlstate = "42S02"


class ColumnError(TiDBError):
    code = ErrCode.BadField
    sqlstate = "42S22"


class DupEntryError(TiDBError):
    code = ErrCode.DupEntry
    sqlstate = "23000"


class WriteConflictError(TiDBError):
    code = ErrCode.WriteConflict
    sqlstate = "HY000"


class SchemaChangedError(TiDBError):
    """The schema a transaction's mutations were built against changed
    before commit (reference: domain.ErrInfoSchemaChanged, 8028 — the
    commit-time schema check that upholds the F1 online-DDL invariant)."""

    code = ErrCode.InfoSchemaChanged
    sqlstate = "HY000"


class LockedError(TiDBError):
    """Key is locked by another transaction (reference: kv lock errors)."""

    code = ErrCode.LockWaitTimeout
    sqlstate = "HY000"

    def __init__(self, msg="", key=None, lock_ts=0):
        super().__init__(msg)
        self.key = key
        self.lock_ts = lock_ts


class DeadlockError(TiDBError):
    code = ErrCode.DeadlockDetected
    sqlstate = "40001"


class TypeError_(TiDBError):
    code = ErrCode.TruncatedWrongValue
    sqlstate = "22007"


class OutOfRangeError(TiDBError):
    code = ErrCode.DataOutOfRange
    sqlstate = "22003"


class PrivilegeError(TiDBError):
    code = ErrCode.NoPermission
    sqlstate = "42000"


class QueryInterruptedError(TiDBError):
    code = ErrCode.QueryInterrupted
    sqlstate = "70100"


class MemoryQuotaExceeded(TiDBError):
    code = ErrCode.MemExceedThreshold
    sqlstate = "HY000"


class DeviceHangError(TiDBError):
    """A supervised device call exceeded its hard wall-clock deadline
    (`tidb_device_call_timeout` / the remaining `max_execution_time`
    window): the backend is presumed hung inside a GIL-holding C call the
    engine cannot interrupt.  The call is ABANDONED on its worker thread,
    the JAX backend is fenced (compiled-executable caches quarantined and
    reinitialized before the next fragment), and the hang is recorded
    against the per-shape circuit breaker so repeated hangs degrade the
    fragment class to the host engine.

    `shape` names the fragment class that hung (agg / join / window /
    mpp), `deadline_s` the budget that expired."""

    code = ErrCode.DeviceHang
    sqlstate = "HY000"
    shape = ""
    deadline_s = 0.0


class DeviceAdmissionError(TiDBError):
    """The serving scheduler (executor/scheduler.py) refused this
    fragment a device slot: the admission queue is at
    ``tidb_device_sched_queue_depth``, the queued wait exceeded
    ``tidb_device_admission_timeout``, or an admission failpoint fired.

    This is LOAD, not ill-health: run_device converts the refusal into
    ``DeviceUnsupported`` so the fragment degrades to the host engine
    (counted in the per-tenant ``sched_degradations`` gauge) without
    charging the circuit breaker — the co-processing answer to overload
    is host+device serving different work concurrently, not an error."""

    code = ErrCode.DeviceAdmission
    sqlstate = "HY000"


class DeviceCompileError(TiDBError):
    """The compile service (executor/compile_service.py) failed to build a
    device executable for a fragment signature: the remote-compile
    RPC/transport died mid-compile, an injected ``compile-fail`` failpoint
    fired, or the ``compileRetry`` backoff budget ran out.

    This is a COMPILE-path failure, not an execution failure: it charges
    the compile-scoped circuit breaker (shape="compile") — never the
    fragment-shape breakers — and the fragment degrades to the host
    engine (the executable may still land on a later attempt, flipping
    subsequent executions back to device)."""

    code = ErrCode.DeviceCompile
    sqlstate = "HY000"


class FreshnessWaitError(TiDBError):
    """A snapshot's fleet-frontier wait (kv/shared_store.fresh_read_ts)
    exhausted its ``freshnessWait`` budget: some live origin published a
    durable commit frontier this replica could not apply up to in time.

    This is the LOUD stale-read refusal of the consistency ladder — the
    engine never silently serves a snapshot older than the fleet
    frontier.  The lagging origin's per-origin freshness breaker trips
    with the raise, so subsequent reads degrade to an explicit
    ``stale_ok`` downgrade (surfaced in EXPLAIN ANALYZE and the
    ``freshness_stale_ok`` gauge) instead of re-paying the budget."""

    code = ErrCode.FreshnessWaitTimeout
    sqlstate = "HY000"


class BackoffExhaustedError(TiDBError):
    """A budgeted retry loop ran out of budget (reference: client-go
    "backoffer.maxSleep exceeded" — surfaced as a region-unavailable
    class timeout, never an unbounded loop).

    Carries `retry_kind` (which curve exhausted) and `error_class` (the
    taxonomy label of the last triggering error, utils/backoff.classify)."""

    code = ErrCode.BackoffExhausted
    sqlstate = "HY000"
    retry_kind = ""
    error_class = ""
