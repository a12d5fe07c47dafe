"""Load a data set's generated tables into a tidb_tpu Domain (worker side).

Schema, optimizer statistics and the tables a data set lists as
``SQL_TABLES`` go through SQL and so live in the durable store (the WAL);
a worker restarted over that store replays them.  The other tables are
bulk-installed into the process-local columnar cache
(``ColumnarCache.install_bulk``, the program's physical-import path) and
must be rebuilt from the seed by every worker process.  The column
packing is a copy of ``bench._install`` / ``bench._dict_col``; the
original is listed in PERF.md for deletion.

A table is ``{column: array | (int32 codes, [bytes, ...])}``; a schema is
``{table: {column: SQL type}}``, where a type that ends in `` pk`` marks a
single-column integer primary key.
"""

import numpy as np


def ddl(schema: dict, table: str, columns: list) -> str:
    defs = [f"{c} " + schema[table][c].replace(" pk", " primary key")
            for c in columns]
    return f"create table {table} ({', '.join(defs)})"


def _values(col):
    return col[0] if isinstance(col, tuple) else col


def _dict_column(codes, dictionary, ftype):
    """Dict-encoded string Column; set_dict wants sorted uniques."""
    from tidb_tpu.utils.chunk import Column
    arr = np.asarray(dictionary, dtype=object)
    order = np.argsort(arr)
    remap = np.empty(len(arr), dtype=np.int64)
    remap[order] = np.arange(len(arr))
    col = Column(ftype, arr[codes], np.zeros(len(codes), dtype=bool))
    col.set_dict(remap[codes].astype(np.int32), arr[order])
    return col


def _bulk_install(tk, db, types: dict, table: str, data: dict,
                  tag: str) -> int:
    from tidb_tpu.utils.chunk import Column
    info = tk.domain.infoschema().table_by_name(db, table)
    cols = {c.name: c for c in info.public_columns()}
    n = len(_values(next(iter(data.values()))))
    nulls = np.zeros(n, dtype=bool)
    columns = {}
    handles = np.arange(1, n + 1, dtype=np.int64)
    for name, arr in data.items():
        c = cols[name]
        if isinstance(arr, tuple):
            columns[c.id] = _dict_column(arr[0], arr[1], c.ftype)
        else:
            columns[c.id] = Column(c.ftype, arr, nulls)
            if types[name].endswith(" pk"):
                # an integer primary key IS the row handle; orders' keys
                # are sparse, so the handles must be the keys themselves
                handles = np.asarray(arr, dtype=np.int64)
    tk.domain.columnar_cache.install_bulk(info, columns, handles,
                                          content_tag=f"{tag}/{table}/n{n}")
    return n


def _sql_literal(v) -> str:
    if isinstance(v, bytes):
        return "'" + v.decode() + "'"
    return str(int(v))


def load(tk, db: str, schema: dict, sql_tables, tables: dict, want: dict,
         seeded: bool, tag: str) -> dict:
    """Install `tables` (the data set's generate() result for `want`).
    With `seeded` the store already holds schema, stats and the
    `sql_tables`: only the bulk columns are rebuilt.  Returns row counts."""
    if not seeded:
        tk.must_exec(f"create database if not exists {db}")
    tk.must_exec(f"use {db}")
    rows = {}
    for table, cols in want.items():
        if not seeded:
            tk.must_exec(ddl(schema, table, cols))
        data = tables[table]
        if table in sql_tables:
            n = len(_values(data[cols[0]]))
            if not seeded:
                for i in range(n):
                    vals = [(data[c][1][data[c][0][i]]
                             if isinstance(data[c], tuple) else data[c][i])
                            for c in cols]
                    tk.must_exec(f"insert into {table} values ("
                                 + ", ".join(map(_sql_literal, vals)) + ")")
            rows[table] = n
        else:
            rows[table] = _bulk_install(tk, db, schema[table], table, data,
                                        tag)
    if seeded:
        # ANALYZE's blobs were replayed from the log with the schema
        tk.domain.load_stats()
    else:
        for table in want:
            tk.must_exec(f"analyze table {table}")
    return rows
