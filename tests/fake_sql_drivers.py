"""Wire-behavior doubles for the ``psycopg2`` and ``pymysql`` drivers.

This image has neither SQL servers nor the DB-API drivers (zero egress,
no pip), so the PGSQL/MYSQL dialects could never execute — the round-3
suite's one skip. These modules emulate the exact DB-API surface and
the SERVER BEHAVIORS the real dialects branch on, over a shared
on-disk sqlite database per (host, database) pair, so that the REAL
``PostgresDialect`` / ``MySQLDialect`` classes
(predictionio_tpu/storage/sqldialect.py) execute their own SQL and
error handling unmodified:

======================  ==============================================
dialect behavior        emulated how
======================  ==============================================
format paramstyle       ``%s`` placeholders rewritten at the cursor
PG DDL types            SERIAL PRIMARY KEY / BYTEA translated to the
                        sqlite sqlite equivalents before execution
PG ``RETURNING id``     sqlite >= 3.35 runs it natively
PG ON CONFLICT upsert   sqlite >= 3.24 runs it natively (EXCLUDED.*)
PG aborted transaction  after any statement error the connection
                        refuses further statements
                        (``InFailedSqlTransaction``) until
                        ``rollback()`` — the behavior
                        ``SQLDialect.recover`` exists for
PG UndefinedTable       sqlite "no such table" mapped to
                        ``psycopg2.errors.UndefinedTable``
PG named cursor         ``cursor(name=...)`` accepted (streaming)
MySQL DDL types         AUTO_INCREMENT / LONGBLOB translated
MySQL REPLACE INTO      sqlite runs it natively
MySQL error codes       "no such table" → ``ProgrammingError`` with
                        ``args[0] == 1146`` (ER_NO_SUCH_TABLE);
                        duplicate ``CREATE INDEX`` →
                        ``InternalError`` with ``args[0] == 1061``
                        (ER_DUP_KEYNAME, no IF NOT EXISTS in MySQL)
MySQL SSCursor          ``cursor(SSCursor)`` accepted (streaming)
======================  ==============================================

What this cannot prove: the C wire protocol, authentication, and
genuine server-side DDL/planner behavior — that remains the live smoke
test's job (``test_pgsql_live_smoke``) on an image with a real server.

Shared state: connections with the same ``(host, database)`` hit the
same sqlite file under a process-wide temp dir — two fake connections
see each other's committed writes, like two sessions of one server.
:func:`reset_all` gives the next test empty servers in a directory of
its own.
"""

from __future__ import annotations

import atexit
import os
import shutil
import sqlite3
import tempfile
import threading
import types
import weakref
from typing import Optional

_ROOT = tempfile.mkdtemp(prefix="pio_fake_sql_")
atexit.register(shutil.rmtree, _ROOT, ignore_errors=True)
_LOCK = threading.Lock()
_DIR = tempfile.mkdtemp(dir=_ROOT)   # the servers' state since the last reset
_OPEN = weakref.WeakSet()            # connections handed out since then


def _db_path(host: str, database: str) -> str:
    with _LOCK:
        return os.path.join(_DIR, f"{host}_{database}.db")


def _connect(path: str) -> sqlite3.Connection:
    sq = sqlite3.connect(path, timeout=30.0)
    sq.execute("PRAGMA journal_mode=WAL")
    return sq


def reset_all() -> None:
    """Fresh-test isolation: close the connections the previous test
    left open and give the fake servers an empty directory of their
    own. No file is unlinked here (the root goes at exit). Unlinking
    raced the garbage collector: a store nobody closes keeps its
    connection until it is collected, sqlite deletes ``-wal`` and
    ``-shm`` by PATH when a database's last connection closes, and a
    collection in the middle of the wipe took ``…_db0.db-shm`` from
    under it (``FileNotFoundError``), every test naming its first
    database ``db0``."""
    global _DIR
    with _LOCK:
        for conn in list(_OPEN):
            try:
                conn.close()
            except sqlite3.ProgrammingError:
                pass  # made on another thread, which alone may close it
        _OPEN.clear()
        _DIR = tempfile.mkdtemp(dir=_ROOT)


# -- fake psycopg2 ------------------------------------------------------------


class PGError(Exception):
    pass


class PGOperationalError(PGError):
    pass


class PGUndefinedTable(PGError):
    pass


class PGInFailedSqlTransaction(PGError):
    pass


def _pg_translate(q: str) -> str:
    q = q.replace("%s", "?")
    q = q.replace("SERIAL PRIMARY KEY", "INTEGER PRIMARY KEY AUTOINCREMENT")
    q = q.replace("BYTEA", "BLOB")
    return q


def _pg_map(e: sqlite3.Error) -> PGError:
    if isinstance(e, sqlite3.OperationalError) and "no such table" in str(e):
        return PGUndefinedTable(str(e))
    return PGOperationalError(str(e))


class _PGCursor:
    def __init__(self, conn: "_PGConnection", name: Optional[str] = None):
        self._conn = conn
        self._cur = conn._sq.cursor()
        self.name = name

    def _run(self, method, q, arg):
        self._conn._check_usable()
        try:
            return method(_pg_translate(q), arg)
        except sqlite3.Error as e:
            # the server aborts the transaction: everything until
            # ROLLBACK now fails
            self._conn._failed = True
            raise _pg_map(e) from e

    def execute(self, q, args=()):
        return self._run(self._cur.execute, q, args)

    def executemany(self, q, rows):
        return self._run(self._cur.executemany, q, rows)

    def fetchone(self):
        return self._cur.fetchone()

    def fetchall(self):
        return self._cur.fetchall()

    def fetchmany(self, n=1):
        return self._cur.fetchmany(n)

    def close(self):
        self._cur.close()

    @property
    def lastrowid(self):
        return self._cur.lastrowid

    @property
    def rowcount(self):
        return self._cur.rowcount


class _PGConnection:
    def __init__(self, path: str):
        self._sq = _connect(path)
        self._failed = False
        _OPEN.add(self)

    def _check_usable(self):
        if self._failed:
            raise PGInFailedSqlTransaction(
                "current transaction is aborted, commands ignored until "
                "end of transaction block")

    def cursor(self, name: Optional[str] = None):
        return _PGCursor(self, name)

    def commit(self):
        # COMMIT inside an aborted transaction is turned into ROLLBACK
        # by the server (no error)
        self._sq.rollback() if self._failed else self._sq.commit()
        self._failed = False

    def rollback(self):
        self._sq.rollback()
        self._failed = False

    def close(self):
        self._sq.close()


def make_psycopg2_module() -> types.ModuleType:
    m = types.ModuleType("psycopg2")
    errors = types.ModuleType("psycopg2.errors")
    errors.UndefinedTable = PGUndefinedTable
    errors.InFailedSqlTransaction = PGInFailedSqlTransaction
    m.errors = errors
    m.Error = PGError
    m.OperationalError = PGOperationalError
    m.Binary = lambda b: b
    m.connect_calls = []  # recorded kwargs, for URL-parsing assertions

    def connect(host=None, port=None, user=None, password=None, dbname=None):
        m.connect_calls.append(dict(host=host, port=port, user=user,
                                    password=password, dbname=dbname))
        return _PGConnection(_db_path(host or "localhost", dbname or "pio"))

    m.connect = connect
    return m


# -- fake pymysql -------------------------------------------------------------


class MyError(Exception):
    pass


class MyOperationalError(MyError):
    pass


class MyProgrammingError(MyError):
    pass


class MyInternalError(MyError):
    pass


class SSCursor:
    """Marker class token (pymysql.cursors.SSCursor)."""


def _my_translate(q: str) -> str:
    q = q.replace("%s", "?")
    q = q.replace("INTEGER PRIMARY KEY AUTO_INCREMENT",
                  "INTEGER PRIMARY KEY AUTOINCREMENT")
    q = q.replace("LONGBLOB", "BLOB")
    return q


def _my_map(e: sqlite3.Error) -> MyError:
    s = str(e)
    if isinstance(e, sqlite3.OperationalError):
        if "no such table" in s:
            return MyProgrammingError(1146, f"Table doesn't exist ({s})")
        if "already exists" in s and "index" in s:
            return MyInternalError(1061, f"Duplicate key name ({s})")
    return MyOperationalError(9999, s)


class _MyCursor:
    def __init__(self, conn: "_MyConnection"):
        self._cur = conn._sq.cursor()

    def _run(self, method, q, arg):
        try:
            return method(_my_translate(q), arg)
        except sqlite3.Error as e:
            raise _my_map(e) from e

    def execute(self, q, args=()):
        return self._run(self._cur.execute, q, args)

    def executemany(self, q, rows):
        return self._run(self._cur.executemany, q, rows)

    def fetchone(self):
        return self._cur.fetchone()

    def fetchall(self):
        return self._cur.fetchall()

    def fetchmany(self, n=1):
        return self._cur.fetchmany(n)

    def close(self):
        self._cur.close()

    @property
    def lastrowid(self):
        return self._cur.lastrowid

    @property
    def rowcount(self):
        return self._cur.rowcount


class _MyConnection:
    def __init__(self, path: str):
        self._sq = _connect(path)
        _OPEN.add(self)

    def cursor(self, cursor=None):
        assert cursor is None or cursor is SSCursor
        return _MyCursor(self)

    def commit(self):
        self._sq.commit()

    def rollback(self):
        self._sq.rollback()

    def close(self):
        self._sq.close()


def make_pymysql_module() -> types.ModuleType:
    m = types.ModuleType("pymysql")
    err = types.ModuleType("pymysql.err")
    err.ProgrammingError = MyProgrammingError
    err.OperationalError = MyOperationalError
    err.InternalError = MyInternalError
    m.err = err
    cursors = types.ModuleType("pymysql.cursors")
    cursors.SSCursor = SSCursor
    m.cursors = cursors
    m.connect_calls = []

    def connect(host=None, port=None, user=None, password=None,
                database=None):
        m.connect_calls.append(dict(host=host, port=port, user=user,
                                    password=password, database=database))
        return _MyConnection(_db_path(host or "localhost",
                                      database or "pio"))

    m.connect = connect
    return m
