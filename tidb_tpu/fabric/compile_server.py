"""The separated compile server: one subprocess per host owns the
expensive XLA compiles for the whole fleet.

Why (ISSUE 14): compiles measured on a v5e in July 2026 ran 147-379s per
shape, and N worker processes would pay them N times — compilation must be a
shared fleet-level resource.  The split of labor follows the
PJIT/shard_map compile-helper shape (SNIPPETS.md [3]): the WORKER traces
(cheap Python, needs the query's builder closures), the SERVER compiles
(expensive XLA, needs only the traced module):

    worker                          compile server
    ------                          --------------
    build() -> jitted fn
    jax.export trace -> StableHLO
    ---- compile(key, module) --->  deserialize module
                                    warm-call -> XLA compile into the
                                      shared host-fingerprinted AOT cache
                                    store module artifact + persist-index
    <------------- ok ------------
    exported.call(...)              (XLA comes off the AOT cache:
                                     a deserialize, not a compile)

A SECOND worker's cold obtain finds the artifact (shared directory, or
the ``fetch`` op) and installs the deserialized module directly — zero
new local traces, zero local XLA compiles (the acceptance regression in
tests/test_compile_server.py).

Protocol: length-prefixed frames (fabric/codec.py) over a unix-domain
socket (or ``host:port`` TCP).  Ops: ``ping``, ``compile``, ``fetch``,
``stats``, ``shutdown``.  Every worker-side failure — dead socket, torn
frame, server-side compile error — is CLASSIFIED (DeviceCompileError
9010 / transport) and walks the existing compile-service resilience
ladder: retry curve, compile-scoped breaker, degrade to inline/host
compile.  The server going away can slow compiles down; it can never
fail a query.

Run:  python -m tidb_tpu.fabric.compile_server --socket /path/c.sock
"""

from __future__ import annotations

import argparse
import contextlib
import json
import logging
import os
import socket
import sys
import threading
import time

from . import codec

log = logging.getLogger("tidb_tpu.fabric.compile_server")

#: artifact directory (serialized jax.export modules) lives next to the
#: AOT cache + pipe-index, host-fingerprint-scoped like both
ARTIFACT_DIRNAME = "fabric-artifacts"


def artifact_dir() -> "str | None":
    d = os.environ.get("TIDB_TPU_COMPILE_ARTIFACTS", "")
    if d == "off":
        return None
    if d:
        return d
    import jax
    base = getattr(jax.config, "jax_compilation_cache_dir", None)
    if not base:
        return None
    return os.path.join(base, ARTIFACT_DIRNAME)


def artifact_path(key_hash: str) -> "str | None":
    d = artifact_dir()
    return os.path.join(d, key_hash + ".jexp") if d else None


def store_artifact(key_hash: str, blob: bytes) -> bool:
    path = artifact_path(key_hash)
    if path is None:
        return False
    try:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        tmp = f"{path}.{os.getpid()}.tmp"
        with open(tmp, "wb") as f:
            f.write(blob)
        os.replace(tmp, path)
        return True
    except OSError:
        return False


def load_artifact(key_hash: str) -> "bytes | None":
    path = artifact_path(key_hash)
    if path is None:
        return None
    try:
        with open(path, "rb") as f:
            return f.read()
    except OSError:
        return None


def exported_zeros(exp):
    """Zero-filled call args matching an Exported's input avals (the
    server's warm call; weak-typed scalar avals stay literal zeros so
    the compiled aval matches real dispatches)."""
    import numpy as np
    out = []
    for a in exp.in_avals:
        if getattr(a, "weak_type", False) and a.shape == ():
            out.append(np.zeros((), a.dtype)[()].item())
        else:
            out.append(np.zeros(a.shape, a.dtype))
    return out


class CompileServer:
    """The serving loop.  One thread per connection; compiles serialize
    through one lock (XLA compile is process-dominating anyway, and a
    deterministic one-at-a-time order keeps the AOT cache writes sane)."""

    def __init__(self, address: str):
        self.address = address
        self._compile_lock = threading.Lock()
        self._stop = threading.Event()
        self.stats = {"compiles": 0, "compile_s": 0.0, "fetches": 0,
                      "errors": 0, "pings": 0, "dedup_served": 0}
        self._known: dict = {}  # key_hash -> compile_s (already compiled)
        self._sock = self._bind(address)

    @staticmethod
    def _bind(address: str):
        if ":" in address:
            host, port = address.rsplit(":", 1)
            s = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
            s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
            s.bind((host, int(port)))
        else:
            with contextlib.suppress(OSError):
                os.unlink(address)
            os.makedirs(os.path.dirname(address) or ".", exist_ok=True)
            s = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
            s.bind(address)
            os.chmod(address, 0o600)
        s.listen(64)
        return s

    @property
    def port(self) -> int:
        if self._sock.family == socket.AF_INET:
            return self._sock.getsockname()[1]
        return 0

    def serve_forever(self):
        self._sock.settimeout(0.25)
        while not self._stop.is_set():
            try:
                conn, _addr = self._sock.accept()
            except socket.timeout:
                continue
            except OSError:
                break
            t = threading.Thread(target=self._serve_conn, args=(conn,),
                                 daemon=True)
            t.start()
        with contextlib.suppress(OSError):
            self._sock.close()

    def start(self) -> "CompileServer":
        threading.Thread(target=self.serve_forever, daemon=True,
                         name="compile-server-accept").start()
        return self

    def shutdown(self):
        self._stop.set()
        with contextlib.suppress(OSError):
            self._sock.close()

    # -- per-connection loop -------------------------------------------------

    def _serve_conn(self, conn):
        from ..session import tracing
        with contextlib.suppress(Exception), conn:
            while True:
                try:
                    req = codec.read_frame(conn)
                except codec.FrameError:
                    return  # torn frame / disconnect: drop the conn
                # record this hop into OUR ring on behalf of the caller's
                # trace (one branch when the request carries no context)
                rtr = tracing.begin_remote(
                    req.pop("trace", None),
                    f"compile_server.{req.get('op', '?')}")
                try:
                    resp = self._handle(req)
                except Exception as e:  # noqa: BLE001 — reply, never die
                    self.stats["errors"] += 1
                    log.warning("compile server: %s failed: %s",
                                req.get("op"), e, exc_info=True)
                    resp = {"ok": False, "error": f"{type(e).__name__}: {e}"}
                sub = tracing.finish_remote(rtr, succ=bool(resp.get("ok")))
                if sub is not None:
                    resp["_trace"] = sub
                codec.write_frame(conn, resp)
                if req.get("op") == "shutdown":
                    self.shutdown()
                    return

    def _handle(self, req: dict) -> dict:
        op = req.get("op")
        if op == "ping":
            self.stats["pings"] += 1
            return {"ok": True, "pid": os.getpid(),
                    "compiles": self.stats["compiles"]}
        if op == "stats":
            return {"ok": True, **self.stats,
                    "known": len(self._known)}
        if op == "compile":
            return self._compile(req)
        if op == "fetch":
            self.stats["fetches"] += 1
            blob = load_artifact(req["key_hash"])
            if blob is None:
                return {"ok": True, "found": False}
            return {"ok": True, "found": True, "module": blob}
        if op == "shutdown":
            return {"ok": True}
        return {"ok": False, "error": f"unknown op {op!r}"}

    def _compile(self, req: dict) -> dict:
        """Deserialize the worker's traced module, compile it (the warm
        call populates the shared AOT cache), persist the artifact +
        signature-index entry."""
        from jax import export
        from ..session import tracing
        key_hash = req["key_hash"]
        with self._compile_lock:
            if key_hash in self._known:
                # fleet-wide compile dedup: N workers racing the same
                # cold signature pay ONE server compile
                self.stats["dedup_served"] += 1
                tracing.event("compile.dedup", key=key_hash[:12])
                return {"ok": True, "compile_s": self._known[key_hash],
                        "dedup": True}
            t0 = time.perf_counter()
            with tracing.span("xla.compile", key=key_hash[:12],
                              shape=req.get("shape", "")):
                exp = export.deserialize(bytearray(req["module"]))
                exp.call(*exported_zeros(exp))
            elapsed = time.perf_counter() - t0
            store_artifact(key_hash, bytes(req["module"]))
            _record_index(key_hash, req.get("shape", ""),
                          req.get("sig", ""))
            self._known[key_hash] = elapsed
            self.stats["compiles"] += 1
            self.stats["compile_s"] += elapsed
        return {"ok": True, "compile_s": elapsed}


def _record_index(key_hash: str, shape: str, sig: str):
    """Write the persistent signature-index entry the compile service
    reads (compile_service._persist_lookup keys by the same hash), so a
    worker restart sees server-compiled signatures as warm."""
    from ..executor.compile_service import _persist_dir
    d = _persist_dir()
    if d is None:
        return
    try:
        os.makedirs(d, exist_ok=True)
        path = os.path.join(d, key_hash + ".json")
        if os.path.exists(path):
            return
        tmp = f"{path}.{os.getpid()}.tmp"
        with open(tmp, "w") as f:
            json.dump({"shape": shape, "sig": str(sig)[:512],
                       "origin": "compile-server", "ts": time.time()}, f)
        os.replace(tmp, path)
    except OSError:
        pass


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--socket", required=True,
                    help="unix socket path, or host:port")
    args = ap.parse_args(argv)
    import tidb_tpu  # noqa: F401 — x64 + the fingerprint-scoped AOT cache
    from tidb_tpu.session import tracing
    tracing.set_process_label("compile-server")
    srv = CompileServer(args.socket)
    print(json.dumps({"metric": "compile_server_ready",
                      "pid": os.getpid(), "address": args.socket,
                      "port": srv.port}), flush=True)
    import signal

    def _stop(_sig, _frm):
        srv.shutdown()

    signal.signal(signal.SIGTERM, _stop)
    signal.signal(signal.SIGINT, _stop)
    srv.serve_forever()
    return 0


if __name__ == "__main__":
    sys.exit(main())
