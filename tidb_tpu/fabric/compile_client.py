"""Worker-side client of the separated compile server.

The client owns the full remote-compile decision for one pipeline
resolution (``serve``): artifact hit (shared directory, then the
server's ``fetch`` op) → install the deserialized module with ZERO local
traces; otherwise trace locally, ship the StableHLO to the server for
the expensive XLA compile, and dispatch through the exported module so
the local "compile" is an AOT-cache deserialize.

Failure discipline (one refused compile request must not cost the
queries after it): the client NEVER
raises out of ``serve`` — a dead socket, torn frame or server-side error
returns ``(None, classified_error)`` so the caller builds inline and the
compile-scoped breaker (9010) records the remote failure; a down-window
then short-circuits further attempts for a few seconds so a dead server
costs one timeout, not one per fragment.
"""

from __future__ import annotations

import contextlib
import logging
import socket
import threading
import time

from . import codec, compile_server as artifacts

log = logging.getLogger("tidb_tpu.fabric.compile_client")

#: how long a transport failure silences remote attempts (the breaker's
#: cooldown shapes query-visible behavior; this just stops re-dialing a
#: dead socket on every obtain in between)
DOWN_COOLDOWN_S = 5.0
CONNECT_TIMEOUT_S = 5.0
#: per-request bound — a remote compile of a big fragment is minutes on
#: a real TPU; the sync caller is already the slow path
REQUEST_TIMEOUT_S = 300.0

_LOCK = threading.Lock()
_CLIENTS: dict = {}


def get_client(address: "str | None" = None) -> "CompileClient | None":
    """The process's client for `address` (default: the fabric state's
    compile-server address), or None when no server is configured."""
    if address is None:
        from . import state
        address = state.compile_server_addr()
    if not address:
        return None
    with _LOCK:
        cli = _CLIENTS.get(address)
        if cli is None:
            cli = _CLIENTS[address] = CompileClient(address)
        return cli


class CompileClient:
    def __init__(self, address: str,
                 down_cooldown_s: float = DOWN_COOLDOWN_S):
        self.address = address
        self._down_until = 0.0
        self._down_cooldown = down_cooldown_s
        self._mu = threading.Lock()

    def healthy(self) -> bool:
        return time.monotonic() >= self._down_until

    def _mark_down(self):
        self._down_until = time.monotonic() + self._down_cooldown

    def _connect(self):
        if ":" in self.address:
            host, port = self.address.rsplit(":", 1)
            return socket.create_connection((host, int(port)),
                                            timeout=CONNECT_TIMEOUT_S)
        s = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
        s.settimeout(CONNECT_TIMEOUT_S)
        s.connect(self.address)
        return s

    def request(self, obj: dict, timeout_s: float = REQUEST_TIMEOUT_S):
        """One round trip.  Raises DeviceCompileError (errno 9010,
        taxonomy class ``compile``) on any transport/frame/server
        failure — the caller's breaker records exactly that class."""
        from ..errors import DeviceCompileError
        from ..session import tracing
        from . import state
        ctx = tracing.wire_ctx()
        if ctx is not None:  # propagate the statement's trace across the hop
            obj["trace"] = ctx
        t0 = time.perf_counter()
        try:
            with self._mu:  # one in-flight request per client: the
                #             server serializes compiles anyway
                sock = self._connect()
                try:
                    sock.settimeout(timeout_s)
                    codec.write_frame(sock, obj)
                    resp = codec.read_frame(sock)
                finally:
                    with contextlib.suppress(OSError):
                        sock.close()
        except (OSError, codec.FrameError) as e:
            self._mark_down()
            state.bump("fabric_remote_errors")
            raise DeviceCompileError(
                f"compile server {self.address} unreachable/torn: "
                f"{type(e).__name__}: {e}") from e
        state.note_rtt((time.perf_counter() - t0) * 1000.0)
        # stitch the server's recorded subtree (attached even on a
        # server-side error reply: the failed hop still belongs in the
        # statement's timeline)
        tracing.attach_remote(resp.pop("_trace", None))
        if not resp.get("ok"):
            state.bump("fabric_remote_errors")
            raise DeviceCompileError(
                f"compile server {self.address} failed the request: "
                f"{resp.get('error', 'unknown error')}")
        return resp

    def ping(self, timeout_s: float = 5.0) -> dict:
        return self.request({"op": "ping"}, timeout_s=timeout_s)

    # -- the pipeline-resolution entry ---------------------------------------

    def serve(self, key, build, spec, shape: str, sig) -> tuple:
        """Resolve one cold pipeline via the fabric: returns
        ``(fn, None)`` on success, ``(None, classified_error)`` when the
        remote path failed (caller builds inline and charges the 9010
        breaker), ``(None, None)`` when remote is in its down-window or
        the shape can't export (caller builds inline, no charge)."""
        from ..executor.compile_service import _persist_hash
        from ..session import tracing
        from . import state
        key_hash = _persist_hash(key)
        # 1. shared artifact directory: another worker (or a previous
        #    incarnation) already compiled this — zero local traces
        fn = self._from_artifact(key_hash, artifacts.load_artifact(key_hash))
        if fn is not None:
            state.bump("fabric_artifact_hits")
            tracing.event("fabric.compile", mode="artifact")
            return fn, None
        if not self.healthy():
            return None, None
        # 2. server fetch: the artifact may exist on the server's side of
        #    a non-shared mount
        try:
            resp = self.request({"op": "fetch", "key_hash": key_hash},
                                timeout_s=10.0)
            if resp.get("found"):
                fn = self._from_artifact(key_hash, resp["module"])
                if fn is not None:
                    state.bump("fabric_artifact_hits")
                    tracing.event("fabric.compile", mode="fetch")
                    return fn, None
        except Exception as e:  # noqa: BLE001 — classified below
            return None, e
        # 3. trace locally (cheap), compile remotely (expensive)
        if spec is None or build is None:
            return None, None  # nothing to trace: caller handles it
        try:
            exp, blob = export_pipeline(build, spec)
        except Exception as e:  # noqa: BLE001 — shape opt-out, not health
            # this shape doesn't export (exotic pytree, unsupported
            # primitive): not a server health signal — build inline
            log.debug("pipeline shape %s does not export (inline "
                      "build): %s", shape, e)
            return None, None
        try:
            with tracing.span("compile.remote", shape=shape):
                self.request({"op": "compile", "key_hash": key_hash,
                              "module": blob, "shape": shape,
                              "sig": repr(sig)[:512]})
        except Exception as e:  # noqa: BLE001 — classified DeviceCompileError
            return None, e
        state.bump("fabric_remote_compiles")
        tracing.event("fabric.compile", mode="remote")
        return wrap_exported(exp), None

    @staticmethod
    def _from_artifact(key_hash: str, blob):
        if blob is None:
            return None
        try:
            from jax import export
            return wrap_exported(export.deserialize(bytearray(blob)))
        except Exception as e:  # noqa: BLE001 — corrupt artifact != fatal
            log.warning("artifact %s undeserializable (recompiling): %s",
                        key_hash, e)
            return None


def export_pipeline(build, spec) -> tuple:
    """Trace `build()`'s jitted pipeline over `spec` and serialize it.

    The export goes through a FLAT-LEAF wrapper: jax.export cannot
    serialize int-keyed dict pytrees (the pipelines' env arg), so the
    exported module takes ``tree_leaves(spec)`` positionally and
    reassembles the original tree inside — wrap_exported applies the
    mirror flattening at call time.  Tracing runs HERE (the worker owns
    the builder closures); only the XLA compile ships to the server."""
    import jax
    from jax import export
    fn = build()
    flat_spec, in_tree = jax.tree_util.tree_flatten(spec)

    def _flat(*leaves):
        return fn(*jax.tree_util.tree_unflatten(in_tree, leaves))

    exp = export.export(jax.jit(_flat))(*flat_spec)
    return exp, exp.serialize()


def wrap_exported(exp):
    """A pipeline-callable view of an Exported: same ``fn(*args)``
    convention as the jitted builders, flat-leaf calling inside.  The
    module's XLA compile happens on first call and rides the shared AOT
    cache (the compile server already populated it), and the original
    Python body is never traced here — the zero-local-traces property
    the second-worker regression pins."""
    import jax
    call = exp.call

    def fn(*args):
        return call(*jax.tree_util.tree_leaves(args))

    fn._fabric_exported = True
    return fn


def reset_for_tests():
    with _LOCK:
        _CLIENTS.clear()
