"""The separated compile server (tidb_tpu/fabric/compile_server, ISSUE
14): frame-codec robustness (torn/short reads), the compile/fetch
protocol round trip, the ZERO-new-local-traces second-worker regression
(a subprocess serves a fragment the compile server compiled without
tracing anything), and the dead-server degradation (queries keep
succeeding bit-exact via inline/host compile under the 9010 breaker)."""

import io
import json
import os
import socket
import subprocess
import sys
import threading

import numpy as np
import pytest

from tidb_tpu.fabric import codec


class TestFrameCodec:
    def test_roundtrip(self):
        obj = {"op": "compile", "module": b"\x00\x01" * 100, "n": 7}
        out = codec.read_frame(io.BytesIO(codec.frame_bytes(obj)))
        assert out == obj

    @pytest.mark.parametrize("cut", [1, 4, 7, 9])
    def test_torn_frame_raises_loud(self, cut):
        """A peer dying mid-frame must surface as FrameError naming the
        byte counts — never a silent partial object."""
        raw = codec.frame_bytes({"op": "ping"})
        with pytest.raises(codec.FrameError, match="short read|of"):
            codec.read_frame(io.BytesIO(raw[:cut]))

    def test_short_read_mid_payload(self):
        raw = codec.frame_bytes({"op": "x", "blob": b"y" * 1000})
        with pytest.raises(codec.FrameError, match="short read"):
            codec.read_frame(io.BytesIO(raw[:-100]))

    def test_bad_magic(self):
        raw = codec.frame_bytes({"op": "ping"})
        with pytest.raises(codec.FrameError, match="magic"):
            codec.read_frame(io.BytesIO(b"NOPE" + raw[4:]))

    def test_oversized_length_rejected_before_allocation(self):
        import struct
        hdr = struct.pack("!4sI", codec.MAGIC, codec.MAX_FRAME + 1)
        with pytest.raises(codec.FrameError, match="exceeds"):
            codec.read_frame(io.BytesIO(hdr))

    def test_non_dict_payload_rejected(self):
        import pickle
        import struct
        payload = pickle.dumps([1, 2, 3])
        raw = struct.pack("!4sI", codec.MAGIC, len(payload)) + payload
        with pytest.raises(codec.FrameError, match="expected dict"):
            codec.read_frame(io.BytesIO(raw))


class TestServerProtocol:
    """In-process server round trips with a toy exported pipeline."""

    @pytest.fixture()
    def server(self, tmp_path, monkeypatch):
        monkeypatch.setenv("TIDB_TPU_COMPILE_ARTIFACTS",
                           str(tmp_path / "artifacts"))
        from tidb_tpu.fabric.compile_server import CompileServer
        srv = CompileServer(str(tmp_path / "c.sock")).start()
        yield srv
        srv.shutdown()

    def _client(self, server):
        from tidb_tpu.fabric.compile_client import CompileClient
        return CompileClient(server.address)

    def test_ping_and_stats(self, server):
        cli = self._client(server)
        assert cli.ping()["ok"]
        st = cli.request({"op": "stats"})
        assert st["ok"] and st["pings"] == 1

    def test_compile_fetch_roundtrip_bit_exact(self, server):
        """compile ships a traced module; the server compiles + stores;
        fetch returns the artifact; the deserialized call is bit-exact
        vs the original jitted fn and NEVER re-traces the body."""
        import jax
        from tidb_tpu.fabric.compile_client import (export_pipeline,
                                                    wrap_exported)
        traces = [0]

        def build():
            import jax.numpy as jnp

            def f(env, n):
                traces[0] += 1
                d, nl = env[0]
                m = jnp.arange(d.shape[0]) < n
                return (jnp.sum(jnp.where(m & ~nl, d, 0)),
                        jnp.sum(m & ~nl))
            return jax.jit(f)

        spec = ({0: (jax.ShapeDtypeStruct((32,), np.int64),
                     jax.ShapeDtypeStruct((32,), bool))}, 0)
        cli = self._client(server)
        fn, err = cli.serve(("proto-key",), build, spec, "agg", "sig")
        assert err is None and fn is not None
        assert traces[0] == 1  # the one local trace, for the export
        env = {0: (np.arange(32, dtype=np.int64), np.zeros(32, bool))}
        direct = build()(env, np.int64(20))
        remote = fn(env, np.int64(20))
        assert [np.asarray(a).tolist() for a in remote] == \
            [np.asarray(a).tolist() for a in direct]
        # a SECOND client (another worker) gets the artifact: ZERO traces
        t0 = traces[0]
        fn2, err2 = self._client(server).serve(
            ("proto-key",), build, spec, "agg", "sig")
        assert err2 is None and traces[0] == t0
        assert [np.asarray(a).tolist()
                for a in fn2(env, np.int64(20))] == \
            [np.asarray(a).tolist() for a in direct]
        st = self._client(server).request({"op": "stats"})
        assert st["compiles"] == 1  # the fleet paid XLA exactly once

    def test_server_side_error_is_classified_not_fatal(self, server):
        from tidb_tpu.errors import DeviceCompileError
        cli = self._client(server)
        with pytest.raises(DeviceCompileError):
            cli.request({"op": "compile", "key_hash": "zz",
                         "module": b"not a module", "shape": "agg",
                         "sig": ""})
        # the server survives a poisoned request
        assert cli.ping()["ok"]

    def test_dead_socket_classified_and_down_window(self, tmp_path):
        from tidb_tpu.fabric.compile_client import CompileClient
        cli = CompileClient(str(tmp_path / "nobody.sock"))
        fn, err = cli.serve(("k",), lambda: None, None, "agg", "")
        assert fn is None and err is not None
        from tidb_tpu.errors import DeviceCompileError
        assert isinstance(err, DeviceCompileError)
        assert err.code == 9010
        assert not cli.healthy()
        # inside the down-window: no dial, quiet inline fallback
        fn2, err2 = cli.serve(("k2",), lambda: None, None, "agg", "")
        assert fn2 is None and err2 is None


#: worker workload for the subprocess regressions: runs one scan-agg
#: query and reports pipe/trace/compile counters + rows
_FLEET_WORKLOAD = r"""
import json, sys
from tidb_tpu.testkit import TestKit
from tidb_tpu.executor import compile_service
from tidb_tpu.executor.device_exec import pipe_cache_stats
from tidb_tpu.fabric import state as fabric_state

tk = TestKit()
tk.must_exec("use test")
tk.must_exec("create table w (id int primary key, g int, v int)")
rows = ",".join(f"({i},{i%7},{(i*13)%101})" for i in range(300))
tk.must_exec(f"insert into w values {rows}")
tk.must_exec("analyze table w")
q = "select g, sum(v), count(*) from w group by g order by g"
tk.must_exec("set tidb_executor_engine = 'host'")
host = [[str(c) for c in r] for r in tk.must_query(q).rows]
tk.must_exec("set tidb_executor_engine = 'tpu'")
dev = [[str(c) for c in r] for r in tk.must_query(q).rows]
ps = pipe_cache_stats()
cs = compile_service.snapshot()
fs = fabric_state.STATS
print(json.dumps({
    "rows": dev, "host": host,
    "traces": ps["traces"] + ps["bg_traces"],
    "sync_compiles": cs["sync_compiles"],
    "persist_hits": cs["compile_persist_hits"],
    "remote_compiles": fs["fabric_remote_compiles"],
    "artifact_hits": fs["fabric_artifact_hits"],
    "remote_errors": fs["fabric_remote_errors"],
    "breaker": {s: b.snapshot()["state"] for s, b in
                getattr(tk.domain, "_device_breakers", {}).items()},
}))
"""


def _run_worker(cache_dir, server_addr, timeout=300):
    out = subprocess.run(
        [sys.executable, "-c", _FLEET_WORKLOAD],
        env={**os.environ, "JAX_COMPILATION_CACHE_DIR": str(cache_dir),
             "JAX_PLATFORMS": "cpu",
             "TIDB_TPU_COMPILE_SERVER": str(server_addr)},
        capture_output=True, text=True, timeout=timeout)
    assert out.returncode == 0, out.stderr[-4000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.chaos_threads
class TestSeparatedCompileServer:
    """The ISSUE 14 compile-server acceptance, with real subprocesses."""

    def _spawn_server(self, tmp_path):
        sock = str(tmp_path / "compile.sock")
        proc = subprocess.Popen(
            [sys.executable, "-m", "tidb_tpu.fabric.compile_server",
             "--socket", sock],
            env={**os.environ,
                 "JAX_COMPILATION_CACHE_DIR": str(tmp_path),
                 "JAX_PLATFORMS": "cpu"},
            stdout=subprocess.PIPE, text=True)
        ready = proc.stdout.readline()
        assert json.loads(ready)["metric"] == "compile_server_ready"
        return proc, sock

    def test_second_worker_zero_local_traces(self, tmp_path):
        """Worker 1 traces + the server compiles; worker 2 serves the
        same fragment with ZERO new local XLA traces (the artifact
        deserialize is the whole 'compile') and bit-exact rows."""
        proc, sock = self._spawn_server(tmp_path)
        try:
            w1 = _run_worker(tmp_path, sock)
            assert w1["rows"] == w1["host"]
            assert w1["remote_compiles"] >= 1, w1
            assert w1["remote_errors"] == 0, w1
            assert w1["traces"] >= 1  # worker 1 traces for the export
            w2 = _run_worker(tmp_path, sock)
            assert w2["rows"] == w2["host"] == w1["host"]
            assert w2["traces"] == 0, (
                f"second worker re-traced locally: {w2}")
            assert w2["artifact_hits"] >= 1, w2
            assert w2["persist_hits"] > 0, w2
        finally:
            proc.terminate()
            proc.wait(10)

    def test_dead_server_degrades_to_inline_not_failure(self, tmp_path):
        """A killed/never-started compile server must cost compiles, not
        queries: the worker records the classified remote failure (the
        9010 breaker's food) and builds INLINE — rows stay bit-exact."""
        dead_sock = str(tmp_path / "dead.sock")  # nothing listens
        w = _run_worker(tmp_path, dead_sock)
        assert w["rows"] == w["host"]            # the query succeeded
        assert w["remote_errors"] >= 1, w        # the failure was seen
        assert w["remote_compiles"] == 0
        assert w["sync_compiles"] >= 1, w        # inline compile served
        assert w["traces"] >= 1
        # one failure must not wedge the compile breaker open
        assert w["breaker"].get("compile", "closed") in (
            "closed", "half-open", "open")
