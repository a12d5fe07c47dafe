"""Where the persistent compile cache lives (tidb_tpu/__init__.py): it is
placed from OUTSIDE.  ``JAX_COMPILATION_CACHE_DIR=<dir>`` is used exactly
as given — no subdirectory, no other variable — and only when it is unset
does the cache default to the fixed path ``<checkout>/.jaxcache``.  The
path is part of what a later process must find again: a directory the
program moves by itself never hits."""

import os
import pathlib
import subprocess
import sys

REPO = pathlib.Path(__file__).resolve().parents[1]


def _cache_dir_of_fresh_process(env):
    out = subprocess.run(
        [sys.executable, "-c",
         "import tidb_tpu, jax; "
         "print(jax.config.jax_compilation_cache_dir)"],
        env=env, capture_output=True, text=True, timeout=120, check=True)
    return out.stdout.strip().splitlines()[-1]


class TestCacheDirPlacement:
    def test_env_var_is_used_exactly(self, tmp_path):
        env = {**os.environ, "JAX_COMPILATION_CACHE_DIR": str(tmp_path),
               "JAX_PLATFORMS": "cpu"}
        assert _cache_dir_of_fresh_process(env) == str(tmp_path)

    def test_unset_defaults_to_checkout_jaxcache(self):
        env = {k: v for k, v in os.environ.items()
               if k != "JAX_COMPILATION_CACHE_DIR"}
        env["JAX_PLATFORMS"] = "cpu"
        assert _cache_dir_of_fresh_process(env) == str(REPO / ".jaxcache")


_PERSIST_WORKLOAD = r"""
import json
from tidb_tpu.testkit import TestKit
from tidb_tpu.executor import compile_service

tk = TestKit()
tk.must_exec("use test")
tk.must_exec("create table p (id int primary key, g int, v int)")
rows = ",".join(f"({i},{i%5},{(i*31)%97})" for i in range(200))
tk.must_exec(f"insert into p values {rows}")
# pin the group-count estimate: the compiled-pipeline capacity rides the
# stats, and the persistent-index key must be IDENTICAL across processes
tk.must_exec("analyze table p")
q = "select g, sum(v), count(*) from p group by g order by g"
tk.must_exec("set tidb_executor_engine = 'host'")
host = [[str(c) for c in r] for r in tk.must_query(q).rows]
tk.must_exec("set tidb_executor_engine = 'tpu'")
dev = [[str(c) for c in r] for r in tk.must_query(q).rows]
snap = compile_service.snapshot()
print(json.dumps({"rows": dev, "host": host,
                  "persist_hits": snap["compile_persist_hits"],
                  "sync_compiles": snap["sync_compiles"]}))
"""


class TestPersistentExecutableCache:
    """ISSUE 8 acceptance: a fresh subprocess against a populated
    persistent cache reports compile_persist_hits > 0 and bit-exact
    query results vs host goldens — a process restart (or a second
    serving process on the same cache mount) starts WARM: the signature
    index (executor/compile_service.py pipe-index/) marks what compiled
    here, and the jax AOT cache underneath holds the executables."""

    def _run(self, cache_dir):
        import json
        out = subprocess.run(
            [sys.executable, "-c", _PERSIST_WORKLOAD],
            env={**os.environ,
                 "JAX_COMPILATION_CACHE_DIR": str(cache_dir),
                 "JAX_PLATFORMS": "cpu"},
            capture_output=True, text=True, timeout=240, check=True)
        return json.loads(out.stdout.strip().splitlines()[-1])

    def test_second_process_starts_warm_and_bit_exact(self, tmp_path):
        first = self._run(tmp_path)
        assert first["rows"] == first["host"]
        assert first["sync_compiles"] >= 1  # cold: built + recorded
        # the executables landed DIRECTLY under the directory given
        assert any(f.endswith("-cache") for f in os.listdir(tmp_path))
        second = self._run(tmp_path)
        # the restart is WARM: the cold obtain found its signature in the
        # index (the "compile" under it is an AOT-cache deserialize)...
        assert second["persist_hits"] > 0
        # ...and the deserialized executable computes the same bits
        assert second["rows"] == second["host"] == first["host"]
