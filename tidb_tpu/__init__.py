"""tpu-htap: a TPU-native distributed SQL engine with TiDB's capability surface.

Architecture (see SURVEY.md §7): the control plane — MySQL-dialect parser,
cost-based planner, MVCC transactions, online DDL, catalog — runs host-side in
Python (C++ for the hot codecs/storage in later rounds); the data plane
executes columnar batches as JAX/XLA kernels, with ``shard_map`` collectives
over ICI/DCN taking the role of the reference's MPP exchanges
(reference: planner/core/fragment.go, store/copr/mpp.go) and coprocessor
fan-out (reference: store/copr/coprocessor.go).

Import side effect: enables jax x64 so decimal aggregation (scaled int64) is
exact on device — the north star requires bit-exact parity (BASELINE.md).
"""

import os as _os
import sys as _sys

import jax as _jax

_jax.config.update("jax_enable_x64", True)

# Persistent compilation cache: fused fragment programs (a TPC-H query is ONE
# XLA program) are compiled once per cache directory, not once per process.
# The directory is placed from OUTSIDE: JAX_COMPILATION_CACHE_DIR, which jax
# reads into `jax_compilation_cache_dir` itself, is used exactly as given;
# only when it is unset does the cache default to <checkout>/.jaxcache — a
# fixed path, because the path is part of what a later run must find again.
if not _os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    _cache_dir = _os.path.join(
        _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))),
        ".jaxcache")
    try:
        _os.makedirs(_cache_dir, exist_ok=True)
    except OSError as _e:
        # serve uncached, but say so: where the cache lives decides what a
        # cold start costs
        print(f"tidb_tpu: compile cache {_cache_dir!r} unavailable, "
              f"running without a persistent cache: {_e}", file=_sys.stderr)
    else:
        _jax.config.update("jax_compilation_cache_dir", _cache_dir)
# cache every fragment: the default 1s/small-entry filters would skip the
# many sub-second shrink-to-fit recompiles
_jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
_jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)

__version__ = "0.1.0"

