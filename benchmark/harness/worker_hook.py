"""Worker-side half of the benchmark: the ``Fleet(init=...)`` seed hook.

Runs inside the ONE process that owns the chip(s), after it took its
device and before any data exists.  It refuses the wrong platform or too
few chips (the worker exits before ready and ``Fleet.start`` fails with
this message), builds the configuration's tables from the seed with the
data set the configuration names, and
starts a small thread that serves the parent's requests for what only
this process can give: a ``jax.profiler`` trace of the device and the
allocator's peak bytes.  The parent asks through files in the run
directory (it never touches JAX).
"""

import json
import os
import threading
import time

ENV_SPEC = "TPU_HTAP_BENCH_SPEC"
POLL_S = 0.02


def seed(domain, seeded: bool = False):
    import jax
    spec = json.loads(os.environ[ENV_SPEC])
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu" and not spec["rehearse"]:
        pinned = os.environ.get("JAX_PLATFORMS")
        raise RuntimeError(
            f"benchmark: the worker's JAX platform is {platform!r}, not "
            "'tpu'" + (f" (the environment exports JAX_PLATFORMS="
                       f"{pinned!r})" if pinned else "")
            + "; refusing to load data (only --rehearse runs off the chip)")
    if len(devices) < spec["chips"]:
        raise RuntimeError(
            f"benchmark: the cell needs {spec['chips']} chip(s), JAX "
            f"found {len(devices)}")
    from tidb_tpu.testkit import TestKit
    from .resolve import load_dataset
    dataset = load_dataset(spec["dataset"])
    t0 = time.monotonic()
    tables = dataset.generate(spec["seed"], spec["sf"], spec["tables"])
    t1 = time.monotonic()
    rows = dataset.load(TestKit(domain), tables, spec["tables"], seeded,
                        tag=f"{spec['dataset']}/v{dataset.GEN_VERSION}/"
                            f"seed{spec['seed']}/sf{spec['sf']:g}")
    print(json.dumps({"metric": "bench_seed", "rows": rows,
                      "seeded_before": seeded,
                      "gen_s": round(t1 - t0, 3),
                      "load_s": round(time.monotonic() - t1, 3)}),
          flush=True)
    threading.Thread(target=_serve, args=(spec["run_dir"],), daemon=True,
                     name="benchmark-worker-hook").start()


def write_json(path: str, obj) -> None:
    """Whole or not at all: readers poll for these files."""
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(obj, f)
    os.replace(tmp, path)


def _take(path: str) -> bool:
    try:
        os.remove(path)
    except FileNotFoundError:
        return False
    return True


def _serve(run_dir: str) -> None:
    """Answer ``trace.start`` / ``trace.stop`` / ``mem.req`` files."""
    import jax
    t_on = None
    while True:
        time.sleep(POLL_S)
        if _take(os.path.join(run_dir, "trace.start")):
            opts = jax.profiler.ProfileOptions()
            # a Python-level tracer would multiply the host work of a
            # Python SQL layer; the runtime's own host events stay on
            opts.python_tracer_level = 0
            jax.profiler.start_trace(os.path.join(run_dir, "trace"),
                                     profiler_options=opts)
            t_on = time.monotonic()
            write_json(os.path.join(run_dir, "trace.on"), {})
        if _take(os.path.join(run_dir, "trace.stop")) and t_on is not None:
            window_s = time.monotonic() - t_on
            jax.profiler.stop_trace()
            t_on = None
            write_json(os.path.join(run_dir, "trace.done"),
                   {"window_s": window_s})
        if _take(os.path.join(run_dir, "mem.req")):
            write_json(os.path.join(run_dir, "mem.json"),
                   [d.memory_stats() for d in jax.local_devices()])
