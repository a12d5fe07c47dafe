"""Record the short chip trace ``test_trace_owners.py`` reads (by hand).

    chiprun -- python3 benchmark/tests/record_short_trace.py \
        tpch-sf1.q6 <seed> chiprun_out/tpu_q6_named

runs the cell once with ``--trace 1`` and the profiler's minimum window cut
from 10 s to 1 s (about five Q6), and writes ``<out>.xplane.pb.gz`` and
``<out>.window_s.txt`` (the window the worker clocked, which the reductions
take as an argument).  Move both to ``benchmark/tests/data/``.  A fourth
argument ``--rehearse`` tries it on XLA:CPU.  PR 24 found no free chip, so
the files do not exist yet and the tests that want them skip.
"""

import glob
import gzip
import json
import os
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main(workload, seed, out, *flags):
    os.chdir(ROOT)
    sys.path.insert(0, ROOT)
    from benchmark import run
    from benchmark.harness import cell
    cell.TRACE_MIN_S = 1.0
    rc = run.main(["--workload", workload, "--seed", seed, "--seconds", "3",
                   "--trace", "1", *flags])
    if rc:
        return rc
    run_dir = os.path.join(cell.CACHE_DIR, "run", workload)
    path = max(glob.glob(os.path.join(run_dir, "trace", "plugins", "profile",
                                      "*", "*.xplane.pb")),
               key=os.path.getmtime)
    os.makedirs(os.path.dirname(os.path.abspath(out)), exist_ok=True)
    with open(path, "rb") as src, gzip.open(out + ".xplane.pb.gz", "wb",
                                            9) as dst:
        shutil.copyfileobj(src, dst)
    with open(os.path.join(run_dir, "trace.done")) as f:
        window_s = json.load(f)["window_s"]
    with open(out + ".window_s.txt", "w") as f:
        f.write(repr(window_s) + "\n")
    print(json.dumps({"saved": out + ".xplane.pb.gz", "window_s": window_s,
                      "bytes": os.path.getsize(out + ".xplane.pb.gz")}))
    return 0


if __name__ == "__main__":
    sys.exit(main(*sys.argv[1:]))
