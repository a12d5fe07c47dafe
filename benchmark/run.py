"""The benchmark's one command.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s>
                            --trace <0|1> [--rehearse]

Runs one cell of BENCHMARK.json and prints, as the last line of its
standard output, one JSON object with the keys ``correct``,
``attempted``, ``failed``, ``metrics`` and ``device`` (with ``--trace
1`` also ``breakdown``).  Exit code 0 means that line was printed; on
any failure the code is non-zero and no such line is printed.

``--rehearse`` is for the CPU sandbox only (the chip command never
passes it): SF0.01 on XLA:CPU, the mesh cell on four virtual devices,
``platform: cpu`` on every line and ``null`` under every metric.
"""

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    os.chdir(ROOT)   # the worker imports tidb_tpu and benchmark from here
    sys.path.insert(0, ROOT)
    from benchmark.harness.resolve import Cell, ResolveError
    try:
        cell = Cell(args.workload)
    except ResolveError as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 2
    try:
        from tidb_tpu.fabric.client import WireError
    except ImportError as e:
        print(f"benchmark: the tpu-htap checkout is not beside this "
              f"directory ({e})", file=sys.stderr)
        return 2
    from benchmark.harness.cell import Failed, run_cell
    try:
        result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                          args.rehearse, T_START)
    except (Failed, WireError, NotImplementedError, OSError,
            RuntimeError) as e:
        print(f"benchmark: FAILED: {type(e).__name__}: {e}",
              file=sys.stderr)
        return 1
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
