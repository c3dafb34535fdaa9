"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout. The cell, its configuration, its traffic and
its metrics are read from ``BENCHMARK.json`` and the files under
``bench/``. The program under test is the package under ``src/``.

With ``--trace 0`` the line carries the cell's end-to-end metrics; with
``--trace 1`` its per-layer metrics, read from the same kind of window run
under the profiler. Progress goes to standard error, whose last lines are
the compared numbers, each beside its limit; the last line of standard
output is the JSON result. Without a TPU of a kind in ``bench/peaks.json``
the run exits non-zero and prints no result.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

BENCH_DIR = pathlib.Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
sys.path.insert(0, str(BENCH_DIR))
sys.path.insert(0, str(ROOT / "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from harness import device, runner

    cell = runner.load_cell(ROOT, args.workload)
    try:
        out = runner.execute(cell, args.seed, args.seconds, bool(args.trace),
                             T_PROCESS)
    except device.DeviceError as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
