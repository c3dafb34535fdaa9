"""The readings a cell's limits are set from: the program's and the control's.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds <s>

For each seed, one run of the cell as `bench/run.py` makes it (one process
for all seeds, so set-up compiles once), and besides the program's
compared numbers the control's: the plain reference put in the program's
place, with the options of the configuration's ``control`` (a guarantee
it states broken), its answers put through the same comparison
(`harness.check`) in place of the served values. The program's numbers
give each limit's lower reading, the control's its upper reading. The benchmark's own runs do not run the
control. Prints one JSON line a seed.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

BENCH_DIR = pathlib.Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
sys.path.insert(0, str(BENCH_DIR))
sys.path.insert(0, str(ROOT / "src"))


def control_answers(sent, control):
    """Copies of the window's answered requests with the ``control``
    reference's answers in place of the served values."""
    return [dataclasses.replace(s, value=control.answer(s.request.query),
                                handle=None)
            for s in sent if s.value is not None]


def control_readings(cell):
    """``readings`` for `harness.runner.execute`: the control's answers to
    every answered request of the window, put through the benchmark's own
    comparison (`harness.check`) in the served values' place."""
    from harness import check, queries

    options = cell.config["control"]["reference"]

    def read(win, host):
        ref = queries.Reference(host.bitmaps, host.columns, host.bits,
                                host.n_rows)
        ctl = queries.Reference(host.bitmaps, host.columns, host.bits,
                                host.n_rows, **options)
        answers = control_answers(win.sent, ctl)
        checks = check.compare(answers, ref)
        off = {s.request.template for s in answers
               if s.value != ref.answer(s.request.query)}
        return {"control": {"correct": check.correct(checks),
                            "checks": checks, "answered": len(answers),
                            "templates_off": sorted(off)}}
    return read


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated whole numbers")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    from harness import runner

    cell = runner.load_cell(ROOT, args.workload)
    t_start = T_PROCESS
    for seed in (int(s) for s in args.seeds.split(",")):
        out = runner.execute(cell, seed, args.seconds, False, t_start,
                             readings=control_readings(cell))
        t_start = time.perf_counter()
        print(json.dumps({"seed": seed, **out}), flush=True)
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
