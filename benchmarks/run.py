"""Aggregate benchmark runner: one section per paper table/figure.

Usage: PYTHONPATH=src python -m benchmarks.run [section ...]
Prints ``name,us_per_call,derived`` CSV rows.
"""
from __future__ import annotations

import sys
import time

from benchmarks.common import emit
from repro.compile_cache import enable_compile_cache

SECTIONS = [
    "fig9_throughput",
    "table1_tra",
    "table3_energy",
    "fig10_bitmap",
    "fig11_bitweaving",
    "fig12_setops",
    "serve_qps",
    "serve_loop",
    "optimizer",
    "arith_throughput",
    "vm_dispatch",
    "vm_stream",
    "cluster_scaling",
    "reliability",
    "obs_overhead",
    "extra_apps",
    "perf_summary",
]


def main(argv: list = None) -> None:
    want = sys.argv[1:] if argv is None else list(argv)
    # a typo'd section name used to be silently skipped (the run printed
    # only the CSV header and exited 0) — reject unknown names instead
    unknown = [w for w in want if w not in SECTIONS]
    if unknown:
        print(f"unknown section(s): {', '.join(unknown)}", file=sys.stderr)
        print(f"valid sections: {', '.join(SECTIONS)}", file=sys.stderr)
        raise SystemExit(2)
    want = want or SECTIONS
    enable_compile_cache()
    print("name,us_per_call,derived")
    for section in SECTIONS:
        if section not in want:
            continue
        mod = __import__(f"benchmarks.{section}", fromlist=["run"])
        t0 = time.perf_counter()
        rows = mod.run()
        emit(rows)
        dt = time.perf_counter() - t0
        print(f"{section}/_section_total,{dt * 1e6:.0f},")


if __name__ == "__main__":
    main()
