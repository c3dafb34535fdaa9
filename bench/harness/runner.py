"""One run of one cell: set-up, warm-up, the window, the check, the line.

Everything a cell is made of is found by name from ``BENCHMARK.json``:
its configuration's file, its traffic file ``bench/traffic/<traffic>.json``,
the driver of the traffic's loop ``bench/loops/<loop>.py``
(`harness.serve`), and one reader a metric, ``bench/metrics/<metric>.py``
with a function ``read(run)`` that returns the metric's value, or None
where the run has nothing for it to read (the metric is then left out of
the line).
"""
from __future__ import annotations

import collections
import dataclasses
import importlib.util
import json
import math
import pathlib
import shutil
import sys
import time
from typing import Callable, Dict, List, Optional

from harness import check, data, device, queries, serve, spans, xtrace
from harness.traffic import Traffic

#: where a traced run's profile is written and read back, then removed
TRACE_DIR = pathlib.Path(__file__).resolve().parents[1] / ".runs" / "trace"


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


@dataclasses.dataclass
class Cell:
    name: str
    bench_dir: pathlib.Path       # the benchmark's files: traffic/, metrics/
    chips: int
    config: dict                  # the configuration file's contents
    traffic: dict                 # the traffic file's contents
    end_to_end: List[dict]        # BENCHMARK.json metric entries that
    per_layer: List[dict]         # this cell reports


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(root: pathlib.Path, name: str) -> Cell:
    """Cell ``name`` of the checkout at ``root``."""
    bench_dir = root / "bench"
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} (known: {sorted(cells)})")
    w = cells[name]
    cfg_entry = next(c for c in bench["configs"] if c["name"] == w["config"])
    return Cell(
        name=name, bench_dir=bench_dir, chips=int(w["chips"]),
        config=json.loads((root / cfg_entry["file"]).read_text()),
        traffic=json.loads(
            (bench_dir / "traffic" / f"{w['traffic']}.json").read_text()),
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, name)])


def load_reader(cell: Cell, name: str):
    """``read`` of the metric's own file, ``<bench>/metrics/<name>.py``."""
    path = cell.bench_dir / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@dataclasses.dataclass
class Run:
    """What a metric reader may read."""

    cell: Cell
    seconds: float
    setup_s: float
    window: serve.Window
    bits: Dict[str, int]          # column widths
    vector_bytes: int             # bytes of one catalog vector
    peak: dict                    # the chip's row of bench/peaks.json
    trace: Optional[xtrace.Summary] = None
    plan_timer: Optional[spans.Timer] = None

    @property
    def answered(self) -> list:
        return [s for s in self.window.sent if s.value is not None]

    def vectors(self, sent) -> set:
        return queries.vectors_read(sent.request.query, self.bits)

    def latency_percentile_ms(self, pct: float) -> Optional[float]:
        """Nearest-rank percentile of the answered requests' latency,
        `submit()` (or the due time, where the loop sets one) to
        `result()` returning, in ms."""
        lats = sorted((s.t_answer - s.t_from) * 1e3 for s in self.answered)
        if not lats:
            return None
        i = min(len(lats) - 1, max(0, math.ceil(pct / 100.0 * len(lats)) - 1))
        return lats[i]


def execute(cell: Cell, seed: int, seconds: float, trace: bool,
            t_process: float, readings: Optional[Callable] = None) -> dict:
    """Run ``cell`` once; return the result line's object.

    ``readings(window, host)``, where given, returns more numbers read
    from the finished window (the control's, in `bench/control.py`); they
    go into the result under "readings". The benchmark's runs pass none.
    """
    import jax

    devices = jax.devices()
    peak = device.check_devices(devices, cell.chips, device.load_peaks())
    log(f"device: {devices[0].platform} {devices[0].device_kind!r} x "
        f"{len(devices)}; compile cache {device.enable_compile_cache()}")
    counter = device.CompileCounter()
    from repro.service import QueryService, ServiceConfig

    svc = QueryService(ServiceConfig(
        n_chips=cell.chips if cell.chips > 1 else None))
    t0 = time.perf_counter()
    host = data.build(svc, cell.config, seed)
    log(f"catalog: {len(svc.catalog)} vectors of {cell.config['domain_bits']}"
        f" bits, {time.perf_counter() - t0:.3f} s, "
        f"{counter.compiles} compiles")
    traffic = Traffic(cell.traffic)
    drive = serve.load_driver(cell.bench_dir, traffic.loop)
    serve.warm_up(svc, traffic, host.bits, seed, counter, drive, log=log)
    setup_s = time.perf_counter() - t_process
    log(f"setup: {setup_s:.3f} s, {counter.compiles} compiles "
        f"({counter.cache_hits} from the cache)")

    loop = svc.serve_loop(depth=serve.DEPTH, slo=None)
    instr = spans.Instrumented(svc, loop) if trace else None
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
    try:
        win = drive(serve.Drive(svc, loop, traffic, host.bits, seed,
                                seconds, counter=counter, traced=trace))
    finally:
        if trace:
            jax.profiler.stop_trace()
        if instr is not None:
            instr.close()
    log(f"window: {len(win.sent)} requests, {len(win.report.ticks)} ticks, "
        f"{win.compiles} compiles ({win.cache_hits} from the cache)")
    groups = collections.Counter((r.tick, win.sent[r.index].request.shape)
                                 for r in win.report.served)
    sizes = sorted(t.n_queries for t in win.report.ticks)
    log(f"window: largest plan group {max(groups.values(), default=0)} "
        f"(warm-up covered {traffic.warm['max_group']}); queries a tick "
        f"min {sizes[0] if sizes else 0} median "
        f"{sizes[len(sizes) // 2] if sizes else 0} max "
        f"{sizes[-1] if sizes else 0}")
    dev = device.device_record(devices, cell.chips)

    summary = None
    if trace:
        summary = xtrace.reduce(xtrace.load(str(TRACE_DIR)))
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        dev["busy_s"] = summary.busy_s
        dev["window_s"] = summary.window_s

    t0 = time.perf_counter()
    ref = queries.Reference(host.bitmaps, host.columns, host.bits,
                            host.n_rows)
    checks = check.compare(win.sent, ref, log=log)
    log(f"check: {len(win.sent)} answers against the reference, "
        f"{time.perf_counter() - t0:.3f} s")

    run = Run(cell, seconds, setup_s, win, host.bits,
              data.vector_bytes(cell.config), peak, summary,
              instr.plan if instr is not None else None)
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = load_reader(cell, m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    out = {"correct": check.correct(checks), "attempted": len(win.sent),
           "failed": checks["wrong"]["value"], "metrics": metrics,
           "device": dev}
    if summary is not None:
        out["breakdown"] = {"device_ops": summary.device_ops,
                            "idle_gaps": summary.idle_gaps}
    if readings is not None:
        out["readings"] = readings(win, host)
    out["checks"] = checks
    for name, c in checks.items():
        log(f"check {name}: {c['value']} (limit {c['limit']})")
    return out
