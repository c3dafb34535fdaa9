"""Helpers for the benchmark's CPU tests: a cell cut to a tiny size, and a
run of it with the chip check steered off inside the test."""
import pathlib
import sys
import time

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
for _p in (str(BENCH), str(ROOT / "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from harness import device, runner, spans, xtrace  # noqa: E402

TINY_BITS = 4096


def tiny(cell, domain_bits=TINY_BITS, clients=4, max_group=3, choices=2):
    """``cell`` at ``domain_bits`` rows, with few clients, small warm-up
    groups and at most ``choices`` choices a parameter set."""
    cell.config["domain_bits"] = domain_bits
    cell.traffic["clients"] = clients
    cell.traffic["warm"] = {"max_group": max_group, "replay_ticks": 2,
                            "replay_passes": 2}
    for t in cell.traffic["templates"]:
        for name, options in t["params"].items():
            t["params"][name] = options[:choices]
    return cell


def load(name, root=ROOT):
    return runner.load_cell(pathlib.Path(root), name)


def cpu_trace(log_dir):
    """The traced run's events on the CPU, which has no TPU plane: the
    benchmark's host spans as read from the profile, and each
    ``bench.launch`` span standing in for a device program run."""
    events = _real_load(log_dir)
    launches = [["launch", s, d] for n, s, d in events["host"]
                if n == "bench.launch"]
    events["devices"] = {"/device:TPU:0": {"modules": launches,
                                           "ops": launches}}
    return events


_real_load = xtrace.load


def run(monkeypatch, tmp_path, cell, seed=12345678901, seconds=1.0,
        trace=False, readings=None):
    """One run of ``cell`` on the CPU, chip check and compile cache off."""
    monkeypatch.setattr(device, "check_devices",
                        lambda devices, chips, peaks: peaks["TPU v5 lite"])
    monkeypatch.setattr(device, "enable_compile_cache", lambda: "off")
    monkeypatch.setattr(runner, "TRACE_DIR", tmp_path / "trace")
    monkeypatch.setattr(xtrace, "load", cpu_trace)
    assert spans.WINDOW in spans.NAMES
    return runner.execute(cell, seed, seconds, trace, time.perf_counter(),
                          readings=readings)
