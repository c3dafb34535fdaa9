"""From the profiler's trace to busy time, idle share, top ops and gaps.

`load` reads the ``.xplane.pb`` that `jax.profiler` writes into a plain
dict of events (JSON-able, so a small recorded trace can be kept as a test
fixture); `reduce` works on that dict alone:

    {"devices": {plane name: {"modules": [[name, start_ns, dur_ns], ...],
                              "ops": [[name, start_ns, dur_ns], ...]}},
     "host": [[name, start_ns, dur_ns], ...]}

A device is busy while one of its programs runs: the union of the
intervals on its "XLA Modules" line (one event a program execution),
clipped to the window. The window is the benchmark's ``bench.window``
span. Idle share is 1 - busy / window. Each idle gap is labelled by the
innermost benchmark span (`harness.spans`) that covers its middle.
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import os
import re
from typing import List, Tuple

from harness import spans

MODULES_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"
DEVICE_PREFIX = "/device:TPU:"
TOP = 10


def load(log_dir: str) -> dict:
    """The events of the one ``.xplane.pb`` under ``log_dir``."""
    from jax.profiler import ProfileData

    files = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise FileNotFoundError(f"want one .xplane.pb under {log_dir}, "
                                f"found {files}")
    data = ProfileData.from_file(files[0])
    out = {"devices": {}, "host": []}
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            dev = out["devices"].setdefault(plane.name,
                                            {"modules": [], "ops": []})
            for line in plane.lines:
                key = {MODULES_LINE: "modules", OPS_LINE: "ops"}.get(line.name)
                if key is not None:
                    dev[key].extend([e.name, e.start_ns, e.duration_ns]
                                    for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                out["host"].extend([e.name, e.start_ns, e.duration_ns]
                                   for e in line.events
                                   if e.name in spans.NAMES)
    return out


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    merged: List[List[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def _clip(intervals, lo: float, hi: float):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


_OP_RE = re.compile(r"^%?([A-Za-z_][\w\-]*?)(?:\.\d+)?(?:\s*=|$)")


def op_label(name: str) -> str:
    """A stable name for an op event: ``%_vm_call.1 = s32[...] ...`` is
    ``_vm_call``; ``jit__dispatch(1234)`` is ``jit__dispatch``."""
    name = name.split("(", 1)[0] if "=" not in name else name
    m = _OP_RE.match(name.strip())
    return m.group(1) if m else name.strip()[:64]


@dataclasses.dataclass
class Summary:
    busy_s: float                 # mean over the devices
    window_s: float
    idle_share: float             # 1 - busy / window, in [0, 1]
    device_ops: List[list]        # [[op, seconds]], most time first
    idle_gaps: List[list]         # [[label, seconds]], most time first


def _window(events: dict) -> Tuple[float, float]:
    wins = [(s, s + d) for n, s, d in events["host"] if n == spans.WINDOW]
    if wins:
        return min(s for s, _ in wins), max(e for _, e in wins)
    starts = [s for dev in events["devices"].values()
              for _, s, _ in dev["modules"]]
    ends = [s + d for dev in events["devices"].values()
            for _, s, d in dev["modules"]]
    if not starts:
        raise ValueError("the trace has no window span and no device event")
    return min(starts), max(ends)


def _labels(mids: List[float], host: List[list]) -> List[str]:
    """For each time in ``mids`` (ascending), the innermost benchmark
    span covering it; the serving thread's spans outrank the clients'."""
    evs = sorted((s, s + d, name) for name, s, d in host
                 if name != spans.WINDOW)
    out, active, i = [], [], 0
    for mid in mids:
        while i < len(evs) and evs[i][0] <= mid:
            active.append(evs[i])
            i += 1
        active = [e for e in active if e[1] >= mid]
        cands = [(e[2] == spans.CLIENT, e[1] - e[0], e[2]) for e in active]
        out.append(min(cands)[2] if cands else "outside spans")
    return out


def reduce(events: dict) -> Summary:
    lo, hi = _window(events)
    window_ns = hi - lo
    if window_ns <= 0:
        raise ValueError("empty trace window")
    busy_ns = []
    gaps = collections.Counter()
    ops = collections.Counter()
    for i, (_, dev) in enumerate(sorted(events["devices"].items())):
        busy = _union(_clip([(s, s + d) for _, s, d in dev["modules"]],
                            lo, hi))
        busy_ns.append(sum(e - s for s, e in busy))
        for name, s, d in dev["ops"]:
            if s + d > lo and s < hi:
                ops[op_label(name)] += (min(s + d, hi) - max(s, lo))
        if i == 0:
            edges = [lo] + [x for iv in busy for x in iv] + [hi]
            idle = [(gs, ge) for gs, ge in zip(edges[::2], edges[1::2])
                    if ge > gs]
            labels = _labels([(gs + ge) / 2 for gs, ge in idle],
                             events["host"])
            for (gs, ge), name in zip(idle, labels):
                gaps[name] += ge - gs
    if not busy_ns:
        raise ValueError("the trace has no TPU device plane")
    busy_s = sum(busy_ns) / len(busy_ns) / 1e9
    window_s = window_ns / 1e9
    return Summary(
        busy_s=busy_s, window_s=window_s,
        idle_share=1.0 - busy_s / window_s,
        device_ops=[[n, v / len(busy_ns) / 1e9]
                    for n, v in ops.most_common(TOP)],
        idle_gaps=[[n, v / 1e9] for n, v in gaps.most_common(TOP)])
