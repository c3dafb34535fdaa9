"""Host spans the benchmark puts around the calls into each layer.

In a traced run, `instrument` wraps the serving loop's and the scheduler's
calls in `jax.profiler.TraceAnnotation` spans, which land in the profiler's
own trace on its clock, and times the planner on the host clock. The
wrappers sit on the instances of this run only; the program is not
edited. An idle gap of the device is labelled by the innermost of these
spans that covers it (`harness.xtrace`).

    bench.window    the measured window, first submit to last answer
    bench.client    the clients: collect answers, submit the next
    bench.form      ServingLoop._form_tick: pick the tick's queries
    bench.plan      Scheduler.plan_queries: parse, plan, bind
    bench.tick      Scheduler.submit: one tick's groups and bookkeeping
    bench.group     Scheduler._run_group: one plan group, from its operand
                    table (one chip: the numpy table of the group's catalog
                    arena rows, gathered inside the dispatch; several
                    chips: one `jnp.stack` of the sharded copies an
                    operand) through launch, wait and readout
    bench.launch    lowering.execute_lowered: enqueue the group's program
    bench.finalize  ServingLoop._finalize: resolve the tick's handles

`bench.launch` wraps `lowering.execute_lowered` only, which the one-chip
path calls. The sharded path (`Scheduler._run_group_sharded`) launches
through the chip cluster instead, so there its launch lies under
`bench.group`, with no `bench.launch` inside.
"""
from __future__ import annotations

import functools
import time

import jax

WINDOW = "bench.window"
CLIENT = "bench.client"
NAMES = (WINDOW, CLIENT, "bench.form", "bench.plan", "bench.tick",
         "bench.group", "bench.launch", "bench.finalize")


def _wrap(fn, name, timer=None):
    @functools.wraps(fn)
    def wrapped(*args, **kwargs):
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(name):
            try:
                return fn(*args, **kwargs)
            finally:
                if timer is not None:
                    timer.add(time.perf_counter() - t0)
    return wrapped


class Timer:
    """Summed host seconds of the calls one wrapper saw."""

    def __init__(self):
        self.seconds = 0.0

    def add(self, s: float) -> None:
        self.seconds += s


class Instrumented:
    """The wrappers of one traced run; `plan` times the planner."""

    def __init__(self, svc, loop):
        from repro.core import lowering

        self.plan = Timer()
        sched = svc.scheduler
        self._undo = []

        def patch(obj, attr, name, timer=None):
            orig = getattr(obj, attr)
            self._undo.append((obj, attr, orig, attr in vars(obj)))
            setattr(obj, attr, _wrap(orig, name, timer))

        patch(loop, "_form_tick", "bench.form")
        patch(loop, "_finalize", "bench.finalize")
        patch(sched, "plan_queries", "bench.plan", self.plan)
        patch(sched, "submit", "bench.tick")
        patch(sched, "_run_group", "bench.group")
        patch(lowering, "execute_lowered", "bench.launch")

    def close(self) -> None:
        for obj, attr, orig, own in reversed(self._undo):
            if own:
                setattr(obj, attr, orig)
            else:
                delattr(obj, attr)
        self._undo = []
