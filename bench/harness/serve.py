"""Driving the program's live serving loop: the window and the warm-up.

Every request goes through the program's own path:
`QueryService.serve_loop(...)`, `start()`, then `submit()` and
`QueryHandle.result()` for each request, then `stop()`.

How requests arrive is the traffic's ``loop``: a driver of its own,
``bench/loops/<loop>.py``, found by name, with a function
``drive(d: Drive) -> Window``. This module holds what every driver shares:
the records (`Sent`, `Window`), sending a set of requests in one step
(`Drive.send`), waiting for answers (`Drive.collect`), and the window's
start and close (`Drive.open`, `Drive.close`).
"""
from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import pathlib
import time
from typing import Callable, List, Optional

from harness import queries
from harness.traffic import Request, Traffic

#: a tick holds 8 banks x depth 4 queries (`ServingLoop` capacity)
DEPTH = 4
#: how long past the window's close an answer is still waited for
GRACE_S = 60.0
#: how often a driver that waits on several requests looks at them, s
POLL_S = 0.001


@dataclasses.dataclass
class Sent:
    """One request as the client saw it."""

    client: int
    request: Request
    t_due: Optional[float] = None   # when an open loop meant to send it
    t_submit: Optional[float] = None
    handle: object = None           # the open `QueryHandle`
    t_answer: Optional[float] = None
    value: Optional[int] = None
    error: Optional[str] = None

    @property
    def t_from(self) -> float:
        """Where its latency starts: the due time where the driver set
        one, else `submit()`."""
        return self.t_due if self.t_due is not None else self.t_submit


@dataclasses.dataclass
class Window:
    sent: List[Sent]              # in submit order: Sent i is record index i
    t_start: float
    t_close: float
    report: object = None         # the loop's `ServeReport`
    compiles: int = 0             # backend compiles inside the window
    cache_hits: int = 0


def to_query(req: Request, bits):
    from repro.service import Query

    text, mode = queries.render(req.query, bits)
    return Query(text, mode, req.tenant)


@dataclasses.dataclass
class Drive:
    """What a loop driver is given: the service and its loop, the traffic,
    the seed its streams are drawn from, and the window's length."""

    svc: object
    loop: object                  # the `ServingLoop`, not yet started
    traffic: Traffic
    bits: dict
    seed: int
    seconds: float
    counter: object = None        # `harness.device.CompileCounter`
    traced: bool = False
    stop: Optional[Callable[[], bool]] = None   # ends the window early
    stream_base: int = 0          # client c draws stream stream_base + c

    def stream(self, client: int):
        return self.traffic.client_stream(self.seed, self.stream_base + client)

    def span(self, name: str):
        import jax

        return (jax.profiler.TraceAnnotation(name) if self.traced
                else contextlib.nullcontext())

    def open(self) -> Window:
        """Start the loop and the window's clock."""
        self.loop.start()
        self._c0 = self.counter.snapshot() if self.counter else (0, 0)
        t = time.perf_counter()
        return Window([], t, t + self.seconds)

    def closed(self, win: Window) -> bool:
        return (time.perf_counter() >= win.t_close
                or (self.stop is not None and self.stop()))

    def send(self, win: Window, batch: List[Sent]) -> None:
        """Submit ``batch`` in one step: the loop's condition is held
        while the requests go in, so the loop takes all of them or none
        when it next forms a tick."""
        texts = [to_query(s.request, self.bits) for s in batch]
        lock = getattr(self.loop, "_cv", None)
        with lock if lock is not None else contextlib.nullcontext():
            for s, q in zip(batch, texts):
                s.t_submit = time.perf_counter()
                s.handle = self.svc.submit(q)
                win.sent.append(s)

    def collect(self, s: Sent, timeout: float) -> bool:
        """Wait up to ``timeout`` s for ``s``'s answer and record it;
        False if none came."""
        try:
            s.value = int(s.handle.result(timeout=max(0.0, timeout)).value)
        except Exception as e:  # noqa: BLE001 - a failed answer
            if not s.handle.done():
                return False
            s.error = f"{type(e).__name__}: {e}"
        s.t_answer = time.perf_counter()
        s.handle = None
        return True

    def close(self, win: Window) -> Window:
        """Stop the loop (shedding what never came back) and fill in the
        window's report and compile counts."""
        c1 = self.counter.snapshot() if self.counter else (0, 0)
        pending = any(s.t_answer is None for s in win.sent)
        win.report = self.loop.stop(drain=not pending)
        win.compiles = c1[0] - self._c0[0]
        win.cache_hits = c1[1] - self._c0[1]
        return win


def load_driver(bench_dir: pathlib.Path, loop: str):
    """``drive`` of the loop's own file, ``<bench>/loops/<loop>.py``."""
    path = bench_dir / "loops" / f"{loop}.py"
    spec = importlib.util.spec_from_file_location(f"bench_loop_{loop}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.drive


def dispatch_direct(svc, batch) -> None:
    """One tick's dispatch exactly as the serving loop makes it
    (`ServingLoop._execute`): plan, then one preplanned scheduler batch
    with the cross-query sharing pass off."""
    sched = svc.scheduler
    sched.submit(batch, preplanned=sched.plan_queries(batch), allow_cse=False)


def warm_groups(svc, traffic: Traffic, bits) -> int:
    """Every plan group the window can form: each request shape at every
    group size up to the traffic's ``max_group`` (requests of one shape
    differ only in tenant, so they share one plan). Returns dispatches."""
    n = 0
    for shape in traffic.shapes():
        for k in range(1, int(traffic.warm["max_group"]) + 1):
            batch = [to_query(traffic.with_tenant(shape, i), bits)
                     for i in range(k)]
            dispatch_direct(svc, batch)
            n += 1
    return n


def warm_up(svc, traffic: Traffic, bits, seed: int, counter, drive,
            log=print) -> None:
    """Warm every program the window will run, then the live loop.

    First every plan group the traffic can form (`warm_groups`); then the
    cell's own loop driver ``drive`` on the live serving loop, on streams
    of another seed, for ``replay_ticks`` ticks, until a pass adds no
    compile or ``replay_passes`` passes are spent.
    """
    c0 = counter.snapshot()
    t0 = time.perf_counter()
    n = warm_groups(svc, traffic, bits)
    c1 = counter.snapshot()
    log(f"warm: {n} group dispatches, {c1[0] - c0[0]} compiles "
        f"({c1[1] - c0[1]} from the cache), {time.perf_counter() - t0:.3f} s")
    ticks = []
    loop = svc.serve_loop(depth=DEPTH, slo=None, on_tick=ticks.append)
    w = traffic.warm
    for p in range(int(w["replay_passes"])):
        ticks.clear()
        win = drive(Drive(
            svc, loop, traffic, bits, seed, seconds=3600.0, counter=counter,
            stop=lambda: len(ticks) >= int(w["replay_ticks"]),
            stream_base=(1 << 20) + p * 4096))
        log(f"warm: live pass {p}: {len(win.sent)} requests, "
            f"{len(win.report.ticks)} ticks, {win.compiles} compiles")
        if win.compiles == 0:
            break
