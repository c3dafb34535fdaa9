"""open: requests arrive on their own schedule, whatever is still open.

The traffic file gives ``rate``, requests a second. The gaps between
arrivals are exponential (a Poisson process) and are the same set for
every seed, drawn once from a fixed generator and put in an order drawn
from the seed; the requests are client 0's stream. Each request is sent
at its due time, and its latency runs from that due time, so a sender
that falls behind adds to the latency it measures. Answers are looked
for every `harness.serve.POLL_S` while the sender waits. The window closes
after ``seconds``; what is open then is waited for, up to
`harness.serve.GRACE_S`.
"""
import math
import time

import numpy as np

from harness import serve, spans


def arrivals(rate: float, seconds: float, seed: int) -> np.ndarray:
    """Offsets of the arrivals from the window's start, in s."""
    n = int(math.ceil(rate * seconds * 1.5)) + 16
    gaps = np.random.default_rng(0).exponential(1.0 / rate, n)
    gaps = np.random.default_rng([seed, 1 << 30]).permutation(gaps)
    return np.cumsum(gaps)


def _sweep(d: serve.Drive, open_: list) -> None:
    for s in [s for s in open_ if s.handle.done()]:
        d.collect(s, 0.0)
        open_.remove(s)


def drive(d: serve.Drive) -> serve.Window:
    stream = d.stream(0)
    win = d.open()
    due = win.t_start + arrivals(float(d.traffic.spec["rate"]), d.seconds,
                                 d.seed)
    open_ = []
    with d.span(spans.WINDOW):
        for t_due in due:
            if t_due >= win.t_close or d.closed(win):
                break
            while (now := time.perf_counter()) < t_due:
                _sweep(d, open_)
                time.sleep(min(serve.POLL_S, t_due - now))
            with d.span(spans.CLIENT):
                s = serve.Sent(0, next(stream), t_due=float(t_due))
                d.send(win, [s])
                open_.append(s)
        give_up = win.t_close + serve.GRACE_S
        for s in open_:
            d.collect(s, give_up - time.perf_counter())
    return d.close(win)
