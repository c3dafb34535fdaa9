"""closed: the traffic's ``clients`` each keep one request open, with no
think time, and send in steps.

The first step sends every client's first request together; once every
request of a step is answered, the clients send their next requests
together, as the next step. A step goes in at once (`Drive.send`), so the
loop serves it as one tick where it fits one: a tick's size is the
traffic's, not the chance of how the submits interleave with the loop's
thread. The window closes after ``seconds``; the step in flight then is
waited for, up to `harness.serve.GRACE_S`.
"""
import time

from harness import serve, spans


def drive(d: serve.Drive) -> serve.Window:
    streams = [d.stream(c) for c in range(d.traffic.clients)]
    win = d.open()
    give_up = win.t_close + serve.GRACE_S
    with d.span(spans.WINDOW):
        while True:
            with d.span(spans.CLIENT):
                step = [serve.Sent(c, next(s)) for c, s in enumerate(streams)]
                d.send(win, step)
            came = [d.collect(s, give_up - time.perf_counter()) for s in step]
            if not all(came) or d.closed(win):
                break
    return d.close(win)
