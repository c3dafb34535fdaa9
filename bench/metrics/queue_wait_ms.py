"""queue_wait_ms: mean time a request of the window waited in the serving
loop's queue, `ServeRecord.dispatch_ns - arrival_ns` (both on the loop's
wall clock in live mode), in ms."""


def read(run):
    served = [r for r in run.window.report.served]
    if not served:
        return None
    return sum(r.dispatch_ns - r.arrival_ns for r in served) / len(served) / 1e6
