"""hbm_share: the bytes the window's ticks had to read, over what the
chips' HBM could move in the device's busy time, in %.

For each tick, the distinct catalog vectors that its queries need
(`harness.queries.vectors_read`, reckoned from the benchmark's own
description of each query, whatever executor runs it) times one vector's
bytes; summed over the ticks of the traced window, and divided by what
the cell's chips could move in that time: (chips x one chip's HBM peak
bytes/s x device busy seconds, the mean over the chips)."""


def read(run):
    if run.trace is None or run.trace.busy_s <= 0:
        return None
    sent = run.window.sent
    by_tick = {}
    for r in run.window.report.served:
        s = sent[r.index]
        if s.value is None or int(r.result.value) != s.value:
            return None         # a record that is not this request's
        by_tick.setdefault(r.tick, set()).update(run.vectors(s))
    n_bytes = sum(len(v) for v in by_tick.values()) * run.vector_bytes
    return 100.0 * n_bytes / (run.cell.chips * run.peak["hbm_bytes_per_s"]
                              * run.trace.busy_s)
