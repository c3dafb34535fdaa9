"""account_ms_per_tick: host time in the program's `repro.tick.account`
span (after a tick's groups: the modeled placement, one `QueryResult` a
query, the totals), summed per tick in `TickStats.phase_us`, over the
window's ticks, in ms a tick. None where the program keeps no phase
totals."""


def read(run):
    ticks = run.window.report.ticks
    if not ticks or not hasattr(ticks[0], "phase_us"):
        return None
    total = sum(t.phase_us.get("repro.tick.account", 0.0) for t in ticks)
    return total / len(ticks) / 1e3
