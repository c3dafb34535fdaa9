"""tick_plan_us_per_query: host time the serving loop spent planning its
ticks, as the program times it itself (its `repro.tick.plan` span, summed
per tick in `TickStats.phase_us`), over the answered queries of the
window, in microseconds. None where the program keeps no phase totals."""


def read(run):
    ticks = run.window.report.ticks
    if not ticks or not run.answered or not hasattr(ticks[0], "phase_us"):
        return None
    total = sum(t.phase_us.get("repro.tick.plan", 0.0) for t in ticks)
    return total / len(run.answered)
