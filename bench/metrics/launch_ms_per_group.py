"""launch_ms_per_group: host time in the program's `repro.group.launch`
span (the call into the group's executor, to its return: the enqueue,
and any compile), summed per tick in `TickStats.phase_us`, over the
window's plan groups (`TickStats.n_groups`), in ms a group. None where
the program keeps no phase totals."""


def read(run):
    ticks = run.window.report.ticks
    if not ticks or not hasattr(ticks[0], "phase_us"):
        return None
    groups = sum(t.n_groups for t in ticks)
    if not groups:
        return None
    total = sum(t.phase_us.get("repro.group.launch", 0.0) for t in ticks)
    return total / groups / 1e3
