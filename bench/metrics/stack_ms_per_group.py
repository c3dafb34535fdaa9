"""stack_ms_per_group: host time in the program's `repro.group.stack`
span, summed per tick in `TickStats.phase_us`, over the window's plan
groups (`TickStats.n_groups`), in ms a group. None where the program keeps
no phase totals.

The span times how a group's operands are named for its dispatch. On one
chip that is the numpy slot table of the group's catalog arena rows; the
rows themselves are gathered on the device inside the dispatch, so no
device operation runs in the span. On several chips it is one
`jnp.stack` of the chip-sharded copies an operand."""


def read(run):
    ticks = run.window.report.ticks
    if not ticks or not hasattr(ticks[0], "phase_us"):
        return None
    groups = sum(t.n_groups for t in ticks)
    if not groups:
        return None
    total = sum(t.phase_us.get("repro.group.stack", 0.0) for t in ticks)
    return total / groups / 1e3
