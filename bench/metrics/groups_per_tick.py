"""groups_per_tick: mean `TickStats.n_groups` over the window's ticks:
stacked plan-group dispatches, each with its own host sync, a tick."""


def read(run):
    ticks = run.window.report.ticks
    if not ticks:
        return None
    return sum(t.n_groups for t in ticks) / len(ticks)
