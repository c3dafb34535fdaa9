"""plan_us_per_query: host time inside `Scheduler.plan_queries` (parse,
plan, bind; timed by the benchmark's span around the call) over the
answered queries of the window, in microseconds."""


def read(run):
    if run.plan_timer is None or not run.answered:
        return None
    return run.plan_timer.seconds / len(run.answered) * 1e6
