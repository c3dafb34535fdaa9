"""p95_ms: the 95th percentile (nearest rank) of the latency of every
answered request of the window, from `submit()` (or from the due time,
where the traffic's loop sets one) to `result()` returning (host clock),
in ms."""


def read(run):
    return run.latency_percentile_ms(95)
