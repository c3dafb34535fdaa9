"""qps: answered queries over the whole window, from the first submit to
the last answer of a request sent before the close (host clock)."""


def read(run):
    answered = run.answered
    if not answered:
        return None
    span = max(s.t_answer for s in answered) - run.window.t_start
    return len(answered) / span
