"""Whether the timed path's answers are correct: every request of the window
against the plain reference (`harness.queries.Reference`).

One number is compared: ``wrong``, the requests of the window that did not
come back with the reference's answer, whether the value differed, the
handle raised (shed, or a serving failure), or no answer came
`harness.serve.GRACE_S` after the window closed. It is an exact count with
the limit 0; the three kinds are logged apart.
"""
from __future__ import annotations

from typing import Dict, List

LIMIT = 0


def compare(sent, reference, log=None) -> Dict[str, dict]:
    """The compared numbers, each with its value and limit."""
    bad: List[str] = []
    kinds = {"mismatched": 0, "raised": 0, "unanswered": 0}
    for i, s in enumerate(sent):
        if s.t_answer is None:
            kinds["unanswered"] += 1
        elif s.error is not None:
            kinds["raised"] += 1
            bad.append(f"request {i}: {s.error}")
        else:
            want = reference.answer(s.request.query)
            if s.value != want:
                kinds["mismatched"] += 1
                bad.append(f"request {i} {s.request.template} "
                           f"{s.request.tenant} {dict(s.request.params)}: "
                           f"served {s.value}, reference {want}")
    if log is not None:
        for line in bad[:10]:
            log(f"check: {line}")
        log("check: " + ", ".join(f"{k} {v}" for k, v in kinds.items()))
    return {"wrong": {"value": sum(kinds.values()), "limit": LIMIT}}


def correct(checks: Dict[str, dict]) -> bool:
    return all(c["value"] <= c["limit"] for c in checks.values())
