"""Planner (core/parallelizer.py ``plan``) per request: the ``plan``
span.  Read only where requests miss the plan cache."""

from spans import per_request_ms


def read(window):
    return per_request_ms(window, ("plan",))
