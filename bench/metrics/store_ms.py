"""Host store (``Executable.run``: dict to dense arrays and back) per
request: the self time of the ``run`` span, ``run`` less the spans
directly under it."""

from spans import per_request_ms


def read(window):
    run = per_request_ms(window, ("run",))
    if run is None:
        return None
    children = {n for n, *_rest, par in window.spans if par == "run"}
    return run - (per_request_ms(window, children, parent="run") or 0.0)
