"""Span arithmetic shared by the per-layer readers: time per request
completed in the window, from the program's host spans."""


def per_request_ms(window, names, parent=None):
    """Summed duration of the spans named ``names`` (only those directly
    under ``parent``, when given) per request the spans cover, in ms; None
    when there is no such span in the window."""

    total, seen = 0.0, False
    for name, a, b, _depth, par in window.spans:
        if name in names and (parent is None or par == parent):
            total += b - a
            seen = True
    return total * 1e3 / window.span_requests if seen else None


def mean_latency_ms(window):
    return sum(r.latency_ms for r in window.requests) / len(window.requests)
