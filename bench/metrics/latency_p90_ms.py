"""90th percentile of the latency, submit to reply, of all requests
completed in the window (cells that complete 100 or more)."""

from harness import percentile


def read(window):
    return percentile([r.latency_ms for r in window.requests], 90)
