"""Median latency, submit to reply, of all requests completed in the
window."""

from harness import percentile


def read(window):
    return percentile([r.latency_ms for r in window.requests], 50)
