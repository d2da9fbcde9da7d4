"""Serve layer (serve/service.py: admission, plan LRU, worker queue, the
store copy) per request: client latency less the request's ``run``,
``plan`` and ``compile`` spans."""

from spans import mean_latency_ms, per_request_ms


def read(window):
    inner = per_request_ms(window, ("run", "plan", "compile"), None)
    if inner is None:
        return None
    return mean_latency_ms(window) - inner
