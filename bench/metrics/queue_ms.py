"""Serve layer's worker queue (serve/service.py: ``submit`` to the worker
picking the request up) per request: the ``serve.queue`` span."""

from spans import per_request_ms


def read(window):
    return per_request_ms(window, ("serve.queue",))
