"""Level loop (``CompiledProgram._exec``) per request, on the host's
clock: the ``xla.execute`` span, which blocks on the result."""

from spans import per_request_ms


def read(window):
    return per_request_ms(window, ("xla.execute",))
