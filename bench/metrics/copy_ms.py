"""Host-device copies (``CompiledProgram.execute``) per request: the
``xla.to_device`` and ``xla.to_host`` spans."""

from spans import per_request_ms


def read(window):
    return per_request_ms(window, ("xla.to_device", "xla.to_host"))
