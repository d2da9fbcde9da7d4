"""Level-table building (compile/lowering.py ``prepare``) per request: the
``compile.tables`` span.  Read only where requests miss the table cache."""

from spans import per_request_ms


def read(window):
    return per_request_ms(window, ("compile.tables",))
