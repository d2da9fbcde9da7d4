"""Host store conversions per request: the service's copy of the caller's
store (``store.copy``), dict to dense (``store.to_dense``) and dense to
dict (``store.to_dicts``)."""

from spans import per_request_ms


def read(window):
    return per_request_ms(window,
                          ("store.copy", "store.to_dense", "store.to_dicts"))
