"""Share of the lanes the level loop computes that do work, in %: counter
``xla.lanes_live`` (lanes with the mask set) over ``xla.lanes_padded``
(each statement's table rows times its padded width), summed over the
loop's runs in the process.  None where the program keeps no such
counters."""

from repro.obs import metrics


def read(window):
    padded = metrics.counter("xla.lanes_padded").value
    if not padded:
        return None
    return 100.0 * metrics.counter("xla.lanes_live").value / padded
