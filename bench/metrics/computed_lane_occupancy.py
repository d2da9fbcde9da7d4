"""Share of the lanes the level loop really computes that do work, in %:
counter ``xla.lanes_live`` (lanes with the mask set) over
``xla.lanes_computed`` (each table row at the lane cap it runs at), summed
over the loop's runs in the process.  None where the program keeps no
``xla.lanes_computed``."""

from repro.obs import metrics


def read(window):
    computed = metrics.counter("xla.lanes_computed").value
    if not computed:
        return None
    return 100.0 * metrics.counter("xla.lanes_live").value / computed
