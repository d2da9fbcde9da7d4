"""Device time of one level of the level loop, in us: the loop's mean
device time (as ``level_loop_device_ms`` reads it) over the mean level
count of the loop's runs in the process (counter ``xla.levels`` over the
runs, each of which counts one ``xla.bucket_hits`` or
``xla.bucket_misses``).  None where the program keeps no ``xla.levels``."""

from repro.obs import metrics


def read(window):
    if window.device is None or not window.device["module_s"]:
        return None
    levels = metrics.counter("xla.levels").value
    runs = (metrics.counter("xla.bucket_hits").value
            + metrics.counter("xla.bucket_misses").value)
    if not levels or not runs:
        return None
    module_s = window.device["module_s"]
    return sum(module_s) / len(module_s) * 1e6 / (levels / runs)
