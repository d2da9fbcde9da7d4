"""The level loop's share of its roofline, in %: the least time (the nest's
own bytes, every store cell read once and written once at 8 B, over the
chip's HBM bandwidth) over the loop's mean device time.  Bound by memory:
no float64 peak is published for the chip, and the nest does a few
operations per 8-byte cell."""


def read(window):
    if window.device is None or not window.device["module_s"]:
        return None
    runs = window.device["module_s"]
    least_s = window.nest_bytes / window.peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / (sum(runs) / len(runs))
