"""Share of the traced window, in %, in which the device ran no
operation (averaged over the chips used)."""


def read(window):
    if window.device is None:
        return None
    return 100.0 * (1.0 - window.device["busy_s"] / window.device["window_s"])
