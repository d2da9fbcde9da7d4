"""Level loop on the device: the mean device duration of the level loop's
module events in the traced window."""


def read(window):
    if window.device is None or not window.device["module_s"]:
        return None
    runs = window.device["module_s"]
    return sum(runs) / len(runs) * 1e3
