"""Requests completed in the window, over the window's length."""


def read(window):
    return len(window.requests) / window.seconds
