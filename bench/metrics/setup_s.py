"""Process start to the first timed request: loading, data, warm-up and,
in a run that compiles, compilation."""


def read(window):
    return window.setup_s
