"""Colorings completed in the measured window over the window's whole time (host clock)."""


def read(run):
    if run.window_s is None or run.window_s <= 0:
        return None
    return run.colorings / run.window_s
