"""Share of the traced window in which no operation runs on the first chip:
1 - (union of its operation intervals) / window, in %."""


def read(run):
    reading = run.trace
    if reading is None or reading.window_s <= 0:
        return None
    first = min(reading.busy_s)
    return 100.0 * (1.0 - reading.busy_s[first] / reading.window_s)
