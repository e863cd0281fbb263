"""Share of the traced window in which a collective (all-gather,
collective-permute, all-reduce, ...) runs on the first chip and no other
leaf operation does, in %."""


def read(run):
    reading = run.trace
    if reading is None or reading.window_s <= 0 or run.chips < 2:
        return None
    first = min(reading.collective_exposed_s)
    return 100.0 * reading.collective_exposed_s[first] / reading.window_s
