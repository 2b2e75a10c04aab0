"""Device: the share of the traced span in which no operation ran on the
chip, 1 - (union of device op intervals) / span, in %."""


def read(rec):
    tr = rec.get("trace")
    if tr is None:
        return None
    red = tr["reduced"]
    if red.busy_s <= 0:
        return None
    return 100.0 * (1.0 - red.busy_s / red.window_s)
