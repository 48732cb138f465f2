"""Device: share of the traced slice in which no operation ran on the chip,
in %."""


def read(rec):
    red = rec["trace"]
    if red is None or red["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - red["busy_s"] / red["window_s"])
