"""Scheduler: the longest single ``submit()`` or ``step()`` call in the
window, in ms. Every request due during such a call waits for its end, and
every live request's next token with it."""


def read(rec):
    xs = rec["spans"].get("step", []) + rec["spans"].get("submit", [])
    return max(xs) * 1e3 if xs else None
