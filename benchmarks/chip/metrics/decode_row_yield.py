"""Fleet cohort: tokens emitted over the rows the cohort decode dispatches
ran (participant bucket x slots x iterations; program counters
``cohort.tokens_emitted`` / ``cohort.rows_decoded``), in %."""


def read(rec):
    prog = rec.get("program")
    if not prog:
        return None
    c = prog["counters"]
    rows = c.get("cohort.rows_decoded", 0)
    return 100.0 * c.get("cohort.tokens_emitted", 0) / rows if rows else None
