"""Fleet cohort and model step: mean device time of one cohort decode
dispatch (the executable of ``_cohort_decode_chunk``) in the traced slice,
in ms."""
import statistics

JIT = "_cohort_decode_chunk"


def read(rec):
    red = rec["trace"]
    if red is None:
        return None
    xs = [d for name, ds in red["modules"].items() if JIT in name
          for d in ds]
    return statistics.fmean(xs) * 1e3 if xs else None
