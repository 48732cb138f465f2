"""Engine admission: mean host time of one admission (batch-1 prefill,
splice into the slot, first-token sync) in the window, in ms."""
import statistics


def read(rec):
    xs = rec["spans"].get("admit")
    return statistics.fmean(xs) * 1e3 if xs else None
