"""Scheduler: mean host time of one ``ClusterServer.step()`` in the window,
in ms."""
import statistics


def read(rec):
    xs = rec["spans"].get("step")
    return statistics.fmean(xs) * 1e3 if xs else None
