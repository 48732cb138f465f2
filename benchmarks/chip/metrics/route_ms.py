"""Router: mean host time of one ``RequestRouter.route`` call the server
made in the window, in ms."""
import statistics


def read(rec):
    xs = rec["spans"].get("route")
    return statistics.fmean(xs) * 1e3 if xs else None
