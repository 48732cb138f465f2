"""Engine admission: mean length of the ``admit.first_token`` span, the
device sync that yields an admitted request's first token (program
timeline), in ms."""
import statistics


def read(rec):
    prog = rec.get("program")
    if not prog:
        return None
    xs = [end - start for _, name, start, end, _, _ in prog["spans"]
          if name == "admit.first_token"]
    return statistics.fmean(xs) * 1e-6 if xs else None
