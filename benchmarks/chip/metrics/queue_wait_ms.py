"""Engine admission: mean wait from a request's ``server.submit`` span to
the start of its first ``engine.admit`` span (program timeline), over the
requests admitted in the window, in ms."""
import statistics


def read(rec):
    prog = rec.get("program")
    if not prog:
        return None
    submit, admit = {}, {}
    for _, name, start, _, _, rid in prog["spans"]:
        if name == "server.submit":
            submit.setdefault(rid, start)
        elif name == "engine.admit":
            admit[rid] = min(start, admit.get(rid, start))
    xs = [admit[r] - submit[r] for r in admit if r in submit]
    return statistics.fmean(xs) * 1e-6 if xs else None
