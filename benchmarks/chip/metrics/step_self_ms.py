"""Scheduler: mean self time of ``server.step`` (program timeline): the
step's length minus what its child spans cover, in ms."""
import statistics

from repro.obs.timeline import self_times


def read(rec):
    prog = rec.get("program")
    if not prog:
        return None
    own = self_times(prog["spans"])
    xs = [own[r[0]] for r in prog["spans"] if r[1] == "server.step"]
    return statistics.fmean(xs) * 1e-6 if xs else None
