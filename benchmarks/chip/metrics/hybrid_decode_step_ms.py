"""Fleet cohort and hybrid model step: in a cell served by the Jamba kind,
the mean device time of one cohort decode dispatch, in ms. The dispatch is
one iteration of every slot through the Mamba, expert, dense and attention
layers. Read as ``decode_step_ms`` reads it."""
from benchmarks.chip.cell import load_reader


def read(rec):
    return load_reader("decode_step_ms")(rec)
