"""Whole step: in a cell served by the Jamba kind, the model FLOPs decoded
and prefilled in the traced slice over the slice's length times the chip's
peak, in %. The kind counts the experts' FLOPs as their expectation under
uniform routing (``archs/jamba.py``). Read as ``step_mfu`` reads it."""
from benchmarks.chip.cell import load_reader


def read(rec):
    return load_reader("step_mfu")(rec)
