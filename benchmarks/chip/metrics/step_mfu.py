"""Whole step: model FLOPs of the tokens decoded and prefilled in the traced
slice, over the slice's length times the chip's peak, in %."""


def read(rec):
    red, flops = rec["trace"], rec["counts"].get("model_flops", 0.0)
    if red is None or not flops or red["window_s"] <= 0:
        return None
    return 100.0 * flops / (red["window_s"] * rec["peak"]["bf16_flops_per_s"])
