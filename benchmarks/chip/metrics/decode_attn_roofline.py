"""Decode-attention kernel (``kernels/decode_attention.py``): the least time
the chip needs for the positions the live rows hold (the larger of FLOPs
over peak and bytes over bandwidth), over the kernel's device time in the
traced slice, in %."""

KERNEL = "gqa_decode_attention"


def read(rec):
    red, need = rec["trace"], rec["counts"].get("attn_min_s", 0.0)
    if red is None or not need:
        return None
    t = sum(s for name, s in red["op_time"].items() if KERNEL in name)
    return 100.0 * need / t if t > 0 else None
