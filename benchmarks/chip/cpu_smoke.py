"""Small stand-ins for the CPU tests: the served architectures at the
program's ``.smoke()`` widths, and a cell cut so that a CPU finishes
requests inside a window of a few seconds. The chip never runs these."""
from __future__ import annotations

import copy
import time
from typing import Dict

from benchmarks.chip.cell import Cell, load_cell

ARCHS = {
    "stablelm-3b": dict(kind="dense", n_layers=4, d_model=128, n_heads=4,
                        n_kv_heads=4, head_dim=32, d_ff=352, vocab=512,
                        rope_theta=1e4, norm_eps=1e-5, qk_norm=False,
                        dtype="bfloat16"),
    "qwen3-1.7b": dict(kind="dense", n_layers=4, d_model=128, n_heads=4,
                       n_kv_heads=2, head_dim=32, d_ff=256, vocab=512,
                       rope_theta=1e6, norm_eps=1e-5, qk_norm=True,
                       dtype="bfloat16"),
}
PEAK = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}


def cell(name: str) -> Cell:
    """The named cell offered at most 8 requests/s, with long outputs
    shortened to 4-16 tokens and ``max_seq`` and the check's padding cut to
    64 to match. A CPU serves far less than a chip, and past its capacity
    requests wait long enough for the server to hedge them."""
    c = copy.deepcopy(load_cell(name))
    arrival = c.traffic["arrival"]
    c.traffic["arrival"] = dict(arrival, rate=min(arrival["rate"], 8.0))
    out = c.traffic["output"]
    if out["law"] == "lognormal":
        c.traffic["output"] = dict(out, median=8, min=4, max=16)
        c.config["engine"]["max_seq"] = 64
        c.config["check"]["pad_to"] = 64
    return c


def archs(c: Cell) -> Dict[str, Dict]:
    return {a: ARCHS[a] for a in c.config["architectures"]}


def run(name: str, seed: int, seconds: float, trace: bool = False,
        **kwargs) -> Dict:
    from benchmarks.chip import harness
    c = cell(name)
    return harness.run_cell(c, seed, seconds, trace, peak=PEAK,
                            t_start=time.monotonic(), archs=archs(c),
                            **kwargs)
