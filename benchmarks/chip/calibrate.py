#!/usr/bin/env python3
"""Reads the two numbers each check limit is set between, on the chip: the
program's widest logit gap, and the fp8 control's, over many seeds in one
process. The benchmark's own runs never run it.

    python3 benchmarks/chip/calibrate.py --workload testbed.paper_mix \
        --seeds 101,102,103 --seconds 10

For each seed: weights from the seed, warm-up, an open-loop window of
``--seconds`` at the cell's own load, then, on the same sample of finished
requests, the gap of what the program served and the gap of what the fp8
control (the kind's forward in ``reference.py``'s fp8 mode) puts first. One
JSON line per seed.
"""
from __future__ import annotations

import argparse
import gc
import json
import math
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(REPO), str(REPO / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)

    import jax
    if jax.devices()[0].platform != "tpu":
        print("no TPU", file=sys.stderr)
        return 2
    from benchmarks.chip import checks, deploy, harness, openloop
    from benchmarks.chip.cell import load_cell
    from benchmarks.chip.traffic import generator
    harness.configure_cache()
    cell = load_cell(args.workload)
    spec = cell.config["check"]
    for seed in (int(s) for s in args.seeds.split(",")):
        items = generator.schedule(cell.traffic, args.seconds, seed)
        dep = deploy.build(cell.config, seed)
        deploy.warm_up(dep, [it.prompt for it in
                             items[:harness.WARM_PROMPTS]])
        w = openloop.drive(dep, items, args.seconds)
        finished = [i for i in range(len(items))
                    if not math.isnan(w.finish[i])]
        rows = checks.sample(dep, finished, seed, spec["sample_requests"])
        dep.srv = None
        gc.collect()
        limits = spec["logit_gap_max"]
        prog = checks.check(dep, rows, spec, limits)
        ctrl = checks.check(dep, rows, spec, limits, control=True)
        print(json.dumps({"seed": seed, "window": openloop.describe(w),
                          "program": prog, "control": ctrl}), flush=True)
        del dep, rows
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
