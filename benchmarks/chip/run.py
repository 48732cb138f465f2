#!/usr/bin/env python3
"""Runs one cell of the benchmark once, on the chips of this machine.

    python3 benchmarks/chip/run.py --workload testbed.paper_mix --seed 7 \
        --seconds 30 --trace 0

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer metrics), ``device``, with ``--trace 1``
``breakdown``, and last ``checks``, each number compared with its limit.
The command exits non-zero, with no result, where JAX finds no TPU or fewer
chips than the cell asks for.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

REPO = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(REPO), str(REPO / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from benchmarks.chip.cell import load_cell, load_peak
    cell = load_cell(args.workload)
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"no TPU: JAX found {len(devs)} {devs[0].platform} device(s)",
              file=sys.stderr)
        return 2
    if len(devs) < cell.chips:
        print(f"{args.workload} needs {cell.chips} chips, found {len(devs)}",
              file=sys.stderr)
        return 2
    peak = load_peak(devs[0].device_kind)
    from benchmarks.chip import harness
    harness.configure_cache()
    result = harness.run_cell(cell, args.seed, args.seconds,
                              bool(args.trace), peak=peak, t_start=T_START)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
