#!/usr/bin/env python3
"""Finds a cell's knee: the highest offered Poisson rate the deployment
sustains. Run once when a cell is defined, on the chip; the benchmark's own
runs never run it.

    python3 benchmarks/chip/sweep.py --workload testbed.paper_mix --seed 3 \
        --start 10 --steps 8 --seconds 20

One set-up serves the whole sweep. Each step offers the cell's mix at a
rate 1.25x the last for ``--seconds``, then drains. The backlog is the
number of requests submitted that have no first token; a rate is sustained
when the backlog at the end of its step is no larger than at its midpoint.
The knee is the last rate sustained before the first that is not: a later
step that ties its two backlogs while the queue is already long is not read
as sustained. Each step prints one JSON line; the last line names the knee.
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

DRAIN_TICKS = 20000


def backlog(srv, rid0: int, n: int) -> int:
    """Requests of this step that were submitted and hold neither a slot
    nor a result."""
    held = {s.request_id for e in srv.engines.values() for s in e.slots}
    held.update(rid for rid in srv.done if rid0 <= rid < rid0 + n)
    return sum(1 for rid in srv.inflight
               if rid0 <= rid < rid0 + n and rid not in held)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--start", type=float, required=True)
    ap.add_argument("--steps", type=int, default=8)
    ap.add_argument("--factor", type=float, default=1.25)
    ap.add_argument("--seconds", type=float, default=20.0)
    args = ap.parse_args(argv)

    from benchmarks.chip.cell import load_cell
    import jax
    if jax.devices()[0].platform != "tpu":
        print("no TPU", file=sys.stderr)
        return 2
    from benchmarks.chip import deploy, harness, openloop
    from benchmarks.chip.traffic import generator
    harness.configure_cache()
    cell = load_cell(args.workload)
    dep = deploy.build(cell.config, args.seed)
    rate = args.start
    first = generator.schedule(cell.traffic, args.seconds, args.seed, rate)
    deploy.warm_up(dep, [it.prompt for it in first[:harness.WARM_PROMPTS]])
    print(f"setup_s {time.monotonic() - T_START}", flush=True)
    knee, broken, rid0 = None, False, 0
    for _ in range(args.steps):
        items = generator.schedule(cell.traffic, args.seconds, args.seed,
                                   rate)
        mid = {}
        w = openloop.drive(
            dep, items, args.seconds, rid0=rid0,
            marks={args.seconds / 2: lambda: mid.setdefault(
                "b", backlog(dep.srv, rid0, len(items)))})
        end = backlog(dep.srv, rid0, len(items))
        row = {"rate": rate, "requests": len(items),
               "backlog_mid": mid.get("b"), "backlog_end": end,
               **openloop.describe(w), **openloop.end_to_end(w)}
        t0 = time.monotonic()
        for _ in range(DRAIN_TICKS):
            if not dep.srv.inflight:
                break
            dep.srv.step()
        row["drain_s"] = time.monotonic() - t0
        row["sustained"] = mid.get("b") is not None and end <= mid["b"]
        print(json.dumps(row), flush=True)
        broken = broken or not row["sustained"]
        if not broken:
            knee = rate
        rid0 += len(items)
        rate *= args.factor
    print(json.dumps({"workload": args.workload, "knee": knee}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
