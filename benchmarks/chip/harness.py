"""One run of one cell: set-up, the measured window, the check, the result.

``run_cell`` is the Python entry; ``run.py`` is the command around it. In
order: the weights from the seed, the router's genome, the server, a
warm-up of every shape the window uses (all of it ``setup_s``), then the
open loop for ``seconds``. With ``trace`` the profiler runs over a slice of
the window and the per-layer readers (``metrics/<name>.py``) report;
without, the end-to-end metrics do. After the window the device's peak
memory is read, the server is dropped, and the reference checks a sample of
what the window served.
"""
from __future__ import annotations

import gc
import json
import math
import shutil
import sys
import tempfile
import time
import traceback
from typing import Dict, Optional

import jax

from benchmarks.chip import checks, deploy, openloop, trace_reduce
from benchmarks.chip.cell import REPO, Cell, load_reader
from benchmarks.chip.traffic import generator

TRACE_S = 3.0          # the traced slice: the window's last seconds
WARM_PROMPTS = 8       # requests routed through submit during warm-up


def configure_cache() -> None:
    """JAX's persistent compilation cache at a fixed path in the checkout,
    every program kept, so only a checkout's first run compiles."""
    jax.config.update("jax_compilation_cache_dir", str(REPO / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def _finite(x) -> Optional[float]:
    return None if x is None or not math.isfinite(x) else float(x)


def _read(metric: str, rec: Dict) -> Optional[float]:
    """A reader that raises reports nothing; the run goes on."""
    try:
        return _finite(load_reader(metric)(rec))
    except Exception:
        traceback.print_exc()
        return None


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             peak: Dict, t_start: float, archs: Optional[Dict] = None,
             limits: Optional[Dict] = None) -> Dict:
    """Returns the result line's object. ``archs`` and ``limits`` replace
    the configuration's sizes and check limits (tests pass small ones)."""
    spec = cell.config["check"]
    limits = limits or spec["logit_gap_max"]
    items = generator.schedule(cell.traffic, seconds, seed)
    dep = deploy.build(cell.config, seed, archs)
    deploy.warm_up(dep, [it.prompt for it in items[:WARM_PROMPTS]])
    probe = openloop.Probe(dep, peak) if trace else None
    tdir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    # the slice closes with the window: stopping the profiler writes the
    # trace, which takes seconds, and that must not stall the open loop
    tcfg = ({"start": max(0.0, seconds - TRACE_S), "length": TRACE_S,
             "dir": tdir} if trace else None)
    setup_s = time.monotonic() - t_start
    w = openloop.drive(dep, items, seconds, probe=probe, trace=tcfg)
    dev = jax.devices()[0]
    mem = (dev.memory_stats() or {}).get("peak_bytes_in_use", 0)
    info = openloop.describe(w)
    e2e = openloop.end_to_end(w)
    e2e["setup_s"] = setup_s
    print("window:", json.dumps(info), flush=True)
    print("end_to_end:", json.dumps(e2e), flush=True)

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": int(mem)}
    metrics: Dict[str, Dict] = {}
    breakdown = None
    if trace:
        path = trace_reduce.find_xplane(tdir)
        red = (trace_reduce.reduce(trace_reduce.load_xplane(path))
               if path else None)
        shutil.rmtree(tdir, ignore_errors=True)
        rec = {"spans": dict(probe.spans), "trace": red,
               "counts": dict(probe.counts),
               "peak": peak}
        if red is not None:
            device["busy_s"] = red["busy_s"]
            device["window_s"] = red["window_s"]
            breakdown = trace_reduce.breakdown(red)
            print("trace:", json.dumps({k: red[k] for k in
                                        ("window_s", "busy_s",
                                         "idle_by_span")}), flush=True)
        for m in cell.per_layer:
            v = _read(m["name"], rec)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": _finite(e2e[m["name"]]),
                                  "unit": m["unit"]}

    finished = [i for i in range(len(items)) if not math.isnan(w.finish[i])]
    rows = checks.sample(dep, finished, seed, spec["sample_requests"])
    dep.srv = None            # the program's state goes before the reference
    gc.collect()
    got = checks.check(dep, rows, spec, limits)
    correct = bool(got) and info["failed"] == 0 and all(
        c["value"] <= c["limit"] for c in got.values())
    for arch, c in got.items():
        print(f"check logit_gap.{arch}: {c['tokens']} served tokens of "
              f"{c['requests']} requests", flush=True)
    result = {"correct": correct, "attempted": info["attempted"],
              "failed": info["failed"], "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = {f"logit_gap.{a}": {"value": c["value"],
                                           "limit": c["limit"]}
                        for a, c in got.items()}
    for name, c in result["checks"].items():
        print(f"{name} {c['value']} limit {c['limit']}", file=sys.stderr,
              flush=True)
    return result
