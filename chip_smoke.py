#!/usr/bin/env python3
"""Smoke test of the router's main path on a TPU, at published widths.

    python chip_smoke.py             # one chip: tune, serve, kernel agreement
    python chip_smoke.py --chips 4   # four chips: device-sharded fitness only

One chip, in order:

1. device check — JAX's devices must be TPUs; there is no CPU fallback;
2. router tuning — a short NSGA-II ``evolve_scan`` of the paper's threshold
   genome over a 64-request trace (Pallas dominance kernel in the survival
   sort), one genome picked by ``select_by_weights``;
3. serving — ``ClusterServer(fleet=True)`` over ``paper_testbed()``:
   stablelm-3b (32 layers x 2560) serves ``gemma3:27b`` and one qwen3-1.7b
   (28 layers x 2048) serves the three qwen2.5 names, with random weights
   from fixed seeds; 8 requests of 16 new tokens each run to completion;
4. kernel agreement — Pallas against the jnp reference: decode attention
   at both models' decode shapes, and both dominance kernels.

``--chips 4`` runs only the path that spans chips: the population fitness
sharded over a 4-device mesh, compared with the single-device fitness on the
same genomes.

Every phase prints its wall seconds, split into XLA compile, trace+lower and
the rest. The script exits non-zero if a phase raised, a request went
unanswered, a node failed or a request was rerouted (no faults are scheduled,
so either is a hidden error), or a kernel disagreed beyond bf16 tolerance.
The last line of a passing run is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
import traceback
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.cluster.spec import paper_testbed  # noqa: E402
from repro.configs import get  # noqa: E402
from repro.core.fitness import (EvalConfig, TraceEvaluator,  # noqa: E402
                                population_mesh)
from repro.kernels import ops  # noqa: E402
from repro.launch.serve import (build_models, enable_compile_cache,  # noqa: E402
                                tune_router)
from repro.models import lm  # noqa: E402
from repro.serving import ClusterServer, EngineConfig, ServeRequest  # noqa: E402
from repro.workload.slo import attach_slos  # noqa: E402
from repro.workload.trace import build_trace  # noqa: E402

N_REQUESTS, NEW_TOKENS = 8, 16
# 4 slots x 512 positions per engine: 2.79 GB of KV caches next to 9.65 GB
# of weights; a v5e run of this script peaked at 15.7 of 16.9 GB of HBM
ENGINE = EngineConfig(max_slots=4, max_seq=512, max_new_tokens=NEW_TOKENS)
DECODE_TOL = 3e-2            # bf16 outputs of magnitude ~1

_COMPILE_EVENTS = {
    "/jax/core/compile/backend_compile_duration": "xla",
    "/jax/core/compile/jaxpr_trace_duration": "trace_lower",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "trace_lower",
}
_spans: list = []            # (kind, start, end) on the time.time() clock


def _on_span(event: str, start: float, end: float, **_) -> None:
    if event in _COMPILE_EVENTS:
        _spans.append((_COMPILE_EVENTS[event], start, end))


def _covered(spans) -> float:
    """Seconds covered by the union of (start, end) intervals: a function
    jitted inside another records its own trace span within the outer one,
    so summing durations would count that time twice."""
    total, reach = 0.0, float("-inf")
    for s, e in sorted(spans):
        if e > reach:
            total += e - max(s, reach)
            reach = e
    return total


class Smoke:
    """Runs phases, times them and collects failures."""

    def __init__(self):
        self.failures: list = []

    def phase(self, name: str, fn, *args):
        n0, t0 = len(_spans), time.time()
        try:
            out = fn(*args)
        except Exception:
            traceback.print_exc()
            self.failures.append(f"{name}: raised")
            out = None
        wall = time.time() - t0
        spans = _spans[n0:]
        xla = _covered((s, e) for k, s, e in spans if k == "xla")
        compile_s = _covered((s, e) for _, s, e in spans)
        print(f"phase {name}: wall_s={wall} compile_s={compile_s} "
              f"(xla_compile_s={xla} trace_lower_s={compile_s - xla}) "
              f"run_s={wall - compile_s}", flush=True)
        return out

    def check(self, ok: bool, what: str) -> None:
        if not ok:
            print(f"FAIL: {what}", flush=True)
            self.failures.append(what)


def tune(smoke: Smoke, cluster, trace):
    thresholds, F = tune_router(cluster, trace, generations=5, pop_size=32)
    thresholds, F = np.asarray(thresholds), np.asarray(F)
    print("tuned thresholds:", thresholds.tolist(),
          "objectives (RQ, C, RT):", F.tolist())
    smoke.check(bool(np.isfinite(F).all()), "tuned objectives not finite")
    return thresholds


def _bytes(tree) -> int:
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))


def serve(smoke: Smoke, cluster, trace, thresholds):
    big, small = get("stablelm-3b").config(), get("qwen3-1.7b").config()
    for name, c in (("stablelm-3b", big), ("qwen3-1.7b", small)):
        print(f"model {name}: {c.n_layers} layers x d_model {c.d_model}, "
              f"heads {c.n_heads}/{c.n_kv_heads} x {c.hd}, vocab {c.vocab}")
    models = build_models(big, small)
    params = {id(p): p for _, p in models.values()}
    print("params bytes:", sum(_bytes(p) for p in params.values()))
    srv = ClusterServer(cluster, models, thresholds, ENGINE, fleet=True)
    kv = sum(_bytes(jax.eval_shape(lambda c=c: lm.make_cache(
        c, ENGINE.max_slots, ENGINE.max_seq)))
             for c in srv.pair_model_cfg.values())
    print(f"kv cache bytes: {kv} ({len(srv.engines)} engines x "
          f"{ENGINE.max_slots} slots x {ENGINE.max_seq} positions)")
    for i, r in enumerate(trace.requests[:N_REQUESTS]):
        srv.submit(ServeRequest(request_id=i, req=r,
                                max_new_tokens=NEW_TOKENS))
    done = srv.run()
    st = srv.stats()
    dispatches = sum(c["dispatches"] for c in st["cohorts"])
    print(f"served {len(done)}/{N_REQUESTS} requests; node failures "
          f"{st['failed']} (down nodes {st['down_nodes']}); reroutes "
          f"{st['reroutes']}; hedges {st['hedges']}; cohort decode "
          f"dispatches {dispatches} over {len(st['cohorts'])} cohorts; "
          f"decode dispatches {st['decode_dispatches']}")
    smoke.check(sorted(done) == list(range(N_REQUESTS)),
                "a request went unanswered")
    smoke.check(st["failed"] == 0 and not st["down_nodes"],
                "a node failed")
    smoke.check(st["reroutes"] == 0, "a request was rerouted")
    smoke.check(dispatches > 0, "no cohort decode dispatch ran")
    smoke.check(all(len(r["tokens"]) == NEW_TOKENS for r in done.values()),
                f"a request did not get {NEW_TOKENS} tokens")
    smoke.check(all(0 <= t < max(big.vocab, small.vocab)
                    for r in done.values() for t in r["tokens"]),
                "a token is outside the vocabulary")


def kernels(smoke: Smoke):
    keys = iter(jax.random.split(jax.random.key(0), 16))
    B, S = ENGINE.max_slots, ENGINE.max_seq
    for name in ("qwen3-1.7b", "stablelm-3b"):
        c = get(name).config()
        q = jax.random.normal(next(keys), (B, c.n_heads, c.hd), jnp.bfloat16)
        k, v = (jax.random.normal(next(keys), (B, c.n_kv_heads, S, c.hd),
                                  jnp.bfloat16) for _ in range(2))
        kv_len = jax.random.randint(next(keys), (B,), 1, S + 1)
        got, want = (ops.gqa_decode_attention(q, k, v, kv_len, mode=m)
                     for m in ("pallas", "ref"))
        diff = float(jnp.max(jnp.abs(got.astype(jnp.float32)
                                     - want.astype(jnp.float32))))
        print(f"gqa_decode_attention {name} {tuple(q.shape)} x "
              f"{tuple(k.shape)}: pallas vs ref max abs diff {diff}")
        smoke.check(diff <= DECODE_TOL, f"decode attention {name} disagrees")
    # dominance: quantized objectives so ties and duplicates occur
    F = jnp.round(jax.random.uniform(next(keys), (300, 3)) * 8) / 8
    for fn in (ops.dominance_matrix, ops.dominance_counts):
        got, want = (np.asarray(fn(F, mode=m)).astype(np.int32)
                     for m in ("pallas", "ref"))
        diff = int(np.max(np.abs(got - want)))
        print(f"{fn.__name__} {tuple(F.shape)}: pallas vs ref max abs diff "
              f"{diff}")
        smoke.check(diff == 0, f"{fn.__name__} disagrees")


def sharded_fitness(smoke: Smoke, n_chips: int):
    tr = build_trace(120, seed=0)
    attach_slos(tr, seed=0)
    ev = TraceEvaluator(tr, paper_testbed(), EvalConfig(concurrency=4),
                        bucket="pow2")
    lo, span = jnp.asarray([0.3, 0.0]), jnp.asarray([0.8, 20.0])
    genomes = lo + jax.random.uniform(jax.random.key(0), (64, 2)) * span
    key = jax.random.key(1)
    mesh = population_mesh(n_chips)
    F, viol = ev.make_fitness("slo", objectives="qoe", mesh=mesh)(genomes,
                                                                  key)
    F0, v0 = ev.make_fitness("slo", objectives="qoe")(genomes, key)
    F, viol, F0, v0 = map(np.asarray, (F, viol, F0, v0))
    diff = float(np.max(np.abs(F - F0)))
    print(f"sharded fitness over {mesh.devices.size} devices "
          f"{[d.id for d in mesh.devices.flat]}: {F.shape} objectives, "
          f"max abs diff vs one device {diff}, violations equal "
          f"{bool((viol == v0).all())}")
    smoke.check(mesh.devices.size == n_chips, f"mesh is not {n_chips} chips")
    smoke.check(bool(np.isfinite(F).all()), "sharded objectives not finite")
    smoke.check(bool(np.allclose(F, F0, rtol=1e-5, atol=1e-6)),
                "sharded fitness disagrees with one device")
    smoke.check(bool((viol == v0).all()), "sharded violations disagree")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        print(f"no TPU: JAX found {len(devs)} {dev.platform} device(s)",
              file=sys.stderr)
        return 2
    if len(devs) < args.chips:
        print(f"--chips {args.chips} needs {args.chips} TPU devices, found "
              f"{len(devs)}", file=sys.stderr)
        return 2
    print(f"device: {dev.platform} {dev.device_kind} x {len(devs)}; jax "
          f"{jax.__version__}", flush=True)
    enable_compile_cache()
    jax.monitoring.register_event_time_span_listener(_on_span)

    smoke = Smoke()
    if args.chips == 4:
        smoke.phase("sharded_fitness", sharded_fitness, smoke, 4)
    else:
        cluster = paper_testbed()
        trace = build_trace(64, seed=0)
        thresholds = smoke.phase("tune", tune, smoke, cluster, trace)
        if thresholds is not None:
            smoke.phase("serve", serve, smoke, cluster, trace, thresholds)
        smoke.phase("kernels", kernels, smoke)
    mem = dev.memory_stats() or {}
    print("peak_bytes_in_use:", mem.get("peak_bytes_in_use", "not reported"),
          "bytes_limit:", mem.get("bytes_limit", "not reported"))
    if smoke.failures:
        print("FAILED:", "; ".join(smoke.failures), file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
