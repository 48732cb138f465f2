"""Serving launcher: the paper's cloud-edge cluster with real models on this
host, NSGA-II-optimized routing, continuous batching.

    PYTHONPATH=src python -m repro.launch.serve --requests 32 \
        [--optimize-router] [--fail-node 1 --fail-at 5]

The launcher serves ``.smoke()`` widths, sized for a CPU host;
``chip_smoke.py`` at the repo root drives the same path at published widths
on a TPU.
"""
from __future__ import annotations

import argparse
import os
import time
from pathlib import Path

import jax
import jax.numpy as jnp

from ..cluster.spec import paper_testbed
from ..configs import get
from ..core.fitness import EvalConfig, TraceEvaluator
from ..core.nsga2 import NSGA2, NSGA2Config
from ..core.policies.threshold import BOUNDS_HI, BOUNDS_LO, PAPER_DEFAULTS
from ..models import lm
from ..serving import ClusterServer, EngineConfig, ServeRequest
from ..workload.trace import build_trace

REPO = Path(__file__).resolve().parents[3]


def enable_compile_cache() -> None:
    """Keep JAX's persistent compilation cache at a fixed path. JAX reads
    ``JAX_COMPILATION_CACHE_DIR`` itself when it is set; otherwise the cache
    goes to ``<repo>/.jax_cache`` (the path is part of the cache key, so it
    must not move between runs)."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(REPO / ".jax_cache"))


def build_models(big=None, small=None):
    """Model name -> (ModelConfig, params) for the paper testbed's four
    model names. ``big`` serves ``gemma3:27b``; the three qwen2.5 names
    share ``small`` and ONE params pytree, so their engines form one fleet
    cohort. Defaults are the ``.smoke()`` widths of stablelm-3b and
    qwen3-1.7b. Weights are random from fixed seeds; the init is jitted so a
    published-width model is written to device memory once, without a
    second stacked copy."""
    big = get("stablelm-3b").smoke() if big is None else big
    small = get("qwen3-1.7b").smoke() if small is None else small
    init = jax.jit(lm.init, static_argnums=1)
    pb = init(jax.random.key(0), big)
    ps = init(jax.random.key(1), small)
    return {"gemma3:27b": (big, pb),
            "qwen2.5:1.5b-instruct": (small, ps),
            "qwen2.5-coder:1.5b-instruct": (small, ps),
            "qwen2.5-math:1.5b-instruct": (small, ps)}


def tune_router(cluster, trace, generations: int = 30, pop_size: int = 48):
    """NSGA-II over the paper's threshold genome on ``trace``; returns the
    genome the paper's equal-weight Eq. (1) selects and its objectives
    (RQ, C, RT)."""
    ev = TraceEvaluator(trace, cluster, EvalConfig(concurrency=4))
    cfg = NSGA2Config(pop_size=pop_size, n_generations=generations,
                      lo=jnp.asarray(BOUNDS_LO), hi=jnp.asarray(BOUNDS_HI))
    opt = NSGA2(ev.make_fitness("threshold"), cfg)
    state = opt.evolve_scan(jax.random.key(0), generations)
    return opt.select_by_weights(state, jnp.full((3,), 1 / 3))


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=24)
    ap.add_argument("--optimize-router", action="store_true")
    ap.add_argument("--generations", type=int, default=30)
    ap.add_argument("--fail-node", type=int, default=None)
    ap.add_argument("--fail-at", type=int, default=5)
    ap.add_argument("--max-new-tokens", type=int, default=4)
    args = ap.parse_args(argv)
    enable_compile_cache()

    cluster = paper_testbed()
    trace = build_trace(max(args.requests, 64), seed=0)

    thresholds = PAPER_DEFAULTS
    if args.optimize_router:
        print("optimizing router thresholds with NSGA-II ...")
        thresholds, F = tune_router(cluster, trace, args.generations)
        print("selected thresholds:", [round(float(x), 3) for x in thresholds],
              "objectives (RQ, C, RT):", [float(x) for x in F])

    print("building cluster server (4 nodes, 10 routable pairs) ...")
    srv = ClusterServer(cluster, build_models(), thresholds,
                        EngineConfig(max_slots=2, max_seq=48,
                                     max_new_tokens=args.max_new_tokens))
    t0 = time.time()
    for i, r in enumerate(trace.requests[:args.requests]):
        srv.submit(ServeRequest(request_id=i, req=r,
                                max_new_tokens=args.max_new_tokens))
        if args.fail_node is not None and i == args.fail_at:
            print(f"!! injecting failure of node {args.fail_node}")
            srv.fail_node(args.fail_node)
    done = srv.run()
    dt = time.time() - t0
    print(f"served {len(done)} requests in {dt:.2f}s on "
          f"{jax.devices()[0].platform}")
    print("stats:", srv.stats())


if __name__ == "__main__":
    main()
