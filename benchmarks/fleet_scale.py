"""Fleet-vectorized serving at cluster scale: open-loop replay on 64 nodes.

The tentpole claim of the fleet refactor is that decode cost per scheduler
tick is O(#cohorts), not O(#engines): every engine sharing a
``(ModelConfig, EngineConfig, params)`` identity decodes inside ONE vmapped
jit dispatch with one stacked host transfer (``serving.fleet``). This
benchmark measures that end to end with real tiny models:

* **replay** — an open-loop arrival replay of >= 100k single-turn sessions
  against the 64-node ``fleet_testbed`` (8 cloud + 56 edge nodes -> 176
  engines -> exactly 2 cohorts), arrivals paced above service capacity so
  the decode plane stays saturated. Reported: tokens/s over the cold window
  (first ticks, includes trace + XLA compile of the cohort dispatch) vs the
  warm remainder, router decisions/s (the submit-side routing hot path),
  and **decode dispatches per saturated tick** — asserted to equal the
  cohort count exactly, the O(#cohorts) evidence.
* **head-to-head** — the same replay at moderate scale on an 8-node fleet,
  fleet cohorts vs the per-engine Python loop (``fleet=False``), which is
  byte-identical (tests/test_fleet.py) but pays one jit dispatch per busy
  engine per tick.

Writes ``results/fleet_scale.csv`` + ``BENCH_fleet.json`` (``*_smoke``
variants under ``--smoke`` so CI cannot clobber committed full-scale
results).
"""
from __future__ import annotations

import sys
import time

import numpy as np

from repro.cluster.spec import fleet_testbed
from repro.core.policy import PAPER_DEFAULTS
from repro.launch.serve import build_models
from repro.serving import ClusterServer, EngineConfig, ServeRequest
from repro.workload.trace import build_trace

from .common import write_bench_json, write_csv

SMOKE = "--smoke" in sys.argv    # CI: tiny fleet + short replay, same paths

N_SESSIONS = 400 if SMOKE else 100_000
TRACE_POOL = 400 if SMOKE else 10_000   # distinct requests, cycled to N
ARRIVALS_PER_TICK = 40 if SMOKE else 400  # > capacity: keeps decode saturated
HEAD_TO_HEAD_N = 200 if SMOKE else 2_000
WARM_TICKS = 3                   # cold window: compile + first dispatches
MAX_NEW = 2

ECFG = EngineConfig(max_slots=4, max_seq=32, max_new_tokens=MAX_NEW,
                    prefill_bucket=16)


def _server(cluster, builders, fleet=True):
    return ClusterServer(cluster, builders, PAPER_DEFAULTS, ECFG,
                         hedge_after=10**9, fleet=fleet)


def _emitted(srv) -> int:
    return sum(e.tokens_emitted for e in srv.engines.values())


def replay(srv, reqs, n_sessions: int, rate: int) -> dict:
    """Open-loop replay: session ``i`` arrives at tick ``i // rate``; every
    iteration submits the due arrivals (timed separately — the router
    decision hot path) then runs one scheduler tick. The dispatch-count
    window spans the saturated phase: from the end of the cold window until
    the arrival process drains."""
    i = 0
    route_s = 0.0
    cold_s = warm_s = 0.0
    cold_toks = 0
    sat = None                    # (dispatches, ticks) at saturation start
    disp_per_tick = float("nan")
    while i < n_sessions or srv.inflight or srv.transfers:
        t0 = time.perf_counter()
        while i < n_sessions and i // rate <= srv.ticks:
            srv.submit(ServeRequest(request_id=i, req=reqs[i % len(reqs)],
                                    max_new_tokens=MAX_NEW))
            i += 1
        t1 = time.perf_counter()
        route_s += t1 - t0
        srv.step()
        dt = time.perf_counter() - t0
        if srv.ticks <= WARM_TICKS:
            cold_s += dt
            cold_toks = _emitted(srv)
        else:
            warm_s += dt
        if srv.ticks == WARM_TICKS:
            sat = (srv.decode_dispatches, srv.ticks)
        if i == n_sessions and sat is not None and srv.ticks > sat[1]:
            disp_per_tick = ((srv.decode_dispatches - sat[0])
                             / (srv.ticks - sat[1]))
            sat = None            # freeze the window at arrival exhaustion
    toks = _emitted(srv)
    return {
        "sessions": n_sessions,
        "completed": len(srv.done),
        "ticks": srv.ticks,
        "tokens": toks,
        "wall_s": cold_s + warm_s,
        "cold_s": cold_s,
        "warm_s": warm_s,
        "tokens_per_s": toks / (cold_s + warm_s),
        "cold_tokens_per_s": cold_toks / cold_s if cold_s else 0.0,
        "warm_tokens_per_s": (toks - cold_toks) / warm_s if warm_s else 0.0,
        "router_decisions_per_s": n_sessions / route_s,
        "dispatches_per_tick": disp_per_tick,
        "decode_dispatches": srv.decode_dispatches,
    }


def run(seed: int = 7):
    builders = build_models()
    reqs = build_trace(TRACE_POOL, seed=seed).requests
    rows, bench = [], {}

    # -- 64-node open-loop replay (the scale proof) -------------------------
    cluster = (fleet_testbed(n_edge=6, n_cloud=2) if SMOKE
               else fleet_testbed(n_edge=56, n_cloud=8))
    srv = _server(cluster, builders)
    rep = replay(srv, reqs, N_SESSIONS, ARRIVALS_PER_TICK)
    rep.update(nodes=len(cluster.nodes), engines=len(srv.engines),
               cohorts=len(srv._cohorts))
    assert rep["completed"] == N_SESSIONS
    bench["replay"] = rep
    rows.append(["replay", rep["nodes"], rep["engines"], rep["cohorts"],
                 rep["sessions"], rep["ticks"],
                 f"{rep['wall_s']:.2f}", rep["tokens"],
                 f"{rep['cold_tokens_per_s']:.1f}",
                 f"{rep['warm_tokens_per_s']:.1f}",
                 f"{rep['router_decisions_per_s']:.1f}",
                 f"{rep['dispatches_per_tick']:.3f}"])

    # -- fleet vs per-engine head-to-head (moderate scale) ------------------
    h2h_cluster = fleet_testbed(n_edge=6, n_cloud=2)
    for mode, fleet in (("fleet", True), ("per-engine", False)):
        srv = _server(h2h_cluster, builders, fleet=fleet)
        rep = replay(srv, reqs, HEAD_TO_HEAD_N, ARRIVALS_PER_TICK // 4)
        rep.update(nodes=len(h2h_cluster.nodes), engines=len(srv.engines),
                   cohorts=len(srv._cohorts))
        bench[f"h2h_{mode}"] = rep
        rows.append([f"h2h-{mode}", rep["nodes"], rep["engines"],
                     rep["cohorts"], rep["sessions"], rep["ticks"],
                     f"{rep['wall_s']:.2f}", rep["tokens"],
                     f"{rep['cold_tokens_per_s']:.1f}",
                     f"{rep['warm_tokens_per_s']:.1f}",
                     f"{rep['router_decisions_per_s']:.1f}",
                     f"{rep['dispatches_per_tick']:.3f}"])

    suffix = "_smoke" if SMOKE else ""
    write_csv(f"fleet_scale{suffix}.csv",
              ["section", "nodes", "engines", "cohorts", "sessions", "ticks",
               "wall_s", "tokens", "cold_tokens_per_s", "warm_tokens_per_s",
               "router_decisions_per_s", "dispatches_per_tick"], rows)
    write_bench_json(f"fleet{suffix}", bench)
    return bench


def main():
    bench = run()
    rep = bench["replay"]
    print(f"fleet_scale.replay,{rep['wall_s'] / rep['ticks'] * 1e6:.0f},"
          f"nodes={rep['nodes']} cohorts={rep['cohorts']} "
          f"warm_tok_s={rep['warm_tokens_per_s']:.0f} "
          f"disp_per_tick={rep['dispatches_per_tick']:.3f}")
    f, p = bench["h2h_fleet"], bench["h2h_per-engine"]
    print(f"fleet_scale.h2h,{f['wall_s'] * 1e6:.0f},"
          f"fleet_tok_s={f['tokens_per_s']:.0f} "
          f"perengine_tok_s={p['tokens_per_s']:.0f} "
          f"dispatches={f['decode_dispatches']}vs{p['decode_dispatches']}")
    # the saturated decode plane must cost exactly one dispatch per cohort
    # per tick — O(#cohorts), the refactor's core claim
    assert rep["dispatches_per_tick"] == rep["cohorts"], rep
    if SMOKE:
        return   # tiny replay: throughput verdicts are noise
    assert rep["sessions"] >= 100_000 and rep["nodes"] == 64
    # fewer dispatches must not cost throughput: once the cohort jit's
    # participant-bucket variants are compiled (the cold window), the
    # stacked path wins (or at minimum matches) the per-engine loop at
    # equal byte-exact output
    assert f["warm_tokens_per_s"] >= 0.9 * p["warm_tokens_per_s"], (f, p)
    assert f["decode_dispatches"] < p["decode_dispatches"]


if __name__ == "__main__":
    main()
