"""Chaos hardening: deterministic fault scenarios across both layers.

Four seeded fault scenarios from ``repro.faults.FaultSchedule`` are replayed
through the analytic evaluator (the same fault tables the NSGA-II fitness
scan and both DES oracles consume) and through the live serving runtime
(``ClusterServer`` with retries, circuit breakers, and load shedding armed):

* **crash-storm** — repeated node crashes with no spare (the cloud node
  crashes too). The ``resilient`` policy is NSGA-II-tuned against the
  *faulty* evaluator and compared to the naive-failover baseline: the same
  deadline-aware routing family (``slo`` hand defaults) relying solely on
  the router's stock dead-pair failover, with no brownout term and no
  fault-aware tuning — so the measured delta is exactly the resilience
  machinery. The paper's Algorithm-2 ``threshold`` defaults are reported
  alongside for context. The run asserts the tuned configuration reaches
  >= 1.2x the baseline's SLO attainment at matched quality
  (quality >= baseline - 5e-3).
* **link-flap** — the disaggregated KV link degrades 20x in repeated
  windows; the ``disagg`` policy is evaluated clean vs flapping on long
  prompts (transfer seconds must grow, attainment must not improve).
* **straggler** — two nodes run 4x slow for long stretches; the
  crash-tuned resilient genome is transferred unchanged to show regime
  robustness.
* **overload** — a serving-runtime arrival burst past admission capacity,
  SLO-class shedding on vs off (batch sheds first, interactive survives).

Every scenario also drives a live ``ClusterServer`` under the same schedule
and asserts per-node ledger conservation (``dispatched == completed +
failed + cancelled``) and **zero leaked KV blocks** — these asserts run in
smoke mode too.

Reported: capacity availability (time-mean alive/slowdown-discounted node
fraction of the schedule), SLO attainment, goodput (attained requests per
second of makespan; served requests per tick on the serving side), quality,
cost, and the serving retry/timeout/shed/breaker counters.

Writes ``results/chaos.csv`` + ``BENCH_chaos.json`` (``*_smoke`` variants
under ``--smoke`` so CI cannot clobber committed full-run results).
"""
from __future__ import annotations

import dataclasses
import sys

import jax
import numpy as np

from repro.cluster.spec import disagg_testbed, paper_testbed
from repro.core.fitness import EvalConfig, TraceEvaluator
from repro.core.nsga2 import NSGA2, NSGA2Config
from repro.core.policies import get_policy
from repro.core.policy import PAPER_DEFAULTS
from repro.faults import FaultSchedule, TransientErrors, node_available_np, \
    node_slowdown_np
from repro.launch.serve import build_models
from repro.serving import ClusterServer, EngineConfig, ResilienceConfig, \
    ServeRequest
from repro.workload.sessions import SessionConfig, build_session_trace
from repro.workload.slo import attach_slos
from repro.workload.trace import build_trace

from .common import timed, write_bench_json, write_csv

SMOKE = "--smoke" in sys.argv    # CI: tiny shapes, same code path

N_REQUESTS = 160
POP, GENS = 16, 10
TIGHTNESS = 2.0
STORM_SEED = 3                   # crash-storm regime the verdict is run on
ATTAIN_RATIO = 1.2               # tuned resilient vs naive failover
QUALITY_TOL = 5e-3               # "matched quality" tolerance
NO_HEDGE = 10 ** 9

HEADER = ["scenario", "config", "layer", "capacity_avail",
          "slo_attainment", "goodput", "avg_quality", "avg_cost", "avg_rt",
          "served_frac", "retries", "timeouts", "sheds", "breaker_opens"]


# ---------------------------------------------------------------------------
# analytic layer: faulty TraceEvaluator
# ---------------------------------------------------------------------------
def _workload(seed: int = 0, prompt_scale: float = 1.0):
    n = 48 if SMOKE else N_REQUESTS
    cfg = SessionConfig(n_sessions=max(2, n // 3), mean_turns=3.0,
                        session_rate=1.5, think_time_s=3.0)
    tr = build_session_trace(cfg, seed=seed, n_requests=n)
    attach_slos(tr, tightness=TIGHTNESS, seed=seed)
    if prompt_scale != 1.0:
        tr.prompt_tokens = np.maximum(
            (tr.prompt_tokens * prompt_scale).astype(np.int32), 1)
    return tr


def _capacity_availability(sched: FaultSchedule, n_nodes: int,
                           horizon: float) -> float:
    """Time-mean fraction of scheduled node capacity: alive nodes weighted
    by the inverse of their straggler slowdown."""
    ft = sched.compile(n_nodes)
    grid = np.linspace(0.0, horizon, 257, dtype=np.float32)
    cap = [np.mean(node_available_np(ft, t).astype(np.float32)
                   / node_slowdown_np(ft, t)) for t in grid]
    return float(np.mean(cap))


def _eval(ev: TraceEvaluator, name: str, genome, tr) -> dict:
    res = ev.run_policy(name, genome)
    s = ev.summarize(res)
    rt = np.asarray(res.rt)
    makespan = float(np.max(tr.arrival_time[:len(rt)] + rt))
    att = s.get("slo_attainment", 0.0)
    s["goodput"] = att * len(rt) / max(makespan, 1e-9)
    s["transfer_s"] = float(np.mean(np.asarray(res.transfer)))
    return s


def _tune_resilient(ev: TraceEvaluator, tr, qfloor: float, seed: int = 0):
    """NSGA-II fit against the *faulty* evaluator, then pick the survivor
    with the highest SLO attainment among candidates at matched quality
    (>= qfloor) — attainment must never be bought by trading quality below
    the baseline. Hand defaults join the candidate set so tuning cannot
    regress them."""
    pop = 8 if SMOKE else POP
    gens = 4 if SMOKE else GENS
    cfg = NSGA2Config.from_policy(get_policy("resilient"), pop_size=pop,
                                  n_generations=gens)
    opt = NSGA2(ev.make_fitness("resilient", objectives="qoe"), cfg)
    state, fit_s = timed(
        lambda: opt.evolve_scan(jax.random.key(seed), gens),
        warmup=0, iters=1)
    cands = np.unique(np.asarray(state.genomes), axis=0)
    defaults = np.asarray(get_policy("resilient").genome_spec.defaults,
                          cands.dtype)
    cands = np.vstack([cands, defaults])
    scored = [(g, _eval(ev, "resilient", g, tr)) for g in cands]
    matched = [(g, s) for g, s in scored if s["avg_quality"] >= qfloor]
    pool = matched or scored       # smoke fallback: tiny fronts may miss
    g, s = max(pool, key=lambda t: (t[1]["slo_attainment"],
                                    -t[1]["avg_cost"]))
    return g, s, fit_s


def _analytic_row(scenario: str, config: str, avail: float, s: dict):
    return [scenario, config, "analytic", f"{avail:.3f}",
            f"{s.get('slo_attainment', 0.0):.4f}", f"{s['goodput']:.3f}",
            f"{s['avg_quality']:.4f}", f"{s['avg_cost']:.4e}",
            f"{s['avg_response_time']:.4f}", "", "", "", "", ""]


# ---------------------------------------------------------------------------
# serving layer: live ClusterServer under the same schedules
# ---------------------------------------------------------------------------
def _ecfg(**over):
    kw = dict(max_slots=2, max_seq=48, max_new_tokens=4, prefix_cache=True,
              block_size=8, cache_blocks=32)
    kw.update(over)
    return EngineConfig(**kw)


def _assert_conserved(srv):
    for node, s in srv.monitor.stats.items():
        assert s.total_dispatched == (s.total_completed + s.total_failed
                                      + s.total_cancelled), (node, s)
        assert s.outstanding == 0, (node, s)


def _leaked_blocks(srv) -> int:
    leaked = 0
    for eng in srv.engines.values():
        if eng.kv is not None:
            eng.kv.cache.check_invariants()
            leaked += int(np.sum(eng.kv.cache.pool.ref > 0))
    return leaked


def _serve(srv, sreqs, scenario: str, config: str):
    """Drive the server to drain, assert conservation + zero leaked KV
    blocks (the hard chaos invariants — asserted in smoke mode too), and
    return the serving-side row + counters."""
    for sr in sreqs:
        srv.submit(sr)
    done = srv.run()
    assert sorted(done) == sorted(sr.request_id for sr in sreqs)
    st = srv.stats()
    served = sum(1 for d in done.values()
                 if isinstance(d, dict) and "tokens" in d)
    _assert_conserved(srv)
    leaked = _leaked_blocks(srv)
    assert leaked == 0, (scenario, config, leaked)
    counters = {
        "served": served, "total": len(sreqs),
        "served_frac": served / max(len(sreqs), 1),
        "retries": st["retries"], "timeouts": st["timeouts"],
        "sheds": st["sheds"], "transients": st["transient_faults"],
        "breaker_opens": sum(st["breaker_opens"]),   # per-node open counts
        "ticks": srv.ticks,
        "goodput": served / max(srv.ticks, 1),
        "leaked_blocks": leaked,
    }
    row = [scenario, config, "serving", "", "", f"{counters['goodput']:.3f}",
           "", "", "", f"{counters['served_frac']:.3f}",
           counters["retries"], counters["timeouts"], counters["sheds"],
           counters["breaker_opens"]]
    return row, counters


def _paper_server(builders, faults=None, resilience=None):
    return ClusterServer(paper_testbed(), builders, PAPER_DEFAULTS, _ecfg(),
                         hedge_after=NO_HEDGE,
                         router_kwargs={"mode": "threshold"},
                         faults=faults, resilience=resilience)


def _serve_reqs(n: int, max_new: int = 3, classes=None):
    reqs = build_trace(max(24, n), seed=5).requests[:n]
    out = []
    for i, r in enumerate(reqs):
        kw = {"slo_class": classes[i % len(classes)]} if classes else {}
        out.append(ServeRequest(request_id=i, req=r,
                                max_new_tokens=max_new, **kw))
    return out


# ---------------------------------------------------------------------------
# scenarios
# ---------------------------------------------------------------------------
def _crash_storm(rows, bench, builders):
    tr = _workload()
    horizon = float(np.max(tr.arrival_time))
    cluster = paper_testbed()
    n_nodes = len(cluster.nodes)
    sched = FaultSchedule.crash_storm(
        n_nodes, seed=STORM_SEED, n_crashes=6, horizon=horizon,
        mean_down=0.25 * horizon, spare=0)
    avail = _capacity_availability(sched, n_nodes, horizon)
    ev = TraceEvaluator(tr, cluster,
                        EvalConfig(mode="open", prefix_cache=True),
                        bucket="pow2", faults=sched)
    naive = _eval(ev, "slo", get_policy("slo").genome_spec.defaults, tr)
    alg2 = _eval(ev, "threshold",
                 get_policy("threshold").genome_spec.defaults, tr)
    qfloor = naive["avg_quality"] - QUALITY_TOL
    genome, tuned, fit_s = _tune_resilient(ev, tr, qfloor)

    rows.append(_analytic_row("crash_storm", "naive-failover(slo)",
                              avail, naive))
    rows.append(_analytic_row("crash_storm", "alg2-threshold", avail, alg2))
    rows.append(_analytic_row("crash_storm", "resilient-tuned", avail,
                              tuned))

    # serving replay: same storm shape in scheduler ticks plus transient
    # dispatch errors (retries + breakers exercised); spare=1 keeps one
    # node up so the run drains
    serve_sched = dataclasses.replace(
        FaultSchedule.crash_storm(n_nodes, seed=0, n_crashes=4,
                                  horizon=40.0, mean_down=10.0, spare=1),
        transient=TransientErrors(rate=0.2, seed=7))
    srow, counters = _serve(_paper_server(builders, faults=serve_sched),
                            _serve_reqs(10 if SMOKE else 16),
                            "crash_storm", "serving-replay")
    rows.append(srow)

    ratio = tuned["slo_attainment"] / max(naive["slo_attainment"], 1e-9)
    bench["crash_storm"] = {
        "capacity_availability": avail,
        "naive_failover": naive, "alg2_threshold": alg2,
        "resilient_tuned": tuned,
        "tuned_genome": [float(x) for x in genome],
        "attain_ratio": ratio,
        "quality_margin": tuned["avg_quality"] - naive["avg_quality"],
        "nsga2_fit_s": fit_s,
        "serving": counters,
    }
    return genome


def _link_flap(rows, bench, builders):
    tr = _workload(prompt_scale=3.0)    # long prompts: the KV link matters
    horizon = float(np.max(tr.arrival_time))
    cluster = disagg_testbed()
    sched = FaultSchedule.link_flap(seed=STORM_SEED, n_flaps=4,
                                    horizon=horizon, factor=20.0,
                                    mean_len=0.3 * horizon)
    dflt = get_policy("disagg").genome_spec.defaults
    cfg = EvalConfig(mode="open", prefix_cache=True, disaggregated=True)
    clean = _eval(TraceEvaluator(tr, cluster, cfg, bucket="pow2"),
                  "disagg", dflt, tr)
    flap = _eval(TraceEvaluator(tr, cluster, cfg, bucket="pow2",
                                faults=sched), "disagg", dflt, tr)
    avail = _capacity_availability(sched, len(cluster.nodes), horizon)
    rows.append(_analytic_row("link_flap", "disagg-clean", 1.0, clean))
    rows.append(_analytic_row("link_flap", "disagg-flap", avail, flap))

    # serving replay: disagg server with real KV handoffs through a
    # flapping link (single-model long-prompt requests, whole-block KV)
    dcfg, dparams = builders["gemma3:27b"]
    dsrv = ClusterServer(
        disagg_testbed(), {"gemma3:27b": (dcfg, dparams)}, PAPER_DEFAULTS,
        _ecfg(max_new_tokens=3),
        router_kwargs={"mode": "disagg"},
        faults=FaultSchedule.link_flap(seed=0, n_flaps=2, horizon=30.0,
                                       factor=20.0, mean_len=8.0))
    base = build_trace(24, seed=5).requests
    dreqs = [ServeRequest(
        request_id=i, max_new_tokens=3,
        req=dataclasses.replace(r, text=" ".join(f"w{i}_{j}"
                                                 for j in range(20)),
                                prompt_tokens=20))
        for i, r in enumerate(base[:6 if SMOKE else 8])]
    srow, counters = _serve(dsrv, dreqs, "link_flap", "serving-replay")
    rows.append(srow)
    assert dsrv.stats()["handoffs"] >= 1     # split routes actually taken

    bench["link_flap"] = {
        "clean": clean, "flap": flap,
        "transfer_s_clean": clean["transfer_s"],
        "transfer_s_flap": flap["transfer_s"],
        "serving": counters,
    }


def _straggler(rows, bench, builders, tuned_genome):
    tr = _workload()
    horizon = float(np.max(tr.arrival_time))
    cluster = paper_testbed()
    n_nodes = len(cluster.nodes)
    sched = FaultSchedule.straggler_storm(
        n_nodes, seed=STORM_SEED, n_stragglers=2, horizon=horizon,
        factor=4.0, mean_len=0.4 * horizon)
    avail = _capacity_availability(sched, n_nodes, horizon)
    ev = TraceEvaluator(tr, cluster,
                        EvalConfig(mode="open", prefix_cache=True),
                        bucket="pow2", faults=sched)
    naive = _eval(ev, "slo", get_policy("slo").genome_spec.defaults, tr)
    # the crash-tuned genome transfers unchanged (regime robustness)
    tuned = _eval(ev, "resilient", tuned_genome, tr)
    rows.append(_analytic_row("straggler", "naive-failover(slo)",
                              avail, naive))
    rows.append(_analytic_row("straggler", "resilient-crash-tuned",
                              avail, tuned))

    srow, counters = _serve(
        _paper_server(builders,
                      faults=FaultSchedule.straggler_storm(
                          n_nodes, seed=0, n_stragglers=2, horizon=40.0,
                          factor=3.0, mean_len=20.0)),
        _serve_reqs(8 if SMOKE else 12), "straggler", "serving-replay")
    rows.append(srow)
    bench["straggler"] = {"naive_failover": naive,
                          "resilient_crash_tuned": tuned,
                          "capacity_availability": avail,
                          "serving": counters}


def _overload(rows, bench, builders):
    """Serving-only: an admission burst past capacity with SLO-class
    shedding on vs off. Shedding must shed batch work only; with it off
    nothing sheds and the drain takes longer."""
    n = 24 if SMOKE else 40
    classes = ("interactive", "batch")
    out = {}
    for config, rcfg in (
            ("shed-on", ResilienceConfig(shed_threshold=0.5,
                                         shed_interactive_threshold=3.0)),
            ("shed-off", None)):
        srv = _paper_server(builders, resilience=rcfg)
        srow, counters = _serve(srv, _serve_reqs(n, max_new=4,
                                                 classes=classes),
                                "overload", config)
        shed_ids = [i for i, d in srv.done.items()
                    if isinstance(d, dict) and d.get("status") == "shed"]
        counters["shed_classes"] = sorted(
            {classes[i % 2] for i in shed_ids})
        rows.append(srow)
        out[config] = counters
    assert out["shed-on"]["sheds"] > 0, "overload never shed"
    assert out["shed-on"]["shed_classes"] == ["batch"]   # interactive kept
    assert out["shed-off"]["sheds"] == 0
    bench["overload"] = out


# ---------------------------------------------------------------------------
def run(seed: int = 0):
    rows, bench = [], {"smoke": SMOKE}
    builders = build_models()
    tuned_genome = _crash_storm(rows, bench, builders)
    _link_flap(rows, bench, builders)
    _straggler(rows, bench, builders, tuned_genome)
    _overload(rows, bench, builders)

    leaked = (bench["crash_storm"]["serving"]["leaked_blocks"]
              + bench["link_flap"]["serving"]["leaked_blocks"]
              + bench["straggler"]["serving"]["leaked_blocks"]
              + sum(c["leaked_blocks"] for c in bench["overload"].values()))
    bench["verdict"] = {
        "attain_ratio": bench["crash_storm"]["attain_ratio"],
        "attain_ratio_required": ATTAIN_RATIO,
        "quality_margin": bench["crash_storm"]["quality_margin"],
        "quality_tol": QUALITY_TOL,
        "leaked_blocks_total": leaked,
    }
    suffix = "_smoke" if SMOKE else ""
    write_csv(f"chaos{suffix}.csv", HEADER, rows)
    write_bench_json(f"chaos{suffix}", bench)
    return rows, bench


def main():
    rows, bench = run()
    fit_us = bench["crash_storm"]["nsga2_fit_s"] * 1e6
    for r in rows:
        us = f"{fit_us:.0f}" if (r[0], r[1]) == ("crash_storm",
                                                 "resilient-tuned") else ""
        derived = (f"att={r[4]},goodput={r[5]}" if r[2] == "analytic"
                   else f"served={r[9]},goodput={r[5]}")
        print(f"chaos.{r[0]}.{r[1]},{us},{derived}")
    v = bench["verdict"]
    print(f"chaos.verdict,,ratio={v['attain_ratio']:.3f},"
          f"qmargin={v['quality_margin']:+.4f},leaked={v['leaked_blocks_total']}")
    assert v["leaked_blocks_total"] == 0
    if SMOKE:
        return                      # tiny shapes: the verdict is not judged
    assert v["attain_ratio"] >= ATTAIN_RATIO, v
    assert v["quality_margin"] >= -QUALITY_TOL, v
    tf = bench["link_flap"]
    assert tf["transfer_s_flap"] >= tf["transfer_s_clean"], tf


if __name__ == "__main__":
    main()
