"""Runtime request router (paper §IV-B.6, "Routing Policy Execution").

Executes a policy π* selected from the NSGA-II Pareto set. The hot path is
``route()``: feature lookup + one registered policy decision — microseconds
per call (the paper claims "millisecond-level routing decisions"; our
benchmark measures it). Beyond the paper (its §VI future work), the router
is fault-aware:

* **failover** — unhealthy nodes are masked from the candidate set; if the
  chosen node is down the request falls back to the cloud pair, or any
  healthy pair as last resort;
* **hedging** — the scheduler may ask for a *backup* pair to duplicate a
  straggling request onto (different node than the primary);
* **re-optimization** — the rolling-horizon control loop implementing (and
  extending) the paper's "small-scale NSGA-II re-optimization triggered
  periodically": ``record`` appends every served request + realized
  objectives to a bounded history; ``should_reoptimize`` fires when the
  monitor's fast/slow EWMA latency gap signals workload drift;
  ``maybe_reoptimize`` rebuilds a trace from the recorded window (open-loop
  when arrival timestamps were recorded, with the recorded SLO deadlines when
  present), re-runs a small NSGA-II over it **warm-started** from the
  previous run's population archive (``evolve_scan(..., archive=)``), and
  installs the re-selected policy parameters. Re-fits are **compile-once**:
  the window trace is padded to a power-of-two bucket
  (``TraceEvaluator(bucket="pow2")``) and the optimizer's generation step is
  a module-level jitted function keyed on static config, so every re-fit
  after the first reuses cached executables (ms-scale instead of an XLA
  retrace per window).

The decision rule itself is pluggable: ``mode=`` names any runtime-capable
policy in the RoutingPolicy registry (``core.policies.runtime_policies()``
— "threshold", "slo", "affinity", "p2c-hedge", "budget", ...). The router
consults the policy's declared ``requires`` set to decide which inputs to
assemble per request (per-pair phase/cost estimates, deadline contract,
prefix-cache hit fractions), builds one ``PolicyInputs`` bundle, and calls
``policy.decide_py``. Per-policy runtime state (e.g. the budget policy's
spend ledger) is threaded through ``update_py`` after every decision.
Unknown mode names raise ``ValueError`` listing the registered policies;
the re-optimization loop derives its NSGA-II genome encoding from the same
policy object (``NSGA2Config.from_policy``), so a newly registered policy
drives the router — including re-fit — with zero edits here.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..cluster.monitor import ClusterMonitor
from ..cluster.spec import ClusterArrays, ClusterSpec
from ..learn import LearnConfig, OnlineEstimator
from ..learn import estimators as learn_est
from ..obs.timeline import NOOP_TIMELINE
from ..workload.classifier import classify
from ..workload.datasets import Request
from ..workload.features import complexity_score
from ..workload.slo import DEFAULT_SLO_TABLE, slo_arrays
from .fitness import request_pair_estimates
from .policies import (PolicyInputs, get_policy, list_policies,
                       runtime_policies)


@dataclasses.dataclass
class RouteDecision:
    pair: int
    node: int
    model: int
    go_edge: bool
    features: Tuple[float, int, float]   # (c_i, t_i, p_t)
    backup_pair: Optional[int] = None
    # disaggregated serving (route-valued policies): the prefill leg's pair.
    # None for ordinary pair decisions; equal to ``pair`` on colocated
    # routes. ``pair`` is always the decode (billing/retirement) pair.
    prefill_pair: Optional[int] = None
    # modelled $ of the chosen pair (0.0 when the policy never requested
    # estimate rows); the serving scheduler's "spend" metric observation
    est_cost: float = 0.0
    # the (possibly learned-corrected) estimates the decision acted on —
    # fed back through ``record(ttft=, tpot=)`` as the expected side of the
    # online estimator's realized-vs-estimated residual targets. Zero when
    # the policy never requested estimate rows.
    est_ttft: float = 0.0
    est_tpot: float = 0.0
    est_quality: float = 0.0


@dataclasses.dataclass
class Observation:
    """One served request in the router's rolling history window."""

    req: Request
    pair: int
    features: Tuple[float, int, float]
    quality: float
    cost: float
    rt: float
    now: Optional[float] = None            # arrival timestamp (open loop)
    ttft_deadline: Optional[float] = None  # QoE contract, if any
    tpot_deadline: Optional[float] = None


class RequestRouter:
    def __init__(self, cluster: ClusterSpec, thresholds=None,
                 monitor: Optional[ClusterMonitor] = None,
                 hedge_factor: float = 3.0, mode: str = "threshold",
                 slo_params: Optional[Sequence[float]] = None,
                 slo_table=DEFAULT_SLO_TABLE,
                 affinity_params: Optional[Sequence[float]] = None,
                 cache_block: int = 16,
                 params: Optional[Sequence[float]] = None,
                 audit=None, learned: bool = False,
                 learner: LearnConfig = LearnConfig(),
                 timeline=NOOP_TIMELINE):
        self.policy = get_policy(mode)     # ValueError lists registry names
        if self.policy.genome_spec.per_request:
            raise ValueError(
                f"policy {self.policy.name!r} has a per-request genome and "
                f"cannot drive the runtime router; runtime-capable policies: "
                f"{', '.join(runtime_policies())}")
        self.cluster = cluster
        self.arrays: ClusterArrays = cluster.to_arrays()
        # per-policy genome store, seeded from every registered policy's
        # GenomeSpec defaults; explicit ctor args override their slot
        self._params: Dict[str, np.ndarray] = {}
        for name in list_policies():
            spec = get_policy(name).genome_spec
            if spec.defaults is not None:
                self._params[name] = np.asarray(spec.defaults, np.float32)
        if thresholds is not None:
            self._params["threshold"] = np.asarray(thresholds, np.float32)
        if slo_params is not None:
            self._params["slo"] = np.asarray(slo_params, np.float32)
        if affinity_params is not None:
            self._params["affinity"] = np.asarray(affinity_params, np.float32)
        if params is not None:
            self._params[self.policy.name] = np.asarray(params, np.float32)
        self.cache_block = cache_block
        self._slo_ttft, self._slo_tpot = slo_arrays(slo_table)
        self.monitor = monitor or ClusterMonitor(len(cluster.nodes))
        self.hedge_factor = hedge_factor
        # optional repro.obs.AuditLog: every route() call logs its
        # per-candidate decision breakdown (None = zero overhead)
        self.audit = audit
        # optional repro.obs.Timeline: each route() is a router.route span
        self.timeline = timeline
        self._rng = np.random.default_rng(0)
        # numpy view of the pair table, converted once: the per-request hot
        # path must not pay device-to-host transfers on every decision
        self._np_arrays = self.arrays.numpy()
        # online learned estimators (repro.learn): corrections ride on the
        # monitor so the scheduler's completion path can feed observations
        # without holding a router reference
        self.learned = learned
        self.learner = learner
        if learned and self.monitor.estimator is None:
            self.monitor.estimator = OnlineEstimator(
                learner, len(cluster.nodes),
                node_conc=self._np_arrays.node_conc)
        self._pair_node = self._np_arrays.pair_node
        self._pair_is_edge = self._np_arrays.pair_is_edge
        self._n_pairs = len(self._pair_node)
        self._pstate = self.policy.init_state()  # per-policy runtime state
        self._history: list = []        # Observation rolling window
        self._archive = None            # (P, D) genomes from the last re-opt
        self._n_recorded = 0            # monotone (history list is trimmed)
        self._last_reopt_at = 0         # _n_recorded at the last re-fit
        self._n_routed = 0              # decision counter (PolicyInputs.index)

    # -- params compatibility views ------------------------------------------
    @property
    def mode(self) -> str:
        return self.policy.name

    @property
    def params(self) -> np.ndarray:
        """Active policy's genome."""
        return self._params[self.policy.name]

    @params.setter
    def params(self, value) -> None:
        self._params[self.policy.name] = np.asarray(value, np.float32)

    @property
    def thresholds(self) -> np.ndarray:
        return self._params["threshold"]

    @thresholds.setter
    def thresholds(self, value) -> None:
        self._params["threshold"] = np.asarray(value, np.float32)

    @property
    def slo_params(self) -> np.ndarray:
        return self._params["slo"]

    @slo_params.setter
    def slo_params(self, value) -> None:
        self._params["slo"] = np.asarray(value, np.float32)

    @property
    def affinity_params(self) -> np.ndarray:
        return self._params["affinity"]

    @affinity_params.setter
    def affinity_params(self, value) -> None:
        self._params["affinity"] = np.asarray(value, np.float32)

    # -- hot path -------------------------------------------------------------
    def route(self, req: Request, want_backup: bool = False,
              ttft_deadline: Optional[float] = None,
              tpot_deadline: Optional[float] = None,
              now: Optional[float] = None) -> RouteDecision:
        """Route one request through the active policy.

        Explicit per-request deadlines override the per-category SLO table
        defaults (consumed by policies declaring the "deadlines"
        requirement). ``now`` is the decision timestamp for time-windowed
        policies (e.g. the budget ledger); it defaults to the router's
        request counter (pseudo-seconds: one window = WINDOW_S requests).
        Callers driving a time-windowed policy under real/simulated
        timestamps — in particular anyone also passing ``now=`` to
        :meth:`record`, whose re-fit evaluates the genome against those
        recorded trace-seconds — must pass the same clock here, or the
        tuned window budget is applied on a different time base than the
        one NSGA-II optimized it for."""
        with self.timeline.span("router.route"):
            return self._route(req, want_backup, ttft_deadline,
                               tpot_deadline, now)

    def _route(self, req: Request, want_backup: bool,
               ttft_deadline: Optional[float],
               tpot_deadline: Optional[float],
               now: Optional[float]) -> RouteDecision:
        pol = self.policy
        pred_cat, conf = classify(req, self._rng)
        c_i = complexity_score(req, pred_cat)
        queue = self.monitor.queue_lengths()
        healthy = self.monitor.healthy_mask()

        # mask unhealthy nodes by making their queues look infinite
        masked_queue = np.asarray(
            [q if healthy[j] else 10 ** 6 for j, q in enumerate(queue)],
            np.int64)

        zeros = np.zeros(self._n_pairs, np.float32)
        up = prefill = tpot = cost = prompt_cost = zeros
        if "estimates" in pol.requires:
            est = request_pair_estimates(req.prompt_tokens,
                                         req.resp_tokens_mean,
                                         req.query_bytes, self._np_arrays)
            # unhealthy nodes: push their pairs out of feasibility
            dead = ~np.asarray(healthy)[self._pair_node]
            up = np.where(dead, np.float32(1e9), est["up"])
            prefill, tpot = est["prefill"], est["tpot"]
            cost, prompt_cost = est["cost"], est["prompt_cost"]
        # static expected-quality prior: the build_tables q_mean formula with
        # the observable complexity score standing in for latent difficulty
        quality_row = np.clip(
            np.asarray(self._np_arrays.pair_base_quality,
                       np.float32)[:, req.task_id]
            + np.asarray(self._np_arrays.pair_diff_slope, np.float32)
            * (np.float32(0.5) - np.float32(c_i)),
            np.float32(0.0), np.float32(1.0)).astype(np.float32)
        unc_row = zeros
        if self.learned and self.monitor.estimator is not None:
            d_p, d_t, d_q, unc_n = self.monitor.estimator.predict(
                pred_cat, req.prompt_tokens, c_i, masked_queue,
                self._np_arrays.node_conc)
            prefill, tpot, quality_row, unc_row = learn_est.corrected_rows(
                np, np.asarray(prefill, np.float32),
                np.asarray(tpot, np.float32), quality_row, d_p, d_t, d_q,
                unc_n, self._pair_node)
        ttft_dl = (ttft_deadline if ttft_deadline is not None
                   else float(self._slo_ttft[pred_cat]))
        tpot_dl = (tpot_deadline if tpot_deadline is not None
                   else float(self._slo_tpot[pred_cat]))
        hit = zeros
        if "cache" in pol.requires:
            hit_node = self.monitor.hit_fractions(
                getattr(req, "session_id", -1),
                getattr(req, "sys_id", -1), float(req.prompt_tokens),
                float(getattr(req, "sys_tokens", 0)),
                block=self.cache_block)
            hit = np.asarray(hit_node, np.float32)[self._pair_node]
        kv_bytes = zeros
        if "transfer" in pol.requires:
            blk = float(self.cache_block)
            kv_blk = np.float32(np.floor(
                np.float32(req.prompt_tokens) / np.float32(blk)) * blk)
            kv_bytes = (kv_blk * np.asarray(
                self._np_arrays.pair_kv_bytes_per_token,
                np.float32)).astype(np.float32)

        inp = PolicyInputs(
            index=np.int32(self._n_routed),
            now=np.float32(self._n_routed if now is None else now),
            complexity=np.float32(c_i), pred_category=np.int32(pred_cat),
            pred_conf=np.float32(conf), ttft_deadline=np.float32(ttft_dl),
            tpot_deadline=np.float32(tpot_dl),
            prompt_tokens=np.float32(req.prompt_tokens),
            up=up, prefill=prefill, tpot=tpot, cost=cost,
            prompt_cost=prompt_cost, hit_frac=hit, queue_len=masked_queue,
            kv_bytes=kv_bytes, quality=quality_row, unc=unc_row)
        decision = int(pol.decide_py(self.params, inp, self._np_arrays,
                                     self._pstate))
        raw_decision = decision
        failover = None

        prefill_pair = None
        if pol.decides == "route":
            # route-valued decision: resolve the (prefill, decode) legs;
            # ``pair`` is the decode pair from here on
            rp = self._np_arrays.route_prefill
            rq = self._np_arrays.route_decode
            prefill_pair, pair = int(rp[decision]), int(rq[decision])
            node_p = int(self._pair_node[prefill_pair])
            node = int(self._pair_node[pair])
            if not (healthy[node_p] and healthy[node]):
                # failover: re-pick among routes with both endpoints healthy,
                # preferring colocated ones (no handoff exposure while the
                # cluster is degraded), then least-loaded decode node
                cands = [r for r in range(len(rp))
                         if healthy[self._pair_node[rp[r]]]
                         and healthy[self._pair_node[rq[r]]]]
                if not cands:
                    raise RuntimeError("no healthy nodes in cluster")
                colo = [r for r in cands if rp[r] == rq[r]]
                pool = colo or cands
                decision = min(pool,
                               key=lambda r: queue[self._pair_node[rq[r]]])
                prefill_pair, pair = int(rp[decision]), int(rq[decision])
                node = int(self._pair_node[pair])
                failover = "route-endpoint-down"
        else:
            pair = decision
            node = int(self._pair_node[pair])

            # failover: if the policy returned a pair on a dead node (e.g.
            # the cloud fallback itself is down), pick any healthy pair
            if not healthy[node]:
                alive = [p for p in range(self._n_pairs)
                         if healthy[self._pair_node[p]]]
                if not alive:
                    raise RuntimeError("no healthy nodes in cluster")
                # prefer healthy cloud, then least-loaded healthy edge
                cloud_alive = [p for p in alive if not self._pair_is_edge[p]]
                pair = (cloud_alive[0] if cloud_alive else
                        min(alive, key=lambda p: queue[self._pair_node[p]]))
                node = int(self._pair_node[pair])
                failover = "node-down"

        # policy state advances on the pair actually dispatched (post
        # failover) so e.g. the budget ledger bills real spend, and only for
        # requests that are dispatched at all (the no-healthy-nodes raise
        # above leaves the state untouched)
        self._pstate = pol.update_py(self.params, self._pstate, inp, pair,
                                     float(cost[pair]))
        self._n_routed += 1

        backup = None
        if want_backup:
            backup = self.backup_pair(pair)
        if self.audit is not None:
            self.audit.record(
                int(inp.index), float(inp.now), pol.name, pol.decides,
                self.params, raw_decision, pair, node,
                prefill_pair=prefill_pair, failover=failover,
                healthy=np.asarray(healthy, np.float64), queue=masked_queue,
                category=int(pred_cat),
                up=up if "estimates" in pol.requires else None,
                prefill=prefill if "estimates" in pol.requires else None,
                tpot=tpot if "estimates" in pol.requires else None,
                cost=cost if "estimates" in pol.requires else None,
                hit=hit if "cache" in pol.requires else None,
                est_cost=float(cost[pair]), backup_pair=backup)
        # the estimates this decision acted on (TTFT on the prefill leg,
        # TPOT on the decode pair) — the "expected" side of the estimator's
        # residual targets fed back via record()
        pp = pair if prefill_pair is None else prefill_pair
        return RouteDecision(
            pair=int(pair), node=node,
            model=int(self._np_arrays.pair_model[pair]),
            go_edge=bool(self._pair_is_edge[pair]),
            features=(c_i, pred_cat, conf), backup_pair=backup,
            prefill_pair=prefill_pair, est_cost=float(cost[pair]),
            est_ttft=float(up[pp] + prefill[pp]),
            est_tpot=float(tpot[pair]),
            est_quality=float(quality_row[pair]))

    def backup_pair(self, primary: int) -> Optional[int]:
        """A healthy pair on a *different* node, for hedged duplicates."""
        healthy = self.monitor.healthy_mask()
        pnode = int(self._pair_node[primary])
        cands = [p for p in range(self._n_pairs)
                 if int(self._pair_node[p]) != pnode
                 and healthy[self._pair_node[p]]]
        if not cands:
            return None
        # cheapest viable alternative: cloud if primary was edge, else the
        # least-loaded edge instruct pair
        queue = self.monitor.queue_lengths()
        return min(cands, key=lambda p: (queue[self._pair_node[p]],
                                         self._pair_is_edge[p]))

    # -- feedback & re-optimization --------------------------------------------
    def record(self, req: Request, decision: RouteDecision, quality: float,
               cost: float, rt: float, now: Optional[float] = None,
               ttft_deadline: Optional[float] = None,
               tpot_deadline: Optional[float] = None,
               ttft: Optional[float] = None,
               tpot: Optional[float] = None) -> None:
        """Append one served request + realized objectives to the rolling
        history window ``maybe_reoptimize`` re-fits against. ``now`` is the
        request's arrival timestamp (enables open-loop re-fitting); the
        deadline pair is its QoE contract if it carried one. Realized
        ``ttft``/``tpot`` (caller clock units — the estimator's ratio
        residual absorbs the scale) close the learning loop: each is turned
        into a realized-vs-estimated residual against the decision's own
        estimates and fed to the monitor's :class:`OnlineEstimator`."""
        est_obj = self.monitor.estimator
        if est_obj is not None and (ttft is not None or tpot is not None):
            y_p = (OnlineEstimator.ratio(decision.est_ttft, ttft)
                   if ttft is not None else 0.0)
            y_t = (OnlineEstimator.ratio(decision.est_tpot, tpot)
                   if tpot is not None else 0.0)
            y_q = (float(np.float32(quality)
                         - np.float32(decision.est_quality))
                   if decision.est_quality > 0.0 else 0.0)
            node_p = (decision.node if decision.prefill_pair is None
                      else int(self._pair_node[decision.prefill_pair]))
            self.monitor.feed_estimator(
                int(decision.features[1]), node_p, decision.node,
                req.prompt_tokens, float(decision.features[0]),
                y_p, y_t, y_q)
        self._history.append(Observation(
            req=req, pair=decision.pair, features=decision.features,
            quality=quality, cost=cost, rt=rt, now=now,
            ttft_deadline=ttft_deadline, tpot_deadline=tpot_deadline))
        self._n_recorded += 1
        if len(self._history) > 10000:
            self._history = self._history[-5000:]

    @property
    def history_size(self) -> int:
        return len(self._history)

    def should_reoptimize(self, drift_threshold: float = 0.25,
                          min_history: int = 64,
                          min_new: int = 32) -> bool:
        """Drift trigger: re-optimize when the monitor's fast EWMA latency
        has moved more than ``drift_threshold`` (relative) away from its slow
        baseline, enough history is banked to re-fit on, and at least
        ``min_new`` requests were observed since the last re-fit (cooldown —
        together with the post-re-fit drift re-baseline this makes one
        regime shift trigger one re-fit, not one per check)."""
        return (len(self._history) >= min_history
                and self._n_recorded - self._last_reopt_at >= min_new
                and self.monitor.drift_score() >= drift_threshold)

    def maybe_reoptimize(self, window: int = 256, generations: int = 20,
                         pop_size: int = 32,
                         weights: Optional[Sequence[float]] = None,
                         seed: int = 0, concurrency: int = 4,
                         drift_threshold: float = 0.25,
                         min_history: int = 64,
                         force: bool = False) -> Optional[np.ndarray]:
        """Rolling-horizon re-optimization (paper §IV-B.6, made real).

        Unless ``force``, runs only when :meth:`should_reoptimize` fires.
        Re-fits a small NSGA-II against the last ``window`` *recorded*
        requests: the observed trace is rebuilt with
        ``workload.trace.trace_from_requests`` (open-loop at the recorded
        arrival timestamps when every observation carries one, closed-loop
        with ``concurrency`` clients otherwise; with the recorded deadlines
        and the 4-objective QoE fitness when every observation carries a
        contract). The genome encoding and fitness kind come from the active
        policy's registry entry, so any registered policy re-fits here. The
        search is warm-started from the previous re-opt's survival-ordered
        population (``evolve_scan(..., archive=)``), then the Eq. (1)
        weighted-sum pick (uniform ``weights`` by default) replaces the live
        policy parameters. Returns them, or None if skipped.
        """
        from ..workload.trace import trace_from_requests
        from .fitness import EvalConfig, TraceEvaluator
        from .nsga2 import NSGA2, NSGA2Config

        if not force and not self.should_reoptimize(drift_threshold,
                                                    min_history):
            return None
        obs = self._history[-window:]
        if not obs:
            return None
        pol = self.policy

        arrivals = None
        if all(o.now is not None for o in obs):
            t = np.asarray([o.now for o in obs], np.float32)
            if (np.diff(t) >= 0).all():
                arrivals = t
        trace = trace_from_requests([o.req for o in obs], seed=seed,
                                    arrival_time=arrivals)
        # re-fit against the features the live router actually observed and
        # acted on, not a fresh classifier noise draw
        trace.complexity = np.asarray([o.features[0] for o in obs],
                                      np.float32)
        trace.pred_category = np.asarray([o.features[1] for o in obs],
                                         np.int32)
        trace.pred_conf = np.asarray([o.features[2] for o in obs],
                                     np.float32)
        if all(o.ttft_deadline is not None and o.tpot_deadline is not None
               for o in obs):
            trace.ttft_deadline = np.asarray(
                [o.ttft_deadline for o in obs], np.float32)
            trace.tpot_deadline = np.asarray(
                [o.tpot_deadline for o in obs], np.float32)
        elif "deadlines" in pol.requires:
            # deadline-aware genomes are meaningless against +inf deadlines
            # (every parameter vector is equally feasible -> degenerate flat
            # fitness): fall back to the per-category table defaults
            # route() applies
            cat = trace.pred_category
            trace.ttft_deadline = self._slo_ttft[cat].astype(np.float32)
            trace.tpot_deadline = self._slo_tpot[cat].astype(np.float32)

        cfg_eval = EvalConfig(
            mode="open" if arrivals is not None else "queued",
            concurrency=concurrency,
            # re-fit against the cache dynamics the window actually had
            prefix_cache=(arrivals is not None and trace.has_sessions),
            cache_block=self.cache_block,
            # route-valued policies re-fit against the disaggregated model
            disaggregated=pol.decides == "route",
            # re-fit with the same estimator loop the live router runs, so
            # the tuned genome is optimal for corrected (not static-prior)
            # estimate rows
            learned=self.learned, learner=self.learner)
        # bucketed (compile-once) evaluation: windows of different lengths
        # pad to the same power-of-two bucket, so every re-fit after the
        # first reuses the compiled trace-eval + NSGA-II executables instead
        # of paying an XLA retrace per drifting window
        evaluator = TraceEvaluator(trace, self.cluster, cfg_eval,
                                   bucket="pow2")

        # genome encoding from the active policy's registry entry
        cfg = NSGA2Config.from_policy(pol, pop_size=pop_size,
                                      n_generations=generations)
        objectives = "qoe" if trace.has_slos else "paper"
        opt = NSGA2(evaluator.make_fitness(pol.name, objectives=objectives),
                    cfg)
        # warm start from the previous re-fit's survival-ordered population;
        # the archive is a dynamic argument (same shape every re-fit), so
        # warm-started runs share the compiled executable too
        archive = self._archive
        if archive is not None and archive.shape[1] != cfg.n_genes:
            archive = None              # policy switched since the last fit
        state = opt.evolve_scan(jax.random.key(seed), generations,
                                archive=archive)
        # archive the survival-ordered population for the next warm start
        self._archive = np.asarray(state.genomes)

        M = state.F_raw.shape[1]
        w = (jnp.full((M,), 1.0 / M) if weights is None
             else jnp.asarray(weights, jnp.float32))
        genome, _ = opt.select_by_weights(state, w)
        self.params = np.asarray(genome, np.float32)
        # cooldown: re-arm the drift detector for the *next* regime shift
        self._last_reopt_at = self._n_recorded
        self.monitor.rebaseline_drift()
        return self.params
