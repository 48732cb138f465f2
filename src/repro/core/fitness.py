"""Vectorized trace evaluation for NSGA-II fitness (paper §IV-B.2).

The evaluator turns a routing decision for every request in the trace into the
three objectives (RQ, C, RT) of Eqs. (2)–(4). Two execution models:

* ``mode="eq5"`` — the paper's Eq. (5) exactly: RT_i = upload + T_infer +
  download, no queueing (this is what Table II measures at concurrency 1).
* ``mode="queued"`` — closed-loop with G concurrent clients and per-node
  execution slots (capacity C_j): requests wait for a free slot, which
  reproduces the Fig. 4 concurrency behaviour and enforces the §III resource
  constraint (a policy that floods one node accrues unbounded waits →
  constraint violation via the W_MAX stability bound).
* ``mode="open"`` — open-loop: request i enters the system at the trace's
  ``arrival_time[i]`` (generated by ``workload.arrivals``) instead of being
  released by a closed-loop client; queueing discipline is identical to
  ``queued``. This is the dynamic-workload regime the rolling-horizon
  re-optimization loop (``core.router.maybe_reoptimize``) adapts to, and the
  JAX/DES equivalence property extends to it.

Beyond the paper, the evaluator does **phase-split accounting**: every
request's response time is decomposed into TTFT (upload + queue wait +
prefill — time to first token) and TPOT (decode seconds per output token),
mirroring the prefill/decode split of ``serving.engine``. With per-request
deadlines attached to the trace (``workload.slo``), ``make_fitness`` can
expose SLO violation as a fourth objective ("qoe") and ``_run_trace`` can run
the SLO-aware policy (``policy="slo"``) whose in-scan decisions depend on the
live queue *and* the request's deadline pair.

Beyond that, the evaluator models **prefix-cache dynamics**
(``EvalConfig(prefix_cache=True)`` on a session trace from
``workload.sessions``): a whole-block prefix served on a node stays cached
there, so a later request of the same session (or sharing the same system
prompt) pays only the uncached fraction of its prefill time and a discounted
price for cached prompt tokens. The cache state is part of the scan carry —
per (node × session) and (node × system-prompt) cached-prefix lengths — and
the discounts apply to **every** policy (the cache is physical), while only
the ``affinity`` policy *observes* the expected hit fraction when deciding
(``core.policy.decide_pair_affinity_jnp``). Both DES oracles mirror the
model so the JAX/DES equivalence property extends to it.

Everything static per (trace × cluster) is precomputed into ``EvalTables``
(I × n_pairs matrices); the jitted scan only resolves queue dynamics, so a
population×trace evaluation is one fused XLA program:

    vmap over P policies ∘ lax.scan over I requests ∘ O(n_nodes) queue update

Routing decisions happen *inside* the scan (they depend on live queue,
deadline, and cache state) and are dispatched through the **RoutingPolicy
registry** (``core.policies``): the policy name is a jit-static argument of
``_run_trace``, the scan body builds one uniform ``PolicyInputs`` bundle and
calls ``policy.decide_jnp`` — so a newly registered policy module is
evaluable (and NSGA-II-searchable via :meth:`TraceEvaluator.make_fitness`)
with zero edits here. Policies may thread a small float32 state vector
through the scan carry (``RoutingPolicy.state_size``, e.g. the budget
policy's spend ledger); it is frozen on bucketed-padding rows like every
other carry.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..cluster.spec import ClusterArrays, ClusterSpec
from ..faults import (FaultSchedule, jnp_tables, link_slowdown_jnp,
                      node_available_jnp, node_slowdown_jnp,
                      transient_delay_jnp)
from ..learn import LearnConfig
from ..learn import estimators as learn_est
from ..workload.trace import Trace
from .objectives import slo_ok
from .policies import PolicyInputs, get_policy
from .policies.affinity import CACHED_TOKEN_PRICE_FACTOR

# fault-masking sentinels — the runtime router uses the same constants for
# dead nodes (core/router.py), so masked PolicyInputs agree across layers
DEAD_QUEUE = 10**6
DEAD_UP = 1e9

RESP_BYTES_PER_TOKEN = 4.2  # avg UTF-8 payload bytes per generated token


class EvalTables(NamedTuple):
    """(I, n_pairs) static tables + per-request arrays for the evaluator."""

    quality: jnp.ndarray      # realized q(r_i, pair)
    quality_mean: jnp.ndarray  # noise-free E[q(r_i, pair)] (learned priors)
    cost: jnp.ndarray         # Eq. 3 per-request cost
    service: jnp.ndarray      # T_infer (prefill + decode)
    up_time: jnp.ndarray      # Q_size/B_up + latency_up
    down_time: jnp.ndarray    # R_size/B_down + latency_down
    # phase split (QoE accounting)
    prefill_time: jnp.ndarray  # (I, n_pairs) prompt/prefill_tps
    tpot: jnp.ndarray          # (n_pairs,) decode seconds per output token
    # per-request features for in-scan routing (threshold policies)
    complexity: jnp.ndarray   # (I,)
    pred_category: jnp.ndarray  # (I,) int32 (0=code, 1=math, 2=general)
    pred_conf: jnp.ndarray    # (I,)
    # per-request QoE contract (+inf when the trace carries no SLOs)
    ttft_deadline: jnp.ndarray  # (I,)
    tpot_deadline: jnp.ndarray  # (I,)
    # open-loop arrival timestamps (zeros when the trace is closed-loop)
    arrival_time: jnp.ndarray   # (I,)
    # prefix-cache model inputs (session traces; degenerate otherwise)
    prompt_cost: jnp.ndarray    # (I, n_pairs) prompt-only part of `cost`
    prompt_tokens: jnp.ndarray  # (I,)
    group_id: jnp.ndarray       # (I,) int32 session id, -1 = single-shot
    sys_id: jnp.ndarray         # (I,) int32 shared-system-prompt id, -1=none
    sys_tokens: jnp.ndarray     # (I,)
    # bucketed-evaluation mask: False rows are padding appended by
    # ``pad_tables`` — they contribute nothing to objectives, violation,
    # queue or cache state (all ones for unpadded traces)
    valid: jnp.ndarray          # (I,) bool


def request_pair_estimates(prompt_tokens: float, resp_tokens_mean: float,
                           query_bytes: float, arrays: ClusterArrays
                           ) -> dict:
    """Per-pair phase/cost estimates for ONE request (numpy, router hot path).

    Returns float32 (n_pairs,) vectors ``up``, ``prefill``, ``tpot``,
    ``cost`` using the same formulas as ``build_tables`` so the runtime
    router's SLO decisions agree with the offline evaluator.
    """
    verb = np.asarray(arrays.pair_verbosity, np.float32)
    resp_tokens = np.maximum(np.round(np.float32(resp_tokens_mean) * verb), 1.0)
    price = np.asarray(arrays.pair_price, np.float32)
    cost = (np.float32(prompt_tokens) + resp_tokens) / 1e6 * price
    prompt_cost = np.float32(prompt_tokens) / np.float32(1e6) * price
    prefill = np.float32(prompt_tokens) / np.asarray(arrays.pair_prefill_tps,
                                                     np.float32)
    tpot = np.float32(1.0) / np.asarray(arrays.pair_decode_tps, np.float32)
    node = np.asarray(arrays.pair_node)
    up = (np.float32(query_bytes) / np.asarray(arrays.node_bw_up,
                                               np.float32)[node]
          + np.asarray(arrays.node_lat_up, np.float32)[node])
    return {"up": up.astype(np.float32), "prefill": prefill.astype(np.float32),
            "tpot": tpot.astype(np.float32), "cost": cost.astype(np.float32),
            "prompt_cost": prompt_cost.astype(np.float32)}


def build_tables(trace: Trace, cluster: ClusterSpec, seed: int = 0
                 ) -> Tuple[EvalTables, ClusterArrays]:
    """Precompute all queue-independent quantities."""
    arrays = cluster.to_arrays()
    I = trace.n_requests
    Pn = arrays.n_pairs

    task = trace.task                          # (I,)
    prompt = trace.prompt_tokens.astype(np.float32)
    resp_mean = trace.resp_tokens_mean
    difficulty = trace.difficulty
    qbytes = trace.query_bytes

    verb = np.asarray(arrays.pair_verbosity)   # (Pn,)
    resp_tokens = np.maximum(np.round(resp_mean[:, None] * verb[None, :]), 1.0)

    price = np.asarray(arrays.pair_price)
    total_tokens = prompt[:, None] + resp_tokens
    cost = total_tokens / 1e6 * price[None, :]                     # Eq. 3

    prefill = prompt[:, None] / np.asarray(arrays.pair_prefill_tps)[None, :]
    decode = resp_tokens / np.asarray(arrays.pair_decode_tps)[None, :]
    service = prefill + decode
    tpot = 1.0 / np.asarray(arrays.pair_decode_tps)

    node = np.asarray(arrays.pair_node)
    up = (qbytes[:, None] / np.asarray(arrays.node_bw_up)[node][None, :]
          + np.asarray(arrays.node_lat_up)[node][None, :])
    resp_bytes = resp_tokens * RESP_BYTES_PER_TOKEN
    down = (resp_bytes / np.asarray(arrays.node_bw_down)[node][None, :]
            + np.asarray(arrays.node_lat_down)[node][None, :])

    base_q = np.asarray(arrays.pair_base_quality)  # (Pn, n_tasks)
    slope = np.asarray(arrays.pair_diff_slope)
    rng = np.random.default_rng(np.random.SeedSequence([seed, 777]))
    noise = rng.normal(0.0, 0.05, size=(I, Pn)).astype(np.float32)
    q_mean = base_q.T[task, :] + slope[None, :] * (0.5 - difficulty[:, None])
    quality = np.clip(q_mean + noise, 0.0, 1.0)
    # noise-free expected quality: the static prior the learned-quality
    # estimators (repro.learn) correct, and the "quality" PolicyInputs row
    quality_mean = np.clip(q_mean, 0.0, 1.0)

    if trace.has_slos:
        ttft_dl = trace.ttft_deadline
        tpot_dl = trace.tpot_deadline
    else:
        ttft_dl = np.full(I, np.inf, np.float32)
        tpot_dl = np.full(I, np.inf, np.float32)

    tables = EvalTables(
        quality=jnp.asarray(quality, jnp.float32),
        quality_mean=jnp.asarray(quality_mean, jnp.float32),
        cost=jnp.asarray(cost, jnp.float32),
        service=jnp.asarray(service, jnp.float32),
        up_time=jnp.asarray(up, jnp.float32),
        down_time=jnp.asarray(down, jnp.float32),
        prefill_time=jnp.asarray(prefill, jnp.float32),
        tpot=jnp.asarray(tpot, jnp.float32),
        complexity=jnp.asarray(trace.complexity, jnp.float32),
        pred_category=jnp.asarray(trace.pred_category, jnp.int32),
        pred_conf=jnp.asarray(trace.pred_conf, jnp.float32),
        ttft_deadline=jnp.asarray(ttft_dl, jnp.float32),
        tpot_deadline=jnp.asarray(tpot_dl, jnp.float32),
        arrival_time=jnp.asarray(
            trace.arrival_time if trace.has_arrivals else np.zeros(I),
            jnp.float32),
        prompt_cost=jnp.asarray(prompt[:, None] / 1e6 * price[None, :],
                                jnp.float32),
        prompt_tokens=jnp.asarray(prompt, jnp.float32),
        group_id=jnp.asarray(
            trace.group_id if trace.has_sessions else -np.ones(I), jnp.int32),
        sys_id=jnp.asarray(
            trace.sys_id if trace.sys_id is not None else -np.ones(I),
            jnp.int32),
        sys_tokens=jnp.asarray(
            trace.sys_tokens if trace.sys_tokens is not None else np.zeros(I),
            jnp.float32),
        valid=jnp.ones((I,), bool),
    )
    return tables, arrays


# ---------------------------------------------------------------------------
# Bucketed (compile-once) evaluation
#
# ``_run_trace`` is jitted on the table *shapes*: historically every distinct
# window length I retraced and recompiled it, which made the rolling-horizon
# re-fit pay seconds of XLA compile per drifting window. ``pad_tables`` pads
# all (I, ...) arrays up to a size bucket with a validity mask; the scan
# neutralizes masked rows (zero objective/violation contributions, frozen
# queue/cache/client state), so one compiled executable serves every window
# that lands in the same bucket.
# ---------------------------------------------------------------------------

def next_pow2(n: int) -> int:
    return 1 if n <= 1 else 1 << (int(n) - 1).bit_length()


def bucket_size(n: int, bucket) -> int:
    """Padded length for ``n`` requests: "pow2" -> next power of two,
    an int -> next multiple of that int."""
    if bucket == "pow2":
        return next_pow2(n)
    b = int(bucket)
    assert b > 0
    return -(-n // b) * b


def pad_tables(tables: EvalTables, n: int) -> EvalTables:
    """Pad every per-request array of ``tables`` to ``n`` rows, marking the
    tail invalid. Padded rows carry neutral values (``-1`` ids, ``+inf``
    deadlines, edge-replicated arrivals) but the ``valid`` mask is what
    guarantees they cannot perturb objectives or in-scan state."""
    I = int(tables.quality.shape[0])
    assert n >= I, f"bucket {n} smaller than trace ({I})"
    if n == I:
        return tables
    pad = n - I

    def rows(a):
        return jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))

    def const(a, value):
        return jnp.pad(a, (0, pad), constant_values=value)

    return tables._replace(
        quality=rows(tables.quality),
        quality_mean=rows(tables.quality_mean),
        cost=rows(tables.cost),
        service=rows(tables.service),
        up_time=rows(tables.up_time),
        down_time=rows(tables.down_time),
        prefill_time=rows(tables.prefill_time),
        complexity=rows(tables.complexity),
        pred_category=rows(tables.pred_category),
        pred_conf=rows(tables.pred_conf),
        ttft_deadline=const(tables.ttft_deadline, jnp.inf),
        tpot_deadline=const(tables.tpot_deadline, jnp.inf),
        arrival_time=jnp.pad(tables.arrival_time, (0, pad), mode="edge"),
        prompt_cost=rows(tables.prompt_cost),
        prompt_tokens=rows(tables.prompt_tokens),
        group_id=const(tables.group_id, -1),
        sys_id=const(tables.sys_id, -1),
        sys_tokens=rows(tables.sys_tokens),
        valid=const(tables.valid, False),
    )


@dataclasses.dataclass(frozen=True)
class EvalConfig:
    concurrency: int = 1          # G closed-loop clients (ignored by "open")
    mode: str = "queued"          # "eq5" | "queued" | "open"
    w_max: float = 30.0           # stability bound: wait beyond this = violation
    # prefix-cache model (session traces, open-loop only: the cache state
    # evolves in dispatch order, which the DES oracles replay in time order)
    prefix_cache: bool = False
    cache_block: int = 16         # whole-block sharing granularity (tokens)
    # disaggregated prefill/decode: decisions index the cluster's route
    # table (route-valued policies, ``RoutingPolicy.decides == "route"``);
    # split routes pay a KV-transfer phase between the prefill finish and
    # the decode start, sized prompt_blocks × bytes_per_block over the
    # node-to-node KV link
    disaggregated: bool = False
    # fault injection (repro.faults): when True the scan consumes a
    # FaultTables pytree — crashed nodes are masked out of PolicyInputs
    # (DEAD_QUEUE/DEAD_UP, the router's own sentinels) with a deterministic
    # failover, stragglers stretch service time, link flaps stretch KV
    # transfers, and transient errors delay the effective arrival. Static
    # so the fault-free compiled program is byte-identical to before.
    faulty: bool = False
    # online-learned estimators (repro.learn): when True the scan threads a
    # per-(node, category) residual-estimator state through the carry,
    # overrides the prefill/tpot estimate rows of PolicyInputs with
    # corrected values (and fills the quality/unc rows from the learned
    # posterior), and updates the state from each dispatched request's
    # realized (prefill-ratio, tpot-ratio, quality-delta) observations.
    # Residuals seed at zero, so cold-start decisions are byte-identical to
    # learned=False (the estimators *are* seeded from the static tables).
    learned: bool = False
    learner: LearnConfig = LearnConfig()

    def __post_init__(self):
        assert self.mode in ("eq5", "queued", "open")
        assert not self.prefix_cache or self.open_loop, \
            "prefix_cache needs open-loop traces (deterministic cache order)"

    @property
    def open_loop(self) -> bool:
        return self.mode in ("open",)

    @property
    def is_eq5(self) -> bool:
        return self.mode in ("eq5",)


class EvalResult(NamedTuple):
    q: jnp.ndarray        # (I,) realized quality
    cost: jnp.ndarray     # (I,)
    rt: jnp.ndarray       # (I,)
    assign: jnp.ndarray   # (I,) chosen pair (or route when disaggregated)
    violation: jnp.ndarray  # scalar
    ttft: jnp.ndarray     # (I,) time to first token (up + wait + prefill)
    tpot: jnp.ndarray     # (I,) decode seconds per output token
    hit: jnp.ndarray      # (I,) realized cached-prefix fraction (0 w/o cache)
    # KV-transfer seconds between prefill finish and decode start; exactly
    # 0 on colocated routes and in non-disaggregated evaluation
    transfer: jnp.ndarray = jnp.zeros(())  # (I,)


def _max_conc(arrays: ClusterArrays) -> int:
    return int(np.max(np.asarray(arrays.node_conc)))


@functools.partial(jax.jit,
                   static_argnames=("policy", "cfg", "n_slots", "n_groups"))
def _run_trace(genome: jnp.ndarray, policy: str, tables: EvalTables,
               arrays: ClusterArrays, cfg: EvalConfig, n_slots: int,
               n_groups: Tuple[int, int] = (1, 1),
               faults=None) -> EvalResult:
    pol = get_policy(policy)        # static per jit key (policy identity)
    I = tables.quality.shape[0]
    G = cfg.concurrency
    n_nodes = arrays.node_conc.shape[0]
    n_sess, n_sys = n_groups
    blk = float(cfg.cache_block)

    # slot_free[n, s] = time when slot s of node n becomes free;
    # slots beyond a node's concurrency are pinned at +inf (never chosen).
    slot_ids = jnp.arange(n_slots)[None, :]
    slot_valid = slot_ids < arrays.node_conc[:, None]
    init_slots = jnp.where(slot_valid, 0.0, jnp.inf)
    init_clients = jnp.zeros((G,), jnp.float32)
    # cached-prefix tokens resident per (node, session) / (node, sys prompt)
    init_sess = jnp.zeros((n_nodes, n_sess), jnp.float32)
    init_sys = jnp.zeros((n_nodes, n_sys), jnp.float32)
    # per-policy scan state (e.g. the budget policy's spend ledger)
    init_pstate = jnp.asarray(pol.init_state(), jnp.float32)
    # learned-estimator state (repro.learn); empty when running on priors
    init_lstate = jnp.asarray(
        learn_est.init_state(cfg.learner, n_nodes) if cfg.learned
        else np.zeros((0,), np.float32), jnp.float32)

    def body(carry, i):
        slot_free, client_ready, c_sess, c_sys, pstate, lstate = carry
        v = tables.valid[i]          # False on bucketed-padding rows
        vf = v.astype(jnp.float32)
        arrival = (tables.arrival_time[i] if cfg.open_loop
                   else client_ready[i % G])

        if cfg.faulty:
            # transient error = the request's first dispatch fails and it
            # re-enters after a deterministic jittered backoff: decisions
            # and queue dynamics run at the *effective* arrival while
            # rt/ttft stay measured from the original arrival
            delay = transient_delay_jnp(faults, i)
            t_eff = arrival + delay
            avail = node_available_jnp(faults, t_eff)    # (n_nodes,) bool
            slow = node_slowdown_jnp(faults, t_eff)      # (n_nodes,) f32
            linkf = link_slowdown_jnp(faults, t_eff)     # () f32
        else:
            t_eff = arrival

        # monitor view: busy slots per node at arrival (the q_j feature)
        busy = jnp.sum(jnp.where(slot_valid, slot_free > t_eff, False),
                       axis=1).astype(jnp.int32)

        if cfg.prefix_cache:
            g = tables.group_id[i]
            y = tables.sys_id[i]
            blk_p = jnp.floor(tables.prompt_tokens[i] / blk) * blk
            blk_s = jnp.floor(tables.sys_tokens[i] / blk) * blk
            sess_hit = jnp.where(g >= 0,
                                 jnp.minimum(c_sess[:, jnp.maximum(g, 0)],
                                             blk_p), 0.0)
            sys_hit = jnp.where(y >= 0,
                                jnp.minimum(c_sys[:, jnp.maximum(y, 0)],
                                            blk_s), 0.0)
            hit_node = jnp.maximum(sess_hit, sys_hit)     # (n_nodes,)
            hit_pair = (hit_node / jnp.maximum(tables.prompt_tokens[i], 1.0)
                        )[arrays.pair_node]               # (n_pairs,)
        else:
            hit_pair = jnp.zeros((arrays.pair_node.shape[0],), jnp.float32)

        # whole-block KV footprint per pair (route policies read it; zeros
        # keep the non-disaggregated program unchanged)
        if cfg.disaggregated:
            kv_blk = jnp.floor(tables.prompt_tokens[i] / blk) * blk
            kv_bytes = kv_blk * arrays.pair_kv_bytes_per_token
        else:
            kv_bytes = jnp.zeros_like(tables.up_time[i])

        if cfg.faulty:
            # dead nodes are masked out of the policy's view with the
            # runtime router's own sentinels (core/router.py)
            busy_obs = jnp.where(avail, busy, DEAD_QUEUE).astype(jnp.int32)
            up_obs = jnp.where(avail[arrays.pair_node], tables.up_time[i],
                               jnp.float32(DEAD_UP))
        else:
            busy_obs = busy
            up_obs = tables.up_time[i]

        if cfg.learned:
            # learned-estimator correction: override the static prefill /
            # tpot estimate rows (and fill quality/unc) from the residual
            # posterior. Zero residuals reproduce the static rows bitwise,
            # so cold-start decisions equal the learned=False program.
            cat = tables.pred_category[i]
            x1, x2, x3 = learn_est.features(
                jnp, tables.prompt_tokens[i], tables.complexity[i],
                busy_obs, arrays.node_conc)
            d_p, d_t, d_q, unc_n = learn_est.predict_jnp(
                cfg.learner, lstate, n_nodes, cat, x1, x2, x3)
            prefill_row, tpot_row, quality_row, unc_row = \
                learn_est.corrected_rows(
                    jnp, tables.prefill_time[i], tables.tpot,
                    tables.quality_mean[i], d_p, d_t, d_q, unc_n,
                    arrays.pair_node)
        else:
            prefill_row = tables.prefill_time[i]
            tpot_row = tables.tpot
            quality_row = tables.quality_mean[i]
            unc_row = jnp.zeros_like(quality_row)

        inp = PolicyInputs(
            index=i, now=t_eff,
            complexity=tables.complexity[i],
            pred_category=tables.pred_category[i],
            pred_conf=tables.pred_conf[i],
            ttft_deadline=tables.ttft_deadline[i],
            tpot_deadline=tables.tpot_deadline[i],
            prompt_tokens=tables.prompt_tokens[i],
            up=up_obs, prefill=prefill_row,
            tpot=tpot_row, cost=tables.cost[i],
            prompt_cost=tables.prompt_cost[i], hit_frac=hit_pair,
            queue_len=busy_obs, kv_bytes=kv_bytes,
            quality=quality_row, unc=unc_row)
        decision = pol.decide_jnp(genome, inp, arrays, pstate
                                  ).astype(jnp.int32)

        if cfg.faulty:
            # deterministic failover off crashed nodes, mirrored op-for-op
            # by both DES oracles: prefer the lowest-index alive cloud pair
            # (alive colocated route in disagg), else lowest alive index
            if cfg.disaggregated:
                rp, rq = arrays.route_prefill, arrays.route_decode
                route_dead = (~avail[arrays.pair_node[rp]]
                              | ~avail[arrays.pair_node[rq]])
                rank = ((rp != rq).astype(jnp.float32) * 1e6
                        + jnp.arange(rp.shape[0], dtype=jnp.float32))
                fb = jnp.argmin(jnp.where(route_dead, jnp.inf, rank))
                decision = jnp.where(route_dead[decision], fb,
                                     decision).astype(jnp.int32)
            else:
                dead = ~avail[arrays.pair_node]
                rank = (arrays.pair_is_edge.astype(jnp.float32) * 1e6
                        + jnp.arange(dead.shape[0], dtype=jnp.float32))
                fb = jnp.argmin(jnp.where(dead, jnp.inf, rank))
                decision = jnp.where(dead[decision], fb,
                                     decision).astype(jnp.int32)

        if cfg.disaggregated:
            # decision indexes the route table: prefill leg on pair_p,
            # decode leg on pair_q, KV transfer between them when split
            pair_p = arrays.route_prefill[decision]
            pair_q = arrays.route_decode[decision]
            colo = pair_p == pair_q
            node_p = arrays.pair_node[pair_p]
            node_q = arrays.pair_node[pair_q]
            up = tables.up_time[i, pair_p]
            down = tables.down_time[i, pair_q]
            hf = hit_pair[pair_p]
            prefill = tables.prefill_time[i, pair_p] * (1.0 - hf)
            decode_t = (tables.service[i, pair_q]
                        - tables.prefill_time[i, pair_q])
            tt = (arrays.kv_lat[node_p, node_q]
                  + kv_bytes[pair_p] * arrays.kv_inv_bw[node_p, node_q])
            if cfg.faulty:
                # stragglers stretch each leg on its own node; link flaps
                # stretch the KV transfer (zero on colocated routes)
                prefill = prefill * slow[node_p]
                decode_t = decode_t * slow[node_q]
                tt = tt * linkf
            cost_i = (tables.prompt_cost[i, pair_p]
                      * (1.0 - hf * (1.0 - CACHED_TOKEN_PRICE_FACTOR))
                      + (tables.cost[i, pair_q]
                         - tables.prompt_cost[i, pair_q])
                      + kv_bytes[pair_p] * arrays.kv_egress[node_p, node_q])

            if cfg.is_eq5:
                rt = up + prefill + tt + decode_t + down
                if cfg.faulty:
                    rt = rt + delay
                completion = arrival + rt
                wait = 0.0
                ttft = up + prefill
                new_slot_free = slot_free
            else:
                ready = t_eff + up
                slots_p = slot_free[node_p]
                s_p = jnp.argmin(slots_p)
                start_p = jnp.maximum(ready, slots_p[s_p])
                wait_p = start_p - ready
                finish_p = start_p + prefill
                # colocated routes keep single-slot occupancy for the whole
                # service; split routes free the prefill slot at finish_p
                slots1 = slot_free.at[node_p, s_p].set(
                    jnp.where(colo, finish_p + decode_t, finish_p))
                ready_d = finish_p + tt
                slots_q = slots1[node_q]
                s_q = jnp.argmin(slots_q)
                start_d = jnp.maximum(ready_d, slots_q[s_q])
                finish_d = jnp.where(colo, finish_p + decode_t,
                                     start_d + decode_t)
                slots2 = jnp.where(colo, slots1,
                                   slots1.at[node_q, s_q].set(finish_d))
                wait = wait_p + jnp.where(colo, 0.0, start_d - ready_d)
                completion = finish_d + down
                rt = completion - arrival
                ttft = up + wait_p + prefill
                new_slot_free = jnp.where(v, slots2, slot_free)
            if cfg.faulty:
                ttft = ttft + delay

            transfer_i = jnp.where(colo, 0.0, tt)
            q_i = tables.quality[i, pair_q]
            tpot_i = (tables.tpot[pair_q] * slow[node_q] if cfg.faulty
                      else tables.tpot[pair_q])
            pair = pair_q                    # billing pair for update hooks
            if cfg.prefix_cache:
                # the prefix becomes resident on the prefill node, and the
                # handoff replicates it onto the decode node
                for node in (node_p, node_q):
                    c_sess = c_sess.at[node, jnp.maximum(g, 0)].max(
                        jnp.where(v & (g >= 0), blk_p, -1.0))
                    c_sys = c_sys.at[node, jnp.maximum(y, 0)].max(
                        jnp.where(v & (y >= 0), blk_s, -1.0))
        else:
            pair = decision
            node = arrays.pair_node[pair]
            up = tables.up_time[i, pair]
            down = tables.down_time[i, pair]
            hf = hit_pair[pair]
            # the cache is physical: a hit shortens prefill (and node
            # occupancy) and discounts cached prompt tokens for whichever
            # policy routed here
            service = (tables.service[i, pair]
                       - hf * tables.prefill_time[i, pair])
            prefill = tables.prefill_time[i, pair] * (1.0 - hf)
            if cfg.faulty:
                # a straggling node executes the whole service slower
                service = service * slow[node]
                prefill = prefill * slow[node]
            cost_i = (tables.cost[i, pair]
                      - hf * (1.0 - CACHED_TOKEN_PRICE_FACTOR)
                      * tables.prompt_cost[i, pair])

            if cfg.is_eq5:
                rt = up + service + down                # Eq. (5) verbatim
                if cfg.faulty:
                    rt = rt + delay
                completion = arrival + rt
                wait = 0.0
                new_slot_free = slot_free
            else:
                ready = t_eff + up
                slots_n = slot_free[node]
                s = jnp.argmin(slots_n)
                start = jnp.maximum(ready, slots_n[s])
                wait = start - ready
                finish = start + service
                completion = finish + down
                rt = completion - arrival
                # padding rows must not occupy a slot
                new_slot_free = jnp.where(
                    v, slot_free.at[node, s].set(finish), slot_free)

            if cfg.prefix_cache:
                # this prompt's whole-block prefix is now resident on `node`
                c_sess = c_sess.at[node, jnp.maximum(g, 0)].max(
                    jnp.where(v & (g >= 0), blk_p, -1.0))
                c_sys = c_sys.at[node, jnp.maximum(y, 0)].max(
                    jnp.where(v & (y >= 0), blk_s, -1.0))

            ttft = up + wait + prefill
            if cfg.faulty:
                ttft = ttft + delay
            transfer_i = 0.0
            q_i = tables.quality[i, pair]
            tpot_i = (tables.tpot[pair] * slow[node] if cfg.faulty
                      else tables.tpot[pair])

        client_ready = jnp.where(
            v, client_ready.at[i % G].set(completion), client_ready)
        # policy state advances only on valid rows (frozen under padding)
        new_pstate = pol.update_jnp(genome, pstate, inp, pair, cost_i)
        pstate = jnp.where(v, new_pstate, pstate)
        if cfg.learned:
            # feed the dispatched request's realized observations back into
            # the estimator state: prefill residual on the prefill node,
            # tpot + quality residuals on the decode node (same node when
            # colocated). Latency targets are speed ratios of the full
            # static phase time — exact zeros on fault-free runs.
            if cfg.disaggregated:
                obs_prefill = tables.prefill_time[i, pair_p]
                node_a, node_b = node_p, node_q
            else:
                obs_prefill = tables.prefill_time[i, pair]
                node_a = node_b = node
            if cfg.faulty:
                slow_a, slow_b = slow[node_a], slow[node_b]
            else:
                slow_a = slow_b = jnp.float32(1.0)
            y_p, y_t, y_q = learn_est.observations(
                jnp, obs_prefill, slow_a, tables.tpot[pair], slow_b,
                q_i, tables.quality_mean[i, pair])
            new_lstate = learn_est.update_jnp(
                cfg.learner, lstate, n_nodes, cat, node_a, node_b,
                x1, x2, x3, y_p, y_t, y_q)
            lstate = jnp.where(v, new_lstate, lstate)
        # masked rows contribute exact zeros to every objective and to the
        # violation sum; assign=-1 marks them for downstream consumers
        out = (q_i * vf, cost_i * vf, rt * vf,
               jnp.where(v, decision, -1),
               jnp.maximum(wait - cfg.w_max, 0.0) * vf, ttft * vf,
               tpot_i * vf, hf * vf, transfer_i * vf)
        return (new_slot_free, client_ready, c_sess, c_sys, pstate,
                lstate), out

    _, (q, cost, rt, assign, excess, ttft, tpot, hit, transfer) = \
        jax.lax.scan(
            body,
            (init_slots, init_clients, init_sess, init_sys, init_pstate,
             init_lstate),
            jnp.arange(I))
    return EvalResult(q=q, cost=cost, rt=rt, assign=assign,
                      violation=jnp.sum(excess), ttft=ttft, tpot=tpot,
                      hit=hit, transfer=transfer)


# ---------------------------------------------------------------------------
# Memoized population-fitness kernels
#
# The factory is lru_cached on the evaluator's *static* configuration so two
# evaluators over same-shaped (bucketed) tables hand NSGA2 the **same**
# function object; with the tables flowing through the dynamic ``data``
# argument, the optimizer's module-level jitted step then reuses one
# compiled executable across re-fit windows (see core.nsga2).
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=128)
def _fitness_kernel(policy: str, objectives: str, cfg: EvalConfig,
                    n_slots: int, n_groups: Tuple[int, int]):
    spec = get_policy(policy).genome_spec

    def kernel(genomes, key, data):
        del key
        tables, arrays, *rest = data   # optional 3rd element: FaultTables
        faults = rest[0] if rest else None
        I = tables.quality.shape[0]

        def run_one(g):
            if spec.discrete:
                g = g.astype(jnp.int32)
                if spec.per_request and g.shape[0] < I:
                    g = jnp.pad(g, (0, I - g.shape[0]))  # bucketed tables
            else:
                g = g.astype(jnp.float32)
            res = _run_trace(g, policy, tables, arrays, cfg, n_slots,
                             n_groups, faults)
            # masked means: padded rows carry exact zeros, so sums over the
            # full padded axis divided by the *valid* count reproduce the
            # unpadded objectives bit-for-bit when no padding exists
            vf = tables.valid.astype(jnp.float32)
            n = jnp.maximum(jnp.sum(vf), 1.0)
            RQ = jnp.sum(vf - res.q) / n
            C = jnp.sum(res.cost) / n
            RT = jnp.sum(res.rt) / n
            if objectives == "qoe":
                ok = slo_ok(res.ttft, res.tpot, tables.ttft_deadline,
                            tables.tpot_deadline) & tables.valid
                V = 1.0 - jnp.sum(ok.astype(jnp.float32)) / n
                F = jnp.stack([RQ, C, RT, V])
            else:
                F = jnp.stack([RQ, C, RT])
            return F, res.violation

        F, viol = jax.vmap(run_one)(genomes)
        return F, viol

    return kernel


def population_mesh(n_devices: Optional[int] = None):
    """1-D ``('pop',)`` mesh over the process's devices for device-sharded
    population fitness (``make_fitness(..., mesh=...)``).

    On a CPU host, fabricate N devices by exporting
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` *before* the
    first jax import (see benchmarks/hotpath.py for the recipe)."""
    from jax.sharding import AxisType, Mesh
    devs = jax.devices()
    n = len(devs) if n_devices is None else min(int(n_devices), len(devs))
    return Mesh(np.asarray(devs[:n]), ("pop",), axis_types=(AxisType.Auto,))


@functools.lru_cache(maxsize=128)
def _sharded_fitness_kernel(policy: str, objectives: str, cfg: EvalConfig,
                            n_slots: int, n_groups: Tuple[int, int], mesh):
    """shard_map the population axis of the fitness kernel over ``mesh``.

    Genomes are split along the mesh's single axis (padded by replicating
    row 0 when the population does not divide the device count — the
    surplus rows are dropped from the outputs); tables/arrays are
    replicated. Per-genome evaluation is embarrassingly parallel, so the
    sharded result equals the single-device vmap."""
    from jax.sharding import PartitionSpec as PSpec

    base = _fitness_kernel(policy, objectives, cfg, n_slots, n_groups)
    axis = mesh.axis_names[0]
    ndev = int(np.prod(list(mesh.shape.values())))
    sharded = jax.shard_map(base, mesh=mesh,
                            in_specs=(PSpec(axis), PSpec(), PSpec()),
                            out_specs=(PSpec(axis), PSpec(axis)),
                            check_vma=False)

    def kernel(genomes, key, data):
        P = genomes.shape[0]
        P_pad = -(-P // ndev) * ndev
        gp = genomes
        if P_pad != P:
            fill = jnp.broadcast_to(genomes[:1],
                                    (P_pad - P,) + genomes.shape[1:])
            gp = jnp.concatenate([genomes, fill], axis=0)
        F, viol = sharded(gp, key, data)
        return F[:P], viol[:P]

    return kernel


class TraceEvaluator:
    """Evaluate routing decisions over a fixed (trace × cluster).

    ``bucket`` enables compile-once evaluation: tables are padded up to a
    size bucket ("pow2" -> next power of two over I, an int -> next multiple)
    with a validity mask, and the session/system-prompt group counts are
    padded to powers of two too — every trace landing in the same buckets
    (same cluster, same ``EvalConfig``) then reuses one compiled
    ``_run_trace`` executable instead of retracing per window length. Public
    ``run_*`` results are sliced back to the real request count, so callers
    see outputs identical to an unpadded evaluator (tested as the
    masked-tail invariance property)."""

    def __init__(self, trace: Trace, cluster: ClusterSpec,
                 cfg: EvalConfig = EvalConfig(), seed: int = 0,
                 bucket=None, faults: Optional[FaultSchedule] = None):
        assert not cfg.open_loop or trace.has_arrivals, \
            "mode='open' needs a trace with arrival_time (workload.arrivals)"
        assert not cfg.prefix_cache or trace.has_sessions, \
            "prefix_cache needs a session trace (workload.sessions)"
        self.trace = trace
        self.cluster = cluster
        if faults is not None:
            cfg = dataclasses.replace(cfg, faulty=True)
        assert not cfg.faulty or faults is not None, \
            "EvalConfig(faulty=True) needs a FaultSchedule"
        self.cfg = cfg
        self.tables, self.arrays = build_tables(trace, cluster, seed=seed)
        self.fault_tables = (
            jnp_tables(faults.compile(len(cluster.nodes)))
            if faults is not None else None)
        self.n_slots = _max_conc(self.arrays)
        self.n_valid = trace.n_requests
        n_sess = int(np.max(trace.group_id)) + 2 if trace.has_sessions else 1
        n_sys = (int(np.max(trace.sys_id)) + 2
                 if trace.sys_id is not None else 1)
        if bucket is not None:
            self.tables = pad_tables(
                self.tables, bucket_size(self.n_valid, bucket))
            n_sess, n_sys = next_pow2(n_sess), next_pow2(n_sys)
        self.n_padded = int(self.tables.quality.shape[0])
        self.n_groups = (n_sess, n_sys)

    # -- single policy ------------------------------------------------------
    def _run(self, genome, policy: str) -> EvalResult:
        res = _run_trace(genome, policy, self.tables, self.arrays, self.cfg,
                         self.n_slots, self.n_groups, self.fault_tables)
        if self.n_padded == self.n_valid:
            return res
        n = self.n_valid
        return EvalResult(q=res.q[:n], cost=res.cost[:n], rt=res.rt[:n],
                          assign=res.assign[:n], violation=res.violation,
                          ttft=res.ttft[:n], tpot=res.tpot[:n],
                          hit=res.hit[:n], transfer=res.transfer[:n])

    def _check_decision_space(self, pol) -> None:
        """Route-valued policies need ``disaggregated=True`` and vice versa
        (per-request genomes may encode either index space)."""
        if pol.genome_spec.per_request:
            return
        want = "route" if self.cfg.disaggregated else "pair"
        assert pol.decides == want, \
            (f"policy {pol.name!r} decides over {pol.decides!r} indices but "
             f"EvalConfig(disaggregated={self.cfg.disaggregated}) evaluates "
             f"{want!r} decisions")

    def run_policy(self, policy: str, genome) -> EvalResult:
        """Evaluate any registered policy's genome over the trace.

        ``policy`` is a registry name (``core.policies.list_policies()``);
        unknown names raise ``ValueError`` listing the registered set.
        Discrete per-request genomes (``"direct"``) are padded to the bucket
        automatically."""
        pol = get_policy(policy)        # validates the registry name
        self._check_decision_space(pol)
        if pol.genome_spec.discrete:
            g = jnp.asarray(genome, jnp.int32)
            if pol.genome_spec.per_request and g.shape[0] < self.n_padded:
                g = jnp.pad(g, (0, self.n_padded - g.shape[0]))
        else:
            g = jnp.asarray(genome, jnp.float32)
        return self._run(g, pol.name)

    # named shorthands (pre-registry API, still canonical call sites)
    def run_assignment(self, assign: jnp.ndarray) -> EvalResult:
        return self.run_policy("direct", assign)

    def run_thresholds(self, thresholds: jnp.ndarray) -> EvalResult:
        return self.run_policy("threshold", thresholds)

    def run_slo_policy(self, params: jnp.ndarray) -> EvalResult:
        """Run the SLO-aware policy (genome = [γ, κ], see core.policies)."""
        return self.run_policy("slo", params)

    def run_affinity_policy(self, params: jnp.ndarray) -> EvalResult:
        """Run the cache-affinity policy (genome = [γ, κ, ρ])."""
        return self.run_policy("affinity", params)

    # -- population fitness (for NSGA2) --------------------------------------
    def make_fitness(self, genome: str, objectives: str = "paper",
                     mesh=None):
        """Return FitnessFn mapping (P, D) genomes -> ((P, M), (P,)).

        genome: any registered policy name (``core.policies.list_policies()``
        — e.g. "threshold", "direct", "slo", "affinity", "p2c-hedge",
        "budget", "bandit"); unknown names raise ``ValueError`` listing the
        registered set. objectives: "paper" for the 3-vector (RQ, C, RT);
        "qoe" appends the SLO violation rate as a 4th minimized objective
        (requires a trace with deadlines attached).

        ``mesh`` optionally device-shards the population axis via shard_map
        over a 1-D mesh (see :func:`population_mesh`): each device evaluates
        its genome shard against replicated tables; results are numerically
        identical to the single-device vmap (per-genome programs are
        independent). On hosts, fabricate devices with
        ``XLA_FLAGS=--xla_force_host_platform_device_count=N``.

        The returned callable keeps the legacy ``(genomes, key)`` signature
        but also carries ``.kernel`` (a memoized module-level function — the
        same object for every evaluator with equal static config) and
        ``.data`` (this evaluator's dynamic tables) so ``NSGA2`` can key its
        jitted generation step on the kernel identity and reuse one compiled
        executable across evaluators/windows.
        """
        assert objectives in ("paper", "qoe")
        assert objectives != "qoe" or self.trace.has_slos, \
            "qoe objectives need a trace with SLOs (workload.slo.attach_slos)"
        pol = get_policy(genome)           # validates the registry name
        self._check_decision_space(pol)
        policy = pol.name
        if mesh is not None:
            kernel = _sharded_fitness_kernel(policy, objectives, self.cfg,
                                             self.n_slots, self.n_groups,
                                             mesh)
        else:
            kernel = _fitness_kernel(policy, objectives, self.cfg,
                                     self.n_slots, self.n_groups)
        data = ((self.tables, self.arrays) if self.fault_tables is None
                else (self.tables, self.arrays, self.fault_tables))

        def fitness(genomes, key):
            return kernel(genomes, key, data)

        fitness.kernel = kernel
        fitness.data = data
        return fitness

    # -- reporting ------------------------------------------------------------
    def summarize(self, res: EvalResult) -> dict:
        out = {
            "avg_quality": float(jnp.mean(res.q)),
            "avg_response_time": float(jnp.mean(res.rt)),
            "avg_cost": float(jnp.mean(res.cost)),
            "RQ": float(jnp.mean(1.0 - res.q)),
            "violation": float(res.violation),
            "avg_ttft": float(jnp.mean(res.ttft)),
            "avg_tpot": float(jnp.mean(res.tpot)),
            "cache_hit_frac": float(jnp.mean(res.hit)),
        }
        if self.trace.has_slos:
            ok = slo_ok(res.ttft, res.tpot,
                        self.tables.ttft_deadline[:self.n_valid],
                        self.tables.tpot_deadline[:self.n_valid])
            out["slo_attainment"] = float(jnp.mean(ok.astype(jnp.float32)))
        return out

    def per_dataset_quality(self, res: EvalResult) -> dict:
        from ..cluster.spec import TASKS
        out = {}
        task = jnp.asarray(self.trace.task)
        for t, name in enumerate(TASKS):
            mask = task == t
            out[name] = float(jnp.sum(jnp.where(mask, res.q, 0.0))
                              / jnp.maximum(jnp.sum(mask), 1))
        return out
