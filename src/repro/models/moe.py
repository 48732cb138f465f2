"""Token-choice top-k Mixture-of-Experts with capacity (GShard-style drop).

TPU/GSPMD adaptation: instead of the GShard one-hot dispatch einsum (whose
(T, E, C) combine tensor is ~5·10⁹ elements for the llama4 train cell) we use
the sort-based ragged dispatch used by production JAX MoE stacks:

  1. router top-k → (T, k) expert ids + weights,
  2. flat (T·k,) expert ids argsorted → tokens grouped by expert,
  3. position-in-expert from the sorted order; slots ≥ capacity dropped,
  4. gather tokens into an (E, C, d) buffer, batched expert GEMMs
     (E sharded over the 'model' axis = expert parallelism; the gather from
     data-sharded tokens into expert-sharded buffers is the EP all-to-all,
     visible in the dry-run collective bytes),
  5. combine by gathering each token's (expert, slot) output × router weight.

Capacity C = ceil(T·k·capacity_factor / E), padded to a multiple of 8 for
TPU sublane alignment.

A layer told which experts it holds (``MoECfg.held``, its share under
expert parallelism) is **dropless** instead: the router keeps its full
width, and every held expert runs over every row, weighted by what the row's
top-k gave it (zero where the row did not pick it). Rows stay independent,
so the layer serves a bucket-padded prefill and a fleet cohort exactly; the
experts it does not hold add nothing here (they live on other chips). At a
decode batch every held expert is picked by some row nearly every step, so
this reads the weights a ragged dispatch would.
"""
from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp

from .config import ModelConfig
from .layers import dense_init, _dtype
from .sharding import constrain

Params = Dict


def moe_init(key, cfg: ModelConfig) -> Params:
    m = cfg.moe
    dt = _dtype(cfg)
    ks = jax.random.split(key, 4)
    d, ff, E = cfg.d_model, m.d_ff, m.n_held
    scale_in = 1.0 / math.sqrt(d)
    scale_out = 1.0 / math.sqrt(ff)
    return {
        "router": dense_init(ks[0], d, m.n_experts, jnp.float32),
        "wi": {"w": (jax.random.normal(ks[1], (E, d, ff), jnp.float32)
                     * scale_in).astype(dt)},
        "wg": {"w": (jax.random.normal(ks[2], (E, d, ff), jnp.float32)
                     * scale_in).astype(dt)},
        "wo": {"w": (jax.random.normal(ks[3], (E, ff, d), jnp.float32)
                     * scale_out).astype(dt)},
    }


def capacity(cfg: ModelConfig, n_tokens: int) -> int:
    m = cfg.moe
    c = math.ceil(n_tokens * m.top_k * m.capacity_factor / m.n_experts)
    return max(8, ((c + 7) // 8) * 8)


def _route(p, cfg: ModelConfig, xt):
    """(top-k weights (T, k), top-k expert ids (T, k), aux loss): softmax
    over every expert of float32 logits, then the top k."""
    m = cfg.moe
    E = m.n_experts
    logits = jnp.einsum("td,de->te", xt.astype(jnp.float32), p["router"]["w"])
    probs = jax.nn.softmax(logits, axis=-1)                      # (T, E)
    top_w, top_e = jax.lax.top_k(probs, m.top_k)                 # (T, k)
    if m.renormalize:
        top_w = top_w / jnp.maximum(jnp.sum(top_w, -1, keepdims=True), 1e-9)
    # aux loss (fraction routed vs router prob mass)
    one_hot = jax.nn.one_hot(top_e, E, dtype=jnp.float32)        # (T, k, E)
    f = jnp.mean(jnp.sum(one_hot, axis=1), axis=0)               # (E,)
    aux = E * jnp.sum(f * jnp.mean(probs, axis=0))
    return top_w, top_e, aux


def moe_apply(p, cfg: ModelConfig, x, with_picks: bool = False):
    """x: (B, S, d) -> (y, aux_loss), and with ``with_picks`` the (B, S)
    count of each row's top-k picks among the experts held here. The
    load-balancing aux loss is the standard Switch/GShard  E · Σ_e f_e · p_e
    term."""
    m = cfg.moe
    B, S, d = x.shape
    xt = x.reshape(B * S, d)
    with jax.named_scope("moe.route"):
        top_w, top_e, aux = _route(p, cfg, xt)
        lo, hi = m.held or (0, m.n_experts)
        held = jnp.arange(lo, hi)
        picked = top_e[:, :, None] == held                       # (T, k, H)
        picks = jnp.sum(picked, axis=(1, 2), dtype=jnp.int32)
    if m.dropless:
        with jax.named_scope("moe.experts"):
            y = _dropless(p, xt, jnp.sum(
                jnp.where(picked, top_w[:, :, None], 0.0), axis=1))
    else:
        y = _capacity(p, cfg, xt, top_w, top_e)
    y = y.reshape(B, S, d)
    if with_picks:
        return y, aux, picks.reshape(B, S)
    return y, aux


def _dropless(p, xt, gate):
    """Every held expert over every row; ``gate`` (T, H) is each row's
    weight for each held expert, 0 where the row did not pick it."""
    h = jnp.einsum("td,edf->etf", xt, p["wi"]["w"])
    g = jnp.einsum("td,edf->etf", xt, p["wg"]["w"])
    h = jax.nn.silu(g.astype(jnp.float32)).astype(h.dtype) * h
    # float32 out of the dot, then a product and a sum over the held
    # experts: rounds the same whatever batch axes a vmap over cohort
    # members adds (XLA may fold a bf16 dot's later upcast into the dot in
    # one form of the program and not in the other)
    ye = jnp.einsum("etf,efd->etd", h, p["wo"]["w"],
                    preferred_element_type=jnp.float32)          # (H, T, d)
    y = jnp.sum(ye * gate.T[:, :, None], axis=0)
    return y.astype(xt.dtype)


def _capacity(p, cfg: ModelConfig, xt, top_w, top_e):
    """GShard dispatch: each expert takes at most C rows, the rest drop."""
    m = cfg.moe
    T, d = xt.shape
    E, k = m.n_experts, m.top_k
    C = capacity(cfg, T)

    # ---- sort-based dispatch ------------------------------------------------
    flat_e = top_e.reshape(-1)                                   # (T*k,)
    order = jnp.argsort(flat_e)                                  # stable
    sorted_e = flat_e[order]
    # position within expert group = index - first index of that expert
    counts = jnp.bincount(sorted_e, length=E)                    # (E,)
    starts = jnp.concatenate([jnp.zeros(1, counts.dtype),
                              jnp.cumsum(counts)[:-1]])
    pos_sorted = jnp.arange(T * k) - starts[sorted_e]            # (T*k,)
    keep = pos_sorted < C
    # scatter (expert, slot) <- flat token index; dropped slots point at T
    slot_token = jnp.full((E * C,), T, jnp.int32)
    dst = sorted_e * C + pos_sorted.astype(jnp.int32)
    src_token = (order // k).astype(jnp.int32)
    slot_token = slot_token.at[jnp.where(keep, dst, E * C)].set(
        src_token, mode="drop")
    slot_token = slot_token.reshape(E, C)

    # gather tokens (padded row T = zeros) -> expert buffers
    xpad = jnp.concatenate([xt, jnp.zeros((1, d), xt.dtype)], axis=0)
    xe = constrain(xpad[slot_token], "model", None, None)        # (E, C, d)

    # ---- expert GEMMs (E sharded over 'model') -------------------------------
    h = jnp.einsum("ecd,edf->ecf", xe, p["wi"]["w"])
    g = jnp.einsum("ecd,edf->ecf", xe, p["wg"]["w"])
    h = jax.nn.silu(g.astype(jnp.float32)).astype(h.dtype) * h
    ye = constrain(jnp.einsum("ecf,efd->ecd", h, p["wo"]["w"]),
                   "model", None, None)                          # (E, C, d)

    # ---- combine -------------------------------------------------------------
    # token's k-th choice lives at (expert=top_e, slot): recover slot by
    # inverting the scatter through the sorted order
    slot_flat = jnp.full((T * k,), C, jnp.int32)                 # C = dropped
    slot_flat = slot_flat.at[order].set(
        jnp.where(keep, pos_sorted, C).astype(jnp.int32))
    slot = slot_flat.reshape(T, k)
    ypad = jnp.concatenate(
        [ye, jnp.zeros((E, 1, d), ye.dtype)], axis=1)            # slot C = 0
    gathered = ypad[top_e, slot]                                 # (T, k, d)
    return jnp.sum(gathered.astype(jnp.float32)
                   * top_w[..., None], axis=1).astype(xt.dtype)
