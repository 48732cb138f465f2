"""Mamba selective-SSM block (for the Jamba hybrid arch).

Selective scan with diagonal state transition (Mamba-1, arXiv:2312.00752),
adapted for TPU:

* The recurrence h_t = a_t ⊙ h_{t-1} + b_t (a_t = exp(Δ_t·A)) is evaluated
  **chunkwise**: sequential ``lax.scan`` over chunks of ``cfg.ssm.chunk``
  tokens carrying the (B, d_inner, d_state) boundary state, with a parallel
  ``associative_scan`` inside each chunk. This bounds the live scan tensor to
  (B, chunk, d_inner, d_state) — sharded over 'model' on d_inner — instead of
  the full-sequence (B, L, d_inner, d_state) a naive associative scan would
  materialize (17 GB/device at the jamba train cell).
* d_inner (= expand·d_model) is the tensor-parallel axis throughout: in_proj
  column-parallel, out_proj row-parallel, conv/dt/B/C all elementwise or
  row-local in d_inner — one all-reduce per block, Megatron-style.

Decode is the O(1) recurrent step; its state is (h, conv window).

As in transformers' ``modeling_jamba.py``, dt, B and C are RMSNormed after
``x_proj`` (its ``dt_layernorm``, ``b_layernorm`` and ``c_layernorm``);
``SSMCfg.dt_rank`` states the rank of dt where the published config gives
one.

A prefill given the prompt's true ``length`` (a traced value) may run on
tokens padded past it: positions at or past the length are state-neutral
(Δt = 0, so a = 1 and b = 0) and the conv window is read ending at the
length, so a bucket-padded prompt leaves the exact-length state.
"""
from __future__ import annotations

import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from .config import ModelConfig
from .layers import _dtype, dense_init, norm_init, rms_norm
from .sharding import constrain

Params = Dict


def d_inner(cfg: ModelConfig) -> int:
    return cfg.ssm.expand * cfg.d_model


def dt_rank(cfg: ModelConfig) -> int:
    return cfg.ssm.dt_rank or max(1, cfg.d_model // 16)


def mamba_init(key, cfg: ModelConfig) -> Params:
    s = cfg.ssm
    dt = _dtype(cfg)
    di, dr, ds = d_inner(cfg), dt_rank(cfg), s.d_state
    ks = jax.random.split(key, 7)
    # S4D-real initialization for A
    A = jnp.tile(jnp.arange(1, ds + 1, dtype=jnp.float32)[None, :], (di, 1))
    return {
        "dt_norm": norm_init(dr), "b_norm": norm_init(ds),
        "c_norm": norm_init(ds),
        "in_proj": dense_init(ks[0], cfg.d_model, 2 * di, dt),
        "conv": {"w": (jax.random.normal(ks[1], (s.d_conv, di), jnp.float32)
                       / math.sqrt(s.d_conv)).astype(dt),
                 "b": jnp.zeros((di,), jnp.float32)},
        "x_proj": dense_init(ks[2], di, dr + 2 * ds, dt),
        "dt_proj": {"w": (jax.random.normal(ks[3], (dr, di), jnp.float32)
                          * (dr ** -0.5)).astype(dt),
                    "b": jnp.full((di,), -4.6, jnp.float32)},  # softplus^-1(0.01)
        "A_log": jnp.log(A),
        "D": jnp.ones((di,), jnp.float32),
        "out_proj": dense_init(ks[4], di, cfg.d_model, dt),
    }


def _ssm_params(p, cfg: ModelConfig, xc):
    """xc: (B, L, di) post-conv activations -> (dtv, Bv, Cv) f32."""
    ds = cfg.ssm.d_state
    dr = dt_rank(cfg)
    proj = jnp.einsum("bld,de->ble", xc, p["x_proj"]["w"],
                      preferred_element_type=jnp.float32)
    dt_in, Bv, Cv = jnp.split(proj, [dr, dr + ds], axis=-1)
    dt_in = rms_norm(p["dt_norm"], dt_in, cfg.norm_eps)
    Bv = rms_norm(p["b_norm"], Bv, cfg.norm_eps)
    Cv = rms_norm(p["c_norm"], Cv, cfg.norm_eps)
    dtv = jax.nn.softplus(
        jnp.einsum("blr,rd->bld", dt_in, p["dt_proj"]["w"].astype(jnp.float32))
        + p["dt_proj"]["b"])
    return dtv, Bv, Cv


def _scan_chunked(a, b, h0, chunk: int):
    """h_t = a_t * h_{t-1} + b_t over axis 1, chunked. a, b: (B, L, di, ds)."""
    B, L, di, ds = a.shape
    n = L // chunk
    a = a.reshape(B, n, chunk, di, ds)
    b = b.reshape(B, n, chunk, di, ds)

    def combine(c1, c2):
        a1, b1 = c1
        a2, b2 = c2
        return a1 * a2, a2 * b1 + b2

    def chunk_step(h, ab):
        ac, bc = ab                                  # (B, chunk, di, ds)
        A_cum, B_cum = jax.lax.associative_scan(combine, (ac, bc), axis=1)
        hs = A_cum * h[:, None] + B_cum              # states at every t
        return hs[:, -1], hs

    h_last, hs = jax.lax.scan(
        chunk_step, h0,
        (jnp.moveaxis(a, 1, 0), jnp.moveaxis(b, 1, 0)))
    hs = jnp.moveaxis(hs, 0, 1).reshape(B, L, di, ds)
    return h_last, hs


def mamba_forward(p, cfg: ModelConfig, x, state=None, length=None
                  ) -> Tuple[jax.Array, Tuple[jax.Array, jax.Array]]:
    """Full-sequence forward. x: (B, L, d). Returns (y, (h, conv_win)).

    L is padded up to a chunk multiple with *state-neutral* steps
    (Δt = 0 ⇒ a = 1, b = 0), so the returned state is exact at position L;
    with ``length`` (traced, <= L) every position from ``length`` on is
    state-neutral too and the state is the one at ``length``.
    """
    s = cfg.ssm
    B, L0, _ = x.shape
    chunk0 = min(s.chunk, L0)
    pad = (-L0) % chunk0
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
    B, L, _ = x.shape
    di = d_inner(cfg)
    xz = jnp.einsum("bld,de->ble", x, p["in_proj"]["w"])
    xi, z = jnp.split(xz, 2, axis=-1)                # (B, L, di)
    xi = constrain(xi, "dp", None, "model")
    z = constrain(z, "dp", None, "model")

    # causal depthwise conv (window d_conv)
    with jax.named_scope("mamba.conv"):
        if state is not None:
            conv_win = state[1]                      # (B, d_conv-1, di)
            xpad = jnp.concatenate([conv_win, xi], axis=1)
        else:
            xpad = jnp.pad(xi, ((0, 0), (s.d_conv - 1, 0), (0, 0)))
        xc = sum(xpad[:, i:i + L] * p["conv"]["w"][i]
                 for i in range(s.d_conv))
        xc = jax.nn.silu(
            (xc + p["conv"]["b"]).astype(jnp.float32)).astype(x.dtype)
        new_conv_win = jax.lax.dynamic_slice_in_dim(
            xpad, L0 if length is None else length, s.d_conv - 1, 1)

    with jax.named_scope("mamba.ssm"):
        dtv, Bv, Cv = _ssm_params(p, cfg, xc)        # f32
        if pad or length is not None:
            valid = (jnp.arange(L) < (L0 if length is None else length))
            # a=1, b=0 on steps past the prompt
            dtv = jnp.where(valid[None, :, None], dtv, 0.0)
        A = -jnp.exp(p["A_log"])                     # (di, ds)
        a = jnp.exp(dtv[..., None] * A[None, None])  # (B, L, di, ds)
        bterm = (dtv * xc.astype(jnp.float32))[..., None] * Bv[:, :, None, :]

        h0 = (state[0] if state is not None
              else jnp.zeros((B, di, s.d_state), jnp.float32))
        chunk = min(s.chunk, L)
        assert L % chunk == 0, (L, chunk)
        h_last, hs = _scan_chunked(a, bterm, h0, chunk)
        y = (jnp.einsum("blds,bls->bld", hs, Cv)
             + p["D"] * xc.astype(jnp.float32))
    y = (y * jax.nn.silu(z.astype(jnp.float32))).astype(x.dtype)
    out = constrain(jnp.einsum("bld,de->ble", y, p["out_proj"]["w"]),
                    "dp", None, None)
    if pad:
        out = out[:, :L0]
    return out, (h_last, new_conv_win)


def mamba_decode(p, cfg: ModelConfig, x, state
                 ) -> Tuple[jax.Array, Tuple[jax.Array, jax.Array]]:
    """One-token step. x: (B, 1, d); state: (h (B, di, ds), conv_win)."""
    return mamba_forward(p, cfg, x, state=state)


def init_state(cfg: ModelConfig, batch: int):
    s = cfg.ssm
    di = d_inner(cfg)
    return (jnp.zeros((batch, di, s.d_state), jnp.float32),
            jnp.zeros((batch, s.d_conv - 1, di),
                      _dtype(cfg)))
