"""The Jamba block: its program config, its plain float32 forward pass, and
the operations and bytes that serving it needs.

A period of eight layers (AI21-Jamba2-Mini's ``config.json``:
``attn_layer_offset`` 4, ``attn_layer_period`` 8, ``expert_layer_offset`` 1,
``expert_layer_period`` 2): layer 4 is grouped-query attention with no
positional encoding, the other seven are Mamba-1 mixers; the odd layers
have a sparse expert layer and the even ones a dense SwiGLU of the same
width. Each layer is pre-norm (RMSNorm) with a residual around the mixer
and another around the feed-forward; a final RMSNorm and an untied output
head follow. As in transformers' ``modeling_jamba.py``:

* Mamba (``JambaMambaMixer.slow_forward``): ``in_proj`` to x and the gate
  z; a causal depthwise conv of width ``d_conv`` with bias, then SiLU;
  ``x_proj`` to dt (rank ``dt_rank``), B and C, each RMSNormed
  (``dt_layernorm``, ``b_layernorm``, ``c_layernorm``); Δ = softplus(dt
  ``dt_proj`` + bias); h ← exp(Δ·A)·h + Δ·B·x with A = −exp(A_log);
  y = h·C + D·x, times SiLU(z), then ``out_proj``.
* Experts (``JambaSparseMoeBlock``): a float32 softmax over all
  ``n_experts`` router logits, the top ``top_k`` of them, **not**
  renormalised; each picked expert's SwiGLU output times its weight.

Departures from ``modeling_jamba.py``, each the configuration's cut or the
benchmark's choice: the weights are random (``deploy.make_params`` and the
rules in ``LEAVES``); only the experts this chip holds
(``experts_held``) contribute, and the others add nothing, in the program
and here alike; the router's weights are float32 (the program keeps them
so); one period of layers and a slice of the vocabulary (the
configuration's ``reduced``).

Every matrix product runs in float32 at ``Precision.HIGHEST``, with no
kernel, cache or batching of requests: each row is its own sequence, and a
causal mask and the causal conv and recurrence keep padding after a row's
last token from reaching it. The recurrence is a sequential scan over
positions. Periods run one at a time in a scan and the experts one at a
time inside it, each upcast alone (0.70 GB at the configuration's widths),
so the reference fits beside the served weights. ``mode="fp8"`` is the
control (``reference._mm``) on every weight matrix, the router's included;
the conv's four taps are not a matrix and stay float32.

Weight rules (``LEAVES``), beyond the shared ``table``, ``w`` and ``g``:
``b`` serves both the conv bias and the dt bias, 0.5·z − 2, so
Δ = softplus(dt ``dt_proj`` + b) lies between 0.015 and 0.8 for 95% of
(token, channel) pairs; ``A_log`` = 1.2·z − 2, so A = exp(A_log) lies
between 0.013 and 1.4 for 95% of (channel, state) pairs; ``D`` = 1 + 0.1·z.
The per-step decay exp(−Δ·A) then spans 0.54–0.9996 (1st to 99th
percentile, median 0.983), so the check carries state over tens to
hundreds of steps, and the Mamba branch adds 0.6–4.3 times the RMS of what
a dense FFN branch adds (5th to 95th percentile over positions, median
1.9), as computed for one layer at the configuration's widths over 64
positions of unit-RMS normed input.

The counts follow the sizes in a configuration file's ``architectures``
entry. The experts' share of a token's FLOPs is the expectation under
uniform routing: ``top_k`` × held / ``n_experts`` expert FFNs per MoE
layer (one, at 2 × 8 / 16). An exact count needs the program's routing
counter (``moe.expert_rows``), which the harness does not read yet.
"""
from __future__ import annotations

from typing import Dict, Iterable, Tuple

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig, MoECfg, SSMCfg

from benchmarks.chip.flops import BF16
from benchmarks.chip.reference import HIGHEST, _mm, _rms

PERIOD = tuple(("attn" if i == 4 else "mamba",
                "moe" if i % 2 == 1 else "dense") for i in range(8))

LEAVES = {"b": lambda z, sd: 0.5 * z - 2.0,
          "A_log": lambda z, sd: 1.2 * z - 2.0,
          "D": lambda z, sd: 1.0 + 0.1 * z}


def model_config(name: str, a: Dict) -> ModelConfig:
    """The program's config for the sizes a configuration file states."""
    return ModelConfig(
        name=name, family="hybrid", n_layers=a["n_layers"],
        d_model=a["d_model"], n_heads=a["n_heads"],
        n_kv_heads=a["n_kv_heads"], d_ff=a["d_ff"], vocab=a["vocab"],
        pattern=PERIOD, head_dim=a["head_dim"], rope=False, norm="rmsnorm",
        activation="swiglu", tie_embeddings=False,
        moe=MoECfg(n_experts=a["n_experts"], top_k=a["top_k"],
                   d_ff=a["d_ff"], held=tuple(a["experts_held"]),
                   renormalize=False),
        ssm=SSMCfg(d_state=a["d_state"], d_conv=a["d_conv"],
                   expand=a["expand"], dt_rank=a["dt_rank"]),
        norm_eps=a["norm_eps"], dtype=a["dtype"])


def _f32(t):
    return jax.tree.map(lambda x: x.astype(jnp.float32), t)


def _attention(m, h, a, mode):
    K, S, _ = h.shape
    Hq, Hkv, hd = a["n_heads"], a["n_kv_heads"], a["head_dim"]
    q = _mm(h, m["wq"]["w"], mode).reshape(K, S, Hq, hd)
    k = _mm(h, m["wk"]["w"], mode).reshape(K, S, Hkv, hd)
    v = _mm(h, m["wv"]["w"], mode).reshape(K, S, Hkv, hd)
    k = jnp.repeat(k, Hq // Hkv, axis=2)
    v = jnp.repeat(v, Hq // Hkv, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HIGHEST) * hd ** -0.5
    causal = jnp.tril(jnp.ones((S, S), bool))
    w = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", w, v, precision=HIGHEST)
    return _mm(o.reshape(K, S, Hq * hd), m["wo"]["w"], mode)


def _mamba(m, h, a, mode):
    K, S, _ = h.shape
    dr, ds, dc = a["dt_rank"], a["d_state"], a["d_conv"]
    eps = a["norm_eps"]
    x, z = jnp.split(_mm(h, m["in_proj"]["w"], mode), 2, axis=-1)
    xp = jnp.pad(x, ((0, 0), (dc - 1, 0), (0, 0)))
    x = jax.nn.silu(sum(xp[:, j:j + S] * m["conv"]["w"][j]
                        for j in range(dc)) + m["conv"]["b"])
    dt, B, C = jnp.split(_mm(x, m["x_proj"]["w"], mode), [dr, dr + ds], -1)
    dt = _rms(dt, m["dt_norm"]["g"], eps)
    B = _rms(B, m["b_norm"]["g"], eps)
    C = _rms(C, m["c_norm"]["g"], eps)
    dt = jax.nn.softplus(_mm(dt, m["dt_proj"]["w"], mode) + m["dt_proj"]["b"])
    A = -jnp.exp(m["A_log"])                                  # (di, ds)

    def step(state, t):
        dt_t, x_t, B_t, C_t = t                               # (K, di|ds)
        state = (jnp.exp(dt_t[..., None] * A) * state
                 + (dt_t * x_t)[..., None] * B_t[:, None, :])
        return state, jnp.einsum("kds,ks->kd", state, C_t, precision=HIGHEST)

    seq = tuple(jnp.swapaxes(t, 0, 1) for t in (dt, x, B, C))
    _, y = jax.lax.scan(step, jnp.zeros((K, x.shape[-1], ds), jnp.float32),
                        seq)
    y = (jnp.swapaxes(y, 0, 1) + m["D"] * x) * jax.nn.silu(z)
    return _mm(y, m["out_proj"]["w"], mode)


def _experts(f, h, a, mode):
    """The held experts' share of the layer, one expert upcast at a time."""
    logits = _mm(h, f["router"]["w"].astype(jnp.float32), mode)
    top_w, top_e = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), a["top_k"])
    lo, hi = a["experts_held"]

    def expert(y, e):
        j, wi, wg, wo = e
        gate = jnp.sum(jnp.where(top_e == lo + j, top_w, 0.0), axis=-1)
        u = (jax.nn.silu(_mm(h, wg.astype(jnp.float32), mode))
             * _mm(h, wi.astype(jnp.float32), mode))
        return y + gate[..., None] * _mm(u, wo.astype(jnp.float32), mode), None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(h),
                        (jnp.arange(hi - lo), f["wi"]["w"], f["wg"]["w"],
                         f["wo"]["w"]))
    return y


def forward(params, sizes: Dict, tokens, mode: str = "ref"):
    """Logits (K, S, vocab) of every position of ``tokens`` (K, S)."""
    a = sizes
    eps = a["norm_eps"]
    x = jnp.take(params["embed"]["table"], tokens, axis=0).astype(jnp.float32)

    def period(x, blocks):
        for (mixer, ffn), p in zip(PERIOD, blocks):
            experts = p["ffn"] if ffn == "moe" else None
            p = _f32({k: v for k, v in p.items()
                      if not (k == "ffn" and ffn == "moe")})
            h = _rms(x, p["norm1"]["g"], eps)
            if mixer == "attn":
                x = x + _attention(p["mixer"], h, a, mode)
            else:
                x = x + _mamba(p["mixer"], h, a, mode)
            h = _rms(x, p["norm2"]["g"], eps)
            if ffn == "moe":
                x = x + _experts(experts, h, a, mode)
            else:
                f = p["ffn"]
                u = (jax.nn.silu(_mm(h, f["wg"]["w"], mode))
                     * _mm(h, f["wi"]["w"], mode))
                x = x + _mm(u, f["wo"]["w"], mode)
        return x, None

    x, _ = jax.lax.scan(period, x, params["blocks"])
    x = _rms(x, params["final_norm"]["g"].astype(jnp.float32), eps)
    return _mm(x, params["head"]["table"].astype(jnp.float32).T, mode)


def d_inner(a: Dict) -> int:
    return a["expand"] * a["d_model"]


def n_attention_layers(a: Dict) -> int:
    return a["n_layers"] // len(PERIOD) * sum(m == "attn" for m, _ in PERIOD)


def _mixer_flops(a: Dict, mixer: str) -> int:
    """One token's FLOPs in one mixer, attention's scores left out: the
    projections, and for Mamba the conv taps and the recurrence's state
    update (Δ·A, exp(Δ·A)·h, (Δ·x)·B, their sum) and read-out (h·C)."""
    d, di = a["d_model"], d_inner(a)
    if mixer == "attn":
        hq, hkv = a["n_heads"] * a["head_dim"], a["n_kv_heads"] * a["head_dim"]
        return 2 * (2 * d * hq + 2 * d * hkv)
    proj = 2 * d * di + di * (a["dt_rank"] + 2 * a["d_state"]) \
        + a["dt_rank"] * di + di * d
    return 2 * proj + 2 * a["d_conv"] * di + 6 * di * a["d_state"]


def _ffn_flops(a: Dict, ffn: str) -> float:
    """One token's FLOPs in one feed-forward; for experts the expectation
    under uniform routing, plus the router."""
    dense = 2 * 3 * a["d_model"] * a["d_ff"]
    if ffn == "dense":
        return dense
    lo, hi = a["experts_held"]
    return (a["top_k"] * (hi - lo) / a["n_experts"] * dense
            + 2 * a["d_model"] * a["n_experts"])


def token_flops(a: Dict) -> float:
    """FLOPs of one token through every layer, attention's scores and the
    output head left out."""
    per_period = sum(_mixer_flops(a, m) + _ffn_flops(a, f) for m, f in PERIOD)
    return a["n_layers"] // len(PERIOD) * per_period


def attention_flops(a: Dict, kv_len: int) -> int:
    """Scores and weighted sum of one query over ``kv_len`` positions, in
    every attention layer."""
    return 4 * n_attention_layers(a) * a["n_heads"] * a["head_dim"] * kv_len


def decode_flops(a: Dict, kv_lens: Iterable[int]) -> float:
    """Model FLOPs of one decode step over rows holding ``kv_lens``
    positions each (the new token's included)."""
    kv_lens = list(kv_lens)
    head = 2 * a["vocab"] * a["d_model"]
    return (len(kv_lens) * (token_flops(a) + head)
            + sum(attention_flops(a, k) for k in kv_lens))


def prefill_flops(a: Dict, n_tokens: int) -> float:
    """Model FLOPs of a prefill of ``n_tokens``: every layer for each
    token, the output head for the last one only, and attention over the
    causal triangle."""
    attn = (2 * n_attention_layers(a) * a["n_heads"] * a["head_dim"]
            * n_tokens * (n_tokens + 1))
    return n_tokens * token_flops(a) + 2 * a["vocab"] * a["d_model"] + attn


def decode_attention_cost(a: Dict, kv_lens: Iterable[int]) -> Tuple[int, int]:
    """(FLOPs, HBM bytes) the decode-attention kernel needs over the
    attention layers for rows holding ``kv_lens`` positions: K and V of
    those positions read once, the query read and the output written
    once."""
    kv_lens = list(kv_lens)
    L, hd = n_attention_layers(a), a["head_dim"]
    kv_dim = a["n_kv_heads"] * hd
    flops = sum(4 * L * a["n_heads"] * hd * k for k in kv_lens)
    kv_bytes = sum(2 * L * kv_dim * k * BF16 for k in kv_lens)
    q_bytes = 2 * L * len(kv_lens) * a["n_heads"] * hd * BF16
    return flops, kv_bytes + q_bytes
