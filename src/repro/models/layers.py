"""Parameter initialization + core layer math (pure functional JAX).

Conventions: params are nested dicts of jnp arrays; every layer is an
``init(key, ...) -> params`` / ``apply(params, x, ...) -> y`` pair; compute
dtype is bf16 with f32 accumulation (``preferred_element_type``), norms and
softmax in f32. No framework dependency (flax is not available here), which
also keeps the pytree paths stable for the sharding-rule matcher.
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ..kernels import ops as kops
from .config import ModelConfig
from .sharding import accum_dot, constrain

Params = Dict


def _dtype(cfg: ModelConfig):
    return jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32


# ---------------------------------------------------------------------------
# init helpers
# ---------------------------------------------------------------------------

def dense_init(key, d_in: int, d_out: int, dtype, scale: float | None = None):
    scale = scale if scale is not None else (1.0 / math.sqrt(d_in))
    return {"w": (jax.random.normal(key, (d_in, d_out), jnp.float32)
                  * scale).astype(dtype)}


def norm_init(d: int):
    return {"g": jnp.ones((d,), jnp.float32)}


def layernorm_init(d: int):
    return {"g": jnp.ones((d,), jnp.float32), "b": jnp.zeros((d,), jnp.float32)}


# ---------------------------------------------------------------------------
# norms / rope / embeddings
# ---------------------------------------------------------------------------

def rms_norm(p, x, eps: float = 1e-5):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (y * p["g"]).astype(x.dtype)


def layer_norm(p, x, eps: float = 1e-5):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean((xf - mu) ** 2, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * p["g"] + p["b"]).astype(x.dtype)


def apply_norm(cfg: ModelConfig, p, x):
    return (rms_norm(p, x, cfg.norm_eps) if cfg.norm == "rmsnorm"
            else layer_norm(p, x, cfg.norm_eps))


def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., S, H, D) rotated pairwise; positions: (..., S)."""
    D = x.shape[-1]
    half = D // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freqs   # (..., S, half)
    cos = jnp.cos(ang)[..., None, :]
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    return jnp.concatenate([xf1 * cos - xf2 * sin,
                            xf2 * cos + xf1 * sin], axis=-1).astype(x.dtype)


def rope_qk(cfg: ModelConfig, q, k, positions):
    """Rotary positions on q and k, or neither where the config has no
    positional encoding (``cfg.rope`` False: Jamba's attention)."""
    if not cfg.rope:
        return q, k
    return (rope(q, positions, cfg.rope_theta),
            rope(k, positions, cfg.rope_theta))


def embed_init(key, vocab: int, d: int, dtype):
    return {"table": (jax.random.normal(key, (vocab, d), jnp.float32)
                      * 0.02).astype(dtype)}


def embed(p, tokens):
    out = jnp.take(p["table"], tokens, axis=0)
    return constrain(out, *(("dp",) + (None,) * (out.ndim - 1)))


def unembed(p, x):
    out = jnp.einsum("...d,vd->...v", x.astype(jnp.float32),
                     p["table"].astype(jnp.float32))
    nd = out.ndim
    return constrain(out, *((("dp",) + (None,) * (nd - 2)) + ("model",)))


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------

def attn_init(key, cfg: ModelConfig, cross: bool = False) -> Params:
    dt = _dtype(cfg)
    ks = jax.random.split(key, 6)
    p = {
        "wq": dense_init(ks[0], cfg.d_model, cfg.q_dim, dt),
        "wk": dense_init(ks[1], cfg.d_model, cfg.kv_dim, dt),
        "wv": dense_init(ks[2], cfg.d_model, cfg.kv_dim, dt),
        "wo": dense_init(ks[3], cfg.q_dim, cfg.d_model, dt,
                         scale=1.0 / math.sqrt(cfg.q_dim)),
    }
    if cfg.qk_norm:
        p["qnorm"] = norm_init(cfg.hd)
        p["knorm"] = norm_init(cfg.hd)
    return p


def _qkv(p, cfg: ModelConfig, x, kv_x):
    B = x.shape[0]
    q = jnp.einsum("bsd,de->bse", x, p["wq"]["w"])
    k = jnp.einsum("bsd,de->bse", kv_x, p["wk"]["w"])
    v = jnp.einsum("bsd,de->bse", kv_x, p["wv"]["w"])
    q = constrain(q.reshape(B, -1, cfg.n_heads, cfg.hd),
                  "dp", None, "model", None)
    k = constrain(k.reshape(B, -1, cfg.n_kv_heads, cfg.hd),
                  "dp", None, "model", None)
    v = constrain(v.reshape(B, -1, cfg.n_kv_heads, cfg.hd),
                  "dp", None, "model", None)
    if cfg.qk_norm:
        q = rms_norm(p["qnorm"], q, cfg.norm_eps)
        k = rms_norm(p["knorm"], k, cfg.norm_eps)
    return q, k, v


_CHUNKED_THRESHOLD = 4096
_Q_CHUNK = 512


def _xla_attention(q, k, v, causal: bool, q_offset: int = 0,
                   mask=None) -> jax.Array:
    """(B, S, H, D) attention via XLA einsums; q-chunked beyond threshold so
    the (B, H, Sq, Sk) score tensor never exceeds ~chunk×S per head.
    ``q_offset`` is the global position of query row 0 (prefix-extension
    prefill attends suffix queries over prefix+suffix keys).
    ``mask`` optionally supplies an explicit (Sq, Sk) boolean admission mask
    (True = attend) that *replaces* the index-based causal mask — used by the
    bucketed prefix-extension path whose key layout carries padding (mask
    values may be dynamic; shapes stay static)."""
    B, Sq, Hq, D = q.shape
    Hkv = k.shape[2]
    group = Hq // Hkv
    scale = D ** -0.5
    qh = jnp.swapaxes(q, 1, 2) * scale                     # (B, Hq, Sq, D)
    kh = jnp.swapaxes(k, 1, 2)                             # (B, Hkv, Sk, D)
    vh = jnp.swapaxes(v, 1, 2)
    Sk = kh.shape[2]
    qh = qh.reshape(B, Hkv, group, Sq, D)

    def block(q_blk, q_off):
        # f32 accumulation without materializing f32 copies of K/V
        s = accum_dot("bhgqd,bhkd->bhgqk", q_blk, kh)
        if mask is not None:
            m = jax.lax.dynamic_slice_in_dim(mask, q_off - q_offset,
                                             q_blk.shape[3], axis=0)
            s = jnp.where(m[None, None, None], s, -jnp.inf)
        elif causal:
            qi = q_off + jnp.arange(q_blk.shape[3])
            cm = qi[:, None] >= jnp.arange(Sk)[None, :]
            s = jnp.where(cm[None, None, None], s, -jnp.inf)
        w = jax.nn.softmax(s, axis=-1)
        return accum_dot("bhgqk,bhkd->bhgqd", w.astype(vh.dtype), vh)

    if Sq <= _CHUNKED_THRESHOLD:
        out = block(qh, q_offset)
    else:
        n = Sq // _Q_CHUNK
        qc = qh.reshape(B, Hkv, group, n, _Q_CHUNK, D)

        def body(i, acc):
            o = block(jax.lax.dynamic_index_in_dim(qc, i, axis=3,
                                                   keepdims=False),
                      q_offset + i * _Q_CHUNK)
            return jax.lax.dynamic_update_index_in_dim(acc, o, i, axis=3)

        acc0 = jnp.zeros((B, Hkv, group, n, _Q_CHUNK, D), jnp.float32)
        out = jax.lax.fori_loop(0, n, body, acc0)
        out = out.reshape(B, Hkv, group, Sq, D)
    out = out.reshape(B, Hq, Sq, D)
    return jnp.swapaxes(out, 1, 2).astype(q.dtype)


def attention(p, cfg: ModelConfig, x, *, positions, causal: bool = True,
              kv_x=None, use_pallas: str = "auto") -> jax.Array:
    """Full-sequence attention (train / prefill / encoder / cross)."""
    kv_in = x if kv_x is None else kv_x
    q, k, v = _qkv(p, cfg, x, kv_in)
    if kv_x is None:  # self-attention gets RoPE
        q, k = rope_qk(cfg, q, k, positions)
    if use_pallas in ("pallas", "interpret") or (
            use_pallas == "auto" and jax.default_backend() == "tpu"):
        out = kops.flash_attention(jnp.swapaxes(q, 1, 2),
                                   jnp.swapaxes(k, 1, 2),
                                   jnp.swapaxes(v, 1, 2), causal=causal,
                                   mode=use_pallas)
        out = jnp.swapaxes(out, 1, 2)
    else:
        out = _xla_attention(q, k, v, causal)
    B, S = x.shape[:2]
    out = out.reshape(B, S, cfg.q_dim)
    return constrain(jnp.einsum("bse,ed->bsd", out, p["wo"]["w"]),
                     "dp", None, None)


def attention_prefill_cache(p, cfg: ModelConfig, x, positions
                            ) -> Tuple[jax.Array, Tuple[jax.Array, jax.Array]]:
    """Like attention() but also returns the (k, v) cache (B, S, Hkv, D)."""
    q, k, v = _qkv(p, cfg, x, x)
    q, k = rope_qk(cfg, q, k, positions)
    out = _xla_attention(q, k, v, causal=True)
    B, S = x.shape[:2]
    y = jnp.einsum("bse,ed->bsd", out.reshape(B, S, cfg.q_dim), p["wo"]["w"])
    return y, (k, v)


def attention_prefill_extend(p, cfg: ModelConfig, x, positions, prefix_kv,
                             prefix_len=None
                             ) -> Tuple[jax.Array,
                                        Tuple[jax.Array, jax.Array]]:
    """Prefill the suffix of a prompt whose prefix K/V is already cached.

    x: (B, S_new, d) suffix activations; positions: (1, S_new) absolute
    positions starting at the prefix length; prefix_kv: (k, v) each
    (B, S_pre, Hkv, D). Returns (y, (k_full, v_full)) where the cache covers
    prefix + suffix. Exactness: suffix rows see bitwise the same keys/values
    and causal mask a full-prompt ``attention_prefill_cache`` would compute,
    so prefix reuse cannot perturb the sampled tokens.

    ``prefix_len`` switches to the **bucketed** layout (compile-once
    admission): the prefix buffer is padded to its static S_pre and only the
    first ``prefix_len`` (dynamic) rows are real; suffix rows may be padded
    past their true length too. The explicit mask admits real-prefix columns
    plus index-causal suffix columns (padded *query* rows produce garbage
    that callers discard; padded *key* columns are only reachable from
    padded query rows). Returns (y, (k, v)) with the **suffix-only** K/V —
    the caller assembles the contiguous cache at the dynamic offset.
    """
    k_pre, v_pre = prefix_kv
    S_pre = k_pre.shape[1]
    q, k, v = _qkv(p, cfg, x, x)
    q, k = rope_qk(cfg, q, k, positions)
    k_full = jnp.concatenate([k_pre, k], axis=1)
    v_full = jnp.concatenate([v_pre, v], axis=1)
    B, S = x.shape[:2]
    if prefix_len is None:
        out = _xla_attention(q, k_full, v_full, causal=True, q_offset=S_pre)
        y = jnp.einsum("bse,ed->bsd", out.reshape(B, S, cfg.q_dim),
                       p["wo"]["w"])
        return y, (k_full, v_full)
    col = jnp.arange(S_pre + S)[None, :]
    row = jnp.arange(S)[:, None]
    mask = jnp.where(col < S_pre, col < prefix_len, (col - S_pre) <= row)
    out = _xla_attention(q, k_full, v_full, causal=False, mask=mask)
    y = jnp.einsum("bse,ed->bsd", out.reshape(B, S, cfg.q_dim), p["wo"]["w"])
    return y, (k, v)


def attention_decode(p, cfg: ModelConfig, x, cache, pos, layer,
                     use_pallas: str = "auto"):
    """One-token decode. x: (B, 1, d); cache: (k, v) each stacked over
    periods, (P, B, Smax, Hkv, D) or folded (P, B, Smax, Hkv·D)
    (``lm.fold_kv``); pos: (B,) current lengths. The new token's K/V is
    written at ``(layer, row, pos[row])`` of the stacked buffers, in place
    where they are a loop carry, and the kernel reads that period's slice.
    Returns (y, new_cache) in the form given.

    Sharding: the KV cache is head_dim-sharded over 'model' (Hkv rarely
    divides the axis), so q/k/v here are constrained to the SAME hd sharding
    — otherwise the q·k dot partitioner cannot co-locate the contraction and
    falls back to all-gathering the entire cache per layer (measured: 1 GiB
    per layer per step before this constraint; the score all-reduce it buys
    is 16 MiB)."""
    B = x.shape[0]
    q, k_new, v_new = _qkv(p, cfg, x, x)
    if cfg.rope:
        q = rope(q, pos[:, None], cfg.rope_theta)
        k_new = rope(k_new, pos[:, None], cfg.rope_theta)
    q = constrain(q, "dp", None, None, "model")
    k_new = constrain(k_new, "dp", None, None, "model")
    v_new = constrain(v_new, "dp", None, None, "model")
    k_cache, v_cache = cache
    S = k_cache.shape[2]
    row = (B,) + k_cache.shape[3:]
    # write at pos (per batch row); "clip" clamps a position past the buffer
    # to its last row, as dynamic_update_slice does
    with jax.named_scope("attn.kv_write"):
        rows = jnp.arange(B)
        k_cache = k_cache.at[layer, rows, pos].set(
            k_new[:, 0].reshape(row), mode="clip")
        v_cache = v_cache.at[layer, rows, pos].set(
            v_new[:, 0].reshape(row), mode="clip")
    kv_len = pos + 1
    # the kernels read the cache heads-major
    with jax.named_scope("attn.kv_layout"):
        k_hm, v_hm = (
            jnp.transpose(jax.lax.dynamic_index_in_dim(c, layer, 0, False)
                          .reshape(B, S, cfg.n_kv_heads, cfg.hd),
                          (0, 2, 1, 3))
            for c in (k_cache, v_cache))
    q1 = q[:, 0].reshape(B, cfg.n_heads, cfg.hd)
    with jax.named_scope("attn.kernel"):
        if use_pallas in ("pallas", "interpret") or (
                use_pallas == "auto" and jax.default_backend() == "tpu"):
            out = kops.gqa_decode_attention(q1, k_hm, v_hm, kv_len,
                                            mode=use_pallas)
        else:
            from ..kernels import ref as kref
            out = kref.gqa_decode(q1, k_hm, v_hm, kv_len)
    out = out.reshape(B, 1, cfg.q_dim)
    y = jnp.einsum("bse,ed->bsd", out, p["wo"]["w"])
    return y, (k_cache, v_cache)


def cross_attention_cached(p, cfg: ModelConfig, x, kv_cache):
    """Cross-attn against precomputed encoder/vision (k, v): (B, T, Hkv, D)."""
    B, S = x.shape[:2]
    q = jnp.einsum("bsd,de->bse", x, p["wq"]["w"]).reshape(
        B, S, cfg.n_heads, cfg.hd)
    if cfg.qk_norm:
        q = rms_norm(p["qnorm"], q, cfg.norm_eps)
    k, v = kv_cache
    out = _xla_attention(q, k, v, causal=False)
    y = jnp.einsum("bse,ed->bsd", out.reshape(B, S, cfg.q_dim), p["wo"]["w"])
    return y


def cross_kv(p, cfg: ModelConfig, kv_x):
    """Precompute cross-attention K/V from encoder output / patch embeds."""
    B, T = kv_x.shape[:2]
    k = jnp.einsum("btd,de->bte", kv_x, p["wk"]["w"]).reshape(
        B, T, cfg.n_kv_heads, cfg.hd)
    v = jnp.einsum("btd,de->bte", kv_x, p["wv"]["w"]).reshape(
        B, T, cfg.n_kv_heads, cfg.hd)
    if cfg.qk_norm:
        k = rms_norm(p["knorm"], k, cfg.norm_eps)
    return k, v


# ---------------------------------------------------------------------------
# FFN
# ---------------------------------------------------------------------------

def ffn_init(key, cfg: ModelConfig, d_ff: Optional[int] = None) -> Params:
    dt = _dtype(cfg)
    ff = d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    p = {"wi": dense_init(ks[0], cfg.d_model, ff, dt),
         "wo": dense_init(ks[2], ff, cfg.d_model, dt,
                          scale=1.0 / math.sqrt(ff))}
    if cfg.activation == "swiglu":
        p["wg"] = dense_init(ks[1], cfg.d_model, ff, dt)
    return p


def ffn_apply(p, x, activation: str = "swiglu"):
    with jax.named_scope("mlp"):
        return _ffn(p, x, activation)


def _ffn(p, x, activation: str):
    h = jnp.einsum("bsd,df->bsf", x, p["wi"]["w"])
    if activation == "swiglu":
        g = jnp.einsum("bsd,df->bsf", x, p["wg"]["w"])
        h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * h
    else:  # gelu MLP (whisper)
        h = jax.nn.gelu(h.astype(jnp.float32)).astype(x.dtype)
    # named for selective remat: saving the ffn hidden skips recomputing the
    # two widest matmuls of each layer in the backward pass
    h = checkpoint_name(h, "ffn_hidden")
    h = constrain(h, "dp", None, "model")
    return constrain(jnp.einsum("bsf,fd->bsd", h, p["wo"]["w"]),
                     "dp", None, None)
