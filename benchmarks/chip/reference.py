"""Plain float32 forward pass of the served models, and the gaps it reads.

The block is the one the configuration files state: RMSNorm, attention with
rotary positions over the whole head (grouped-query where ``n_kv_heads`` is
smaller than ``n_heads``, RMSNorm on each query and key head where
``qk_norm``), a SwiGLU feed-forward, a final RMSNorm and an untied output
head. It imports nothing of the program. It reads weights in the layout the
benchmark made them in (``deploy.make_params``): ``embed.table``,
``head.table``, ``final_norm.g`` and ``blocks[0]``, whose leaves are stacked
over layers.

Every matrix product runs in float32 at ``Precision.HIGHEST``, with no
kernel, cache or batching of requests: each row is its own sequence, and a
causal mask keeps padding after a row's last token from reaching it. Layers
run one at a time in a scan, each layer's weights upcast inside it, so the
reference fits beside the served weights.

``mode="fp8"`` is the control: every weight matrix rounded to float8 e4m3
(three mantissa bits) scaled per output channel, and every activation
entering one scaled per token (W8A8), the nearest precision below the
bfloat16 the configurations serve in.
"""
from __future__ import annotations

import functools
from typing import Dict, NamedTuple

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


class Arch(NamedTuple):
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    rope_theta: float
    norm_eps: float
    qk_norm: bool


def arch(sizes: Dict) -> Arch:
    return Arch(**{k: sizes[k] for k in Arch._fields})


def _fp8(x, axis):
    """Rounds to float8 e4m3's three mantissa bits, scaled so the largest
    magnitude along ``axis`` maps to its largest finite value, 448."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 448.0
    s = jnp.where(s == 0, 1.0, s)
    m, e = jnp.frexp(x / s)
    return jnp.ldexp(jnp.round(m * 16.0) / 16.0, e) * s


def _mm(x, w, mode):
    if mode == "fp8":
        x, w = _fp8(x, -1), _fp8(w, 0)
    return jnp.matmul(x, w, precision=HIGHEST)


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _rope(x, theta):
    """x: (K, S, H, D); rotates the two halves of each head (GPT-NeoX)."""
    S, D = x.shape[1], x.shape[-1]
    half = D // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def forward(params, a: Arch, tokens, mode: str = "ref"):
    """Logits (K, S, vocab) of every position of ``tokens`` (K, S)."""
    f32 = lambda t: t.astype(jnp.float32)
    K, S = tokens.shape
    Hq, Hkv, hd, eps = a.n_heads, a.n_kv_heads, a.head_dim, a.norm_eps
    causal = jnp.tril(jnp.ones((S, S), bool))
    x = f32(jnp.take(params["embed"]["table"], tokens, axis=0))

    def layer(x, p):
        p = jax.tree.map(f32, p)
        m = p["mixer"]
        h = _rms(x, p["norm1"]["g"], eps)
        q = _mm(h, m["wq"]["w"], mode).reshape(K, S, Hq, hd)
        k = _mm(h, m["wk"]["w"], mode).reshape(K, S, Hkv, hd)
        v = _mm(h, m["wv"]["w"], mode).reshape(K, S, Hkv, hd)
        if a.qk_norm:
            q = _rms(q, m["qnorm"]["g"], eps)
            k = _rms(k, m["knorm"]["g"], eps)
        q, k = _rope(q, a.rope_theta), _rope(k, a.rope_theta)
        k = jnp.repeat(k, Hq // Hkv, axis=2)
        v = jnp.repeat(v, Hq // Hkv, axis=2)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HIGHEST) * hd ** -0.5
        w = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        o = jnp.einsum("bhqk,bkhd->bqhd", w, v, precision=HIGHEST)
        x = x + _mm(o.reshape(K, S, Hq * hd), m["wo"]["w"], mode)
        h = _rms(x, p["norm2"]["g"], eps)
        f = p["ffn"]
        u = jax.nn.silu(_mm(h, f["wg"]["w"], mode)) * _mm(h, f["wi"]["w"],
                                                         mode)
        return x + _mm(u, f["wo"]["w"], mode), None

    x, _ = jax.lax.scan(layer, x, params["blocks"][0])
    x = _rms(x, f32(params["final_norm"]["g"]), eps)
    return _mm(x, f32(params["head"]["table"]).T, mode)


def _gap_of(ref_logits, picked):
    """How far the logit of ``picked`` lies below the reference's best."""
    best = jnp.max(ref_logits, -1)
    got = jnp.take_along_axis(ref_logits, picked[..., None], -1)[..., 0]
    return best - got


@functools.partial(jax.jit, static_argnames=("a",))
def served_gaps(params, a: Arch, tokens, targets, mask):
    """Per position: the gap of the token the program served next.
    ``targets[k, s]`` is the token served after position ``s``; ``mask``
    marks the positions that predicted a served token."""
    return jnp.where(mask, _gap_of(forward(params, a, tokens), targets), 0.0)


@functools.partial(jax.jit, static_argnames=("a",))
def control_gaps(params, a: Arch, tokens, mask):
    """Per position: the gap of the token the fp8 control puts first, read
    at the same prompts and served tokens."""
    pick = jnp.argmax(forward(params, a, tokens, "fp8"), -1)
    return jnp.where(mask, _gap_of(forward(params, a, tokens), pick), 0.0)
