"""The dense block: its program config, its plain float32 forward pass, and
the operations and bytes that serving it needs.

The block is the one the configuration files state: RMSNorm, attention with
rotary positions over the whole head (grouped-query where ``n_kv_heads`` is
smaller than ``n_heads``, RMSNorm on each query and key head where
``qk_norm``), a SwiGLU feed-forward, a final RMSNorm and an untied output
head. The forward pass imports nothing of the program. It reads weights in
the layout the benchmark made them in (``deploy.make_params``):
``embed.table``, ``head.table``, ``final_norm.g`` and ``blocks[0]``, whose
leaves are stacked over layers. Every leaf is a ``table``, ``w`` or ``g``,
so the kind adds no rule of its own (``LEAVES``).

Every matrix product runs in float32 at ``Precision.HIGHEST``, with no
kernel, cache or batching of requests: each row is its own sequence, and a
causal mask keeps padding after a row's last token from reaching it. Layers
run one at a time in a scan, each layer's weights upcast inside it, so the
reference fits beside the served weights. ``mode="fp8"`` is the control
(``reference._mm``).

The counts follow the sizes in a configuration file's ``architectures``
entry, never the program's own config objects (``flops.py`` says what is
counted).
"""
from __future__ import annotations

from typing import Dict, Iterable, NamedTuple, Tuple

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig

from benchmarks.chip.flops import BF16
from benchmarks.chip.reference import HIGHEST, _mm, _rms

LEAVES: Dict = {}


def model_config(name: str, a: Dict) -> ModelConfig:
    """The program's config for the sizes a configuration file states."""
    return ModelConfig(
        name=name, family="dense", n_layers=a["n_layers"],
        d_model=a["d_model"], n_heads=a["n_heads"],
        n_kv_heads=a["n_kv_heads"], d_ff=a["d_ff"], vocab=a["vocab"],
        pattern=(("attn", "dense"),), head_dim=a["head_dim"],
        qk_norm=a["qk_norm"], rope_theta=a["rope_theta"], norm="rmsnorm",
        activation="swiglu", tie_embeddings=False, norm_eps=a["norm_eps"],
        dtype=a["dtype"])


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


def _rope(x, theta):
    """x: (K, S, H, D); rotates the two halves of each head (GPT-NeoX)."""
    S, D = x.shape[1], x.shape[-1]
    half = D // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def forward(params, sizes: Dict, tokens, mode: str = "ref"):
    """Logits (K, S, vocab) of every position of ``tokens`` (K, S)."""
    a = arch(sizes)
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


def q_dim(a: Dict) -> int:
    return a["n_heads"] * a["head_dim"]


def kv_dim(a: Dict) -> int:
    return a["n_kv_heads"] * a["head_dim"]


def layer_matmul_params(a: Dict) -> int:
    """Weights one token multiplies in one layer: q, k, v and output
    projections and the three SwiGLU matrices."""
    d = a["d_model"]
    return 2 * d * q_dim(a) + 2 * d * kv_dim(a) + 3 * d * a["d_ff"]


def token_matmul_flops(a: Dict) -> int:
    """Weight FLOPs of one token through every layer and the output head."""
    return 2 * (a["n_layers"] * layer_matmul_params(a)
                + a["vocab"] * a["d_model"])


def attention_flops(a: Dict, kv_len: int) -> int:
    """Scores and weighted sum of one query over ``kv_len`` positions, in
    every layer."""
    return 4 * a["n_layers"] * q_dim(a) * kv_len


def decode_flops(a: Dict, kv_lens: Iterable[int]) -> int:
    """Model FLOPs of one decode step over rows holding ``kv_lens``
    positions each (the new token's included)."""
    kv_lens = list(kv_lens)
    return (len(kv_lens) * token_matmul_flops(a)
            + sum(attention_flops(a, k) for k in kv_lens))


def prefill_flops(a: Dict, n_tokens: int) -> int:
    """Model FLOPs of a causal prefill of ``n_tokens``: every layer for each
    token, the output head for the last one only, and attention over the
    causal triangle."""
    d = a["d_model"]
    dense = 2 * n_tokens * a["n_layers"] * layer_matmul_params(a)
    head = 2 * a["vocab"] * d
    attn = 2 * a["n_layers"] * q_dim(a) * n_tokens * (n_tokens + 1)
    return dense + head + attn


def decode_attention_cost(a: Dict, kv_lens: Iterable[int]) -> Tuple[int, int]:
    """(FLOPs, HBM bytes) the decode-attention kernel needs over all layers
    for rows holding ``kv_lens`` positions: K and V of those positions read
    once, the query read and the output written once."""
    kv_lens = list(kv_lens)
    L, hd = a["n_layers"], a["head_dim"]
    flops = sum(4 * L * q_dim(a) * k for k in kv_lens)
    kv_bytes = sum(2 * L * kv_dim(a) * k * BF16 for k in kv_lens)
    q_bytes = 2 * L * len(kv_lens) * a["n_heads"] * hd * BF16
    return flops, kv_bytes + q_bytes
