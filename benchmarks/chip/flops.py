"""Operations and bytes that serving needs, from a model's sizes.

The sizes are the ones in a configuration file's ``architectures`` entry
(``configs/<config>.json``), never the program's own config objects. What is
counted is what the mathematics needs, whatever implements it: a decode
step attends over the ``kv_len`` positions a row holds, not over the
``max_seq`` buffer it is handed, and a prefill counts the prompt's tokens,
not the bucket it is padded to.
"""
from __future__ import annotations

from typing import Dict, Iterable, Tuple

BF16 = 2   # bytes per element of the served weights and caches


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


def roofline_seconds(flops: float, nbytes: float, peak: Dict) -> float:
    """The least time the chip could take: the larger of the compute bound
    and the memory bound."""
    return max(flops / peak["bf16_flops_per_s"],
               nbytes / peak["hbm_bytes_per_s"])
