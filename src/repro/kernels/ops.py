"""Public jit'd wrappers for the Pallas kernels with backend dispatch.

On TPU the Pallas kernels run natively; elsewhere (CPU hosts, tests) they
run through ``interpret=True`` or the jnp reference — controlled by
``mode``:

* "auto"      — Pallas on TPU, reference otherwise (the model zoo default,
                 so dry-runs lower the XLA path and real TPUs get kernels);
* "pallas"    — force the kernel (native);
* "interpret" — force the kernel in interpret mode (kernel-correctness tests);
* "ref"       — force the jnp oracle.

A kernel path never drops to the reference on its own: an axis the
requested tile does not divide gets the largest tile that does, and a
length no tile divides raises.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import decode_attention as _dec
from . import dominance as _dom
from . import flash_attention as _fa
from . import paged_attention as _paged
from . import ref

_MODES = ("auto", "pallas", "interpret", "ref")


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def resolve(mode: str) -> str:
    """The mode a kernel call in ``mode`` runs in on this backend."""
    assert mode in _MODES, mode
    if mode == "auto":
        return "pallas" if _on_tpu() else "ref"
    return mode


def _tile(n: int, want: int) -> int:
    """Largest tile <= ``want`` that divides ``n``: the whole axis when it
    fits, else a multiple of 8 (the TPU sublane tile)."""
    if n <= want:
        return n
    for b in range(want - want % 8, 7, -8):
        if n % b == 0:
            return b
    raise ValueError(f"no tile <= {want} that is a multiple of 8 divides "
                     f"length {n}; pad the sequence to a multiple of 8")


def dominance_matrix(F: jax.Array, mode: str = "auto") -> jax.Array:
    m = resolve(mode)
    if m == "ref":
        return ref.dominance_matrix(F)
    out = _dom.dominance_matrix_pallas(F, interpret=(m == "interpret"))
    return out.astype(bool)


def dominance_counts(F: jax.Array, mode: str = "auto") -> jax.Array:
    m = resolve(mode)
    if m == "ref":
        return ref.dominance_counts(F)
    return _dom.dominance_counts_pallas(F, interpret=(m == "interpret"))


def flash_attention(q, k, v, causal: bool = True, mode: str = "auto",
                    block_q: int = _fa.DEFAULT_BLOCK_Q,
                    block_k: int = _fa.DEFAULT_BLOCK_K) -> jax.Array:
    m = resolve(mode)
    if m == "ref":
        return ref.mha_prefill(q, k, v, causal=causal)
    S = q.shape[2]
    return _fa.flash_attention(q, k, v, causal=causal,
                               block_q=_tile(S, block_q),
                               block_k=_tile(S, block_k),
                               interpret=(m == "interpret"))


def gqa_decode_attention(q, k_cache, v_cache, kv_len, mode: str = "auto",
                         block_k: int = _dec.DEFAULT_BLOCK_K) -> jax.Array:
    m = resolve(mode)
    if m == "ref":
        return ref.gqa_decode(q, k_cache, v_cache, kv_len)
    return _dec.gqa_decode_attention(q, k_cache, v_cache, kv_len,
                                     block_k=_tile(k_cache.shape[2], block_k),
                                     interpret=(m == "interpret"))


def check_decode_length(n: int, mode: str = "auto") -> None:
    """Raise now, rather than at the first trace, if
    :func:`gqa_decode_attention` in ``mode`` cannot tile a cache of ``n``
    positions."""
    if resolve(mode) != "ref":
        _tile(n, _dec.DEFAULT_BLOCK_K)


def paged_gqa_decode_attention(q, k_pool, v_pool, block_table, kv_len,
                               mode: str = "auto") -> jax.Array:
    """Decode attention over a paged KV pool addressed by a per-request
    block table (see ``serving.kvcache``). Pool-resident decode path for
    prefix-reuse serving on TPU; jnp gather+reference elsewhere."""
    m = resolve(mode)
    if m == "ref":
        return ref.paged_gqa_decode(q, k_pool, v_pool, block_table, kv_len)
    return _paged.paged_gqa_decode_attention(q, k_pool, v_pool, block_table,
                                             kv_len,
                                             interpret=(m == "interpret"))
