"""The language-model zoo: one functional LM covering all 10 assigned
architectures via the block-pattern config (dense / MoE / SSM / xLSTM /
hybrid / enc-dec / VLM).

Layer stacking uses ``lax.scan`` over *periods* (the repeating block pattern)
with per-position parameters stacked across periods, so the HLO is O(period)
regardless of depth; each period is wrapped in ``jax.checkpoint`` with a
configurable policy for training.

Public entry points (all pure):
    init(key, cfg)                                  -> params
    loss_fn(params, cfg, batch, use_pallas)         -> (loss, aux)
    train_logits(params, cfg, batch)                -> logits
    prefill(params, cfg, batch)                     -> (last_logits, Cache)
    decode_step(params, cfg, token, cache)          -> (logits, Cache)
    make_cache(cfg, batch, max_seq)                 -> empty Cache (decode-only
                                                       dry-runs)

``batch`` is a dict: tokens (B, S) int32, and for the stub-frontend archs
"frames" (audio) / "patches" (vlm): (B, T, d_model) precomputed embeddings.
Decode state is a ``Cache`` pytree whose leaves are stacked over periods.
"""
from __future__ import annotations

import functools
from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from . import layers as L
from . import moe as M
from . import ssm as S
from . import xlstm as X
from .config import ModelConfig
from .sharding import constrain

Params = Dict


class Cache(NamedTuple):
    """Decode state. Per-pattern-position dict entries, each stacked over
    periods on axis 0. ``pos`` is the shared decode cursor (synchronized
    continuous batching keeps rows aligned; per-row fill lives in kv_len)."""
    layer: Tuple                     # tuple over pattern positions
    cross: Tuple                     # cross-attn K/V per position ((), if none)
    enc: Optional[jax.Array]         # encoder output (whisper), else None
    kv_len: jax.Array                # (B,) valid lengths
    pos: jax.Array                   # scalar int32 cursor


#: Mixers whose decode state is a K/V cache written one position per step.
_KV_MIXERS = ("attn", "attn_bidir", "attn_cross")


def fold_kv(cfg: ModelConfig, cache: Cache) -> Cache:
    """``cache`` with each attention K/V leaf folded from (..., S, Hkv, D)
    to (..., S, Hkv·D). ``decode_step`` reads and writes either form. On a
    TPU the folded form's default layout is the one the decode loop works
    in, while (S, Hkv, D) with a head dim short of 128 lanes (stablelm's
    80) defaults to sequence-minor and costs a whole-cache relayout where
    a dispatch takes and returns it."""
    fold = lambda a: a.reshape(a.shape[:-2] + (-1,))
    return cache._replace(layer=tuple(
        tuple(map(fold, c)) if mixer in _KV_MIXERS else c
        for (mixer, _), c in zip(cfg.pattern, cache.layer)))


def unfold_kv(cfg: ModelConfig, cache: Cache) -> Cache:
    """Inverse of ``fold_kv``."""
    unfold = lambda a: a.reshape(a.shape[:-1] + (cfg.n_kv_heads, cfg.hd))
    return cache._replace(layer=tuple(
        tuple(map(unfold, c)) if mixer in _KV_MIXERS else c
        for (mixer, _), c in zip(cfg.pattern, cache.layer)))


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def _norm_init(cfg: ModelConfig):
    return (L.norm_init(cfg.d_model) if cfg.norm == "rmsnorm"
            else L.layernorm_init(cfg.d_model))


def _block_init(key, cfg: ModelConfig, mixer: str, ffn: str) -> Params:
    ks = jax.random.split(key, 4)
    p: Params = {"norm1": _norm_init(cfg)}
    if mixer in ("attn", "attn_bidir"):
        p["mixer"] = L.attn_init(ks[0], cfg)
    elif mixer == "cross":
        p["mixer"] = L.attn_init(ks[0], cfg, cross=True)
    elif mixer == "attn_cross":
        p["mixer"] = L.attn_init(ks[0], cfg)
        p["mixer2"] = L.attn_init(ks[3], cfg, cross=True)
        p["norm1b"] = _norm_init(cfg)
    elif mixer == "mamba":
        p["mixer"] = S.mamba_init(ks[0], cfg)
    elif mixer == "mlstm":
        p["mixer"] = X.mlstm_init(ks[0], cfg)
    elif mixer == "slstm":
        p["mixer"] = X.slstm_init(ks[0], cfg)
    else:
        raise ValueError(mixer)
    if ffn == "dense":
        p["norm2"] = _norm_init(cfg)
        p["ffn"] = L.ffn_init(ks[1], cfg)
    elif ffn == "moe":
        p["norm2"] = _norm_init(cfg)
        p["ffn"] = M.moe_init(ks[1], cfg)
    return p


def _stacked_block_init(key, cfg: ModelConfig, mixer: str, ffn: str,
                        n: int) -> Params:
    keys = jax.random.split(key, n)
    ps = [_block_init(k, cfg, mixer, ffn) for k in keys]
    return jax.tree.map(lambda *xs: jnp.stack(xs), *ps)


def init(key, cfg: ModelConfig) -> Params:
    ks = jax.random.split(key, len(cfg.pattern) + 4)
    params: Params = {
        "embed": L.embed_init(ks[0], cfg.vocab, cfg.d_model, L._dtype(cfg)),
        "final_norm": (L.norm_init(cfg.d_model) if cfg.norm == "rmsnorm"
                       else L.layernorm_init(cfg.d_model)),
        "blocks": [
            _stacked_block_init(ks[2 + i], cfg, mixer, ffn, cfg.n_periods)
            for i, (mixer, ffn) in enumerate(cfg.pattern)],
    }
    if not cfg.tie_embeddings:
        params["head"] = L.embed_init(ks[1], cfg.vocab, cfg.d_model,
                                      L._dtype(cfg))
    if cfg.encoder is not None:
        enc_cfg = cfg
        ke = jax.random.split(ks[-1], cfg.encoder.n_layers + 2)
        params["encoder"] = {
            "pos": (jax.random.normal(ke[0], (cfg.encoder.n_frames,
                                              cfg.d_model), jnp.float32)
                    * 0.02).astype(L._dtype(cfg)),
            "blocks": _stacked_block_init(ke[1], enc_cfg, "attn_bidir",
                                          "dense", cfg.encoder.n_layers),
            "final_norm": L.layernorm_init(cfg.d_model),
        }
    return params


# ---------------------------------------------------------------------------
# block application (full-sequence path)
# ---------------------------------------------------------------------------

def _apply_block_full(p, cfg: ModelConfig, mixer: str, ffn: str, x, *,
                      positions, cross_x, causal: bool, use_pallas: str,
                      collect_cache: bool, length=None):
    """Returns (x, cache_entry, aux). ``length``: the true length of a
    padded prompt, which a recurrent mixer's state stops at."""
    aux = jnp.float32(0.0)
    cache = ()
    h = L.apply_norm(cfg, p["norm1"], x)
    if mixer in ("attn", "attn_bidir"):
        if collect_cache:
            y, kv = L.attention_prefill_cache(p["mixer"], cfg, h, positions)
            cache = kv
        else:
            y = L.attention(p["mixer"], cfg, h, positions=positions,
                            causal=causal and mixer == "attn",
                            use_pallas=use_pallas)
        x = x + y
    elif mixer == "cross":
        kv = L.cross_kv(p["mixer"], cfg, cross_x)
        y = L.cross_attention_cached(p["mixer"], cfg, h, kv)
        if collect_cache:
            cache = kv
        x = x + y
    elif mixer == "attn_cross":
        if collect_cache:
            y, kv = L.attention_prefill_cache(p["mixer"], cfg, h, positions)
        else:
            y = L.attention(p["mixer"], cfg, h, positions=positions,
                            causal=True, use_pallas=use_pallas)
            kv = None
        x = x + y
        h2 = L.apply_norm(cfg, p["norm1b"], x)
        ckv = L.cross_kv(p["mixer2"], cfg, cross_x)
        x = x + L.cross_attention_cached(p["mixer2"], cfg, h2, ckv)
        if collect_cache:
            cache = (kv, ckv)
    elif mixer == "mamba":
        y, st = S.mamba_forward(p["mixer"], cfg, h, length=length)
        if collect_cache:
            cache = st
        x = x + y
    elif mixer == "mlstm":
        y, st = X.mlstm_forward(p["mixer"], cfg, h)
        if collect_cache:
            cache = st
        x = x + y
    elif mixer == "slstm":
        y, st = X.slstm_forward(p["mixer"], cfg, h)
        if collect_cache:
            cache = st
        x = x + y

    if ffn == "dense":
        x = x + L.ffn_apply(p["ffn"], L.apply_norm(cfg, p["norm2"], x),
                            activation=cfg.activation)
    elif ffn == "moe":
        y, aux = M.moe_apply(p["ffn"], cfg, L.apply_norm(cfg, p["norm2"], x))
        x = x + y
    return x, cache, aux


REMAT_POLICIES = {
    "full": None,   # save only period boundaries; recompute everything
    # save the per-layer FFN hidden activations: ~60% of the remat
    # recompute FLOPs for (B·S·d_ff/TP) bf16 per layer of memory
    "save_ffn_hidden": "ffn_hidden",
}


def _backbone_full(params, cfg: ModelConfig, x, *, positions, cross_x,
                   causal=True, use_pallas="auto", collect_cache=False,
                   remat=True, unroll=False, remat_policy="full",
                   length=None):
    """Run the block pattern over periods: ``lax.scan`` by default (O(1) HLO
    in depth), or a Python loop with ``unroll=True`` — used by the dry-run so
    ``cost_analysis`` sees every period (XLA counts while bodies once).
    Returns (x, caches, aux_sum)."""

    def period_body(x, stacked_slice):
        caches = []
        aux = jnp.float32(0.0)
        x = constrain(x, "dp", None, None)
        for i, (mixer, ffn) in enumerate(cfg.pattern):
            x, c, a = _apply_block_full(
                stacked_slice[i], cfg, mixer, ffn, x, positions=positions,
                cross_x=cross_x, causal=causal, use_pallas=use_pallas,
                collect_cache=collect_cache, length=length)
            caches.append(c)
            aux = aux + a
        return x, (tuple(caches), aux)

    if remat:
        name = REMAT_POLICIES.get(remat_policy)
        policy = (jax.checkpoint_policies.save_only_these_names(name)
                  if name else None)
        body = jax.checkpoint(period_body, policy=policy)
    else:
        body = period_body
    if unroll:
        caches_list, aux_sum = [], jnp.float32(0.0)
        for pi in range(cfg.n_periods):
            sl = jax.tree.map(lambda a: a[pi], params["blocks"])
            x, (caches, aux) = body(x, sl)
            caches_list.append(caches)
            aux_sum = aux_sum + aux
        if caches_list and any(c != () for c in caches_list[0]):
            caches = jax.tree.map(lambda *xs: jnp.stack(xs), *caches_list)
        else:
            caches = caches_list[0] if caches_list else ()
        return x, caches, aux_sum
    x, (caches, aux) = jax.lax.scan(
        lambda carry, sl: body(carry, sl), x, params["blocks"])
    return x, caches, jnp.sum(aux)


def _encode(params, cfg: ModelConfig, frames, unroll=False):
    """Whisper encoder over stubbed conv-frontend output (B, T, d)."""
    enc = params["encoder"]
    T = frames.shape[1]
    x = frames + enc["pos"][None, :T]
    positions = jnp.arange(T)[None, :]

    def body(x, p):
        x, _, _ = _apply_block_full(p, cfg, "attn_bidir", "dense", x,
                                    positions=positions, cross_x=None,
                                    causal=False, use_pallas="auto",
                                    collect_cache=False)
        return x, ()

    if unroll:
        for li in range(cfg.encoder.n_layers):
            x, _ = body(x, jax.tree.map(lambda a: a[li], enc["blocks"]))
    else:
        x, _ = jax.lax.scan(lambda c, p: body(c, p), x, enc["blocks"])
    return L.layer_norm(enc["final_norm"], x, cfg.norm_eps)


def _cross_input(params, cfg: ModelConfig, batch, unroll=False):
    if cfg.family == "audio":
        return _encode(params, cfg, batch["frames"], unroll=unroll)
    if cfg.family == "vlm":
        return batch["patches"]
    return None


# ---------------------------------------------------------------------------
# training
# ---------------------------------------------------------------------------

def train_logits(params, cfg: ModelConfig, batch, use_pallas="auto",
                 remat=True, unroll=False, remat_policy="full"):
    tokens = batch["tokens"]
    B, Sq = tokens.shape
    x = L.embed(params["embed"], tokens)
    positions = jnp.arange(Sq)[None, :]
    cross_x = _cross_input(params, cfg, batch, unroll=unroll)
    x, _, aux = _backbone_full(params, cfg, x, positions=positions,
                               cross_x=cross_x, use_pallas=use_pallas,
                               remat=remat, unroll=unroll,
                               remat_policy=remat_policy)
    x = L.apply_norm(cfg, params["final_norm"], x) \
        if cfg.norm == "rmsnorm" else L.layer_norm(params["final_norm"], x,
                                                   cfg.norm_eps)
    head = params.get("head", params["embed"])
    return L.unembed(head, x), aux


def loss_fn(params, cfg: ModelConfig, batch, use_pallas="auto", remat=True,
            aux_weight: float = 0.01, unroll=False, remat_policy="full"):
    logits, aux = train_logits(params, cfg, batch, use_pallas, remat, unroll,
                               remat_policy)
    labels = batch["labels"]
    # sharding-friendly cross-entropy: logsumexp reduces over the (possibly
    # vocab-sharded) last axis; the label logit comes from a mask-select
    # rather than a gather so no cross-shard index arithmetic is needed.
    m = jax.lax.stop_gradient(jnp.max(logits, axis=-1, keepdims=True))
    lse = m[..., 0] + jnp.log(jnp.sum(jnp.exp(logits - m), axis=-1))
    V = logits.shape[-1]
    onehot = jax.nn.one_hot(labels, V, dtype=logits.dtype)
    label_logit = jnp.sum(logits * onehot, axis=-1)
    nll = constrain(lse - label_logit, "dp", None)
    mask = (labels >= 0).astype(jnp.float32)
    loss = jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return loss + aux_weight * aux, {"nll": loss, "aux": aux}


# ---------------------------------------------------------------------------
# serving: prefill
# ---------------------------------------------------------------------------

def prefill(params, cfg: ModelConfig, batch, max_seq: Optional[int] = None,
            use_pallas="auto", unroll=False,
            length: Optional[jax.Array] = None):
    """Run the prompt, return (last-token logits, Cache). KV caches are
    allocated at ``max_seq`` (default: prompt length) and prefixed with the
    prompt's K/V.

    ``length`` enables **bucketed prefill**: ``tokens`` may be padded past
    the real prompt (to a compile-size bucket) and ``length`` is the dynamic
    true length — logits are read at row ``length - 1`` and the cache's
    ``kv_len``/``pos`` marks only the real prompt as valid, so the padded
    tail (whose K/V rows land beyond ``kv_len`` and get overwritten by
    decode) cannot perturb outputs. Exact where rows are independent and
    state stops at the length: attention (causal masking), Mamba (padding
    is state-neutral, ``ssm.mamba_forward``) and dropless MoE; xLSTM
    integrates every token and MoE capacity lets padding displace real
    tokens from expert slots, so callers must not pad those (the serving
    engine gates on the block pattern)."""
    tokens = batch["tokens"]
    B, Sq = tokens.shape
    max_seq = max_seq or Sq
    x = L.embed(params["embed"], tokens)
    positions = jnp.arange(Sq)[None, :]
    cross_x = _cross_input(params, cfg, batch, unroll=unroll)
    x, caches, _ = _backbone_full(params, cfg, x, positions=positions,
                                  cross_x=cross_x, use_pallas=use_pallas,
                                  collect_cache=True, remat=False,
                                  unroll=unroll, length=length)
    x = (L.apply_norm(cfg, params["final_norm"], x) if cfg.norm == "rmsnorm"
         else L.layer_norm(params["final_norm"], x, cfg.norm_eps))
    head = params.get("head", params["embed"])
    if length is None:
        last = x[:, -1:]
        kv_fill, pos_fill = Sq, jnp.int32(Sq)
    else:
        length = jnp.asarray(length, jnp.int32)
        last = jax.lax.dynamic_slice_in_dim(x, length - 1, 1, axis=1)
        kv_fill, pos_fill = length, length
    logits = L.unembed(head, last)[:, 0]

    layer_caches, cross_caches = [], []
    for (mixer, _), c in zip(cfg.pattern, caches):
        if mixer in ("attn", "attn_bidir"):
            k, v = c
            layer_caches.append((_pad_cache(k, max_seq),
                                 _pad_cache(v, max_seq)))
            cross_caches.append(())
        elif mixer == "attn_cross":
            (k, v), ckv = c
            layer_caches.append((_pad_cache(k, max_seq),
                                 _pad_cache(v, max_seq)))
            cross_caches.append(ckv)
        elif mixer == "cross":
            layer_caches.append(())
            cross_caches.append(c)
        else:  # recurrent state
            layer_caches.append(c)
            cross_caches.append(())
    cache = Cache(layer=tuple(layer_caches), cross=tuple(cross_caches),
                  enc=None, kv_len=jnp.full((B,), kv_fill, jnp.int32),
                  pos=jnp.asarray(pos_fill, jnp.int32))
    return logits, cache


def prefill_extend(params, cfg: ModelConfig, batch, prefix,
                   max_seq: Optional[int] = None,
                   prefix_len: Optional[jax.Array] = None,
                   length: Optional[jax.Array] = None):
    """Prefill only the uncached suffix of a prompt (paged prefix reuse).

    ``batch["tokens"]`` holds the (B, S_new) suffix; ``prefix`` is a tuple
    over pattern positions of (k, v), each (P, B, S_pre, Hkv, D) — the cached
    whole-block prefix gathered by ``serving.kvcache.PagedKVStore``. Returns
    (last-token logits, Cache) covering prefix + suffix, exactly as
    ``prefill`` on the concatenated prompt would (suffix queries attend the
    cached keys under the same causal mask, so outputs are bit-identical).

    **Bucketed mode** (compile-once admission): with ``prefix_len`` given,
    the prefix buffer is padded to a fixed block budget (only the first
    ``prefix_len`` dynamic rows are real) and the suffix tokens may be
    padded to a length bucket with ``length`` as the true suffix length —
    one executable then serves every (matched-blocks, suffix-length)
    combination in the bucket. The returned cache stays contiguous: suffix
    K/V is written at the dynamic ``prefix_len`` offset of a max_seq buffer.

    Pure-attention patterns only: recurrent mixers carry no position-sliceable
    prefix state (the serving engine gates paged mode on the same predicate).
    """
    assert all(mixer == "attn" for mixer, _ in cfg.pattern), \
        "prefill_extend supports pure-attention block patterns"
    tokens = batch["tokens"]
    B, Sn = tokens.shape
    S_pre = prefix[0][0].shape[2]
    bucketed = prefix_len is not None
    if bucketed:
        prefix_len = jnp.asarray(prefix_len, jnp.int32)
        suffix_len = (jnp.asarray(length, jnp.int32) if length is not None
                      else jnp.int32(Sn))
        total = prefix_len + suffix_len
        assert max_seq is not None and Sn <= max_seq, \
            "bucketed extend needs an explicit max_seq >= the padded suffix"
        positions = prefix_len + jnp.arange(Sn)[None, :]
    else:
        total = S_pre + Sn
        positions = S_pre + jnp.arange(Sn)[None, :]
    max_seq = max_seq or (S_pre + Sn)
    x = L.embed(params["embed"], tokens)

    def period_body(x, sl):
        stacked, pref = sl
        new_kv = []
        aux = jnp.float32(0.0)
        x = constrain(x, "dp", None, None)
        for i, (mixer, ffn) in enumerate(cfg.pattern):
            p = stacked[i]
            h = L.apply_norm(cfg, p["norm1"], x)
            y, kv = L.attention_prefill_extend(
                p["mixer"], cfg, h, positions, pref[i],
                prefix_len=prefix_len if bucketed else None)
            x = x + y
            new_kv.append(kv)
            if ffn == "dense":
                x = x + L.ffn_apply(p["ffn"], L.apply_norm(cfg, p["norm2"], x),
                                    activation=cfg.activation)
            elif ffn == "moe":
                y2, aux2 = M.moe_apply(p["ffn"], cfg,
                                       L.apply_norm(cfg, p["norm2"], x))
                x = x + y2
                aux = aux + aux2
        return x, tuple(new_kv)

    x, caches = jax.lax.scan(lambda c, sl: period_body(c, sl), x,
                             (params["blocks"], prefix))
    x = (L.apply_norm(cfg, params["final_norm"], x) if cfg.norm == "rmsnorm"
         else L.layer_norm(params["final_norm"], x, cfg.norm_eps))
    head = params.get("head", params["embed"])
    if bucketed:
        last = jax.lax.dynamic_slice_in_dim(x, suffix_len - 1, 1, axis=1)
    else:
        last = x[:, -1:]
    logits = L.unembed(head, last)[:, 0]

    if bucketed:
        # contiguous cache: prefix buffer padded to max_seq, suffix K/V
        # written at the dynamic prefix_len offset (real rows [0, total) —
        # anything beyond is masked by kv_len and overwritten by decode)
        def assemble(pre, suf):
            base = _pad_cache(pre, max_seq)
            return jax.lax.dynamic_update_slice(
                base, suf.astype(base.dtype),
                (0, 0, prefix_len, 0, 0))

        layer_caches = tuple(
            (assemble(pre_k, k), assemble(pre_v, v))
            for (pre_k, pre_v), (k, v) in zip(prefix, caches))
    else:
        layer_caches = tuple((_pad_cache(k, max_seq), _pad_cache(v, max_seq))
                             for k, v in caches)
    cache = Cache(layer=layer_caches,
                  cross=tuple(() for _ in cfg.pattern), enc=None,
                  kv_len=jnp.full((B,), total, jnp.int32),
                  pos=jnp.asarray(total, jnp.int32))
    return logits, cache


def _pad_cache(k, max_seq):
    """(P_rep, B, S, H, D) -> padded to max_seq along S."""
    pad = max_seq - k.shape[2]
    if pad <= 0:
        return k
    return jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))


# ---------------------------------------------------------------------------
# serving: decode
# ---------------------------------------------------------------------------

def make_cache(cfg: ModelConfig, batch_size: int, max_seq: int,
               kv_len: Optional[jax.Array] = None,
               cross_tokens: Optional[int] = None) -> Cache:
    """Empty (or logically-filled) decode cache for decode-only dry-runs:
    allocates the same buffers prefill would, with kv_len marking the fill."""
    dt = L._dtype(cfg)
    P_rep = cfg.n_periods
    layer, cross = [], []
    for mixer, _ in cfg.pattern:
        if mixer in ("attn", "attn_bidir", "attn_cross"):
            shp = (P_rep, batch_size, max_seq, cfg.n_kv_heads, cfg.hd)
            layer.append((jnp.zeros(shp, dt), jnp.zeros(shp, dt)))
            if mixer == "attn_cross":
                t = cross_tokens or cfg.cross_kv_tokens
                cshp = (P_rep, batch_size, t, cfg.n_kv_heads, cfg.hd)
                cross.append((jnp.zeros(cshp, dt), jnp.zeros(cshp, dt)))
            else:
                cross.append(())
        elif mixer == "cross":
            layer.append(())
            t = cross_tokens or cfg.cross_kv_tokens
            cshp = (P_rep, batch_size, t, cfg.n_kv_heads, cfg.hd)
            cross.append((jnp.zeros(cshp, dt), jnp.zeros(cshp, dt)))
        elif mixer == "mamba":
            h, cw = S.init_state(cfg, batch_size)
            layer.append((_rep(h, P_rep), _rep(cw, P_rep)))
            cross.append(())
        elif mixer == "mlstm":
            st = X.init_mlstm_state(cfg, batch_size)
            layer.append(tuple(_rep(s, P_rep) for s in st))
            cross.append(())
        elif mixer == "slstm":
            st = X.init_slstm_state(cfg, batch_size)
            layer.append(tuple(_rep(s, P_rep) for s in st))
            cross.append(())
    kv_len = (jnp.zeros((batch_size,), jnp.int32) if kv_len is None
              else kv_len)
    return Cache(layer=tuple(layer), cross=tuple(cross), enc=None,
                 kv_len=kv_len, pos=jnp.max(kv_len).astype(jnp.int32))


def _rep(x, n):
    return jnp.broadcast_to(x[None], (n,) + x.shape)


def _apply_block_decode(p, cfg: ModelConfig, mixer: str, ffn: str, x,
                        cache_entry, cross_entry, kv_len, layer, use_pallas):
    """x: (B, 1, d). ``cache_entry`` is, for a K/V mixer, the (k, v) of every
    period, written at period ``layer``; for a recurrent mixer, this period's
    state. Returns (x, new_cache_entry, picks): ``picks`` (B,) counts each
    row's top-k picks among the experts an MoE block holds, None for other
    blocks."""
    h = L.apply_norm(cfg, p["norm1"], x)
    new_entry = cache_entry
    if mixer in _KV_MIXERS:
        y, new_entry = L.attention_decode(p["mixer"], cfg, h, cache_entry,
                                          kv_len, layer,
                                          use_pallas=use_pallas)
        x = x + y
        if mixer == "attn_cross":
            h2 = L.apply_norm(cfg, p["norm1b"], x)
            x = x + L.cross_attention_cached(p["mixer2"], cfg, h2, cross_entry)
    elif mixer == "cross":
        x = x + L.cross_attention_cached(p["mixer"], cfg, h, cross_entry)
    elif mixer == "mamba":
        y, st = S.mamba_decode(p["mixer"], cfg, h, cache_entry)
        new_entry = st
        x = x + y
    elif mixer == "mlstm":
        y, st = X.mlstm_forward(p["mixer"], cfg, h, state=cache_entry)
        new_entry = st
        x = x + y
    elif mixer == "slstm":
        y, st = X.slstm_forward(p["mixer"], cfg, h, state=cache_entry)
        new_entry = st
        x = x + y

    picks = None
    if ffn == "dense":
        x = x + L.ffn_apply(p["ffn"], L.apply_norm(cfg, p["norm2"], x),
                            activation=cfg.activation)
    elif ffn == "moe":
        y, _, picks = M.moe_apply(p["ffn"], cfg,
                                  L.apply_norm(cfg, p["norm2"], x),
                                  with_picks=True)
        x = x + y
        picks = picks[:, 0]
    return x, new_entry, picks


def decode_step(params, cfg: ModelConfig, token, cache: Cache,
                use_pallas="auto", unroll=False, expert_rows=False):
    """token: (B, 1) int32. Returns (logits (B, vocab), new Cache), and with
    ``expert_rows`` a third output: (B,) int32, each row's top-k picks
    among the held experts, summed over the MoE blocks.

    The K/V of attention positions stay stacked over periods in the period
    loop's carry: each period writes the new token's K/V at its own index,
    in place, so the loop neither slices the whole cache in nor emits it
    again. Recurrent states and read-only cross K/V are small and are
    scanned per period."""
    x = L.embed(params["embed"], token)
    is_kv = tuple(mixer in _KV_MIXERS for mixer, _ in cfg.pattern)
    kv = tuple(c if a else () for a, c in zip(is_kv, cache.layer))
    state = tuple(() if a else c for a, c in zip(is_kv, cache.layer))

    def period_body(carry, sl):
        x, kv, layer = carry[:3]
        rows = carry[3:]
        stacked, state_c, cross_c = sl
        kv, new_state = list(kv), []
        x = constrain(x, "dp", None, None)
        for i, (mixer, ffn) in enumerate(cfg.pattern):
            x, nc, picks = _apply_block_decode(
                stacked[i], cfg, mixer, ffn, x,
                kv[i] if is_kv[i] else state_c[i], cross_c[i],
                cache.kv_len, layer, use_pallas)
            if is_kv[i]:
                kv[i] = nc
            new_state.append(() if is_kv[i] else nc)
            if rows and picks is not None:
                rows = (rows[0] + picks,)
        return (x, tuple(kv), layer + 1) + rows, tuple(new_state)

    xs = (params["blocks"], state, cache.cross)
    rows0 = ((jnp.zeros(token.shape[:1], jnp.int32),) if expert_rows
             else ())
    if unroll:
        carry, per_period = (x, kv, 0) + rows0, []
        for pi in range(cfg.n_periods):
            carry, ncs = period_body(carry,
                                     jax.tree.map(lambda a: a[pi], xs))
            per_period.append(ncs)
        new_state = jax.tree.map(lambda *xs: jnp.stack(xs), *per_period)
    else:
        carry, new_state = jax.lax.scan(
            period_body, (x, kv, jnp.int32(0)) + rows0, xs)
    x, kv = carry[:2]
    x = (L.apply_norm(cfg, params["final_norm"], x) if cfg.norm == "rmsnorm"
         else L.layer_norm(params["final_norm"], x, cfg.norm_eps))
    head = params.get("head", params["embed"])
    logits = L.unembed(head, x)[:, 0]
    new_layer = tuple(k if a else st
                      for a, k, st in zip(is_kv, kv, new_state))
    new_cache = cache._replace(layer=new_layer, kv_len=cache.kv_len + 1,
                               pos=cache.pos + 1)
    if expert_rows:
        return logits, new_cache, carry[3]
    return logits, new_cache
