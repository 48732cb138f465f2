"""Per-architecture smoke tests (deliverable f): reduced same-family config,
one forward/train step + one prefill/decode step on CPU; asserts output
shapes and finiteness. The FULL configs are exercised only via the dry-run."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import all_ids, get
from repro.models import lm
from repro.models.config import SHAPES, cell_applicable


def _batch(cfg, B=2, S=16, key=0):
    tokens = jax.random.randint(jax.random.key(key), (B, S), 0, cfg.vocab)
    batch = {"tokens": tokens, "labels": tokens}
    if cfg.family == "audio":
        batch["frames"] = jax.random.normal(
            jax.random.key(key + 1), (B, cfg.encoder.n_frames, cfg.d_model),
            jnp.bfloat16)
    if cfg.family == "vlm":
        batch["patches"] = jax.random.normal(
            jax.random.key(key + 1), (B, cfg.cross_kv_tokens, cfg.d_model),
            jnp.bfloat16)
    return batch


@pytest.mark.parametrize("arch_id", all_ids())
def test_smoke_train_step(arch_id):
    cfg = get(arch_id).smoke()
    params = lm.init(jax.random.key(0), cfg)
    batch = _batch(cfg)

    def loss(p):
        l, _ = lm.loss_fn(p, cfg, batch)
        return l

    val, grads = jax.value_and_grad(loss)(params)
    assert np.isfinite(float(val)), arch_id
    gnorm = sum(float(jnp.sum(jnp.square(g.astype(jnp.float32))))
                for g in jax.tree.leaves(grads))
    assert np.isfinite(gnorm) and gnorm > 0, arch_id


@pytest.mark.parametrize("arch_id", all_ids())
def test_smoke_logits_shape_and_finite(arch_id):
    cfg = get(arch_id).smoke()
    params = lm.init(jax.random.key(0), cfg)
    batch = _batch(cfg)
    logits, aux = lm.train_logits(params, cfg, batch)
    B, S = batch["tokens"].shape
    assert logits.shape == (B, S, cfg.vocab)
    assert np.isfinite(np.asarray(logits, np.float32)).all(), arch_id


@pytest.mark.parametrize("arch_id", all_ids())
def test_smoke_prefill_decode(arch_id):
    cfg = get(arch_id).smoke()
    params = lm.init(jax.random.key(0), cfg)
    batch = _batch(cfg)
    del batch["labels"]
    B, S = batch["tokens"].shape
    logits, cache = lm.prefill(params, cfg, batch, max_seq=S + 4)
    assert logits.shape == (B, cfg.vocab)
    tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
    for _ in range(2):
        logits, cache = lm.decode_step(params, cfg, tok, cache)
        assert logits.shape == (B, cfg.vocab)
        assert np.isfinite(np.asarray(logits, np.float32)).all(), arch_id
        tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
    assert int(cache.pos) == S + 2


@pytest.mark.parametrize("arch_id", ["stablelm-3b", "qwen3-4b", "jamba-v0.1-52b",
                                     "xlstm-1.3b", "whisper-tiny"])
def test_decode_matches_teacher_forcing(arch_id):
    """KV-cache/state decode must agree with the full forward pass."""
    cfg = get(arch_id).smoke()
    if cfg.moe is not None:
        # a decode step routes B tokens and never reaches an expert's
        # capacity; the full pass routes B·S and drops past it, so the two
        # compute the same function only where nothing drops: give every
        # expert room for every token (C = T·k·(E/k)/E = T)
        cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
            cfg.moe, capacity_factor=cfg.moe.n_experts / cfg.moe.top_k))
    params = lm.init(jax.random.key(0), cfg)
    batch = _batch(cfg)
    del batch["labels"]
    tokens = batch["tokens"]
    B, S = tokens.shape
    lp, cache = lm.prefill(params, cfg, batch, max_seq=S + 2)
    full, _ = lm.train_logits(params, cfg, batch)
    np.testing.assert_allclose(np.asarray(lp), np.asarray(full[:, -1]),
                               rtol=5e-2, atol=5e-2)
    nxt = jnp.argmax(lp, -1)[:, None].astype(jnp.int32)
    lg, _ = lm.decode_step(params, cfg, nxt, cache)
    batch2 = dict(batch, tokens=jnp.concatenate([tokens, nxt], 1))
    full2, _ = lm.train_logits(params, cfg, batch2)
    np.testing.assert_allclose(np.asarray(lg), np.asarray(full2[:, -1]),
                               rtol=6e-2, atol=6e-2)


def test_param_counts_match_published_sizes():
    expected = {  # arch id -> (total B, active B, rel tolerance)
        "stablelm-3b": (2.8, 2.8, 0.30),
        "qwen3-4b": (4.0, 4.0, 0.25),
        "stablelm-12b": (12.1, 12.1, 0.15),
        "qwen3-1.7b": (2.0, 2.0, 0.30),
        "dbrx-132b": (132.0, 36.0, 0.10),
        "llama4-maverick-400b-a17b": (400.0, 17.0, 0.20),
        "xlstm-1.3b": (1.3, 1.3, 0.45),
        "llama-3.2-vision-11b": (10.6, 10.6, 0.15),
        "jamba-v0.1-52b": (52.0, 12.0, 0.10),
    }
    for arch_id, (tot, act, tol) in expected.items():
        c = get(arch_id).config().param_counts()
        assert abs(c["total"] / 1e9 - tot) / tot < tol, \
            (arch_id, c["total"] / 1e9)
        assert abs(c["active"] / 1e9 - act) / act < tol + 0.1, \
            (arch_id, c["active"] / 1e9)


def test_long_context_applicability():
    assert cell_applicable(get("xlstm-1.3b").config(), "long_500k")[0]
    assert cell_applicable(get("jamba-v0.1-52b").config(), "long_500k")[0]
    ok, reason = cell_applicable(get("stablelm-3b").config(), "long_500k")
    assert not ok and "sub-quadratic" in reason
