"""Training substrate: optimizers, gradient compression, trainer loop,
checkpoint/restart fault tolerance."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st  # soft optional dep

from repro.configs import get
from repro.training.grad_compress import (compress_int8, decompress_int8,
                                          compress_topk, init_residual)
from repro.training.optim import (OptConfig, _dq8, _q8, adafactor, adamw,
                                  adamw8bit, make_optimizer,
                                  optimizer_for_arch)
from repro.training.trainer import TrainConfig, Trainer


# ---------------------------------------------------------------------------
# optimizers
# ---------------------------------------------------------------------------
def _quadratic_params():
    return {"w": jnp.array([3.0, -2.0, 1.5]), "b": jnp.array([[1.0, -1.0]])}


@pytest.mark.parametrize("name", ["adamw", "adamw8bit", "adafactor"])
def test_optimizers_minimize_quadratic(name):
    opt = make_optimizer(name, OptConfig(lr=0.05, weight_decay=0.0))
    params = _quadratic_params()
    state = opt.init(params)

    def loss(p):
        return sum(jnp.sum(x ** 2) for x in jax.tree.leaves(p))

    l0 = float(loss(params))
    step = jax.jit(lambda g, s, p: opt.update(g, s, p))
    for _ in range(60):
        grads = jax.grad(loss)(params)
        params, state = step(grads, state, params)
    assert float(loss(params)) < 0.05 * l0, name


def test_optimizer_states_match_param_shapes():
    params = {"a": jnp.zeros((8, 16)), "b": jnp.zeros((5,))}
    st8 = adamw8bit().init(params)
    assert st8["m"]["a"]["q"].shape == (8, 16)
    assert st8["m"]["a"]["q"].dtype == jnp.int8
    stf = adafactor().init(params)
    assert stf["f"]["a"]["vr"].shape == (8,)
    assert stf["f"]["a"]["vc"].shape == (16,)
    assert stf["f"]["b"]["v"].shape == (5,)


@given(st.integers(0, 2 ** 31 - 1))
@settings(max_examples=15, deadline=None)
def test_int8_quant_roundtrip_error_bounded(seed):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((7, 300)) * 10, jnp.float32)
    q, s = _q8(x)
    back = _dq8(q, s, x.shape)
    err = np.abs(np.asarray(back) - np.asarray(x))
    # blockwise absmax int8: error <= scale/2 per block
    scale = np.asarray(s)
    assert (err <= np.repeat(scale, 256, axis=-1)[:, :300] * 0.5 + 1e-6).all()


def test_optimizer_tiering():
    assert optimizer_for_arch(2e9) == "adamw"
    assert optimizer_for_arch(130e9) == "adamw8bit"
    assert optimizer_for_arch(400e9) == "adafactor"


# ---------------------------------------------------------------------------
# gradient compression
# ---------------------------------------------------------------------------
def test_int8_error_feedback_telescopes():
    """Sum of dequantized payloads + final residual == sum of raw grads."""
    rng = np.random.default_rng(0)
    grads_seq = [
        {"w": jnp.asarray(rng.standard_normal((4, 300)), jnp.float32)}
        for _ in range(5)]
    residual = init_residual(grads_seq[0])
    sent_total = jnp.zeros((4, 300))
    for g in grads_seq:
        q, s, residual = compress_int8(g, residual)
        sent_total = sent_total + decompress_int8(q, s, g)["w"]
    raw_total = sum(g["w"] for g in grads_seq)
    np.testing.assert_allclose(np.asarray(sent_total + residual["w"]),
                               np.asarray(raw_total), rtol=1e-4, atol=1e-4)


def test_topk_keeps_largest():
    g = {"w": jnp.asarray(np.arange(100, dtype=np.float32))}
    res = init_residual(g)
    sent, new_res = compress_topk(g, res, frac=0.1)
    nz = np.flatnonzero(np.asarray(sent["w"]))
    assert set(nz) == set(range(90, 100))
    np.testing.assert_allclose(np.asarray(sent["w"] + new_res["w"]),
                               np.asarray(g["w"]))


# ---------------------------------------------------------------------------
# trainer: loss decreases + checkpoint/restart
# ---------------------------------------------------------------------------
def test_trainer_loss_decreases():
    cfg = get("stablelm-3b").smoke()
    t = Trainer(cfg, TrainConfig(seq_len=64, global_batch=8, steps=80,
                                 log_every=10, data_vocab=64, data_chains=1,
                                 data_branch=4,
                                 opt=OptConfig(lr=3e-3, weight_decay=0.0)))
    _, _, hist = t.run()
    first, last = hist[0]["nll"], hist[-1]["nll"]
    assert last < first - 0.5, (first, last)


def test_trainer_microbatching_matches_full_batch():
    cfg = get("qwen3-1.7b").smoke()
    kw = dict(seq_len=32, global_batch=4, steps=3, log_every=1,
              opt=OptConfig(lr=1e-3))
    t1 = Trainer(cfg, TrainConfig(microbatches=1, **kw))
    t2 = Trainer(cfg, TrainConfig(microbatches=2, **kw))
    _, _, h1 = t1.run()
    _, _, h2 = t2.run()
    # same data, same init: losses should track closely
    assert abs(h1[0]["loss"] - h2[0]["loss"]) < 2e-2


def test_checkpoint_restart_resumes_exactly(tmp_path):
    cfg = get("stablelm-3b").smoke()
    common = dict(seq_len=32, global_batch=4, log_every=1,
                  checkpoint_dir=str(tmp_path / "ck"), checkpoint_every=5,
                  opt=OptConfig(lr=1e-3))
    # run 10 steps straight through
    t_full = Trainer(cfg, TrainConfig(steps=10, **common))
    p_full, _, _ = t_full.run(resume=False)
    # wipe and run 5, "crash", resume to 10
    import shutil
    shutil.rmtree(tmp_path / "ck")
    t_a = Trainer(cfg, TrainConfig(steps=5, **common))
    t_a.run(resume=False)
    t_b = Trainer(cfg, TrainConfig(steps=10, **common))
    p_b, _, _ = t_b.run(resume=True)
    assert t_b.ckpt.latest_step() == 10
    for a, b in zip(jax.tree.leaves(p_full), jax.tree.leaves(p_b)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=2e-2, atol=2e-2)


def test_checkpoint_atomic_no_partial_state(tmp_path):
    from repro.checkpoint.manager import latest_step, save_checkpoint
    tree = {"x": jnp.arange(10)}
    save_checkpoint(tmp_path, 1, tree)
    # simulate a crashed writer: stray tmp dir must be ignored + cleaned
    (tmp_path / "step_00000002.tmp").mkdir()
    assert latest_step(tmp_path) == 1
    save_checkpoint(tmp_path, 3, tree)
    assert latest_step(tmp_path) == 3
    assert not (tmp_path / "step_00000002.tmp").exists()


def test_checkpoint_elastic_reshard(tmp_path):
    """Save unsharded, restore onto a 1-device 'mesh' with specs."""
    from repro.checkpoint.manager import load_checkpoint, save_checkpoint
    from jax.sharding import PartitionSpec as P
    tree = {"w": jnp.arange(32, dtype=jnp.float32).reshape(4, 8)}
    specs = {"w": P(None, "model")}
    save_checkpoint(tmp_path, 0, tree, specs=specs)
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         (jax.sharding.AxisType.Auto,) * 2)
    restored, manifest = load_checkpoint(tmp_path, tree, mesh=mesh)
    np.testing.assert_array_equal(np.asarray(restored["w"]),
                                  np.asarray(tree["w"]))
    assert manifest["specs"]["w"] == [None, "model"]


# ---------------------------------------------------------------------------
# data pipeline
# ---------------------------------------------------------------------------
def test_data_deterministic_and_host_sharded():
    from repro.data import DataConfig, SyntheticLMData
    cfg = DataConfig(vocab=128, seq_len=16, global_batch=4)
    a = SyntheticLMData(cfg).batch(7)
    b = SyntheticLMData(cfg).batch(7)
    np.testing.assert_array_equal(a["tokens"], b["tokens"])
    # two hosts partition the global batch exactly
    h0 = SyntheticLMData(cfg, host_index=0, n_hosts=2).batch(7)
    h1 = SyntheticLMData(cfg, host_index=1, n_hosts=2).batch(7)
    np.testing.assert_array_equal(
        np.concatenate([h0["tokens"], h1["tokens"]]), a["tokens"])
    # labels are next-token shifted
    np.testing.assert_array_equal(a["tokens"][:, 1:], a["labels"][:, :-1])
