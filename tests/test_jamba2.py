"""The Jamba hybrid on the serving path, at a small size on the CPU: prefill
then decode through the cache against the benchmark's plain reference
(``benchmarks/chip/archs/jamba.py``), the expert layer's share of the
experts, a bucket-padded prefill, a fleet cohort against per-engine
``step_n``, and the named scopes and counter the tracing reads."""
from __future__ import annotations

import dataclasses
import re
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(REPO)]

from benchmarks.chip import deploy  # noqa: E402
from benchmarks.chip.cell import load_kind  # noqa: E402
from repro.cluster.spec import paper_testbed  # noqa: E402
from repro.core.policy import PAPER_DEFAULTS  # noqa: E402
from repro.launch.serve import build_models  # noqa: E402
from repro.models import lm  # noqa: E402
from repro.models import moe as M  # noqa: E402
from repro.obs import Obs  # noqa: E402
from repro.obs.timeline import Timeline  # noqa: E402
from repro.obs.trace import NOOP_TRACER  # noqa: E402
from repro.serving import (ClusterServer, EngineConfig, LLMEngine,  # noqa
                           ServeRequest)
from repro.serving import engine as engine_mod  # noqa: E402
from repro.serving import fleet as fleet_mod  # noqa: E402
from repro.workload.trace import build_trace  # noqa: E402

KIND = load_kind("jamba")
# one period at d 64: 4 experts of which 2 are held, every other width cut
SIZES = dict(kind="jamba", n_layers=8, d_model=64, n_heads=4, n_kv_heads=2,
             head_dim=16, d_ff=96, n_experts=4, experts_held=[0, 2],
             top_k=2, d_state=16, d_conv=4, expand=2, dt_rank=8, vocab=128,
             norm_eps=1e-6, dtype="float32")


def _model(seed=5, **over):
    sizes = dict(SIZES, **over)
    cfg = KIND.model_config("jamba-test", sizes)
    return sizes, cfg, deploy.make_params(cfg, deploy.seed_key(seed), KIND)


def _tokens(vocab, shape, seed=3):
    return np.asarray(jax.random.randint(jax.random.key(seed), shape, 0,
                                         vocab), np.int32)


def test_prefill_then_decode_matches_the_reference():
    """Prefill of 11 tokens, then 13 decode steps fed the reference's own
    next tokens: every logit within 1e-4 of the float32 reference's full
    forward pass over the 24 positions."""
    sizes, cfg, params = _model()
    toks = _tokens(sizes["vocab"], (2, 24))
    with jax.default_matmul_precision("highest"):
        ref = np.asarray(KIND.forward(params, sizes, jnp.asarray(toks)))
        P = 11
        logits, cache = lm.prefill(params, cfg, {"tokens": toks[:, :P]},
                                   max_seq=32)
        got = [np.asarray(logits)]
        step = jax.jit(lambda c, t: lm.decode_step(params, cfg, t, c))
        for t in range(P, 24):
            logits, cache = step(cache, toks[:, t:t + 1])
            got.append(np.asarray(logits))
    got = np.stack(got, axis=1)
    assert np.abs(ref).max() > 0.1
    np.testing.assert_allclose(got, ref[:, P - 1:], atol=1e-4, rtol=0)


def test_expert_shares_add_up_to_the_uncut_layer():
    """Holding experts {0, 1} and {2, 3} on two layers that share the
    router: their outputs add up to the dropless layer that holds all four,
    which is the reference's layer over every expert."""
    _, cfg, params = _model()
    p = params["blocks"][1]["ffn"]              # an expert layer
    p = jax.tree.map(lambda a: a[0], p)         # its one period
    x = jax.random.normal(jax.random.key(9), (3, 5, cfg.d_model))
    whole = dict(p, **{k: {"w": jnp.concatenate(
        [p[k]["w"], p[k]["w"][::-1]])} for k in ("wi", "wg", "wo")})
    parts = []
    for lo, hi in ((0, 2), (2, 4)):
        c = dataclasses.replace(cfg, moe=dataclasses.replace(
            cfg.moe, held=(lo, hi)))
        share = dict(whole, **{k: {"w": whole[k]["w"][lo:hi]}
                               for k in ("wi", "wg", "wo")})
        y, _ = M.moe_apply(share, c, x)
        parts.append(np.asarray(y))
    all_cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
        cfg.moe, held=(0, 4)))
    uncut, _ = M.moe_apply(whole, all_cfg, x)
    np.testing.assert_allclose(parts[0] + parts[1], np.asarray(uncut),
                               atol=1e-5, rtol=0)
    with jax.default_matmul_precision("highest"):
        ref = KIND._experts(whole, x, dict(SIZES, experts_held=[0, 4]),
                            "ref")
    np.testing.assert_allclose(np.asarray(uncut), np.asarray(ref),
                               atol=1e-5, rtol=0)
    assert min(np.abs(p_).max() for p_ in parts) > 1e-3


@pytest.mark.parametrize("L", [1, 4, 13])
def test_bucket_padded_prefill_gives_the_exact_length_state(L):
    """A prompt padded to the 32 bucket with its true length traced gives
    the exact-length prefill's logits, Mamba states and attention K/V."""
    sizes, cfg, params = _model()
    toks = _tokens(sizes["vocab"], (1, L))
    padded = np.zeros((1, 32), np.int32)
    padded[:, :L] = toks
    lg0, c0 = jax.jit(lm.prefill, static_argnames=("cfg", "max_seq"))(
        params, cfg, {"tokens": toks}, max_seq=64)
    lg1, c1 = engine_mod._prefill_bucketed(params, cfg, jnp.asarray(padded),
                                           jnp.int32(L), 64)
    np.testing.assert_allclose(np.asarray(lg1), np.asarray(lg0), atol=2e-5,
                               rtol=0)
    assert int(c1.kv_len[0]) == L and int(c1.pos) == L
    for (mixer, _), a, b in zip(cfg.pattern, c0.layer, c1.layer):
        for x0, x1 in zip(a, b):
            x0, x1 = np.asarray(x0), np.asarray(x1)
            if mixer == "attn":          # rows past the length are masked
                x0, x1 = x0[:, :, :L], x1[:, :, :L]
            np.testing.assert_allclose(x1, x0, atol=2e-5, rtol=0)


def test_hybrid_engine_takes_the_bucket_and_the_cohort():
    _, cfg, params = _model(dtype="bfloat16")
    eng = LLMEngine(cfg, params, EngineConfig(max_slots=2, max_seq=64))
    assert eng._bucket_ok and eng.fleet_ok
    capacity = dataclasses.replace(cfg, moe=dataclasses.replace(
        cfg.moe, held=None))
    eng = LLMEngine(capacity, params, EngineConfig(max_slots=2, max_seq=64))
    assert not eng._bucket_ok and not eng.fleet_ok


def _serve(builders, fleet, chunk):
    tl = Timeline()
    srv = ClusterServer(paper_testbed(), builders, PAPER_DEFAULTS,
                        EngineConfig(max_slots=2, max_seq=48,
                                     max_new_tokens=6),
                        hedge_after=10**9, fleet=fleet,
                        obs=Obs(tracer=NOOP_TRACER, metrics=None,
                                audit=None, timeline=tl))
    for i, r in enumerate(build_trace(24, seed=5).requests[:12]):
        srv.submit(ServeRequest(request_id=i, req=r, max_new_tokens=6))
    return srv, srv.run(chunk=chunk), tl


@pytest.fixture(scope="module")
def fleet_runs():
    """The paper testbed's four model names served by the hybrid (one
    engine for the cloud, a cohort of nine for the edges), chunked, as
    cohorts and as the per-engine loop, holding 2 of 4 experts; and as
    cohorts holding all 4. Each run (server, results, timeline) is made
    when a test first asks for it."""
    runs = {}

    def get(held, fleet):
        if (held, fleet) not in runs:
            _, cfg, _ = _model(dtype="bfloat16", experts_held=held)
            runs[held, fleet] = _serve(build_models(big=cfg, small=cfg),
                                       fleet, chunk=3)
        return runs[held, fleet]
    return get


def test_fleet_cohort_of_hybrid_engines_matches_per_engine_step_n(
        fleet_runs):
    """Chunked cohort dispatch of Mamba and dropless-MoE engines reproduces
    the per-engine ``step_n`` loop: every token and every step stamp."""
    srv, fleet, _ = fleet_runs((0, 2), True)
    assert [len(c) for c in srv._cohorts] == [1, 9]
    _, loop, _ = fleet_runs((0, 2), False)
    assert sorted(fleet) == list(range(12))
    assert fleet == loop


def test_expert_rows_counts_every_pick_of_the_emitted_tokens(fleet_runs):
    """With every expert held, each token a cohort decodes adds top_k picks
    per expert layer to ``moe.expert_rows``; with half held, fewer."""
    n_moe = sum(f == "moe" for _, f in KIND.PERIOD)
    for held in ((0, 4), (0, 2)):
        _, _, tl = fleet_runs(held, True)
        decoded = tl.counters["cohort.tokens_emitted"]
        rows = tl.counters["moe.expert_rows"]
        assert decoded > 0
        if held == (0, 4):
            assert rows == SIZES["top_k"] * n_moe * decoded
        else:
            assert 0 < rows < SIZES["top_k"] * n_moe * decoded


def _op_names(hlo: str):
    return set(re.findall(r'op_name="([^"]+)"', hlo))


def test_named_scopes_split_the_hybrid_decode_and_prefill():
    """``moe.route``, ``moe.experts``, ``mamba.conv`` and ``mamba.ssm`` name
    ops inside the cohort's ``decode.step`` and inside the prefill."""
    _, cfg, params = _model(dtype="bfloat16")
    engines = [LLMEngine(cfg, params, EngineConfig(max_slots=2, max_seq=32))
               for _ in range(2)]
    cohort = fleet_mod.Cohort(engines)
    B = 2
    decode = fleet_mod._cohort_decode_chunk.lower(
        params, cfg, cohort.state, jnp.ones((2, B), jnp.int32),
        jnp.ones((2, B), bool), jnp.arange(2, dtype=jnp.int32),
        jnp.ones(2, bool), 1, -1).compile().as_text()
    prefill = engine_mod._prefill_bucketed.lower(
        params, cfg, jnp.zeros((1, 32), jnp.int32), jnp.int32(5),
        32).compile().as_text()
    for text, outer in ((decode, "decode.step"), (prefill, "prefill")):
        names = _op_names(text)
        for scope in ("moe.route", "moe.experts", "mamba.conv", "mamba.ssm"):
            assert any(f"{outer}/" in n and f"/{scope}/" in n
                       for n in names), (outer, scope)
