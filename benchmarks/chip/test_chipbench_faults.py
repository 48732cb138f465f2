"""``correct`` comes out false when the timed path is broken underneath,
for each fault a serving cell can have, and for the fp8 control.

The limit is the check's, scaled to ``.smoke()`` widths: there the bfloat16
path's widest gap reads 0.003-0.005 on the CPU, so 0.02 separates it from
a wrong token."""
from __future__ import annotations

import sys
from pathlib import Path

import jax
import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(REPO), str(REPO / "src")]

from benchmarks.chip import checks, cpu_smoke, deploy, harness  # noqa: E402
from benchmarks.chip import openloop  # noqa: E402
from benchmarks.chip.traffic import generator  # noqa: E402
from repro.models import lm  # noqa: E402
from repro.serving import engine  # noqa: E402

CELL = "testbed.paper_mix"
LIMITS = {"stablelm-3b": 0.02, "qwen3-1.7b": 0.02}
SEED = 2**31 + 99


@pytest.fixture
def fresh_jit():
    jax.clear_caches()
    yield
    jax.clear_caches()


def test_sound_path_is_correct():
    assert cpu_smoke.run(CELL, SEED, 2.0, limits=LIMITS)["correct"] is True


def test_a_token_altered_where_produced_is_not_correct(monkeypatch):
    commit = engine.LLMEngine._commit_chunk

    def altered(self, work):
        outs = work.outs.copy()
        outs[:, 0] = (outs[:, 0] + 1) % self.cfg.vocab
        return commit(self, work._replace(outs=outs))

    monkeypatch.setattr(engine.LLMEngine, "_commit_chunk", altered)
    r = cpu_smoke.run(CELL, SEED, 2.0, limits=LIMITS)
    assert r["correct"] is False, r["checks"]


def test_a_step_that_leaves_its_state_unchanged_is_not_correct(
        monkeypatch, fresh_jit):
    step = lm.decode_step

    def stale(params, cfg, token, cache, *args, **kwargs):
        logits, new = step(params, cfg, token, cache, *args, **kwargs)
        return logits, new._replace(layer=cache.layer)

    monkeypatch.setattr(lm, "decode_step", stale)
    r = cpu_smoke.run(CELL, SEED, 2.0, limits=LIMITS)
    assert r["correct"] is False, r["checks"]


def test_fp8_control_is_not_correct():
    """The reference in fp8 (W8A8) read at the same prompts and served
    tokens as a sound run: its widest gap fails the limit."""
    c = cpu_smoke.cell(CELL)
    items = generator.schedule(c.traffic, 2.0, SEED)
    dep = deploy.build(c.config, SEED, cpu_smoke.archs(c))
    deploy.warm_up(dep, [it.prompt for it in items[:harness.WARM_PROMPTS]])
    w = openloop.drive(dep, items, 2.0)
    done = [i for i in range(len(items)) if not np.isnan(w.finish[i])]
    rows = checks.sample(dep, done, SEED, c.config["check"]["sample_requests"])
    spec = c.config["check"]
    prog = checks.check(dep, rows, spec, LIMITS)
    ctrl = checks.check(dep, rows, spec, LIMITS, control=True)
    assert all(v["value"] <= v["limit"] for v in prog.values()), prog
    assert any(v["value"] > v["limit"] for v in ctrl.values()), ctrl
