"""The plain reference against the program's own prefill and decode, at
``.smoke()`` widths on the CPU, in float32 so that the two agree to
rounding."""
from __future__ import annotations

import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(REPO), str(REPO / "src")]

from benchmarks.chip import cpu_smoke, deploy, reference  # noqa: E402
from benchmarks.chip.cell import load_kind  # noqa: E402
from repro.models import lm  # noqa: E402

PROMPT = [3, 17, 99, 250, 7, 41, 300, 12, 5]
NEW = 8


def _program(arch: str, dtype: str):
    sizes = dict(cpu_smoke.ARCHS[arch], dtype=dtype)
    kind = load_kind(sizes["kind"])
    cfg = kind.model_config(arch, sizes)
    params = deploy.make_params(cfg, deploy.seed_key(2**33 + 5), kind)
    logits, cache = lm.prefill(params, cfg,
                               {"tokens": jnp.asarray([PROMPT])}, max_seq=32)
    steps, served = [logits[0]], []
    for _ in range(NEW):
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        served.append(int(tok[0]))
        logits, cache = lm.decode_step(params, cfg, tok[:, None], cache)
        steps.append(logits[0])
    return sizes, params, np.stack(steps[:NEW]), served


@pytest.mark.parametrize("arch", ["stablelm-3b", "qwen3-1.7b"])
def test_reference_equals_prefill_then_decode(arch):
    sizes, params, prog, served = _program(arch, "float32")
    seq = jnp.asarray([PROMPT + served])
    ref = load_kind(sizes["kind"]).forward(params, sizes, seq)[0]
    L = len(PROMPT)
    want = np.asarray(ref[L - 1:L - 1 + NEW])
    scale = float(np.abs(want).max())
    np.testing.assert_allclose(prog, want, atol=1e-4 * scale, rtol=0)


@pytest.mark.parametrize("arch", ["stablelm-3b", "qwen3-1.7b"])
def test_served_gap_is_rounding_and_the_fp8_control_is_wider(arch):
    sizes, params, _, served = _program(arch, "bfloat16")
    seq = np.zeros((2, 32), np.int32)
    seq[0, :len(PROMPT) + NEW] = PROMPT + served
    tgt = np.zeros_like(seq)
    mask = np.zeros(seq.shape, bool)
    L = len(PROMPT)
    tgt[0, L - 1:L - 1 + NEW] = served
    mask[0, L - 1:L - 1 + NEW] = True
    fwd, a = load_kind(sizes["kind"]).forward, reference.Sizes(sizes)
    got = np.asarray(reference.served_gaps(params, fwd, a, seq, tgt, mask))
    ctl = np.asarray(reference.control_gaps(params, fwd, a, seq, mask))
    assert (got[~mask] == 0).all() and (ctl[~mask] == 0).all()
    assert got.max() < 0.02
    assert ctl.max() >= 0.0
    # a served token replaced by another lies far below the reference's best
    tgt[0, L - 1] = (tgt[0, L - 1] + 1) % sizes["vocab"]
    bad = np.asarray(reference.served_gaps(params, fwd, a, seq, tgt, mask))
    assert bad.max() > 0.05
    jax.clear_caches()
