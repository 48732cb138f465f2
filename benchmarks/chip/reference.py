"""What every kind's plain float32 forward pass shares, and the gaps read
from it.

Each kind of served architecture owns its forward pass
(``archs/<kind>.py``: ``forward(params, sizes, tokens, mode)``, logits of
every position, every matrix product in float32 at ``Precision.HIGHEST``),
so that a kind can upcast its weights as far as fits beside the served
ones. This module holds the pieces they share and the gap arithmetic, which
takes the kind's forward.

``mode="fp8"`` is the control: every weight matrix rounded to float8 e4m3
(three mantissa bits) scaled per output channel, and every activation
entering one scaled per token (W8A8), the nearest precision below the
bfloat16 the configurations serve in.
"""
from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


class Sizes(dict):
    """A configuration's sizes, hashable so that a jitted function can take
    them as a static argument."""

    def __hash__(self):
        return hash(json.dumps(self, sort_keys=True))


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


def _gap_of(ref_logits, picked):
    """How far the logit of ``picked`` lies below the reference's best."""
    best = jnp.max(ref_logits, -1)
    got = jnp.take_along_axis(ref_logits, picked[..., None], -1)[..., 0]
    return best - got


@functools.partial(jax.jit, static_argnames=("forward", "sizes"))
def served_gaps(params, forward, sizes: Sizes, tokens, targets, mask):
    """Per position: the gap of the token the program served next.
    ``targets[k, s]`` is the token served after position ``s``; ``mask``
    marks the positions that predicted a served token."""
    return jnp.where(mask, _gap_of(forward(params, sizes, tokens, "ref"),
                                   targets), 0.0)


@functools.partial(jax.jit, static_argnames=("forward", "sizes"))
def control_gaps(params, forward, sizes: Sizes, tokens, mask):
    """Per position: the gap of the token the fp8 control puts first, read
    at the same prompts and served tokens."""
    pick = jnp.argmax(forward(params, sizes, tokens, "fp8"), -1)
    return jnp.where(mask, _gap_of(forward(params, sizes, tokens, "ref"),
                                   pick), 0.0)
