"""Pallas TPU kernel: blocked Pareto-dominance matrix for NSGA-II.

The O(P²·M) all-pairs comparison is the non-dominated-sort hot spot at large
population sizes (P ≥ 4k when the router is re-optimized over long traces
with direct-assignment genomes). The MXU offers nothing for boolean
domination, so this is a **VPU/bandwidth kernel**: each grid cell loads two
objective slabs — F_i (BI, M) and F_j (BJ, M) — into VMEM and writes one
(BI, BJ) int8 tile of the dominance matrix.

TPU adaptation notes (vs a CUDA port):
* tiles are (128, 128) to match the VPU lane layout (8×128 vregs; the BI
  dimension vectorizes over sublanes, BJ over lanes);
* the dominated side arrives transposed, as an (M, BJ) slab, so both
  operands of every comparison are 2-D: the kernel loops over the static M
  objectives (≤ 8 in practice) and folds each into (BI, BJ) ``le``/``lt``
  masks, a (BI, 1) column against a (1, BJ) row. A 3-D (BI, BJ, M) boolean
  reduction would need an i1 relayout that Mosaic rejects;
* both slabs together occupy 2·128·M·4 B ≤ 8 KiB — far under VMEM, so the
  kernel is bound by the (BI·BJ) output-tile write, exactly what a roofline
  for a boolean all-pairs op predicts;
* output is int8 (0/1): TPU stores would waste 4× on an int32 mask and bool
  stores pack awkwardly across lanes.

``dominance_counts_kernel`` fuses the column reduction (dominator counts used
by front peeling) so the P×P matrix never hits HBM: grid is (j_blocks,
i_blocks) with i innermost, accumulating counts into the same lane-aligned
(1, BJ) output block across i steps — the standard Pallas revisiting-output
accumulation pattern.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_BLOCK = 128


def _dominates(fi_ref, fjt_ref):
    """(BI, BJ) bool: row i of ``fi`` (BI, M) dominates column j of ``fjt``
    (M, BJ), i.e. all(Fi <= Fj) & any(Fi < Fj), one objective at a time."""
    le = lt = None
    for m in range(fi_ref.shape[1]):
        a = fi_ref[:, m:m + 1].astype(jnp.float32)     # (BI, 1)
        b = fjt_ref[m:m + 1, :].astype(jnp.float32)    # (1, BJ)
        le_m, lt_m = a <= b, a < b
        le = le_m if le is None else le & le_m
        lt = lt_m if lt is None else lt | lt_m
    return le & lt


def _dominance_tile_kernel(fi_ref, fjt_ref, out_ref):
    """One (BI, BJ) tile of the dominance matrix."""
    out_ref[...] = jnp.where(_dominates(fi_ref, fjt_ref), 1, 0).astype(
        out_ref.dtype)


def _pad_inf(F: jax.Array, block: int) -> jax.Array:
    """Pad the population to a multiple of ``block`` with +inf rows: an
    all-inf row dominates no real row (inf <= x is false), and real rows'
    domination of padded columns lands outside the (P, P) slice."""
    P = F.shape[0]
    Pp = -(-P // block) * block
    return jnp.pad(F.astype(jnp.float32), ((0, Pp - P), (0, 0)),
                   constant_values=jnp.inf)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def dominance_matrix_pallas(F: jax.Array, *, block: int = DEFAULT_BLOCK,
                            interpret: bool = False) -> jax.Array:
    """(P, M) -> (P, P) int8 dominance matrix. P padded to ``block``."""
    P, M = F.shape
    Fp = _pad_inf(F, block)
    Pp = Fp.shape[0]
    grid = (Pp // block, Pp // block)
    out = pl.pallas_call(
        _dominance_tile_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block, M), lambda i, j: (i, 0)),
            pl.BlockSpec((M, block), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((block, block), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Pp, Pp), jnp.int8),
        interpret=interpret,
    )(Fp, Fp.T)
    return out[:P, :P]


def _dominance_counts_kernel(fjt_ref, fi_ref, out_ref):
    """Accumulate dominator counts for one (1, BJ) column block over i."""
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    d = jnp.where(_dominates(fi_ref, fjt_ref), 1, 0).astype(jnp.int32)
    out_ref[...] += jnp.sum(d, axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def dominance_counts_pallas(F: jax.Array, *, block: int = DEFAULT_BLOCK,
                            interpret: bool = False) -> jax.Array:
    """(P, M) -> (P,) int32 dominator counts, P×P matrix never materialized."""
    P, M = F.shape
    Fp = _pad_inf(F, block)
    nb = Fp.shape[0] // block
    out = pl.pallas_call(
        _dominance_counts_kernel,
        grid=(nb, nb),          # (j, i) with i innermost -> accumulation
        in_specs=[
            pl.BlockSpec((M, block), lambda j, i: (0, j)),
            pl.BlockSpec((block, M), lambda j, i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((1, block), lambda j, i: (0, j)),
        out_shape=jax.ShapeDtypeStruct((1, Fp.shape[0]), jnp.int32),
        interpret=interpret,
    )(Fp.T, Fp)
    return out[0, :P]
