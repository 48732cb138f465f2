"""Ahead-of-time compiles of the main path's Pallas kernels for a described
TPU v5e, at the published widths of the served models.

Nothing runs: each test lowers a kernel with ``ShapeDtypeStruct`` inputs
placed on a device of a described ``v5e:2x2`` topology and compiles it with
the TPU compiler, which refuses what Mosaic cannot lay out (unaligned
slices, i1 relayouts, oversized VMEM) exactly as it would on the chip.

The topology is described inside a module fixture — never at import — so
that only the worker that runs this file loads the TPU compiler library.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get
from repro.kernels import ops

SLOTS, MAX_SEQ, COHORT = 4, 512, 8


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    cache_on = jax.config.jax_enable_compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        if "TPU_LOG_DIR" not in os.environ:
            mp.setenv("TPU_LOG_DIR", "disabled")
        # a compile for a described chip cannot be read back from the
        # persistent cache without one: keep these compiles out of it
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            try:
                topo = topologies.get_topology_desc(platform="tpu",
                                                    topology_name="v5e:2x2")
            except Exception as e:  # no TPU compiler library on this host
                pytest.skip(reason="no v5e:2x2 topology can be described "
                                   f"here: {e}")
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            # later files on this worker see the process as it was
            jax.config.update("jax_enable_compilation_cache", cache_on)
            compilation_cache.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _widths(arch):
    c = get(arch).config()
    return c.n_heads, c.n_kv_heads, c.hd


@pytest.mark.parametrize("arch", ["qwen3-1.7b", "stablelm-3b"])
@pytest.mark.parametrize("cohort", [False, True])
@pytest.mark.parametrize("max_seq", [128, MAX_SEQ])   # chip smoke's, default
def test_gqa_decode_compiles(one_chip, arch, cohort, max_seq):
    Hq, Hkv, D = _widths(arch)
    lead = (COHORT,) if cohort else ()
    q = _spec(lead + (SLOTS, Hq, D), jnp.bfloat16, one_chip)
    kv = _spec(lead + (SLOTS, Hkv, max_seq, D), jnp.bfloat16, one_chip)
    kv_len = _spec(lead + (SLOTS,), jnp.int32, one_chip)
    fn = lambda q, k, v, n: ops.gqa_decode_attention(q, k, v, n,
                                                     mode="pallas")
    _compile(jax.vmap(fn) if cohort else fn, q, kv, kv, kv_len)


@pytest.mark.parametrize("block_size", [8, 16, 128])
def test_paged_decode_compiles(one_chip, block_size):
    Hq, Hkv, D = _widths("qwen3-1.7b")
    n_blocks = SLOTS * MAX_SEQ // block_size
    q = _spec((SLOTS, Hq, D), jnp.bfloat16, one_chip)
    pool = _spec((n_blocks, Hkv, block_size, D), jnp.bfloat16, one_chip)
    table = _spec((SLOTS, MAX_SEQ // block_size), jnp.int32, one_chip)
    kv_len = _spec((SLOTS,), jnp.int32, one_chip)
    _compile(lambda *a: ops.paged_gqa_decode_attention(*a, mode="pallas"),
             q, pool, pool, table, kv_len)


def test_flash_prefill_compiles(one_chip):
    Hq, Hkv, D = _widths("qwen3-1.7b")
    q = _spec((1, Hq, MAX_SEQ, D), jnp.bfloat16, one_chip)
    kv = _spec((1, Hkv, MAX_SEQ, D), jnp.bfloat16, one_chip)
    _compile(lambda q, k, v: ops.flash_attention(q, k, v, mode="pallas"),
             q, kv, kv)


@pytest.mark.parametrize("kernel", ["matrix", "counts"])
@pytest.mark.parametrize("M", [3, 4])
def test_dominance_compiles(one_chip, kernel, M):
    fn = {"matrix": ops.dominance_matrix,
          "counts": ops.dominance_counts}[kernel]
    F = _spec((200, M), jnp.float32, one_chip)    # pads to two 128-blocks
    _compile(lambda F: fn(F, mode="pallas"), F)


def _entry_ops(hlo):
    """``(opcode, shape, parameter number)`` of each instruction in the
    entry computation of an optimized HLO module's text."""
    entry = re.search(r"\nENTRY .*?\n}\n", hlo, re.S).group(0)
    ops = re.findall(r"\n\s*(?:ROOT )?%\S+ = \w+\[([\d,]*)\]\S* "
                     r"([\w\-]+)\(([^)]*)\)", entry)
    return [(op, shape, args) for shape, op, args in ops]


@pytest.mark.parametrize("arch,n_layers,slots,max_seq,members,bucket", [
    ("stablelm-3b", 2, 12, 512, 1, 1),    # cloud-only: the whole cohort
    ("qwen3-1.7b", 28, 4, 128, 9, 4),     # testbed edges: a gathered bucket
])
def test_cohort_decode_writes_cache_in_place(one_chip, monkeypatch, arch,
                                             n_layers, slots, max_seq,
                                             members, bucket):
    """The cohort dispatch at published widths, with the attention kernel:
    every stacked cache leaf is donated and aliased to its output, and the
    entry computation holds no copy, select or fusion of a whole cache leaf
    — no relayout, merge or copy of the cache at the executable's boundary.
    (Full depth for qwen3: at two layers its cohort's cache is small enough
    that the compiler stages it through on-chip memory.)"""
    import dataclasses
    from repro.models import lm
    from repro.serving import fleet
    cfg = dataclasses.replace(get(arch).config(), n_layers=n_layers)
    spec = lambda a: _spec(a.shape, a.dtype, one_chip)
    # the serving path takes the Pallas kernel on a TPU backend; this
    # process's backend is the CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    params = jax.eval_shape(lambda: lm.init(jax.random.PRNGKey(0), cfg))
    state = jax.tree.map(spec, jax.eval_shape(lambda: fleet._stack(cfg, [
        fleet.FleetState(cache=lm.make_cache(cfg, slots, max_seq),
                         next_token=jnp.zeros((slots, 1), jnp.int32))]
        * members)))
    rows = lambda dt, *s: _spec((bucket,) + s, dt, one_chip)
    compiled = fleet._cohort_decode_chunk.lower(
        jax.tree.map(spec, params), cfg, state, rows(jnp.int32, slots),
        rows(jnp.bool_, slots), rows(jnp.int32), rows(jnp.bool_), 1,
        -1).compile()
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo
    ops = _entry_ops(hlo)
    leaf = ",".join(map(str, state.cache.layer[0][0].shape))
    params_at = {int(a) for op, shape, a in ops
                 if op == "parameter" and shape == leaf}
    assert len(params_at) == 2                                   # K and V
    aliased = {int(p) for p in re.findall(
        r"\{\d+\}: \((\d+), \{\}, may-alias\)", hlo.split("\n", 1)[0])}
    assert params_at <= aliased, (params_at, aliased)
    whole = [op for op, shape, _ in ops if shape == leaf and op in (
        "copy", "copy-start", "copy-done", "select", "fusion")]
    assert not whole, whole
