"""Per-kernel correctness: Pallas (interpret mode) vs pure-jnp oracle,
swept over shapes and dtypes, plus hypothesis property tests."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st  # soft optional dep

import repro.kernels.decode_attention as dec
import repro.kernels.dominance as dom
import repro.kernels.flash_attention as fa
import repro.kernels.paged_attention as paged
from repro.kernels import ops, ref

# ---------------------------------------------------------------------------
# dominance
# ---------------------------------------------------------------------------
DOM_SHAPES = [(8, 2), (100, 3), (128, 3), (130, 4), (256, 1), (300, 8)]


@pytest.mark.parametrize("P,M", DOM_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_dominance_matrix_matches_ref(P, M, dtype):
    rng = np.random.default_rng(P * 31 + M)
    F = jnp.asarray(rng.random((P, M)), dtype)
    got = dom.dominance_matrix_pallas(F, block=64, interpret=True)
    want = ref.dominance_matrix(F.astype(jnp.float32))
    np.testing.assert_array_equal(np.asarray(got, bool), np.asarray(want))


@pytest.mark.parametrize("P,M", [(64, 3), (129, 3), (257, 5)])
def test_dominance_counts_matches_ref(P, M):
    rng = np.random.default_rng(P)
    # ties included: quantized objectives
    F = jnp.asarray(np.round(rng.random((P, M)), 1), jnp.float32)
    got = dom.dominance_counts_pallas(F, block=64, interpret=True)
    want = ref.dominance_counts(F)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@given(st.integers(0, 2 ** 31 - 1))
@settings(max_examples=10, deadline=None)
def test_dominance_kernel_property_duplicates(seed):
    """Duplicated rows never dominate each other; padding never leaks."""
    rng = np.random.default_rng(seed)
    P = int(rng.integers(3, 70))
    F = rng.random((P, 3)).astype(np.float32)
    F[P // 2] = F[0]
    D = np.asarray(dom.dominance_matrix_pallas(jnp.asarray(F), block=32,
                                               interpret=True), bool)
    assert not D[0, P // 2] and not D[P // 2, 0]
    assert not D.diagonal().any()
    np.testing.assert_array_equal(
        D, np.asarray(ref.dominance_matrix(jnp.asarray(F))))


# ---------------------------------------------------------------------------
# flash attention (prefill)
# ---------------------------------------------------------------------------
FA_CASES = [
    # (B, Hq, Hkv, S, D, block_q, block_k)
    (1, 4, 4, 128, 64, 64, 64),      # MHA
    (2, 8, 2, 256, 64, 128, 128),    # GQA 4:1
    (1, 8, 1, 128, 128, 64, 32),     # MQA, uneven blocks
    (1, 2, 2, 64, 32, 64, 64),       # single q block
    (2, 4, 2, 512, 64, 128, 256),    # bk > bq
]


@pytest.mark.parametrize("B,Hq,Hkv,S,D,bq,bk", FA_CASES)
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_matches_ref(B, Hq, Hkv, S, D, bq, bk, causal, dtype):
    k1, k2, k3 = jax.random.split(jax.random.key(S + Hq), 3)
    q = jax.random.normal(k1, (B, Hq, S, D), dtype)
    k = jax.random.normal(k2, (B, Hkv, S, D), dtype)
    v = jax.random.normal(k3, (B, Hkv, S, D), dtype)
    got = fa.flash_attention(q, k, v, causal=causal, block_q=bq, block_k=bk,
                             interpret=True)
    want = ref.mha_prefill(q, k, v, causal=causal)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=tol, rtol=tol)


def test_flash_attention_extreme_logits_stable():
    """Online softmax must survive large score magnitudes (no inf/nan)."""
    q = jnp.full((1, 1, 128, 64), 12.0, jnp.float32)
    k = jnp.full((1, 1, 128, 64), 12.0, jnp.float32)
    v = jnp.ones((1, 1, 128, 64), jnp.float32)
    out = fa.flash_attention(q, k, v, causal=True, block_q=64, block_k=64,
                             interpret=True)
    assert np.isfinite(np.asarray(out)).all()
    np.testing.assert_allclose(np.asarray(out), 1.0, rtol=1e-5)


def test_flash_attention_first_row_attends_self_only():
    """Causal row 0 output == v[0] regardless of other positions."""
    key = jax.random.key(0)
    q = jax.random.normal(key, (1, 2, 128, 64))
    k = jax.random.normal(jax.random.key(1), (1, 2, 128, 64))
    v = jax.random.normal(jax.random.key(2), (1, 2, 128, 64))
    out = fa.flash_attention(q, k, v, causal=True, interpret=True)
    np.testing.assert_allclose(np.asarray(out[0, :, 0]),
                               np.asarray(v[0, :, 0]), rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# decode attention
# ---------------------------------------------------------------------------
DEC_CASES = [
    # (B, Hq, Hkv, Smax, D, bk)
    (1, 8, 8, 256, 64, 128),     # MHA
    (2, 8, 2, 512, 64, 128),     # GQA 4:1
    (1, 32, 8, 1024, 128, 256),  # assigned-arch shape (GQA 4:1, D=128)
    (3, 4, 1, 128, 32, 64),      # MQA
]


@pytest.mark.parametrize("B,Hq,Hkv,Smax,D,bk", DEC_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention_matches_ref(B, Hq, Hkv, Smax, D, bk, dtype):
    keys = jax.random.split(jax.random.key(Smax + Hq), 4)
    q = jax.random.normal(keys[0], (B, Hq, D), dtype)
    kc = jax.random.normal(keys[1], (B, Hkv, Smax, D), dtype)
    vc = jax.random.normal(keys[2], (B, Hkv, Smax, D), dtype)
    kv_len = jax.random.randint(keys[3], (B,), 1, Smax + 1)
    got = dec.gqa_decode_attention(q, kc, vc, kv_len, block_k=bk,
                                   interpret=True)
    want = ref.gqa_decode(q, kc, vc, kv_len)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=tol, rtol=tol)


@given(st.integers(0, 2 ** 31 - 1))
@settings(max_examples=8, deadline=None)
def test_decode_attention_kv_len_property(seed):
    """Tokens beyond kv_len must not affect the output: growing the cache
    with garbage while holding kv_len fixed leaves results unchanged."""
    rng = np.random.default_rng(seed)
    B, Hq, Hkv, D = 2, 4, 2, 32
    Smax = 256
    q = jnp.asarray(rng.standard_normal((B, Hq, D)), jnp.float32)
    kc = jnp.asarray(rng.standard_normal((B, Hkv, Smax, D)), jnp.float32)
    vc = jnp.asarray(rng.standard_normal((B, Hkv, Smax, D)), jnp.float32)
    kv_len = jnp.asarray(rng.integers(1, 128, B), jnp.int32)
    a = dec.gqa_decode_attention(q, kc, vc, kv_len, block_k=64,
                                 interpret=True)
    kc2 = kc.at[:, :, 128:].set(999.0)
    vc2 = vc.at[:, :, 128:].set(-999.0)
    b = dec.gqa_decode_attention(q, kc2, vc2, kv_len, block_k=64,
                                 interpret=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6)


# ---------------------------------------------------------------------------
# paged decode attention (block-table gather, see serving.kvcache)
# ---------------------------------------------------------------------------
PAGED_CASES = [
    # (B, Hq, Hkv, n_blocks, block_size, max_blocks, D)
    (1, 8, 8, 16, 16, 4, 64),     # MHA
    (2, 8, 2, 24, 16, 4, 64),     # GQA 4:1
    (3, 4, 1, 12, 8, 6, 32),      # MQA, small blocks
]


@pytest.mark.parametrize("B,Hq,Hkv,nb,bs,mb,D", PAGED_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_decode_attention_matches_ref(B, Hq, Hkv, nb, bs, mb, D, dtype):
    rng = np.random.default_rng(B * 100 + Hq)
    q = jnp.asarray(rng.standard_normal((B, Hq, D)), dtype)
    kp = jnp.asarray(rng.standard_normal((nb, Hkv, bs, D)), dtype)
    vp = jnp.asarray(rng.standard_normal((nb, Hkv, bs, D)), dtype)
    # distinct physical blocks per row (blocks are shared across rows in
    # serving, but distinctness makes aliasing bugs visible)
    bt = jnp.asarray(np.stack([rng.choice(nb, mb, replace=False)
                               for _ in range(B)]), jnp.int32)
    kv_len = jnp.asarray(rng.integers(1, mb * bs + 1, B), jnp.int32)
    got = paged.paged_gqa_decode_attention(q, kp, vp, bt, kv_len,
                                           interpret=True)
    want = ref.paged_gqa_decode(q, kp, vp, bt, kv_len)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=tol, rtol=tol)


def test_paged_decode_matches_contiguous_kernel_on_gathered_cache():
    """The paged kernel gathering through the block table must agree with
    the contiguous kernel on the explicitly gathered cache — same online-
    softmax math, different addressing."""
    rng = np.random.default_rng(7)
    B, Hq, Hkv, D, bs, nb, mb = 2, 8, 2, 64, 16, 24, 4
    q = jnp.asarray(rng.standard_normal((B, Hq, D)), jnp.float32)
    kp = jnp.asarray(rng.standard_normal((nb, Hkv, bs, D)), jnp.float32)
    vp = jnp.asarray(rng.standard_normal((nb, Hkv, bs, D)), jnp.float32)
    bt = jnp.asarray(np.stack([rng.choice(nb, mb, replace=False)
                               for _ in range(B)]), jnp.int32)
    kv_len = jnp.asarray([9, mb * bs], jnp.int32)
    got = paged.paged_gqa_decode_attention(q, kp, vp, bt, kv_len,
                                           interpret=True)

    def gather(pool):
        g = jnp.take(pool, bt, axis=0)
        return jnp.transpose(g, (0, 2, 1, 3, 4)).reshape(B, Hkv, mb * bs, D)

    cont = dec.gqa_decode_attention(q, gather(kp), gather(vp), kv_len,
                                    block_k=bs, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(cont),
                               atol=1e-6, rtol=1e-6)


def test_paged_decode_pad_entries_are_masked():
    """Block-table entries beyond kv_len (and negative pads) must not
    affect the output."""
    rng = np.random.default_rng(11)
    B, Hq, Hkv, D, bs, nb, mb = 1, 4, 2, 32, 8, 8, 4
    q = jnp.asarray(rng.standard_normal((B, Hq, D)), jnp.float32)
    kp = jnp.asarray(rng.standard_normal((nb, Hkv, bs, D)), jnp.float32)
    vp = jnp.asarray(rng.standard_normal((nb, Hkv, bs, D)), jnp.float32)
    kv_len = jnp.asarray([10], jnp.int32)      # 2 live blocks of 4
    bt_a = jnp.asarray([[3, 5, 6, 7]], jnp.int32)
    bt_b = jnp.asarray([[3, 5, -1, 1]], jnp.int32)   # different dead tail
    a = paged.paged_gqa_decode_attention(q, kp, vp, bt_a, kv_len,
                                         interpret=True)
    b = paged.paged_gqa_decode_attention(q, kp, vp, bt_b, kv_len,
                                         interpret=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6)


def test_ops_paged_dispatch_ref_matches_interpret():
    rng = np.random.default_rng(3)
    B, Hq, Hkv, D, bs, nb, mb = 2, 4, 2, 32, 8, 10, 3
    q = jnp.asarray(rng.standard_normal((B, Hq, D)), jnp.float32)
    kp = jnp.asarray(rng.standard_normal((nb, Hkv, bs, D)), jnp.float32)
    vp = jnp.asarray(rng.standard_normal((nb, Hkv, bs, D)), jnp.float32)
    bt = jnp.asarray(rng.integers(0, nb, (B, mb)), jnp.int32)
    kv_len = jnp.asarray(rng.integers(1, mb * bs + 1, B), jnp.int32)
    a = ops.paged_gqa_decode_attention(q, kp, vp, bt, kv_len, mode="ref")
    b = ops.paged_gqa_decode_attention(q, kp, vp, bt, kv_len,
                                       mode="interpret")
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5,
                               rtol=2e-5)


# ---------------------------------------------------------------------------
# ops dispatch
# ---------------------------------------------------------------------------
def test_ops_auto_mode_on_cpu_uses_ref():
    F = jnp.asarray(np.random.default_rng(0).random((16, 3)), jnp.float32)
    out = ops.dominance_matrix(F, mode="auto")
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(ref.dominance_matrix(F)))


def test_ops_interpret_equals_ref_for_attention():
    q = jax.random.normal(jax.random.key(0), (1, 4, 128, 64))
    k = jax.random.normal(jax.random.key(1), (1, 2, 128, 64))
    v = jax.random.normal(jax.random.key(2), (1, 2, 128, 64))
    a = ops.flash_attention(q, k, v, mode="interpret")
    b = ops.flash_attention(q, k, v, mode="ref")
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5,
                               rtol=2e-5)


@pytest.mark.parametrize("n, want, tile", [(48, 512, 48), (768, 512, 384),
                                           (1000, 128, 40)])
def test_ops_tile_divides_instead_of_falling_back(n, want, tile):
    assert ops._tile(n, want) == tile
    q = jax.random.normal(jax.random.key(0), (2, 4, 32))
    kc = jax.random.normal(jax.random.key(1), (2, 2, n, 32))
    vc = jax.random.normal(jax.random.key(2), (2, 2, n, 32))
    kv_len = jnp.asarray([n // 3, n], jnp.int32)
    a = ops.gqa_decode_attention(q, kc, vc, kv_len, mode="interpret",
                                 block_k=want)
    b = ops.gqa_decode_attention(q, kc, vc, kv_len, mode="ref")
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5,
                               rtol=2e-5)


def test_ops_kernel_path_raises_when_no_tile_divides():
    q = jnp.zeros((1, 4, 16, 32))
    kv = jnp.zeros((1, 2, 1030, 32))
    with pytest.raises(ValueError, match="divides"):
        ops.gqa_decode_attention(q[:, :, 0], kv, kv,
                                 jnp.asarray([4], jnp.int32),
                                 mode="interpret")
