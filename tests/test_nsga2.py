"""Tests for the vectorized NSGA-II engine (operators + convergence)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hypothesis_compat import given, settings, st  # soft optional dep

from repro.core.nsga2 import (NSGA2, NSGA2Config, binary_tournament,
                              polynomial_mutation, reassignment_mutation,
                              sbx_crossover, survival_select,
                              uniform_swap_crossover)
from repro.kernels import ops


def test_sbx_respects_bounds_and_prob_zero_identity():
    key = jax.random.key(0)
    lo, hi = jnp.zeros(8), jnp.ones(8)
    p1 = jax.random.uniform(jax.random.key(1), (16, 8))
    p2 = jax.random.uniform(jax.random.key(2), (16, 8))
    c1, c2 = sbx_crossover(key, p1, p2, lo, hi, pc=0.0, eta=15.0)
    np.testing.assert_array_equal(np.asarray(c1), np.asarray(p1))
    np.testing.assert_array_equal(np.asarray(c2), np.asarray(p2))
    c1, c2 = sbx_crossover(key, p1, p2, lo, hi, pc=1.0, eta=15.0)
    for c in (c1, c2):
        assert (np.asarray(c) >= 0).all() and (np.asarray(c) <= 1).all()


def test_sbx_preserves_parent_mean_per_gene():
    # SBX children are symmetric around the parent mean where applied
    key = jax.random.key(3)
    lo, hi = jnp.full(4, -10.0), jnp.full(4, 10.0)
    p1 = jnp.array([[1.0, 2.0, 3.0, 4.0]])
    p2 = jnp.array([[2.0, 1.0, 5.0, 0.0]])
    c1, c2 = sbx_crossover(key, p1, p2, lo, hi, pc=1.0, eta=20.0)
    np.testing.assert_allclose(np.asarray(c1 + c2), np.asarray(p1 + p2),
                               rtol=1e-5)


@given(st.integers(0, 10 ** 6), st.floats(0.0, 1.0))
@settings(max_examples=20, deadline=None)
def test_polynomial_mutation_bounds(seed, pm):
    key = jax.random.key(seed)
    x = jax.random.uniform(jax.random.key(seed + 1), (10, 5))
    out = polynomial_mutation(key, x, jnp.zeros(5), jnp.ones(5), pm, 20.0)
    assert (np.asarray(out) >= 0).all() and (np.asarray(out) <= 1).all()


def test_uniform_swap_is_permutation_of_genes():
    key = jax.random.key(0)
    p1 = jnp.arange(12, dtype=jnp.int32).reshape(2, 6)
    p2 = p1 + 100
    c1, c2 = uniform_swap_crossover(key, p1, p2, pc=1.0)
    # at every gene position the multiset {c1, c2} == {p1, p2}
    same = (jnp.minimum(c1, c2) == jnp.minimum(p1, p2)) & \
           (jnp.maximum(c1, c2) == jnp.maximum(p1, p2))
    assert bool(same.all())


def test_reassignment_mutation_stays_in_range():
    key = jax.random.key(0)
    x = jnp.zeros((8, 20), jnp.int32)
    out = reassignment_mutation(key, x, pm=1.0, n_choices=7)
    o = np.asarray(out)
    assert (o >= 0).all() and (o < 7).all()


def test_binary_tournament_prefers_better_rank():
    rank = jnp.array([0, 5], jnp.int32)
    crowd = jnp.array([1.0, 1.0])
    winners = binary_tournament(jax.random.key(0), rank, crowd, 256)
    # index 0 strictly better: it must win every tournament it appears in;
    # expected win share is 3/4 (wins unless both draws are index 1)
    share = float(jnp.mean((winners == 0).astype(jnp.float32)))
    assert share > 0.6


def test_survival_select_keeps_nondominated():
    # 4 points where 2 dominate the other 2 -> survivors must be the dominators
    F = jnp.array([[0.1, 0.1], [0.2, 0.2], [0.9, 0.9], [1.0, 1.0]])
    sel, rank, crowd = survival_select(F, 2)
    assert set(np.asarray(sel).tolist()) == {0, 1}


def _zdt1_fitness(genomes, key):
    f1 = genomes[:, 0]
    g = 1 + 9 * jnp.mean(genomes[:, 1:], axis=1)
    f2 = g * (1 - jnp.sqrt(f1 / g))
    return jnp.stack([f1, f2], axis=1), jnp.zeros(genomes.shape[0])


def test_nsga2_converges_on_zdt1():
    # 90 generations: 60 leaves g.mean ≈ 1.5 (marginal) on this jax version's
    # RNG stream; 90 converges decisively (g.mean ≈ 1.09)
    D = 8
    cfg = NSGA2Config(pop_size=48, n_generations=90, lo=jnp.zeros(D),
                      hi=jnp.ones(D))
    opt = NSGA2(_zdt1_fitness, cfg)
    state = opt.evolve_scan(jax.random.key(0), 90)
    g = 1 + 9 * np.mean(np.asarray(state.genomes)[:, 1:], axis=1)
    assert g.mean() < 1.5  # optimum g = 1
    # front should span f1 (diversity via crowding)
    front = np.asarray(state.F_raw)[np.asarray(state.rank) == 0]
    assert front[:, 0].max() - front[:, 0].min() > 0.5


def test_nsga2_penalty_excludes_infeasible():
    # violation > 0 on half the space: survivors should be feasible
    def fit(genomes, key):
        F = jnp.stack([genomes[:, 0], 1 - genomes[:, 0]], axis=1)
        viol = jnp.where(genomes[:, 1] > 0.5, genomes[:, 1], 0.0)
        return F, viol

    cfg = NSGA2Config(pop_size=32, n_generations=30, lo=jnp.zeros(2),
                      hi=jnp.ones(2))
    opt = NSGA2(fit, cfg)
    state = opt.evolve_scan(jax.random.key(1), 30)
    genomes, front = opt.pareto_front(state)
    assert front.shape[0] > 0
    assert (np.asarray(genomes)[:, 1] <= 0.5 + 1e-6).all()


def test_pallas_dominance_flag_matches_reference():
    """use_pallas_dominance must produce the exact same evolution as the jnp
    reference sort (the flag was stored-but-dead before; interpret-mode
    kernel on CPU)."""
    D = 6
    cfg = NSGA2Config(pop_size=16, n_generations=6, lo=jnp.zeros(D),
                      hi=jnp.ones(D))
    ref = NSGA2(_zdt1_fitness, cfg).evolve_scan(jax.random.key(0), 6)
    pal = NSGA2(_zdt1_fitness, cfg,
                use_pallas_dominance=True).evolve_scan(jax.random.key(0), 6)
    np.testing.assert_allclose(np.asarray(ref.F_raw), np.asarray(pal.F_raw),
                               rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(ref.rank), np.asarray(pal.rank))
    np.testing.assert_allclose(np.asarray(ref.genomes),
                               np.asarray(pal.genomes), rtol=1e-6)


@pytest.mark.parametrize("on_tpu, flag, mode", [
    (False, None, "ref"), (True, None, "pallas"),
    (False, True, "interpret"), (True, True, "pallas"),
    (True, False, "ref")])
def test_dominance_backend_follows_kernel_ops(monkeypatch, on_tpu, flag,
                                              mode):
    """The default takes the kernel exactly where ``kernels.ops`` "auto"
    does; the flag forces the kernel (interpreted off TPU) or the
    reference."""
    monkeypatch.setattr(ops, "_on_tpu", lambda: on_tpu)
    cfg = NSGA2Config(pop_size=8, n_generations=1, lo=jnp.zeros(2),
                      hi=jnp.ones(2))
    assert NSGA2(_zdt1_fitness, cfg,
                 use_pallas_dominance=flag)._dominance == mode


def _discrete_fitness(genomes, key):
    m = jnp.mean(genomes.astype(jnp.float32), axis=1)
    return jnp.stack([m, -m], axis=1), jnp.zeros(genomes.shape[0])


def test_discrete_default_init_uses_genome_length():
    """Regression: the default discrete init hardcoded D=1, silently
    optimizing a single gene per individual."""
    n_requests = 53
    cfg = NSGA2Config(pop_size=8, n_generations=2, genome="discrete",
                      n_choices=7, genome_length=n_requests)
    opt = NSGA2(_discrete_fitness, cfg)
    state = opt.init(jax.random.key(0))
    assert state.genomes.shape == (8, n_requests)
    g = np.asarray(state.genomes)
    assert (g >= 0).all() and (g < 7).all()
    # and the genes are not all identical within an individual (D>1 entropy)
    assert any(len(np.unique(g[i])) > 1 for i in range(8))
    # evolution preserves the shape
    state = opt.evolve_scan(jax.random.key(0), 2)
    assert state.genomes.shape == (8, n_requests)


def test_discrete_init_without_length_raises():
    cfg = NSGA2Config(pop_size=4, n_generations=1, genome="discrete",
                      n_choices=3)
    with pytest.raises(AssertionError):
        NSGA2(_discrete_fitness, cfg).init(jax.random.key(0))


def test_evolve_matches_evolve_scan():
    D = 4
    cfg = NSGA2Config(pop_size=16, n_generations=5, lo=jnp.zeros(D),
                      hi=jnp.ones(D))
    opt = NSGA2(_zdt1_fitness, cfg)
    s1 = opt.evolve(jax.random.key(7), 5)
    s2 = opt.evolve_scan(jax.random.key(7), 5)
    np.testing.assert_allclose(np.asarray(s1.F_raw), np.asarray(s2.F_raw),
                               rtol=1e-5, atol=1e-6)
