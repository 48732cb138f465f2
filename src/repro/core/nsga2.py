"""NSGA-II (Deb et al. 2002) fully vectorized in JAX.

This is the paper's §IV optimizer: a population of routing policies evolved
with non-dominated sorting + crowding distance, binary tournament selection,
crossover and mutation. Two genome encodings are supported, matching the two
policy representations in the paper:

* **continuous** (threshold genome, §IV-B.6): D decision variables in
  ``[lo, hi]`` — SBX crossover + polynomial mutation. This is what the runtime
  rule-based router consumes (θ_d,code, θ_d,math, θ_d,general, θ_q, θ_t,code,
  θ_t,math).
* **discrete** (direct assignment genome, §IV-B.1): one integer gene per
  request selecting a (node, model) pair — uniform-swap crossover ("swapping
  node-LLM pairs for a subset of requests") + random reassignment mutation.

The whole generation step is a single jitted function; ``evolve`` runs a
Python loop for logging, ``evolve_scan`` runs the entire optimization as one
``lax.scan`` (used by the perf benchmarks).

Constraints are handled with the standard constrained-domination trick folded
into a penalty: the fitness function may return a violation vector alongside
objectives; infeasible individuals get all objectives shifted by
``violation * PENALTY`` which makes every feasible point dominate them while
still ordering infeasible points by violation.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from ..kernels import ops
from .pareto import crowding_distance, non_dominated_sort

PENALTY = 1e6


class _StaticConfig(NamedTuple):
    """Hashable static slice of :class:`NSGA2Config` — the jit cache key of
    the module-level generation step. Two ``NSGA2`` instances with equal
    static configs (and the same fitness kernel) share one compiled
    executable; the continuous bounds stay *dynamic* arguments so re-fits
    with different bounds of the same shape also hit the cache."""

    pop_size: int
    crossover_prob: float
    mutation_prob: float
    eta_crossover: float
    eta_mutation: float
    genome: str
    n_choices: int
    n_genes: int


@dataclasses.dataclass(frozen=True)
class NSGA2Config:
    """Hyper-parameters (paper §V-A: P=100, T=100, pc=0.8, pm=0.1)."""

    pop_size: int = 100
    n_generations: int = 100
    crossover_prob: float = 0.8
    mutation_prob: float = 0.1
    eta_crossover: float = 15.0
    eta_mutation: float = 20.0
    genome: str = "continuous"  # "continuous" | "discrete"
    # continuous bounds (D,) arrays; discrete cardinality + genome length
    lo: Optional[jnp.ndarray] = None
    hi: Optional[jnp.ndarray] = None
    n_choices: int = 0
    # number of genes D for the default *discrete* init (e.g. n_requests for
    # direct-assignment genomes); continuous genomes take D from lo/hi
    genome_length: int = 0

    def __post_init__(self):
        assert self.pop_size % 2 == 0, "pop_size must be even"
        assert self.genome in ("continuous", "discrete")

    @classmethod
    def from_policy(cls, policy, **overrides) -> "NSGA2Config":
        """Derive the genome encoding from a registered RoutingPolicy.

        ``policy`` is a registry name or RoutingPolicy object. Continuous
        policies contribute their search bounds (D = GenomeSpec.length, so
        genome-length defaults cannot drift from the decision rule);
        discrete per-request policies ("direct") set ``genome="discrete"``
        and require the caller to pass trace-dependent ``genome_length`` /
        ``n_choices`` via ``overrides``. Other NSGA-II hyper-parameters
        (pop_size, n_generations, ...) pass through ``overrides``.
        """
        from .policies import get_policy
        pol = get_policy(policy) if isinstance(policy, str) else policy
        spec = pol.genome_spec
        if spec.discrete:
            overrides.setdefault("genome", "discrete")
            return cls(**overrides)
        overrides.setdefault("genome", "continuous")
        overrides.setdefault("lo", jnp.asarray(spec.lo))
        overrides.setdefault("hi", jnp.asarray(spec.hi))
        return cls(**overrides)

    @property
    def n_genes(self) -> int:
        """Genome dimensionality D implied by the config."""
        if self.genome == "continuous":
            assert self.lo is not None, "continuous genome requires bounds"
            return int(self.lo.shape[0])
        assert self.genome_length > 0, \
            "discrete genome requires genome_length (or a custom init_fn)"
        return self.genome_length

    @property
    def static_key(self) -> _StaticConfig:
        """Static (hashable) part of the config; D = -1 when only a custom
        ``init_fn`` can determine the genome length."""
        if self.genome == "continuous" and self.lo is not None:
            D = int(self.lo.shape[0])
        elif self.genome == "discrete" and self.genome_length > 0:
            D = self.genome_length
        else:
            D = -1
        return _StaticConfig(
            pop_size=self.pop_size, crossover_prob=self.crossover_prob,
            mutation_prob=self.mutation_prob,
            eta_crossover=self.eta_crossover,
            eta_mutation=self.eta_mutation, genome=self.genome,
            n_choices=self.n_choices, n_genes=D)


class NSGA2State(NamedTuple):
    genomes: jax.Array     # (P, D) float32 or int32
    F: jax.Array           # (P, M) penalized objectives
    F_raw: jax.Array       # (P, M) unpenalized objectives
    violation: jax.Array   # (P,)
    rank: jax.Array        # (P,)
    crowd: jax.Array       # (P,)
    key: jax.Array
    generation: jax.Array  # scalar int32


# FitnessFn: (genomes (P, D), key) -> (F (P, M), violation (P,))
FitnessFn = Callable[[jax.Array, jax.Array], Tuple[jax.Array, jax.Array]]


def _penalize(F: jax.Array, violation: jax.Array) -> jax.Array:
    return F + (violation[:, None] > 0) * (PENALTY + violation[:, None] * PENALTY)


# ---------------------------------------------------------------------------
# Variation operators
# ---------------------------------------------------------------------------

def sbx_crossover(key: jax.Array, p1: jax.Array, p2: jax.Array,
                  lo: jax.Array, hi: jax.Array, pc: float, eta: float
                  ) -> Tuple[jax.Array, jax.Array]:
    """Simulated binary crossover on (n_pairs, D) parent blocks."""
    k_pair, k_gene, k_u = jax.random.split(key, 3)
    n_pairs, D = p1.shape
    u = jax.random.uniform(k_u, (n_pairs, D))
    beta = jnp.where(
        u <= 0.5,
        (2.0 * u) ** (1.0 / (eta + 1.0)),
        (1.0 / (2.0 * (1.0 - u))) ** (1.0 / (eta + 1.0)),
    )
    c1 = 0.5 * ((1.0 + beta) * p1 + (1.0 - beta) * p2)
    c2 = 0.5 * ((1.0 - beta) * p1 + (1.0 + beta) * p2)
    # per-gene 0.5 exchange, per-pair pc gate
    do_pair = jax.random.uniform(k_pair, (n_pairs, 1)) < pc
    do_gene = jax.random.uniform(k_gene, (n_pairs, D)) < 0.5
    apply = do_pair & do_gene
    c1 = jnp.where(apply, c1, p1)
    c2 = jnp.where(apply, c2, p2)
    return jnp.clip(c1, lo, hi), jnp.clip(c2, lo, hi)


def polynomial_mutation(key: jax.Array, x: jax.Array, lo: jax.Array,
                        hi: jax.Array, pm: float, eta: float) -> jax.Array:
    """Polynomial mutation on (P, D)."""
    k_gate, k_u = jax.random.split(key)
    u = jax.random.uniform(k_u, x.shape)
    delta = jnp.where(
        u < 0.5,
        (2.0 * u) ** (1.0 / (eta + 1.0)) - 1.0,
        1.0 - (2.0 * (1.0 - u)) ** (1.0 / (eta + 1.0)),
    )
    mutated = x + delta * (hi - lo)
    gate = jax.random.uniform(k_gate, x.shape) < pm
    return jnp.clip(jnp.where(gate, mutated, x), lo, hi)


def uniform_swap_crossover(key: jax.Array, p1: jax.Array, p2: jax.Array,
                           pc: float) -> Tuple[jax.Array, jax.Array]:
    """Paper §IV-B.4: swap node-LLM pairs for a subset of requests."""
    k_pair, k_gene = jax.random.split(key)
    n_pairs, D = p1.shape
    do_pair = jax.random.uniform(k_pair, (n_pairs, 1)) < pc
    swap = (jax.random.uniform(k_gene, (n_pairs, D)) < 0.5) & do_pair
    c1 = jnp.where(swap, p2, p1)
    c2 = jnp.where(swap, p1, p2)
    return c1, c2


def reassignment_mutation(key: jax.Array, x: jax.Array, pm: float,
                          n_choices: int) -> jax.Array:
    """Paper §IV-B.4: reassign a small fraction of requests to other pairs."""
    k_gate, k_new = jax.random.split(key)
    gate = jax.random.uniform(k_gate, x.shape) < pm
    fresh = jax.random.randint(k_new, x.shape, 0, n_choices, dtype=x.dtype)
    return jnp.where(gate, fresh, x)


# ---------------------------------------------------------------------------
# Warm start
# ---------------------------------------------------------------------------

def archive_init(archive: jax.Array, cfg: NSGA2Config
                 ) -> Callable[[jax.Array], jax.Array]:
    """``init_fn`` seeding a population from an elite archive (warm start).

    The first ``min(len(archive), pop_size)`` individuals are copied from the
    archive (a previous run's survival-ordered population or Pareto front —
    ``NSGA2State.genomes`` rows are already sorted best-first by
    (rank, -crowding)); the remainder is drawn from the default random init
    so the restarted search keeps exploring. Used by the rolling-horizon
    router re-optimization to carry the front across workload windows.
    """
    archive = jnp.asarray(archive)
    assert archive.ndim == 2, "archive must be (A, D) genomes"
    n_seed = min(archive.shape[0], cfg.pop_size)

    def init_fn(key: jax.Array) -> jax.Array:
        if cfg.genome == "continuous":
            u = jax.random.uniform(key, (cfg.pop_size, cfg.n_genes))
            fresh = cfg.lo + u * (cfg.hi - cfg.lo)
            seeds = jnp.clip(archive[:n_seed].astype(fresh.dtype),
                             cfg.lo, cfg.hi)
        else:
            fresh = jax.random.randint(key, (cfg.pop_size, cfg.n_genes), 0,
                                       cfg.n_choices, dtype=jnp.int32)
            seeds = archive[:n_seed].astype(jnp.int32)
        return fresh.at[:n_seed].set(seeds)

    return init_fn


# ---------------------------------------------------------------------------
# Selection / survival
# ---------------------------------------------------------------------------

def binary_tournament(key: jax.Array, rank: jax.Array, crowd: jax.Array,
                      n: int) -> jax.Array:
    """Return (n,) winner indices of n independent binary tournaments."""
    P = rank.shape[0]
    idx = jax.random.randint(key, (n, 2), 0, P)
    a, b = idx[:, 0], idx[:, 1]
    a_better = (rank[a] < rank[b]) | ((rank[a] == rank[b]) & (crowd[a] > crowd[b]))
    return jnp.where(a_better, a, b)


def survival_select(F: jax.Array, P: int,
                    dominance_fn: Optional[Callable[[jax.Array], jax.Array]]
                    = None, top: Optional[int] = None
                    ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Elitist (μ+λ) truncation: top-P of combined population by
    (rank asc, crowding desc). Returns (indices, rank_sel, crowd_sel).

    ``dominance_fn`` optionally computes the (2P, 2P) dominance matrix fed to
    the sort (e.g. the Pallas kernel); default is the jnp reference.
    ``top`` forwards the early-exit quota to ``non_dominated_sort`` —
    survival only needs ranks up to the front containing the P-th survivor,
    so the generation step passes ``top=P`` (ranks of selected individuals
    are identical to the full sort; unpeeled tails share a sentinel rank and
    are never selected)."""
    dom = dominance_fn(F) if dominance_fn is not None else None
    rank = non_dominated_sort(F, dom, top=top)
    crowd = crowding_distance(F, rank)
    # lexsort: primary rank asc, secondary crowd desc. Replace inf for sort
    # stability under -crowd (−inf sorts first which is what we want).
    neg_crowd = jnp.where(jnp.isinf(crowd), -jnp.inf, -crowd)
    order = jnp.lexsort((neg_crowd, rank))
    sel = order[:P]
    return sel, rank[sel], crowd[sel]


# ---------------------------------------------------------------------------
# Module-level jitted generation step / full run
#
# Historically every ``NSGA2`` instance re-jitted its own generation step
# (``jax.jit(self._step_impl)``), so the rolling-horizon router paid a full
# retrace per re-fit even with identical hyper-parameters and table shapes.
# The step now lives here, keyed on (static config, fitness kernel identity,
# dominance backend): any two instances with equal statics share one
# compiled executable, and per-evaluator data (tables, bounds, archives)
# flows through dynamic arguments. ``core.fitness.make_fitness`` returns
# fitness callables carrying a memoized ``.kernel``/``.data`` split exactly
# so this cache hits across evaluators.
# ---------------------------------------------------------------------------


def _call_fitness(fitness_fn, genomes, key, data):
    """Invoke a fitness function in either calling convention: legacy
    ``(genomes, key)`` closures, or cache-friendly ``(genomes, key, data)``
    kernels whose per-evaluator state arrives as a dynamic pytree."""
    if data is None:
        return fitness_fn(genomes, key)
    return fitness_fn(genomes, key, data)


def _dominance_matrix_for(F, dominance: str):
    """The survival dominance matrix for ``dominance``, a resolved
    ``kernels.ops`` mode: None for "ref" (non_dominated_sort computes the
    same reference itself), the Pallas kernel otherwise."""
    if dominance == "ref":
        return None
    return ops.dominance_matrix(F, mode=dominance)


def _init_core(key, lo, hi, archive, fitness_data, scfg: _StaticConfig,
               fitness_fn, dominance: str, init_fn) -> NSGA2State:
    k_pop, k_fit, k_next = jax.random.split(key, 3)
    if init_fn is not None:
        genomes = init_fn(k_pop)
    elif scfg.genome == "continuous":
        assert scfg.n_genes > 0, "continuous genome requires bounds"
        u = jax.random.uniform(k_pop, (scfg.pop_size, scfg.n_genes))
        genomes = lo + u * (hi - lo)
    else:
        if scfg.n_choices <= 0:
            raise ValueError("discrete genome requires init_fn or n_choices>0")
        assert scfg.n_genes > 0, \
            "discrete genome requires genome_length (or a custom init_fn)"
        genomes = jax.random.randint(
            k_pop, (scfg.pop_size, scfg.n_genes), 0, scfg.n_choices,
            dtype=jnp.int32)
    if archive is not None:
        # warm start (same semantics as archive_init, but the archive is a
        # *dynamic* argument so repeated warm-started re-fits share a trace)
        n_seed = min(archive.shape[0], scfg.pop_size)
        if scfg.genome == "continuous":
            seeds = jnp.clip(archive[:n_seed].astype(genomes.dtype), lo, hi)
        else:
            seeds = archive[:n_seed].astype(jnp.int32)
        genomes = genomes.at[:n_seed].set(seeds)
    F_raw, violation = _call_fitness(fitness_fn, genomes, k_fit, fitness_data)
    F = _penalize(F_raw, violation)
    rank = non_dominated_sort(F, _dominance_matrix_for(F, dominance))
    crowd = crowding_distance(F, rank)
    return NSGA2State(genomes, F, F_raw, violation, rank, crowd, k_next,
                      jnp.int32(0))


def _step_core(state: NSGA2State, lo, hi, fitness_data,
               scfg: _StaticConfig, fitness_fn, dominance: str) -> NSGA2State:
    P = scfg.pop_size
    key, k_sel, k_cx, k_mut, k_fit = jax.random.split(state.key, 5)

    parents = binary_tournament(k_sel, state.rank, state.crowd, P)
    pg = state.genomes[parents]
    p1, p2 = pg[0::2], pg[1::2]

    if scfg.genome == "continuous":
        c1, c2 = sbx_crossover(k_cx, p1, p2, lo, hi,
                               scfg.crossover_prob, scfg.eta_crossover)
        offspring = jnp.concatenate([c1, c2], axis=0)
        offspring = polynomial_mutation(k_mut, offspring, lo, hi,
                                        scfg.mutation_prob,
                                        scfg.eta_mutation)
    else:
        c1, c2 = uniform_swap_crossover(k_cx, p1, p2, scfg.crossover_prob)
        offspring = jnp.concatenate([c1, c2], axis=0)
        offspring = reassignment_mutation(k_mut, offspring,
                                          scfg.mutation_prob, scfg.n_choices)

    F_off_raw, viol_off = _call_fitness(fitness_fn, offspring, k_fit,
                                        fitness_data)
    F_off = _penalize(F_off_raw, viol_off)

    # (μ+λ) combine + survival (ranks beyond the top-P cutoff early-exit)
    genomes_all = jnp.concatenate([state.genomes, offspring], axis=0)
    F_all = jnp.concatenate([state.F, F_off], axis=0)
    F_raw_all = jnp.concatenate([state.F_raw, F_off_raw], axis=0)
    viol_all = jnp.concatenate([state.violation, viol_off], axis=0)
    dom_fn = (None if dominance == "ref"
              else lambda F: _dominance_matrix_for(F, dominance))
    sel, rank_sel, crowd_sel = survival_select(F_all, P, dom_fn, top=P)

    return NSGA2State(
        genomes=genomes_all[sel], F=F_all[sel], F_raw=F_raw_all[sel],
        violation=viol_all[sel], rank=rank_sel, crowd=crowd_sel, key=key,
        generation=state.generation + 1)


@functools.partial(jax.jit,
                   static_argnames=("scfg", "fitness_fn", "dominance"))
def _nsga2_step(state: NSGA2State, lo, hi, fitness_data, *,
                scfg: _StaticConfig, fitness_fn, dominance: str
                ) -> NSGA2State:
    return _step_core(state, lo, hi, fitness_data, scfg, fitness_fn,
                      dominance)


@functools.partial(jax.jit,
                   static_argnames=("scfg", "fitness_fn", "dominance",
                                    "n_generations", "init_fn"))
def _nsga2_run(key, lo, hi, archive, fitness_data, *, scfg: _StaticConfig,
               fitness_fn, dominance: str, n_generations: int,
               init_fn=None) -> NSGA2State:
    """Entire optimization (init + T generations) as one compiled program."""
    state = _init_core(key, lo, hi, archive, fitness_data, scfg, fitness_fn,
                       dominance, init_fn)

    def body(s, _):
        return _step_core(s, lo, hi, fitness_data, scfg, fitness_fn,
                          dominance), None

    state, _ = jax.lax.scan(body, state, None, length=n_generations)
    return state


# ---------------------------------------------------------------------------
# Engine
# ---------------------------------------------------------------------------

class NSGA2:
    """Vectorized NSGA-II engine.

    Parameters
    ----------
    fitness_fn : FitnessFn
        Maps (genomes, key) -> (objectives (P, M), violation (P,)). Must be
        traceable (it is called under jit). Objectives are minimized.
    config : NSGA2Config
    init_fn : optional custom population initializer (key) -> (P, D) genomes.
        Defaults to uniform in bounds / uniform categorical. The paper's
        heuristic-biased init for direct genomes lives in core.fitness;
        warm-starting from a previous run's front prefers
        ``evolve_scan(..., archive=)`` (a dynamic argument, so repeated
        warm-started re-fits share one compiled executable) over the legacy
        :func:`archive_init` closure.
    use_pallas_dominance : how the survival-selection dominance matrix is
        computed. None (default): the Pallas kernel
        (``repro.kernels.dominance``) on a TPU and the jnp reference
        elsewhere, as ``kernels.ops`` mode "auto" decides; True: the kernel
        everywhere, interpreted off a TPU (CPU parity tests); False: the jnp
        reference. Semantics are identical (parity-tested in
        tests/test_nsga2.py).
    """

    def __init__(self, fitness_fn: FitnessFn, config: NSGA2Config,
                 init_fn: Optional[Callable[[jax.Array], jax.Array]] = None,
                 use_pallas_dominance: Optional[bool] = None):
        self.fitness_fn = fitness_fn
        self.config = config
        self.init_fn = init_fn
        self.use_pallas_dominance = use_pallas_dominance
        auto = ops.resolve("auto")    # "pallas" on a TPU, "ref" elsewhere
        if use_pallas_dominance is None:
            self._dominance = auto
        elif use_pallas_dominance:
            self._dominance = "pallas" if auto == "pallas" else "interpret"
        else:
            self._dominance = "ref"
        # cache-friendly split: fitness callables built by
        # core.fitness.make_fitness carry a memoized module-level `.kernel`
        # plus a `.data` pytree — the kernel identity is the jit cache key,
        # the data (tables, arrays) stays dynamic, so two optimizers over
        # two same-shaped evaluators share one compiled step. Legacy
        # closures run as-is (static identity -> one trace per closure);
        # the module-level jit cache then retains the closure and whatever
        # it captures for the process lifetime, so long-lived callers
        # creating many NSGA2 instances should prefer make_fitness kernels
        # or call jax.clear_caches() periodically.
        kernel = getattr(fitness_fn, "kernel", None)
        if kernel is not None:
            self._fitness_fn = kernel
            self._fitness_data = fitness_fn.data
        else:
            self._fitness_fn = fitness_fn
            self._fitness_data = None
        if config.genome == "continuous" and config.lo is not None:
            self._lo = jnp.asarray(config.lo)
            self._hi = jnp.asarray(config.hi)
        else:
            self._lo = jnp.zeros((0,), jnp.float32)
            self._hi = jnp.zeros((0,), jnp.float32)

    # -- init ---------------------------------------------------------------
    def init(self, key: jax.Array) -> NSGA2State:
        return _init_core(key, self._lo, self._hi, None, self._fitness_data,
                          self.config.static_key, self._fitness_fn,
                          self._dominance, self.init_fn)

    # -- one generation -------------------------------------------------------
    def _step(self, state: NSGA2State) -> NSGA2State:
        return _nsga2_step(state, self._lo, self._hi, self._fitness_data,
                           scfg=self.config.static_key,
                           fitness_fn=self._fitness_fn,
                           dominance=self._dominance)

    # -- drivers --------------------------------------------------------------
    def evolve(self, key: jax.Array, n_generations: Optional[int] = None,
               callback: Optional[Callable[[NSGA2State], None]] = None
               ) -> NSGA2State:
        """Python-loop driver (allows host callbacks for logging)."""
        state = self.init(key)
        T = n_generations if n_generations is not None else self.config.n_generations
        for _ in range(T):
            state = self._step(state)
            if callback is not None:
                callback(state)
        return state

    def evolve_scan(self, key: jax.Array,
                    n_generations: Optional[int] = None,
                    archive: Optional[jax.Array] = None) -> NSGA2State:
        """Entire run as one lax.scan in one compiled program.

        ``archive`` optionally warm-starts the population from a previous
        run's survival-ordered genomes (same semantics as
        :func:`archive_init`, but passed as a *dynamic* argument so repeated
        warm-started re-fits reuse the compiled executable instead of
        retracing per closure identity)."""
        T = (n_generations if n_generations is not None
             else self.config.n_generations)
        if (self.init_fn is None and self.config.genome == "discrete"
                and self.config.n_choices <= 0):
            raise ValueError("discrete genome requires init_fn or n_choices>0")
        arch = None if archive is None else jnp.asarray(archive)
        return _nsga2_run(key, self._lo, self._hi, arch, self._fitness_data,
                          scfg=self.config.static_key,
                          fitness_fn=self._fitness_fn,
                          dominance=self._dominance, n_generations=T,
                          init_fn=self.init_fn)

    # -- results --------------------------------------------------------------
    def pareto_front(self, state: NSGA2State) -> Tuple[jax.Array, jax.Array]:
        """Feasible rank-0 members: (genomes, raw objectives)."""
        mask = (state.rank == 0) & (state.violation <= 0)
        return state.genomes[mask], state.F_raw[mask]

    def select_by_weights(self, state: NSGA2State, weights: jax.Array
                          ) -> Tuple[jax.Array, jax.Array]:
        """Pick one policy from the front by the paper's Eq. (1) weighted sum
        over min-max normalized objectives (ω1 RQ + ω2 C + ω3 RT)."""
        F = state.F_raw
        fmin = jnp.min(F, axis=0)
        fmax = jnp.max(F, axis=0)
        Fn = (F - fmin) / jnp.where(fmax - fmin <= 0, 1.0, fmax - fmin)
        score = Fn @ weights
        # mask non-front/infeasible
        bad = (state.rank != 0) | (state.violation > 0)
        score = jnp.where(bad, jnp.inf, score)
        i = jnp.argmin(score)
        return state.genomes[i], F[i]
