"""Fleet-vectorized serving: cohort stepping must be byte-identical to the
per-engine loop across every runtime routing policy, disaggregated KV
handoffs and mid-run node failures — plus the satellite regressions
(tick-unit completion latencies under chunking, O(#cohorts) dispatch counts,
vectorized fleet counters, and jit-cache reuse across equal cohorts).

Hedging is disabled in the identity suite (``hedge_after=10**9``): a hedged
loser's cancel lands between cohort dispatch and host commit, one iteration
later than the per-engine interleaving — a documented fleet-mode caveat that
only ever touches the *discarded* copy (see docs/architecture.md).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.cluster.spec import disagg_testbed, fleet_testbed, paper_testbed
from repro.core.policy import PAPER_DEFAULTS
from repro.core.policies import runtime_policies
from repro.launch.serve import build_models
from repro.obs.timeline import Timeline
from repro.serving import ClusterServer, EngineConfig, LLMEngine, ServeRequest
from repro.serving import fleet as fleet_mod
from repro.workload.trace import build_trace

NO_HEDGE = 10**9


@pytest.fixture(scope="module")
def builders():
    """Two real tiny models standing in for the testbed's 4 names: the
    three edge names share ONE (cfg, params) pair, so all edge engines
    collapse into a single cohort."""
    return build_models()


@pytest.fixture(scope="module")
def tiny_model(builders):
    return builders["gemma3:27b"]


@pytest.fixture(scope="module")
def trace():
    return build_trace(24, seed=5)


def _server(cluster, builders, fleet, policy="threshold", ecfg=None, **kw):
    return ClusterServer(cluster, builders, PAPER_DEFAULTS,
                         ecfg or EngineConfig(max_slots=2, max_seq=48,
                                              max_new_tokens=4),
                         hedge_after=NO_HEDGE, fleet=fleet,
                         router_kwargs={"mode": policy}, **kw)


def _drive(srv, reqs, chunk, max_new=4, mid=None):
    """Submit ``reqs``, optionally run ``mid(srv)`` after two ticks, drain."""
    for i, r in enumerate(reqs):
        srv.submit(ServeRequest(request_id=i, req=r, max_new_tokens=max_new))
    if mid is not None:
        srv.step(chunk=chunk)
        srv.step(chunk=chunk)
        mid(srv)
    return srv.run(chunk=chunk)


# ---------------------------------------------------------------------------
# byte-identity: fleet cohorts vs the per-engine loop
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("policy", runtime_policies())
def test_fleet_identity_across_policies(builders, trace, policy):
    """Same cluster, same requests, same policy: fleet=True must reproduce
    fleet=False bit-for-bit — tokens AND the full QoE accounting (ttft/tpot
    step timestamps ride in each result dict)."""
    reqs = trace.requests[:10]
    done = {}
    for fleet in (False, True):
        srv = _server(paper_testbed(), builders, fleet, policy=policy)
        done[fleet] = _drive(srv, reqs, chunk=1)
    assert done[True] == done[False]


@pytest.mark.parametrize("chunk", [2, 4])
def test_fleet_identity_chunked(builders, trace, chunk):
    """Chunked cohort dispatch (one jit call for n iterations x M members)
    must equal per-engine ``step_n`` — including its fall-back-to-one-step
    behavior while admissions are queued."""
    reqs = trace.requests[:12]
    done = {}
    for fleet in (False, True):
        srv = _server(paper_testbed(), builders, fleet)
        done[fleet] = _drive(srv, reqs, chunk=chunk, max_new=6)
    assert done[True] == done[False]


def test_fleet_identity_disagg_handoffs_mid_chunk(tiny_model):
    """Disaggregated routes: prefilled KV rides the transfer queue and lands
    between cohort chunks; the import + admission must leave fleet results
    identical to the per-engine path, with no leaked blocks."""
    cfg, params = tiny_model
    cluster = disagg_testbed()
    bld = {"gemma3:27b": (cfg, params)}
    reqs = [dataclasses.replace(r, text=" ".join(f"w{i}_{j}"
                                                 for j in range(20)),
                                prompt_tokens=20)
            for i, r in enumerate(build_trace(24, seed=5).requests[:8])]
    ecfg = EngineConfig(max_slots=2, max_seq=48, max_new_tokens=3,
                        prefix_cache=True, block_size=8, cache_blocks=32)
    done, srvs = {}, {}
    for fleet in (False, True):
        srv = _server(cluster, bld, fleet, policy="disagg", ecfg=ecfg)
        done[fleet] = _drive(srv, reqs, chunk=2, max_new=3)
        srvs[fleet] = srv
    assert done[True] == done[False]
    assert srvs[True].stats()["handoffs"] >= 1      # splits actually taken
    for eng in srvs[True].engines.values():
        eng.kv.cache.check_invariants()
        assert int(np.sum(eng.kv.cache.pool.ref > 0)) == 0


def test_fleet_identity_node_failure_mid_chunk(builders, trace):
    """``fail_node`` kills a cohort member two ticks in: survivors must be
    byte-identical to the per-engine path and the dead member's paged pool
    must restart empty (no leaked blocks)."""
    reqs = trace.requests[:10]
    ecfg = EngineConfig(max_slots=2, max_seq=48, max_new_tokens=4,
                        prefix_cache=True, block_size=8, cache_blocks=32)
    done, srvs = {}, {}
    for fleet in (False, True):
        srv = _server(paper_testbed(), builders, fleet, ecfg=ecfg)
        done[fleet] = _drive(srv, reqs, chunk=2,
                             mid=lambda s: s.fail_node(1))
        srvs[fleet] = srv
    assert done[True] == done[False]
    assert srvs[True].stats()["reroutes"] == srvs[False].stats()["reroutes"]
    pair_node = np.asarray(srvs[True].router.arrays.pair_node)
    for p, eng in srvs[True].engines.items():
        eng.kv.cache.check_invariants()
        if int(pair_node[p]) == 1:   # restarted empty
            assert eng.kv.cache.pool.n_free == eng.ecfg.cache_blocks


def test_fleet_identity_mixed_workload(tiny_model):
    """The acceptance-criteria workload in one run: multi-turn session
    traffic with prefix reuse + disaggregated KV handoffs + a node failure
    mid-run, chunked — fleet must reproduce the per-engine loop exactly."""
    from repro.workload.sessions import SessionConfig, build_session_trace
    cfg, params = tiny_model
    cluster = disagg_testbed()
    bld = {"gemma3:27b": (cfg, params)}
    tr = build_session_trace(SessionConfig(n_sessions=4, mean_turns=3.0),
                             seed=3, n_requests=10)
    reqs = [dataclasses.replace(r, text=r.text + " " + " ".join(
                f"pad{i}_{j}" for j in range(12)),
                                prompt_tokens=r.prompt_tokens + 12)
            for i, r in enumerate(tr.requests)]
    ecfg = EngineConfig(max_slots=2, max_seq=48, max_new_tokens=3,
                        prefix_cache=True, block_size=8, cache_blocks=32)
    done, srvs = {}, {}
    for fleet in (False, True):
        srv = _server(cluster, bld, fleet, policy="disagg", ecfg=ecfg)
        done[fleet] = _drive(srv, reqs, chunk=2, max_new=3,
                             mid=lambda s: s.fail_node(1))
        srvs[fleet] = srv
    assert done[True] == done[False]
    assert len(done[True]) == len(reqs)
    assert srvs[True].stats()["handoffs"] >= 1
    for eng in srvs[True].engines.values():
        eng.kv.cache.check_invariants()


# ---------------------------------------------------------------------------
# satellite: completion latency unit (ticks, not decode iterations)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("fleet", [False, True])
def test_completion_latency_in_ticks_under_chunking(builders, trace, fleet):
    """Regression: engine completions used to record ``iters + 1`` decode
    iterations while KV-handoff deliveries recorded scheduler ticks — under
    ``chunk=4`` the same wait produced a 4x larger 'latency' depending on
    which path closed it. Both paths now record ticks."""
    srv = _server(paper_testbed(), builders, fleet,
                  ecfg=EngineConfig(max_slots=2, max_seq=48,
                                    max_new_tokens=8))
    seen = []
    orig = srv.monitor.on_complete
    srv.monitor.on_complete = (
        lambda node, latency: (seen.append(latency), orig(node, latency))[1])
    _drive(srv, trace.requests[:6], chunk=4, max_new=8)
    assert seen
    # tick-unit latencies can never exceed the scheduler clock, and the
    # fastest completion (8 decode iterations = 2 chunks, no queueing) takes
    # 2 ticks — the old iteration unit would have recorded >= 8 for it
    assert all(1 <= lat <= srv.ticks for lat in seen), (seen, srv.ticks)
    assert min(seen) < 8


# ---------------------------------------------------------------------------
# satellite: O(#cohorts) dispatches + vectorized fleet counters
# ---------------------------------------------------------------------------
def test_saturated_tick_is_one_dispatch_per_cohort(builders, trace):
    """With every engine busy, one tick costs exactly ``len(cohorts)``
    jitted decode dispatches — not one per engine."""
    srv = _server(paper_testbed(), builders, True)
    assert len(srv._cohorts) == 2          # {big} + {small x 9 edge pairs}
    assert sum(len(c) for c in srv._cohorts) == len(srv.engines)
    for i, pair in enumerate(srv.engines):  # saturate every engine directly
        srv._dispatch(ServeRequest(request_id=100 + i,
                                   req=trace.requests[i % 12],
                                   max_new_tokens=4), pair)
    assert all(e.active_count > 0 for e in srv.engines.values())
    before = srv.decode_dispatches
    srv.step()
    assert srv.decode_dispatches - before == len(srv._cohorts)
    assert all(e._steps == 1 for e in srv.engines.values())


def test_fleet_counters_match_engine_ground_truth(builders, trace):
    """`active_count`/`queue_len`/`stats()` aggregate numpy cohort counters;
    they must track the per-engine Python-loop truth at every tick."""
    srv = _server(paper_testbed(), builders, True)
    for i, r in enumerate(trace.requests[:12]):
        srv.submit(ServeRequest(request_id=i, req=r, max_new_tokens=4))
    while srv.inflight:
        assert srv.active_count == sum(e.active_count
                                       for e in srv.engines.values())
        assert srv.queue_len == sum(e.queue_len
                                    for e in srv.engines.values())
        srv.step()
    st = srv.stats()
    assert st["active"] == 0 and st["queued"] == 0
    assert st["fleet"]["emitted"] == sum(c["emitted"] for c in st["cohorts"])
    assert st["fleet"]["retired"] == len(srv.done)
    assert sum(c["dispatches"] for c in st["cohorts"]) >= 1
    assert st["decode_dispatches"] >= sum(c["dispatches"]
                                          for c in st["cohorts"])


def test_fleet_testbed_collapses_to_two_cohorts(builders):
    """64 nodes -> 176 (node, model) pairs -> exactly 2 cohorts when the
    edge names share one (cfg, params) identity (the benchmark's setup)."""
    cluster = fleet_testbed(n_edge=56, n_cloud=8)
    assert len(cluster.nodes) == 64
    srv = _server(cluster, builders, True)
    assert len(srv.engines) == 8 + 56 * 3
    assert len(srv._cohorts) == 2
    assert sorted(len(c) for c in srv._cohorts) == [8, 168]


# ---------------------------------------------------------------------------
# satellite: jit-cache reuse across cohorts with equal statics
# ---------------------------------------------------------------------------
def test_equal_cohorts_share_one_trace(tiny_model):
    """Two cohorts with identical (ModelConfig, member count, chunk, eos)
    must share ONE compiled executable: the second cohort's dispatches add
    zero new traces to the module-level jit cache."""
    cfg, params = tiny_model
    ecfg = EngineConfig(max_slots=2, max_seq=48, max_new_tokens=4)
    rng = np.random.default_rng(0)

    def make_cohort():
        engines = [LLMEngine(cfg, params, ecfg) for _ in range(2)]
        for e in engines:
            e.submit(0, rng.integers(0, cfg.vocab, size=6), max_new_tokens=4)
        return fleet_mod.Cohort(engines)

    c1, c2 = make_cohort(), make_cohort()
    before = fleet_mod._cohort_decode_chunk._cache_size()
    c1.dispatch(2, [0, 1])
    after_first = fleet_mod._cohort_decode_chunk._cache_size()
    assert after_first == before + 1       # one trace for this identity
    c2.dispatch(2, [0, 1])
    c1.dispatch(2, [0, 1])
    assert fleet_mod._cohort_decode_chunk._cache_size() == after_first, \
        "equal-static cohorts must reuse one executable"


# ---------------------------------------------------------------------------
# in-place decode: donated state, dropped padding, gather counter
# ---------------------------------------------------------------------------
def _members(tiny_model, n, active, seed=0):
    cfg, params = tiny_model
    ecfg = EngineConfig(max_slots=2, max_seq=48, max_new_tokens=4)
    rng = np.random.default_rng(seed)
    engines = [LLMEngine(cfg, params, ecfg) for _ in range(n)]
    for m in active:
        engines[m].submit(m, rng.integers(0, cfg.vocab, size=6),
                          max_new_tokens=4)
    return engines


def _member_np(cohort, m):
    return [np.asarray(a) for a in jax.tree.leaves(
        fleet_mod._member_gather(cohort.state, jnp.int32(m), cohort.cfg))]


def test_dispatch_and_flush_donate_the_stacked_state(tiny_model):
    """A dispatch and a flush each consume the stacked state they are
    given: its cache leaves are deleted, the new state holds what the member
    wrote, and the member's view still reads it."""
    cfg, _ = tiny_model
    engines = _members(tiny_model, 2, active=[0])
    cohort = fleet_mod.Cohort(engines)
    old = cohort.state
    cohort.dispatch(1, [0, 1])
    assert all(a.is_deleted() for a in jax.tree.leaves(old.cache))
    engines[1].submit(7, np.arange(6) % cfg.vocab, max_new_tokens=4)
    written = [np.asarray(a)
               for a in jax.tree.leaves(engines[1]._store._local)]
    assert 1 in cohort._dirty
    old = cohort.state
    cohort._flush()
    assert all(a.is_deleted() for a in jax.tree.leaves(old.cache))
    for a, b in zip(_member_np(cohort, 1), written):
        np.testing.assert_array_equal(a, b)
    for a, b in zip(jax.tree.leaves(engines[1]._store._fresh()), written):
        np.testing.assert_array_equal(np.asarray(a), b)
    assert int(engines[1].cache.kv_len[0]) == 6


def test_padded_bucket_leaves_idle_members_bit_identical(tiny_model):
    """Three of five members ride a bucket of four: the padding member and
    the idle one keep every leaf of their state bit for bit, while each
    participant advances one position."""
    engines = _members(tiny_model, 5, active=range(5))
    cohort = fleet_mod.Cohort(engines, timeline=Timeline())
    before = {m: _member_np(cohort, m) for m in range(5)}
    kv = {m: np.asarray(engines[m].cache.kv_len) for m in range(5)}
    cohort.dispatch(1, [0, 2, 3])      # bucket of 4: member 1 pads
    assert cohort.timeline.counters["cohort.members_gathered"] == 4
    for m in (1, 4):
        for a, b in zip(_member_np(cohort, m), before[m]):
            np.testing.assert_array_equal(a, b)
    for m in (0, 2, 3):
        now = np.asarray(fleet_mod._member_gather(
            cohort.state, jnp.int32(m), cohort.cfg).cache.kv_len)
        np.testing.assert_array_equal(now, kv[m] + 1)


def test_members_gathered_counts_only_gathered_dispatches(tiny_model):
    """``cohort.members_gathered`` adds 0 for a dispatch of every member
    (the whole state, in place) and the bucket size for a gathered one."""
    engines = _members(tiny_model, 3, active=range(3))
    tl = Timeline()
    cohort = fleet_mod.Cohort(engines, timeline=tl)
    cohort.dispatch(1, [0, 1, 2])
    assert tl.counters["cohort.members_gathered"] == 0
    cohort.dispatch(1, [2])
    assert tl.counters["cohort.members_gathered"] == 1
    cohort.dispatch(1, [0, 2])
    assert tl.counters["cohort.members_gathered"] == 3
