"""Chaos hardening: deterministic fault injection across the three analytic
layers (JAX fitness scan == loop DES == heap DES under a non-trivial
``FaultSchedule``, for every registered policy), circuit-breaker state
machine, retry/backoff/budget and load-shedding behavior of the serving
runtime, monitor clock-domain regression, and phase-B exception safety
(an error mid-commit must not leak KV pins or cohort write-backs)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import (crash_storm_schedule, link_flap_schedule,
                      make_session_trace, shared_cluster, straggler_schedule)
from repro.cluster.monitor import ClusterMonitor
from repro.cluster.simulator import ClusterSimulator
from repro.cluster.spec import paper_testbed
from repro.core.fitness import EvalConfig, TraceEvaluator
from repro.core.policies import get_policy, list_policies, runtime_policies
from repro.core.policy import PAPER_DEFAULTS
from repro.faults import (CrashWindow, FaultSchedule, HeartbeatLoss,
                          LinkFlap, Straggler, TransientErrors,
                          backoff_jitter_u, heartbeat_lost, jnp_tables,
                          link_slowdown_jnp, link_slowdown_np,
                          node_available_jnp, node_available_np,
                          node_slowdown_jnp, node_slowdown_np,
                          transient_delay_jnp, transient_delay_np)
from repro.launch.serve import build_models
from repro.serving import (ClusterServer, EngineConfig, ResilienceConfig,
                           ServeRequest)
from repro.workload.trace import build_trace

CLUSTER = shared_cluster()
NO_HEDGE = 10 ** 9


def _chaos_schedule(n_nodes: int) -> FaultSchedule:
    """The non-trivial mixed regime of the equivalence tests: two crash
    windows, a straggler, a link flap, and per-request transient errors."""
    return FaultSchedule(
        crashes=(CrashWindow(1, 1.0, 12.0), CrashWindow(0, 20.0, 26.0)),
        stragglers=(Straggler(2 % n_nodes, 4.0, 30.0, 3.0),),
        link_flaps=(LinkFlap(2.0, 18.0, 15.0),),
        transient=TransientErrors(rate=0.15, backoff=0.08, seed=11))


# ---------------------------------------------------------------------------
# numpy / jnp twins
# ---------------------------------------------------------------------------
def test_fault_table_twins_agree():
    """Every fault-table query has a numpy and a jnp twin; they must agree
    on a dense time grid (and per request index for the transient draws)."""
    sched = FaultSchedule(
        crashes=(CrashWindow(0, 2.0, 9.0), CrashWindow(2, 5.0, 6.5)),
        stragglers=(Straggler(1, 1.0, 20.0, 4.0), Straggler(1, 3.0, 7.0, 2.0)),
        link_flaps=(LinkFlap(4.0, 11.0, 25.0),),
        heartbeat_losses=(HeartbeatLoss(3, 2.0, 4.0),),
        transient=TransientErrors(rate=0.4, backoff=0.1, jitter=0.6, seed=7))
    ft = sched.compile(4)
    jt = jnp_tables(ft)
    for t in np.linspace(0.0, 25.0, 101, dtype=np.float32):
        np.testing.assert_array_equal(
            node_available_np(ft, t), np.asarray(node_available_jnp(jt, t)))
        np.testing.assert_allclose(
            node_slowdown_np(ft, t), np.asarray(node_slowdown_jnp(jt, t)),
            rtol=1e-6)
        np.testing.assert_allclose(
            link_slowdown_np(ft, t), float(link_slowdown_jnp(jt, t)),
            rtol=1e-6)
    for i in range(200):
        np.testing.assert_allclose(
            transient_delay_np(ft, i),
            float(transient_delay_jnp(jt, jnp.int32(i))), rtol=1e-6)
    # the jitter stream is deterministic, bounded, and attempt-sensitive
    us = [backoff_jitter_u(7, 3, a) for a in range(5)]
    assert all(0.0 <= u < 1.0 for u in us) and len(set(us)) == 5
    assert us == [backoff_jitter_u(7, 3, a) for a in range(5)]
    # heartbeat loss is schedule-level (no analytic effect, host-side query)
    assert heartbeat_lost(sched, 3, 3.0) and not heartbeat_lost(sched, 3, 5.0)
    assert not heartbeat_lost(sched, 0, 3.0)


def test_fault_presets_deterministic():
    for mk in (lambda: crash_storm_schedule(seed=4),
               lambda: link_flap_schedule(seed=4),
               lambda: straggler_schedule(seed=4)):
        assert mk() == mk()
    assert crash_storm_schedule(seed=1) != crash_storm_schedule(seed=2)
    # spare nodes never crash in a crash storm
    sched = FaultSchedule.crash_storm(4, seed=3, spare=2)
    assert all(c.node >= 2 for c in sched.crashes)


# ---------------------------------------------------------------------------
# 3-way equivalence under a non-trivial fault regime, every policy
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("policy", list_policies())
def test_policy_decisions_match_des_oracles_under_faults(policy):
    """The JAX fitness scan and both DES oracles replay the SAME fault
    schedule (crashes mask + fail over, stragglers stretch service/TPOT,
    link flaps stretch KV transfers, transients delay arrivals) and must
    still route every request identically and agree on all realized
    metrics — in-loop decisions on all three sides."""
    tr = make_session_trace(n_requests=70, seed=7)
    sched = _chaos_schedule(len(CLUSTER.nodes))
    pol = get_policy(policy)
    if pol.genome_spec.per_request:
        genome = np.random.default_rng(0).integers(
            0, CLUSTER.n_pairs, tr.n_requests).astype(np.int32)
    else:
        genome = pol.genome_spec.defaults
    disagg = pol.decides == "route"
    ev = TraceEvaluator(tr, CLUSTER,
                        EvalConfig(mode="open", prefix_cache=True,
                                   disaggregated=disagg), faults=sched)
    res = ev.run_policy(policy, genome)
    sim = ClusterSimulator(tr, CLUSTER, prefix_cache=True,
                           disaggregated=disagg, faults=sched)
    fields = ("q", "cost", "rt", "ttft", "tpot", "hit")
    if disagg:
        fields += ("transfer",)
    for sr in (sim.run(policy=policy, genome=genome),
               sim.run_event_heap(policy=policy, genome=genome)):
        np.testing.assert_array_equal(np.asarray(res.assign), sr.assign)
        for f in fields:
            np.testing.assert_allclose(np.asarray(getattr(res, f)),
                                       getattr(sr, f), rtol=1e-4, atol=1e-5,
                                       err_msg=f"{policy}:{f}")


def test_faulty_run_differs_from_clean():
    """The schedule must actually bite: same trace/policy with and without
    faults may not produce identical response times."""
    tr = make_session_trace(n_requests=70, seed=7)
    g = get_policy("threshold").genome_spec.defaults
    clean = TraceEvaluator(tr, CLUSTER, EvalConfig(mode="open"))
    faulty = TraceEvaluator(tr, CLUSTER, EvalConfig(mode="open"),
                            faults=_chaos_schedule(len(CLUSTER.nodes)))
    rc = clean.run_policy("threshold", g)
    rf = faulty.run_policy("threshold", g)
    assert not np.allclose(np.asarray(rc.rt), np.asarray(rf.rt))
    assert float(np.asarray(rf.rt).mean()) > float(np.asarray(rc.rt).mean())


# ---------------------------------------------------------------------------
# circuit breaker state machine (monitor level)
# ---------------------------------------------------------------------------
def _breaker_monitor():
    # huge heartbeat timeout: these tests advance the clock to exercise
    # breaker cooldowns and must not trip the (orthogonal) staleness sweep
    return ClusterMonitor(2, heartbeat_timeout=10.0 ** 9,
                          breaker_threshold=0.5, breaker_min_obs=4,
                          breaker_cooldown=10.0)


def test_breaker_opens_on_error_ewma():
    mon = _breaker_monitor()
    for _ in range(3):
        mon.on_dispatch(0)
        mon.on_failure(0)
    assert mon.breaker_states()[0] == "closed"   # min_obs not reached
    mon.on_dispatch(0)
    mon.on_failure(0)
    assert mon.breaker_states()[0] == "open"
    assert mon.healthy_mask() == (False, True)   # open breaker masks routing
    assert int(mon.breaker_opens[0]) == 1


def test_breaker_half_open_probe_success_closes():
    mon = _breaker_monitor()
    for _ in range(4):
        mon.on_dispatch(0)
        mon.on_failure(0)
    mon.advance(5.0)
    assert mon.breaker_states()[0] == "open"     # still cooling down
    mon.advance(11.0)
    assert mon.breaker_states()[0] == "half-open"
    assert mon.healthy_mask()[0]                 # one probe admitted
    mon.on_dispatch(0)                           # the probe
    assert not mon.healthy_mask()[0]             # masked while it resolves
    mon.on_complete(0, latency=1.0)
    assert mon.breaker_states()[0] == "closed"
    assert mon.healthy_mask()[0]


def test_breaker_half_open_probe_failure_reopens():
    mon = _breaker_monitor()
    for _ in range(4):
        mon.on_dispatch(0)
        mon.on_failure(0)
    mon.advance(11.0)
    mon.on_dispatch(0)
    mon.on_failure(0)                            # probe failed
    assert mon.breaker_states()[0] == "open"
    assert int(mon.breaker_opens[0]) == 2
    mon.advance(12.0)
    assert mon.breaker_states()[0] == "open"     # cooldown restarted
    # explicit recovery is the only shortcut back to closed
    mon.reset_breaker(0)
    assert mon.breaker_states()[0] == "closed"
    assert mon.stats[0].err_ewma == 0.0


# ---------------------------------------------------------------------------
# serving runtime under chaos
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def builders():
    return build_models()


@pytest.fixture(scope="module")
def reqs():
    return build_trace(24, seed=5).requests


def _server(builders, policy="threshold", hedge_after=NO_HEDGE, **kw):
    return ClusterServer(paper_testbed(), builders, PAPER_DEFAULTS,
                         EngineConfig(max_slots=2, max_seq=48,
                                      max_new_tokens=4, prefix_cache=True,
                                      block_size=8, cache_blocks=32),
                         hedge_after=hedge_after,
                         router_kwargs={"mode": policy}, **kw)


def _assert_conserved(srv):
    for node, s in srv.monitor.stats.items():
        assert s.total_dispatched == (s.total_completed + s.total_failed
                                      + s.total_cancelled), (node, s)
        assert s.outstanding == 0, (node, s)


def _assert_no_leaks(srv):
    for eng in srv.engines.values():
        if eng.kv is not None:
            eng.kv.cache.check_invariants()
            assert int(np.sum(eng.kv.cache.pool.ref > 0)) == 0


def test_tick_clock_server_never_marks_live_nodes_stale(builders):
    """Clock-domain regression: a server driven purely on its tick clock
    (many idle ticks, no explicit heartbeats) must keep every live node
    healthy — the per-tick auto-heartbeat and ``monitor.advance`` share one
    clock, so simulated time passing cannot look like heartbeat loss."""
    srv = _server(builders)
    for _ in range(10 * int(srv.monitor.heartbeat_timeout) + 5):
        srv.step()
    assert all(srv.monitor.healthy_mask())
    assert srv.monitor.now == srv.ticks


def test_heartbeat_loss_masks_routing_but_not_progress(builders, reqs):
    """A heartbeat-dark node goes stale (masked from routing) without
    crashing: its engines keep executing, and when the window ends the
    auto-heartbeat revives it."""
    sched = FaultSchedule(heartbeat_losses=(HeartbeatLoss(0, 0.0, 40.0),))
    srv = _server(builders, faults=sched)
    timeout = srv.monitor.heartbeat_timeout
    for _ in range(int(timeout) + 2):
        srv.step()
    assert not srv.monitor.healthy_mask()[0]      # stale -> routing-masked
    assert 0 not in srv._down_nodes               # ...but alive
    for i, r in enumerate(reqs[:6]):
        srv.submit(ServeRequest(request_id=i, req=r, max_new_tokens=3))
    arr = srv.router._np_arrays
    assert all(int(arr.pair_node[fl.pair]) != 0
               for fl in srv.inflight.values())   # nothing routed to node 0
    done = srv.run()
    assert sorted(done) == list(range(6))
    _assert_conserved(srv)
    for _ in range(45):
        srv.step()
    assert srv.monitor.healthy_mask()[0]          # window over: revived


def test_straggler_slow_credit_gates_progress(builders):
    """A factor-2 straggler's engines execute every other tick (slow-credit
    integration), everyone else every tick."""
    sched = FaultSchedule(stragglers=(Straggler(1, 0.0, 1000.0, 2.0),))
    srv = _server(builders, faults=sched)
    adv = []
    for _ in range(8):
        srv.step()
        adv.append(bool(srv._advance[1]))
        assert all(srv._advance[[0, 2, 3]])
    assert adv == [False, True] * 4


def test_transient_errors_retry_to_completion(builders, reqs):
    """Transient dispatch errors bounce into the jittered-backoff retry
    queue and drain to completion; the failed dispatches feed the per-node
    ledger (and breakers) without breaking conservation."""
    sched = FaultSchedule(transient=TransientErrors(rate=0.5, seed=11))
    srv = _server(builders, faults=sched)
    for i, r in enumerate(reqs[:12]):
        srv.submit(ServeRequest(request_id=i, req=r, max_new_tokens=3))
    done = srv.run()
    assert sorted(done) == list(range(12))
    st = srv.stats()
    assert st["transient_faults"] > 0 and st["retries"] > 0
    assert all(isinstance(d, dict) and "tokens" in d for d in done.values())
    _assert_conserved(srv)
    _assert_no_leaks(srv)


def test_timeouts_retry_within_budget(builders, reqs):
    """A timeout cancels every copy of the flight, re-queues it with
    backoff, and stops consuming the global budget once attempts run out —
    the request then completes degraded instead of being dropped."""
    rcfg = ResilienceConfig(request_timeout_ticks=3, min_timeout_ticks=1,
                            deadline_timeout_factor=1e9, max_retries=1,
                            backoff_base_ticks=1.0)
    srv = _server(builders, resilience=rcfg)
    for i, r in enumerate(reqs[:8]):
        srv.submit(ServeRequest(request_id=i, req=r, max_new_tokens=6))
    done = srv.run()
    assert sorted(done) == list(range(8))
    st = srv.stats()
    assert st["timeouts"] > 0 and st["retries"] == st["timeouts"]
    assert st["retries"] <= max(rcfg.retry_budget_min,
                                int(rcfg.retry_budget_frac
                                    * sum(s.total_dispatched
                                          for s in srv.monitor.stats.values())))
    _assert_conserved(srv)
    _assert_no_leaks(srv)


def test_shedding_by_slo_class(builders, reqs):
    """Above the utilization threshold, admission sheds batch-class work
    first; interactive requests keep being admitted until the (higher)
    interactive threshold."""
    rcfg = ResilienceConfig(shed_threshold=0.5, shed_interactive_threshold=3.0)
    srv = _server(builders, resilience=rcfg)
    statuses = {}
    for i, r in enumerate((reqs * 2)[:40]):
        cls = "batch" if i % 2 else "interactive"
        srv.submit(ServeRequest(request_id=i, req=r, max_new_tokens=4,
                                slo_class=cls))
        d = srv.done.get(i)
        if isinstance(d, dict) and d.get("status") == "shed":
            statuses[i] = cls
    assert statuses, "overload never shed anything"
    assert set(statuses.values()) == {"batch"}   # interactive survived
    done = srv.run()
    assert len(done) == 40
    assert srv.stats()["sheds"] == len(statuses)
    _assert_conserved(srv)
    _assert_no_leaks(srv)


@pytest.mark.parametrize("policy", runtime_policies())
def test_retry_hedge_failover_conservation(builders, reqs, policy):
    """The adversarial interaction: aggressive hedging, tight timeouts with
    retries, transient errors, and a schedule-driven node crash mid-run —
    per-node ``dispatched == completed + failed + cancelled`` must hold for
    every runtime policy, with zero outstanding and zero leaked KV blocks."""
    sched = FaultSchedule(
        crashes=(CrashWindow(1, 3.0, 10.0 ** 9),),
        transient=TransientErrors(rate=0.3, seed=11))
    rcfg = ResilienceConfig(request_timeout_ticks=6, min_timeout_ticks=4,
                            deadline_timeout_factor=1e9, max_retries=2,
                            backoff_base_ticks=1.0)
    srv = _server(builders, policy=policy, hedge_after=2, faults=sched,
                  resilience=rcfg)
    for i, r in enumerate(reqs[:10]):
        srv.submit(ServeRequest(request_id=i, req=r, max_new_tokens=4))
    done = srv.run(max_ticks=4000)
    assert sorted(done) == list(range(10))
    _assert_conserved(srv)
    _assert_no_leaks(srv)


def test_phase_b_exception_releases_pins(builders, reqs, monkeypatch):
    """Exception safety for ``step`` phase B: an engine blowing up
    mid-commit is treated as a node crash — its flights re-route, its pools
    flush, and pool refcounts return to baseline (nothing pinned, ledger
    conserved)."""
    srv = _server(builders)
    for i, r in enumerate(reqs[:8]):
        srv.submit(ServeRequest(request_id=i, req=r, max_new_tokens=4))
    victim_pair = next(iter(srv.inflight.values())).pair
    victim_node = int(srv.router._np_arrays.pair_node[victim_pair])
    eng = srv.engines[victim_pair]
    boom = {"armed": True}

    def exploding_commit(work):
        if boom.pop("armed", False):
            raise RuntimeError("injected mid-commit fault")
        return type(eng)._commit_chunk(eng, work)

    monkeypatch.setattr(eng, "_commit_chunk", exploding_commit)
    monkeypatch.setattr(
        eng, "step", lambda *a, **k: (_ for _ in ()).throw(
            RuntimeError("injected mid-commit fault"))
        if boom.pop("armed", False) else type(eng).step(eng))
    done = srv.run(max_ticks=4000)
    assert sorted(done) == list(range(8))
    assert victim_node in srv._down_nodes         # crash semantics applied
    assert srv.stats()["reroutes"] >= 1
    _assert_conserved(srv)
    _assert_no_leaks(srv)


@pytest.mark.parametrize("err", [
    jax.errors.JaxRuntimeError("RESOURCE_EXHAUSTED: out of HBM"),
    pytest.param("mosaic", id="LoweringException")])
def test_phase_b_jax_error_propagates(builders, reqs, monkeypatch, err):
    """A compile or device error is a program fault, not a node fault: it
    escapes ``run`` instead of becoming a crash and a silent reroute."""
    if err == "mosaic":
        from jax._src.pallas.mosaic.lowering import LoweringException
        err = LoweringException("Invalid relayout")
    srv = _server(builders)
    for i, r in enumerate(reqs[:4]):
        srv.submit(ServeRequest(request_id=i, req=r, max_new_tokens=4))
    for eng in srv.engines.values():
        def boom(*a, **k):
            raise err
        monkeypatch.setattr(eng, "_commit_chunk", boom)
        monkeypatch.setattr(eng, "step", boom)
    with pytest.raises(type(err)):
        srv.run(max_ticks=50)
    assert not srv._down_nodes and srv.stats()["reroutes"] == 0
