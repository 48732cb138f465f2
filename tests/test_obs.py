"""Observability layer (``repro.obs``): span tracing, percentile metrics,
decision audit — and their contracts against the DES oracles and the
serving runtime.

The load-bearing properties:

* **oracle stream identity** — both DES oracles (loop ``run`` and
  ``run_event_heap``) must emit byte-identical span and audit streams on
  open-loop workloads (arrivals pin absolute time, so even timestamps
  agree);
* **span conservation** — a completed span's phase durations sum to its
  recorded completion latency;
* **accounting mirror** — serving span events (dispatch/complete/failure/
  cancel) mirror the ``ClusterMonitor`` counter calls one-for-one, so
  ``total_dispatched == completed + failed + cancelled`` is checkable from
  the span log alone;
* **zero-overhead no-op** — ``Obs.noop()`` changes nothing observable;
* **timeline** — wall-clock layer spans nest with parent ids, a request's
  spans share its id, self times tile their parent, the ring is bounded,
  and serving counters match the slot bookkeeping without changing a token.
"""
import gc
import json
import math
import warnings

import numpy as np
import pytest

from conftest import make_session_trace, shared_cluster
from repro.cluster.monitor import ClusterMonitor
from repro.cluster.simulator import ClusterSimulator
from repro.core.policies import get_policy
from repro.obs import (NOOP_TIMELINE, NOOP_TRACER, AuditLog, Histogram,
                       MetricsRegistry, Obs, SpanRecord, Timeline, Tracer,
                       chrome_trace, metrics_flat)
from repro.obs.timeline import self_times
from repro.workload.trace import build_trace

REL_TOL = 1e-5   # float32 table arithmetic: ~2.4e-6 max relative error


# ---------------------------------------------------------------------------
# primitives
# ---------------------------------------------------------------------------
def test_tracer_ring_eviction_counts_drops():
    tr = Tracer(capacity=4)
    for i in range(7):
        tr.begin(i, float(i))
        tr.end(i, float(i) + 1)
    assert len(tr) == 4
    assert tr.dropped == 3
    assert [s.request_id for s in tr.spans()] == [3, 4, 5, 6]


def test_tracer_double_open_and_double_close_raise():
    tr = Tracer()
    tr.begin(0, 0.0)
    with pytest.raises(ValueError):
        tr.begin(0, 1.0)
    tr.end(0, 2.0)
    with pytest.raises(ValueError):
        tr.end(0, 3.0)


def test_noop_tracer_is_inert():
    NOOP_TRACER.begin(0, 0.0)
    NOOP_TRACER.event(0, "dispatch", 0.0, node=1)
    NOOP_TRACER.phase(0, "serve", 0.0, 1.0)
    NOOP_TRACER.end(0, 1.0)
    assert len(NOOP_TRACER) == 0 and NOOP_TRACER.spans() == []
    assert not NOOP_TRACER.enabled
    obs = Obs.noop()
    assert not obs.enabled and obs.metrics is None and obs.audit is None


def test_histogram_percentiles_track_numpy():
    """Log-bucket estimate within one bucket width (~26%) of the sample
    percentile, clamped exactly at the observed extremes."""
    rng = np.random.default_rng(0)
    vals = rng.lognormal(mean=0.0, sigma=2.0, size=5000)
    h = Histogram()
    h.observe(vals)
    for q in (50, 95, 99):
        est, true = h.percentile(q), float(np.percentile(vals, q))
        assert abs(math.log(est / true)) < 0.27, (q, est, true)
    # every estimate is clamped into the observed range
    for q in (0, 50, 95, 99, 100):
        assert vals.min() <= h.percentile(q) <= vals.max()
    assert h.n == 5000 and abs(h.mean - vals.mean()) < 1e-9


def test_histogram_scalar_and_vector_paths_agree():
    vals = np.random.default_rng(1).lognormal(0, 3, 500)
    hv, hs = Histogram(), Histogram()
    hv.observe(vals)
    for v in vals:
        hs.observe_one(v)
    assert (hv.counts == hs.counts).all()
    assert hv.n == hs.n and abs(hv.total - hs.total) < 1e-6
    assert hv.vmin == hs.vmin and hv.vmax == hs.vmax


def test_histogram_merge_is_exact():
    rng = np.random.default_rng(2)
    a, b = rng.lognormal(0, 1, 300), rng.lognormal(1, 2, 700)
    ha, hb, hall = Histogram(), Histogram(), Histogram()
    ha.observe(a)
    hb.observe(b)
    hall.observe(np.concatenate([a, b]))
    ha.merge(hb)
    assert (ha.counts == hall.counts).all()
    assert ha.n == hall.n and ha.vmin == hall.vmin and ha.vmax == hall.vmax
    for q in (50, 95, 99):
        assert ha.percentile(q) == hall.percentile(q)


def test_degenerate_distributions_report_exactly():
    h = Histogram()
    h.observe(np.zeros(10))
    assert h.percentile(50) == 0.0 and h.percentile(99) == 0.0
    empty = Histogram()
    assert math.isnan(empty.percentile(50))


def test_registry_label_merge_matches_global():
    """Percentiles aggregated over one free label must equal the exact
    merge of the labelled histograms (shared fixed edges)."""
    m = MetricsRegistry()
    rng = np.random.default_rng(3)
    v0, v1 = rng.lognormal(0, 1, 200), rng.lognormal(1, 1, 200)
    m.observe("ttft", v0, node=0, category=2)
    m.observe("ttft", v1, node=1, category=2)
    by_cat = m.percentiles("ttft", node=None, category=2)
    overall = m.percentiles("ttft")
    assert by_cat["n"] == overall["n"] == 400
    assert by_cat["p95"] == overall["p95"]
    one = m.percentiles("ttft", node=0, category=2)
    assert one["n"] == 200


def test_registry_observe_by_groups_labels():
    m = MetricsRegistry()
    vals = np.array([1.0, 2.0, 3.0, 4.0])
    nodes = np.array([0, 1, 0, 1])
    cats = np.array([5, 5, 6, 6])
    m.observe_by("tpot", vals, nodes, cats)
    assert m.percentiles("tpot")["n"] == 4
    assert m.percentiles("tpot", node=0, category=5)["n"] == 1
    assert sorted(m.labels("tpot")) == [(0, 5), (0, 6), (1, 5), (1, 6)]


def test_counter_vec_scalar_and_scatter():
    m = MetricsRegistry()
    c = m.counter("fleet_tokens_emitted", 4)
    c.add(2, 5)
    c.add(np.array([0, 0, 3]), np.array([1, 1, 7]))
    assert c.values.tolist() == [2, 0, 5, 7]
    assert c.total == 14


def test_metrics_flat_keys():
    m = MetricsRegistry()
    m.observe("latency", [1.0, 2.0], node=3)
    m.counter("fleet_tokens_emitted", 2).add(1, 9)
    m.gauge("drift").set(0.25)
    flat = metrics_flat(m)
    assert "latency.p50" in flat and "latency.node3.p95" in flat
    assert flat["fleet_tokens_emitted.total"] == 9.0
    assert flat["fleet_tokens_emitted.node1"] == 9.0
    assert flat["drift"] == 0.25


def test_audit_ring_and_explain():
    al = AuditLog(capacity=3)
    for i in range(5):
        al.record(i, float(i), "threshold", "pair", (0.5,), i % 2, i % 2,
                  i % 2, healthy=np.ones(4), queue=np.zeros(4),
                  up=np.arange(4.0), prefill=np.arange(4.0),
                  tpot=np.arange(4.0), cost=np.arange(4.0),
                  failover="node-down" if i == 4 else None)
    assert len(al) == 3 and al.dropped == 2
    assert al.counts_by_policy() == {"threshold": 3}
    assert [r.index for r in al.failovers()] == [4]
    txt = al.explain(4)
    assert "policy=threshold" in txt and "failover[node-down]" in txt
    assert "<-- chosen" in txt
    assert al.explain(0) == "request 0: no audit record"


# ---------------------------------------------------------------------------
# monitor satellites: heartbeat clock contract + EWMA seeding regression
# ---------------------------------------------------------------------------
def test_heartbeat_requires_explicit_now():
    """The wall-clock fallback shim is gone: ``now`` is a required argument
    (callers own the clock), and passing it never warns."""
    mon = ClusterMonitor(2)
    with pytest.raises(TypeError):
        mon.heartbeat(0)
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        mon.heartbeat(1, now=42.0)
    assert mon.stats[1].healthy
    assert mon.stats[1].last_heartbeat == 42.0


def test_ewma_seeds_on_first_completion_even_at_zero_latency():
    """Regression: the old ``ewma or latency`` idiom treated a legitimate
    0.0 EWMA as 'unseeded' and re-seeded on every completion."""
    mon = ClusterMonitor(1)
    mon.on_dispatch(0)
    mon.on_complete(0, latency=0.0)
    s = mon.stats[0]
    assert s.ewma_initialized and s.ewma_latency == 0.0
    mon.on_dispatch(0)
    mon.on_complete(0, latency=10.0)
    # second sample must blend, not re-seed to 10.0
    assert s.ewma_latency == pytest.approx(0.2 * 10.0)
    assert s.ewma_fast == pytest.approx(0.3 * 10.0)


# ---------------------------------------------------------------------------
# DES: oracle stream identity + conservation
# ---------------------------------------------------------------------------
def _des_obs():
    return Tracer(capacity=4096), AuditLog(capacity=4096), MetricsRegistry()


def _sorted_keys(tracer):
    return [s.key() for s in sorted(tracer.spans(),
                                    key=lambda s: s.request_id)]


def test_des_open_loop_span_streams_identical_across_oracles():
    """Loop oracle and event-heap oracle must emit byte-identical span AND
    audit streams on an open-loop session workload (absolute timestamps
    included — arrivals pin the clock)."""
    tr = make_session_trace(seed=3)
    sim = ClusterSimulator(tr, shared_cluster(), prefix_cache=True)
    pol = get_policy("threshold")
    g = np.asarray(pol.genome_spec.defaults)

    t1, a1, m1 = _des_obs()
    r1 = sim.run(policy="threshold", genome=g, concurrency=4,
                 tracer=t1, audit=a1, metrics=m1)
    t2, a2, m2 = _des_obs()
    r2 = sim.run_event_heap(policy="threshold", genome=g, concurrency=4,
                            tracer=t2, audit=a2, metrics=m2)

    assert len(t1) == len(t2) == tr.n_requests
    assert _sorted_keys(t1) == _sorted_keys(t2)
    k1 = sorted((r.key() for r in a1), key=lambda k: k[0])
    k2 = sorted((r.key() for r in a2), key=lambda k: k[0])
    assert k1 == k2
    np.testing.assert_allclose(r1.rt, r2.rt, rtol=1e-6)
    # metrics ingested identically
    assert m1.percentiles("latency") == m2.percentiles("latency")


def test_des_disagg_span_streams_identical_across_oracles():
    tr = build_trace(32, seed=5)
    sim = ClusterSimulator(tr, shared_cluster(), disaggregated=True)
    n_routes = len(sim.np_arrays.route_prefill)
    assign = [i % n_routes for i in range(tr.n_requests)]
    arrivals = np.arange(tr.n_requests) * 0.25

    t1, _, _ = _des_obs()
    sim.run(assign=assign, arrivals=arrivals, concurrency=4, tracer=t1)
    t2, _, _ = _des_obs()
    sim.run_event_heap(assign=assign, arrivals=arrivals, concurrency=4,
                       tracer=t2)
    assert len(t1) == tr.n_requests
    assert _sorted_keys(t1) == _sorted_keys(t2)
    # the route mix must actually exercise split routes
    assert any(p.name == "kv-transfer" for s in t1.spans()
               for p in s.phases)


def test_des_span_conservation():
    """Per span: phase durations sum to the span window AND to the
    simulator's recorded response time."""
    tr = make_session_trace(seed=3)
    sim = ClusterSimulator(tr, shared_cluster(), prefix_cache=True)
    pol = get_policy("threshold")
    g = np.asarray(pol.genome_spec.defaults)
    t1, _, _ = _des_obs()
    res = sim.run(policy="threshold", genome=g, concurrency=4, tracer=t1)
    for s in t1.spans():
        assert s.status == "completed"
        window = s.end - s.start
        assert s.phase_total() == pytest.approx(window, rel=REL_TOL)
        assert window == pytest.approx(float(res.rt[s.request_id]),
                                       rel=REL_TOL)


def test_des_disagg_span_conservation():
    tr = build_trace(32, seed=5)
    sim = ClusterSimulator(tr, shared_cluster(), disaggregated=True)
    n_routes = len(sim.np_arrays.route_prefill)
    assign = [i % n_routes for i in range(tr.n_requests)]
    t1, _, _ = _des_obs()
    res = sim.run(assign=assign, arrivals=np.arange(tr.n_requests) * 0.25,
                  concurrency=4, tracer=t1)
    for s in t1.spans():
        assert s.phase_total() == pytest.approx(s.end - s.start, rel=REL_TOL)
        assert s.end - s.start == pytest.approx(
            float(res.rt[s.request_id]), rel=REL_TOL)


def test_des_failover_audited_and_marked_in_spans():
    """Crash a node for a window: affected requests must carry the failover
    reason in both the audit record and the route-decision span event."""
    tr = make_session_trace(seed=3)
    sim = ClusterSimulator(tr, shared_cluster(), prefix_cache=True)
    pol = get_policy("threshold")
    g = np.asarray(pol.genome_spec.defaults)
    t1, a1, _ = _des_obs()
    sim.run(policy="threshold", genome=g, concurrency=4,
            down_nodes={1: (0.0, 1e9), 2: (0.0, 1e9), 3: (0.0, 1e9)},
            tracer=t1, audit=a1)
    fo = a1.failovers()
    if fo:   # the policy may already route everything to the cloud
        assert all(r.failover == "node-down" for r in fo)
        rid = fo[0].index
        span = t1.span(rid)
        ev = next(e for e in span.events if e.name == "route-decision")
        assert dict(ev.attrs)["failover"] == "node-down"
    # regardless of failovers, every audit record names the policy
    assert a1.counts_by_policy() == {"threshold": tr.n_requests}


def test_des_chrome_trace_round_trips(tmp_path):
    tr = make_session_trace(seed=3)
    sim = ClusterSimulator(tr, shared_cluster(), prefix_cache=True)
    pol = get_policy("threshold")
    t1, _, _ = _des_obs()
    sim.run(policy="threshold", genome=np.asarray(pol.genome_spec.defaults),
            concurrency=4, tracer=t1)
    path = tmp_path / "trace.json"
    doc = chrome_trace(t1, path=str(path))
    loaded = json.loads(path.read_text())
    assert loaded == doc
    evs = doc["traceEvents"]
    assert {e["ph"] for e in evs} <= {"X", "i", "M"}
    for e in evs:
        if e["ph"] == "X":
            assert e["dur"] >= 0 and isinstance(e["ts"], float)
    # every request contributes at least one duration event
    tids = {e["tid"] for e in evs if e["ph"] == "X"}
    assert tids == set(range(tr.n_requests))


def test_des_metrics_percentiles_cover_all_series():
    tr = make_session_trace(seed=3)
    sim = ClusterSimulator(tr, shared_cluster(), prefix_cache=True)
    pol = get_policy("threshold")
    _, _, m = _des_obs()
    sim.run(policy="threshold", genome=np.asarray(pol.genome_spec.defaults),
            concurrency=4, metrics=m)
    summ = m.summary(names=("ttft", "tpot", "queue_wait", "transfer",
                            "cache_hit_frac", "spend", "latency"))
    assert set(summ) == {"ttft", "tpot", "queue_wait", "transfer",
                         "cache_hit_frac", "spend", "latency"}
    for name, p in summ.items():
        assert p["n"] == tr.n_requests, name
    assert np.isfinite(summ["latency"]["p99"])


def test_des_noop_default_changes_nothing():
    """Running without obs sinks must produce the exact same SimResult."""
    tr = make_session_trace(seed=3)
    sim = ClusterSimulator(tr, shared_cluster(), prefix_cache=True)
    pol = get_policy("threshold")
    g = np.asarray(pol.genome_spec.defaults)
    bare = sim.run(policy="threshold", genome=g, concurrency=4)
    t1, a1, m1 = _des_obs()
    obs = sim.run(policy="threshold", genome=g, concurrency=4,
                  tracer=t1, audit=a1, metrics=m1)
    np.testing.assert_array_equal(bare.rt, obs.rt)
    np.testing.assert_array_equal(bare.assign, obs.assign)


# ---------------------------------------------------------------------------
# serving: span/monitor mirror across fleet, failover, hedging, handoff
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def serve_parts():
    from repro.launch.serve import build_models
    return shared_cluster(), build_models(), build_trace(24, seed=5)


def _event_counts(tracer):
    """Per-node counts of the accounting events across all closed spans."""
    ev = {}
    for s in tracer.spans():
        for e in s.events:
            if e.name in ("dispatch", "complete", "failure", "cancel"):
                node = dict(e.attrs)["node"]
                ev.setdefault(node, {"dispatch": 0, "complete": 0,
                                     "failure": 0, "cancel": 0})
                ev[node][e.name] += 1
    return ev


def _assert_spans_mirror_monitor(srv, obs, n_req):
    spans = obs.tracer.spans()
    assert len(spans) == n_req
    assert not obs.tracer.open_spans()   # every span closed exactly once
    ev = _event_counts(obs.tracer)
    for node, st in srv.monitor.stats.items():
        got = ev.get(node, {"dispatch": 0, "complete": 0, "failure": 0,
                            "cancel": 0})
        assert got["dispatch"] == st.total_dispatched, (node, got)
        assert got["complete"] == st.total_completed, (node, got)
        assert got["failure"] == st.total_failed, (node, got)
        assert got["cancel"] == st.total_cancelled, (node, got)
        # the ledger closes from the span log alone
        assert got["dispatch"] == (got["complete"] + got["failure"]
                                   + got["cancel"]), node
    for s in spans:
        assert s.status == "completed"
        for p in s.phases:   # every phase inside the span window
            assert s.start <= p.start and p.start + p.duration <= s.end


def test_serving_spans_mirror_monitor_accounting(serve_parts, tmp_path):
    from repro.serving import ClusterServer, EngineConfig, ServeRequest
    cluster, builders, trace = serve_parts
    obs = Obs()
    srv = ClusterServer(cluster, builders, _paper_defaults(),
                        EngineConfig(max_slots=2, max_seq=48,
                                     max_new_tokens=3), obs=obs)
    for i, r in enumerate(trace.requests[:8]):
        srv.submit(ServeRequest(request_id=i, req=r, max_new_tokens=3))
    done = srv.run()
    assert sorted(done) == list(range(8))
    _assert_spans_mirror_monitor(srv, obs, 8)
    # serve-phase duration == the monitor's completion-latency unit (ticks)
    st = srv.stats()
    assert st["percentiles"]["latency"]["n"] == 8
    assert st["percentiles"]["ttft"]["n"] == 8
    # audit captured one record per route() decision
    assert len(obs.audit) >= 8
    # chrome-trace export stays valid JSON on the tick clock
    path = tmp_path / "serve_trace.json"
    doc = chrome_trace(obs.tracer, path=str(path),
                       time_unit=srv.tick_seconds)
    assert json.loads(path.read_text()) == doc


def test_serving_failover_reroutes_traced(serve_parts):
    from repro.serving import ClusterServer, EngineConfig, ServeRequest
    cluster, builders, trace = serve_parts
    obs = Obs()
    srv = ClusterServer(cluster, builders, _paper_defaults(),
                        EngineConfig(max_slots=2, max_seq=48,
                                     max_new_tokens=4), obs=obs)
    for i, r in enumerate(trace.requests[:6]):
        srv.submit(ServeRequest(request_id=i, req=r, max_new_tokens=4))
    for node in (1, 2, 3):
        srv.fail_node(node)
    done = srv.run()
    assert sorted(done) == list(range(6))
    _assert_spans_mirror_monitor(srv, obs, 6)
    n_reroute = sum(1 for s in obs.tracer.spans()
                    for e in s.events if e.name == "reroute")
    assert n_reroute == srv.stats()["reroutes"] >= 1


def test_serving_hedged_cancel_traced(serve_parts):
    from repro.serving import ClusterServer, EngineConfig, ServeRequest
    cluster, builders, trace = serve_parts
    obs = Obs()
    srv = ClusterServer(cluster, builders, _paper_defaults(),
                        EngineConfig(max_slots=1, max_seq=48,
                                     max_new_tokens=4),
                        hedge_after=2, obs=obs)
    for i, r in enumerate(trace.requests[:6]):
        srv.submit(ServeRequest(request_id=i, req=r, max_new_tokens=4))
    done = srv.run()
    assert sorted(done) == list(range(6))
    _assert_spans_mirror_monitor(srv, obs, 6)
    n_hedge = sum(1 for s in obs.tracer.spans()
                  for e in s.events if e.name == "hedge")
    assert n_hedge == srv.stats()["hedges"] >= 1


def test_serving_disagg_handoff_traced():
    """Split routes: one kv-transfer phase + handoff-start event per
    delivered handoff, and the transfer metric counts them."""
    import dataclasses as dc

    import jax

    from repro.cluster.spec import disagg_testbed
    from repro.configs import get
    from repro.models import lm
    from repro.serving import ClusterServer, EngineConfig, ServeRequest
    cfg = get("stablelm-3b").smoke()
    params = lm.init(jax.random.key(0), cfg)
    reqs = [dc.replace(r, text=" ".join(f"w{i}_{j}" for j in range(20)),
                       prompt_tokens=20)
            for i, r in enumerate(build_trace(24, seed=5).requests[:8])]
    obs = Obs()
    srv = ClusterServer(
        disagg_testbed(), {"gemma3:27b": (cfg, params)}, _paper_defaults(),
        EngineConfig(max_slots=2, max_seq=48, max_new_tokens=3,
                     prefix_cache=True, block_size=8, cache_blocks=32),
        router_kwargs={"mode": "disagg"}, obs=obs)
    for i, r in enumerate(reqs):
        srv.submit(ServeRequest(request_id=i, req=r, max_new_tokens=3))
    done = srv.run()
    assert sorted(done) == list(range(8))
    _assert_spans_mirror_monitor(srv, obs, 8)
    spans = obs.tracer.spans()
    n_handoff = sum(1 for s in spans for e in s.events
                    if e.name == "handoff-start")
    assert n_handoff == srv.stats()["handoffs"] >= 1
    kv_phases = [p for s in spans for p in s.phases
                 if p.name == "kv-transfer"]
    assert srv.stats()["percentiles"]["transfer"]["n"] == len(kv_phases)


def _paper_defaults():
    from repro.core.policy import PAPER_DEFAULTS
    return PAPER_DEFAULTS


# ---------------------------------------------------------------------------
# timeline: wall-clock layer spans and counters inside the serving path
# ---------------------------------------------------------------------------
def _named(tl):
    """Closed spans by name, garbage collections left out."""
    return [r for r in tl.spans() if r.name != "python.gc"]


def test_timeline_spans_nest_with_parent_ids():
    tl = Timeline()
    with tl.span("server.step"):
        with tl.span("step.commit"):
            with tl.span("engine.admit", 7):
                pass
        with tl.span("step.age"):
            pass
    recs = _named(tl)
    # a span closes after everything opened inside it
    assert [r.name for r in recs] == ["engine.admit", "step.commit",
                                      "step.age", "server.step"]
    by = {r.name: r for r in recs}
    assert by["server.step"].parent == -1
    assert by["step.commit"].parent == by["server.step"].id
    assert by["engine.admit"].parent == by["step.commit"].id
    assert by["step.age"].parent == by["server.step"].id
    for r in recs:
        assert r.start_ns <= r.end_ns
        if r.parent >= 0:
            p = next(q for q in recs if q.id == r.parent)
            assert p.start_ns <= r.start_ns and r.end_ns <= p.end_ns


def test_timeline_request_spans_share_rid():
    tl = Timeline()
    with tl.span("server.submit", 5):
        with tl.span("router.route"):
            pass
        with tl.span("engine.admit", 5):
            with tl.span("admit.first_token"):
                pass
    with tl.span("server.step"):
        with tl.span("engine.admit", 9):
            with tl.span("admit.prefill"):
                pass
        with tl.span("step.age"):
            pass
    rids = [(r.name, r.rid) for r in _named(tl)]
    assert rids == [("router.route", 5), ("admit.first_token", 5),
                    ("engine.admit", 5), ("server.submit", 5),
                    ("admit.prefill", 9), ("engine.admit", 9),
                    ("step.age", -1), ("server.step", -1)]


def test_timeline_self_time_arithmetic():
    recs = [SpanRecord(2, "engine.admit", 25, 45, 1, 3),
            SpanRecord(1, "step.commit", 20, 50, 0, -1),
            SpanRecord(3, "step.age", 60, 70, 0, -1),
            SpanRecord(0, "server.step", 0, 100, -1, -1)]
    want = {0: 100 - 30 - 10, 1: 30 - 20, 2: 20, 3: 10}
    assert self_times(recs) == want
    # the snapshot's plain lists read the same
    assert self_times([list(r) for r in recs]) == want
    assert sum(want.values()) == 100   # self times tile the top span


def test_timeline_ring_bounds_counts_drops_and_counters():
    tl = Timeline(capacity=4)
    gc.disable()          # no python.gc span may take a ring slot here
    try:
        for i in range(7):
            with tl.span("server.step", i):
                pass
        tl.count("kv.positions_held", 3)
        tl.count("kv.positions_held", 4)
    finally:
        gc.enable()
    assert len(tl) == 4 and tl.dropped == 3
    assert [r.rid for r in tl.spans()] == [3, 4, 5, 6]
    snap = tl.snapshot()
    assert snap["dropped"] == 3 and snap["capacity"] == 4
    assert snap["counters"] == {"kv.positions_held": 7}
    assert [s[5] for s in snap["spans"]] == [3, 4, 5, 6]
    tl.clear()
    assert len(tl) == 0 and tl.dropped == 0 and tl.counters == {}


def test_timeline_records_garbage_collections_under_open_span():
    tl = Timeline()
    with tl.span("server.step"):
        gc.collect()
    recs = tl.spans()
    step = next(r for r in recs if r.name == "server.step")
    gcs = [r for r in recs if r.name == "python.gc"]
    assert gcs and all(r.parent == step.id for r in gcs)
    assert all(step.start_ns <= r.start_ns <= r.end_ns <= step.end_ns
               for r in gcs)


def test_noop_timeline_allocates_no_record(monkeypatch):
    import jax

    from repro.obs import timeline as tlmod

    def boom(*a, **k):
        raise AssertionError("the no-op timeline read a clock or annotated")

    monkeypatch.setattr(tlmod.time, "perf_counter_ns", boom)
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", boom)
    # one shared null span: a span site allocates nothing
    assert NOOP_TIMELINE.span("server.step") is NOOP_TIMELINE.span("x", 3)
    with NOOP_TIMELINE.span("server.step"):
        with NOOP_TIMELINE.span("engine.admit", 1):
            NOOP_TIMELINE.count("kv.positions_held", 5)
    assert not NOOP_TIMELINE.enabled
    assert len(NOOP_TIMELINE) == 0 and NOOP_TIMELINE.spans() == []
    assert NOOP_TIMELINE.counters == {} and NOOP_TIMELINE.dropped == 0
    assert Obs().timeline is NOOP_TIMELINE
    assert Obs.noop().timeline is NOOP_TIMELINE


@pytest.mark.parametrize("fleet", [True, False], ids=["fleet", "per_engine"])
def test_serving_timeline_counters_match_slots(serve_parts, fleet):
    """With the timeline on, the KV counters equal the slot bookkeeping
    recomputed before every step, every request's spans carry its id, and
    the served tokens equal a timeline-off run's."""
    from repro.serving import ClusterServer, EngineConfig, ServeRequest
    cluster, builders, trace = serve_parts
    ecfg = EngineConfig(max_slots=2, max_seq=48, max_new_tokens=4)

    def serve(timeline):
        srv = ClusterServer(cluster, builders, _paper_defaults(), ecfg,
                            fleet=fleet,
                            obs=Obs(tracer=NOOP_TRACER, metrics=None,
                                    audit=None, timeline=timeline))
        for i, r in enumerate(trace.requests[:8]):
            srv.submit(ServeRequest(request_id=i, req=r, max_new_tokens=4))
        held = reserved = 0
        while srv.inflight:
            for eng in srv.engines.values():
                reserved += len(eng.slots) * eng.ecfg.max_seq
                held += sum(s.prompt_tokens + len(s.generated)
                            for s in eng.slots if s.request_id is not None)
            srv.step()
        return srv, held, reserved

    off, _, _ = serve(NOOP_TIMELINE)
    tl = Timeline()
    on, held, reserved = serve(tl)
    assert sorted(on.done) == list(range(8))
    assert ({r: d["tokens"] for r, d in on.done.items()}
            == {r: d["tokens"] for r, d in off.done.items()})
    c = tl.counters
    assert held > 0
    assert c["kv.positions_held"] == held
    assert c["kv.positions_reserved"] == reserved
    assert tl.dropped == 0
    recs = tl.spans()
    names = {r.name for r in recs}
    assert {"server.submit", "router.route", "server.step", "step.deliver",
            "step.retry", "step.commit", "step.age", "engine.admit",
            "admit.prefill", "admit.splice",
            "admit.first_token"} <= names
    admits = [r for r in recs if r.name == "engine.admit"]
    assert sorted(r.rid for r in admits) == list(range(8))
    by_id = {r.id: r for r in recs}
    for r in recs:
        if r.name.startswith("admit."):
            assert by_id[r.parent].name == "engine.admit"
            assert r.rid == by_id[r.parent].rid
        if r.name == "router.route":
            assert by_id[r.parent].name == "server.submit"
    if fleet:
        assert {"cohort.dispatch", "cohort.flush", "cohort.launch",
                "cohort.fetch", "fleet.gather"} <= names
        emitted = sum(int(co.counters.emitted.sum()) for co in on._cohorts)
        assert c["cohort.tokens_emitted"] == emitted > 0
        assert c["cohort.rows_decoded"] >= emitted
    else:
        assert "cohort.dispatch" not in names
        assert "cohort.rows_decoded" not in c


def test_serving_queue_wait_records_admission_wait(serve_parts):
    """``queue_wait`` holds each request's steps queued before its slot
    (``queue_steps``), not a copy of its TTFT."""
    from repro.serving import ClusterServer, EngineConfig, ServeRequest
    cluster, builders, trace = serve_parts
    srv = ClusterServer(cluster, builders, _paper_defaults(),
                        EngineConfig(max_slots=1, max_seq=48,
                                     max_new_tokens=3))
    for i, r in enumerate(trace.requests[:8]):
        srv.submit(ServeRequest(request_id=i, req=r, max_new_tokens=3))
    done = srv.run()
    waits = [d["queue_steps"] for d in done.values()]
    assert max(waits) > 0      # one slot per engine: some requests queued
    for d in done.values():
        assert d["queue_steps"] == d["first_token_step"] - d["submit_step"]
    h = srv.metrics.hist("queue_wait")
    assert h.n == len(done) and h.total == sum(waits)
