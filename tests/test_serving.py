"""Serving substrate: continuous-batching engine exactness + cluster server
fault tolerance (failover, hedging) with real tiny models."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.cluster.spec import paper_testbed
from repro.configs import get
from repro.core.policy import PAPER_DEFAULTS
from repro.kernels import ops
from repro.launch.serve import build_models
from repro.models import lm
from repro.serving import ClusterServer, EngineConfig, LLMEngine, ServeRequest
from repro.workload.trace import build_trace


@pytest.fixture(scope="module")
def tiny_model():
    cfg = get("stablelm-3b").smoke()
    params = lm.init(jax.random.key(0), cfg)
    return cfg, params


def _greedy_reference(cfg, params, tokens, n_new):
    """Offline greedy generation via repeated full forward passes."""
    toks = list(tokens)
    out = []
    for _ in range(n_new):
        logits, _ = lm.train_logits(params, cfg,
                                    {"tokens": jnp.asarray([toks])})
        nxt = int(jnp.argmax(logits[0, -1]))
        out.append(nxt)
        toks.append(nxt)
    return out


def test_engine_matches_offline_greedy(tiny_model):
    cfg, params = tiny_model
    eng = LLMEngine(cfg, params, EngineConfig(max_slots=2, max_seq=64,
                                              max_new_tokens=6))
    rng = np.random.default_rng(0)
    prompts = {i: rng.integers(0, cfg.vocab, size=8 + i) for i in range(2)}
    for i, p in prompts.items():
        eng.submit(i, p, max_new_tokens=6)
    results = eng.run_to_completion()
    for i, p in prompts.items():
        want = _greedy_reference(cfg, params, p, 6)
        assert results[i]["tokens"] == want, i


def test_engine_continuous_batching_admits_from_queue(tiny_model):
    cfg, params = tiny_model
    eng = LLMEngine(cfg, params, EngineConfig(max_slots=2, max_seq=64,
                                              max_new_tokens=4))
    rng = np.random.default_rng(1)
    for i in range(6):  # 6 requests through 2 slots
        eng.submit(i, rng.integers(0, cfg.vocab, size=6))
    assert eng.active_count == 2 and eng.queue_len == 6
    results = eng.run_to_completion()
    assert sorted(results) == list(range(6))
    assert all(len(r["tokens"]) == 4 for r in results.values())


def test_engine_ragged_lengths_independent(tiny_model):
    """A long-prompt slot must not perturb a short-prompt slot's output."""
    cfg, params = tiny_model
    rng = np.random.default_rng(2)
    short = rng.integers(0, cfg.vocab, size=5)
    long = rng.integers(0, cfg.vocab, size=37)

    solo = LLMEngine(cfg, params, EngineConfig(max_slots=1, max_seq=64,
                                               max_new_tokens=5))
    solo.submit(0, short)
    want = solo.run_to_completion()[0]["tokens"]

    both = LLMEngine(cfg, params, EngineConfig(max_slots=2, max_seq=64,
                                               max_new_tokens=5))
    both.submit(0, short)
    both.submit(1, long)
    got = both.run_to_completion()[0]["tokens"]
    assert got == want


# ---------------------------------------------------------------------------
# cluster server
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def server_parts():
    return paper_testbed(), build_models(), build_trace(24, seed=5)


def test_cluster_server_serves_all(server_parts):
    cluster, builders, trace = server_parts
    srv = ClusterServer(cluster, builders, PAPER_DEFAULTS,
                        EngineConfig(max_slots=2, max_seq=48,
                                     max_new_tokens=3))
    for i, r in enumerate(trace.requests[:12]):
        srv.submit(ServeRequest(request_id=i, req=r, max_new_tokens=3))
    done = srv.run()
    assert sorted(done) == list(range(12))
    assert all(len(d["tokens"]) == 3 for d in done.values())


def test_untileable_max_seq_fails_at_construction(server_parts, monkeypatch):
    """Where decode attention runs the kernel, a 1030-position cache has no
    tile: the server refuses to build instead of serving and turning the
    trace-time error into node crashes and reroutes."""
    cluster, builders, _ = server_parts
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    with pytest.raises(ValueError, match="divides"):
        ClusterServer(cluster, builders, PAPER_DEFAULTS,
                      EngineConfig(max_slots=2, max_seq=1030))


def test_cluster_server_failover_requeues(server_parts):
    cluster, builders, trace = server_parts
    srv = ClusterServer(cluster, builders, PAPER_DEFAULTS,
                        EngineConfig(max_slots=2, max_seq=48,
                                     max_new_tokens=4))
    for i, r in enumerate(trace.requests[:8]):
        srv.submit(ServeRequest(request_id=i, req=r, max_new_tokens=4))
    # crash every edge node mid-flight: requests must finish on the cloud
    for node in (1, 2, 3):
        srv.fail_node(node)
    done = srv.run()
    assert sorted(done) == list(range(8))
    assert srv.stats()["reroutes"] >= 1


def test_cluster_server_hedges_stragglers(server_parts):
    cluster, builders, trace = server_parts
    srv = ClusterServer(cluster, builders, PAPER_DEFAULTS,
                        EngineConfig(max_slots=1, max_seq=48,
                                     max_new_tokens=2),
                        hedge_after=1)  # aggressive hedging
    for i, r in enumerate(trace.requests[:6]):
        srv.submit(ServeRequest(request_id=i, req=r, max_new_tokens=2))
    srv.run()
    assert srv.stats()["hedges"] >= 1
    assert len(srv.done) == 6


def test_hedging_accounting_drains_to_zero(server_parts):
    """Regression: the losing hedged duplicate used to leave `outstanding`
    inflated forever, skewing every later queue-based routing decision."""
    cluster, builders, trace = server_parts
    srv = ClusterServer(cluster, builders, PAPER_DEFAULTS,
                        EngineConfig(max_slots=1, max_seq=48,
                                     max_new_tokens=3),
                        hedge_after=1)
    for i, r in enumerate(trace.requests[:8]):
        srv.submit(ServeRequest(request_id=i, req=r, max_new_tokens=3))
    srv.run()
    stats = srv.stats()
    assert stats["hedges"] >= 1
    assert stats["cancelled"] >= 1          # losers were actually cancelled
    assert all(q == 0 for q in stats["queue_lengths"]), stats
    # conservation: every dispatch is closed as complete/failed/cancelled
    for s in srv.monitor.stats.values():
        assert (s.total_dispatched
                == s.total_completed + s.total_failed + s.total_cancelled)


def test_cluster_server_affinity_prefix_reuse_end_to_end(server_parts):
    """Session traffic through the affinity router into prefix-cached
    engines: the prefix-stable tokenizer + paged KV must produce real cache
    hits (strictly fewer prefill tokens run than submitted)."""
    from repro.workload.sessions import SessionConfig, build_session_trace
    cluster, builders, _ = server_parts
    tr = build_session_trace(SessionConfig(n_sessions=4, mean_turns=3.0),
                             seed=2)
    srv = ClusterServer(cluster, builders, PAPER_DEFAULTS,
                        EngineConfig(max_slots=2, max_seq=48,
                                     max_new_tokens=2, prefix_cache=True,
                                     block_size=8, cache_blocks=32),
                        router_kwargs={"mode": "affinity"})
    reqs = tr.requests[:10]
    for i, r in enumerate(reqs):
        srv.submit(ServeRequest(request_id=i, req=r, max_new_tokens=2))
    done = srv.run()
    assert sorted(done) == list(range(len(reqs)))
    stats = [e.cache_stats() for e in srv.engines.values()]
    assert sum(s["hits"] for s in stats) >= 1
    assert (sum(s["prefill_tokens_run"] for s in stats)
            < sum(s["prefill_tokens_total"] for s in stats))
    # the monitor's residency view was populated on dispatch
    assert any(ns.cached_prefixes for ns in srv.monitor.stats.values())

    # a crashed node restarts with empty caches: both the monitor's
    # residency view and its engines' paged pools must flush, or affinity
    # routing keeps crediting KV that did not survive
    node = next(n for n, ns in srv.monitor.stats.items()
                if ns.cached_prefixes)
    srv.fail_node(node)
    assert not srv.monitor.stats[node].cached_prefixes
    pair_node = np.asarray(srv.router.arrays.pair_node)
    for p, eng in srv.engines.items():
        if int(pair_node[p]) == node:
            assert eng.kv.cache.pool.n_free == eng.ecfg.cache_blocks


def test_tokenize_is_stable_and_prefix_preserving(server_parts):
    """Regression: `abs(hash(text))` was salted per process (PYTHONHASHSEED),
    so served token streams — and every prefix-cache hit — were
    irreproducible across runs. crc32 word hashing is stable and maps an
    extending prompt to an extending token stream."""
    cluster, builders, trace = server_parts
    srv = ClusterServer(cluster, builders, PAPER_DEFAULTS,
                        EngineConfig(max_slots=1, max_seq=48,
                                     max_new_tokens=2))
    req = trace.requests[0]
    toks = srv._tokenize(req, vocab=1000)
    # stable: recomputing (and any other process) yields identical streams
    np.testing.assert_array_equal(toks, srv._tokenize(req, vocab=1000))
    import dataclasses as _dc
    import zlib as _zlib
    assert toks[0] == _zlib.crc32(req.text.split()[0].encode()) % 1000
    # prefix-preserving: an extended prompt extends the token stream
    longer = _dc.replace(req, text=req.text + " extra tail words here",
                         prompt_tokens=req.prompt_tokens + 4)
    toks2 = srv._tokenize(longer, vocab=1000, cap=64)
    toks1 = srv._tokenize(req, vocab=1000, cap=64)
    np.testing.assert_array_equal(toks2[:len(toks1)], toks1)


def test_recover_node_uses_simulated_clock(server_parts):
    """Regression: recover_node injected wall-clock time.monotonic() into
    the monitor's simulated timeline."""
    cluster, builders, trace = server_parts
    srv = ClusterServer(cluster, builders, PAPER_DEFAULTS,
                        EngineConfig(max_slots=2, max_seq=48,
                                     max_new_tokens=2))
    for i, r in enumerate(trace.requests[:4]):
        srv.submit(ServeRequest(request_id=i, req=r, max_new_tokens=2))
    srv.fail_node(1)
    srv.step()
    srv.recover_node(1)
    hb = srv.monitor.stats[1].last_heartbeat
    assert hb == srv.ticks            # scheduler ticks, not time.monotonic()
    assert srv.monitor.healthy_mask()[1]
    srv.run()
