"""Fleet-vectorized device data plane: decode a whole cohort in one dispatch.

``ClusterServer`` used to advance N ``LLMEngine``s in a Python loop — one
device dispatch (or fused ``step_n`` chunk) per engine per tick, the hard
ceiling on simulated fleet scale. This module stacks per-engine device state
(decode caches, slot next-token arrays) into a single pytree with a leading
**node axis** and ``vmap``s the fused decode chunk of PR 4 over that axis, so
every engine in a **cohort** — engines sharing an identical
``(ModelConfig, EngineConfig, params)`` triple — advances in ONE jitted
dispatch per chunk with ONE stacked ``(member, n, 3, B)`` host transfer.

Split of responsibilities (the host/device contract):

* **device data plane (here)** — ``FleetState`` (stacked ``lm.Cache`` +
  next-token array), ``decode_chunk_body`` (the un-jitted scan shared with
  ``engine._decode_chunk``), and the module-level ``_cohort_decode_chunk``
  jit keyed on the shared static config. A dispatch decodes the stacked
  state in place (donated, attention K/V folded). When every member
  participates it runs on the whole state; otherwise it gathers only the
  **participating** members — their rows are indexed out inside the jit at
  a power-of-two-padded participant count below the member count (the
  bucketing idiom of the prefills, so the drain tail of a replay costs
  O(participants), not O(members)) — runs the vmapped chunk on that
  sub-fleet, and scatters the participants' rows back; a skipped member's
  device state never advances.
* **host control plane (``engine.LLMEngine``)** — admission, continuous
  batching, prefix-cache matching and result accounting are unchanged; a
  fleet-adopted engine simply reads and writes its device state through a
  member view into the stacked arrays (``FleetMemberStore``). The view is
  **write-back**: reads gather the member's slice once per dispatch epoch
  (one jitted call), writes land host-side and are flushed into the stacked
  pytree at most once per member per dispatch — an admission no longer pays
  a whole-fleet copy per slot write.

Byte-identity: a cohort dispatch runs ``n_f = max`` over the participating
members' clipped chunk lengths, but each member commits only its own
``n_eff`` iterations host-side — device state past a member's ``n_eff``
touches only slots that are already dead (all ops are row-independent for
the patterns ``LLMEngine.fleet_ok`` admits: no MoE, or dropless MoE, whose
every held expert runs over every row alone), and admission rewrites a
slot's rows wholesale, so fleet stepping reproduces per-engine ``step()`` /
``step_n()`` bit-for-bit. Recurrent rows (a Mamba layer's ``(h, conv
window)``) ride the stacked state like K/V rows, on the same slot axis: a
dead row's state goes on advancing on garbage that nothing reads, and
admission's splice overwrites both of its leaves, so the next request in
that slot starts from its own prefill's state, as on the per-engine path.
(XLA's CPU backend can round a Mamba state update of the vmapped program
one float32 ulp away from the per-engine program's; the served tokens and
step stamps of ``tests/test_jamba2.py`` are identical.) ``tests/test_fleet.py`` enforces this across every
registered routing policy, disaggregated KV handoffs and node failures.
"""
from __future__ import annotations

import functools
from typing import Dict, List, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..models import lm
from ..models.config import ModelConfig
from ..obs.timeline import NOOP_TIMELINE
from .kvcache import FleetKVPools


class FleetState(NamedTuple):
    """Stacked device state for one cohort: every leaf carries a leading
    member (node) axis. The stacked cache holds its attention K/V folded
    (``lm.fold_kv``); a member view unfolds its own slice."""

    cache: object          # lm.Cache, leaves (M, ...), K/V folded
    next_token: jnp.ndarray  # (M, B, 1)


def decode_chunk_body(params, cfg: ModelConfig, tok, cache, budget, alive,
                      n: int, eos: int):
    """``n`` fused decode iterations with device-side retirement (un-jitted).

    The single source of truth for the chunk state evolution: jitted
    per-engine as ``engine._decode_chunk`` and vmapped over the member axis
    by ``_cohort_decode_chunk``. Mirrors ``LLMEngine.step`` exactly: every
    iteration decodes all slots, budgets decrement for live slots, a live
    slot retires on exhausted budget or EOS (its ``kv_len`` zeroes and its
    next token resets, exactly like ``_release_slot``), and already-dead
    slots keep decoding garbage that nothing reads. Emits one stacked
    (n, 3, B) int32 tensor (token, emitted-this-iter, retired-this-iter);
    an MoE pattern adds a fourth row, each emitting slot's top-k picks among
    the held experts summed over the MoE blocks (0 for a slot that did not
    emit), so the count rides the same transfer."""
    moe = any(f == "moe" for _, f in cfg.pattern)

    def body(carry, _):
        tok, cache, budget, alive = carry
        with jax.named_scope("decode.step"):
            if moe:
                logits, cache, picks = lm.decode_step(params, cfg, tok, cache,
                                                      expert_rows=True)
            else:
                logits, cache = lm.decode_step(params, cfg, tok, cache)
        with jax.named_scope("decode.sample"):
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            emit = alive
            budget = budget - alive.astype(jnp.int32)
            retire = alive & ((budget <= 0) | (nxt == eos))
            alive = alive & ~retire
            cache = cache._replace(kv_len=jnp.where(retire, 0, cache.kv_len))
            tok = jnp.where(retire, 0, nxt)[:, None]
            out = [nxt, emit.astype(jnp.int32), retire.astype(jnp.int32)]
            if moe:
                out.append(jnp.where(emit, picks, 0))
            out = jnp.stack(out)
        return (tok, cache, budget, alive), out

    (tok, cache, budget, alive), outs = jax.lax.scan(
        body, (tok, cache, budget, alive), None, length=n)
    return tok, cache, outs


@functools.partial(jax.jit, static_argnames=("cfg",))
def _stack(cfg: ModelConfig, members: Sequence[FleetState]) -> FleetState:
    """The members' device states stacked on a leading member axis, K/V
    folded (``lm.fold_kv``)."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *[
        m._replace(cache=lm.fold_kv(cfg, m.cache)) for m in members])


@functools.partial(jax.jit, static_argnames=("cfg", "n", "eos"),
                   donate_argnames=("state",))
def _cohort_decode_chunk(params, cfg: ModelConfig, state: FleetState,
                         budget, alive, idx, valid, n: int, eos: int):
    """One dispatch for a cohort's participating members,
    ``decode_chunk_body`` vmapped over them. ``state`` is donated: the
    returned state reuses its buffers, written in place.

    ``budget``/``alive`` are (K, B) for the K-row participant bucket. A
    bucket of all M members (K == M, a static shape) runs on the whole
    stacked state, with ``idx`` the identity: nothing is gathered or merged.
    A smaller bucket gathers the rows named by ``idx`` — (K,) **unique**
    member rows, participants first, padded to a power of two with distinct
    idle members — and writes back only the rows that ``valid`` marks as
    participants, in place; a padding row's write is dropped, so an idle
    member keeps its state bit for bit. Keyed on the shared static
    ``(cfg, n, eos)`` — every cohort with the same model identity, member
    count and bucket size reuses one executable."""
    run = jax.vmap(
        lambda t, c, b, a: decode_chunk_body(params, cfg, t, c, b, a, n, eos))
    M = state.next_token.shape[0]
    if budget.shape[0] == M:
        tok, cache, outs = run(state.next_token, state.cache, budget, alive)
        return FleetState(cache=cache, next_token=tok), outs
    with jax.named_scope("cohort.gather"):
        sub = jax.tree.map(lambda a: a[idx], state)
    tok, cache, outs = run(sub.next_token, sub.cache, budget, alive)
    with jax.named_scope("cohort.merge"):
        rows = jnp.where(valid, idx, M)     # M is past the end: dropped
        new_state = jax.tree.map(
            lambda full, new: full.at[rows].set(new, mode="drop"),
            state, FleetState(cache=cache, next_token=tok))
    return new_state, outs


@functools.partial(jax.jit, static_argnames=("cfg",))
def _member_gather(state: FleetState, m, cfg: ModelConfig):
    """One member's slice of the stacked state, K/V unfolded — ONE jitted
    call for the whole pytree (an eager per-leaf gather costs ~a millisecond
    of Python per read on the admission hot path)."""
    one = jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, m, 0, keepdims=False),
        state)
    return one._replace(cache=lm.unfold_kv(cfg, one.cache))


@functools.partial(jax.jit, static_argnames=("cfg",),
                   donate_argnames=("state",))
def _member_scatter(state: FleetState, local: FleetState, m,
                    cfg: ModelConfig):
    """Write one member's slice back into the stacked state, in place: the
    state is donated (one jitted call; traced member index, so every member
    shares one executable)."""
    local = local._replace(cache=lm.fold_kv(cfg, local.cache))
    return jax.tree.map(
        lambda full, one: jax.lax.dynamic_update_index_in_dim(
            full, one, m, 0), state, local)


class FleetMemberStore:
    """One engine's **write-back** view into a cohort's stacked device state.

    Drop-in replacement for the engine-local store. Reads gather the
    member's slice once per dispatch epoch (one jitted call) and serve
    repeats from the host-held local copy; writes land in the local copy and
    mark the member dirty — the cohort flushes every dirty member into the
    stacked pytree right before its next dispatch (``Cohort._flush``), so an
    admission's slot writes cost O(member slice), not O(whole fleet). The
    control plane keeps its exact per-engine semantics while the
    authoritative bytes live in the fleet pytree."""

    def __init__(self, cohort: "Cohort", member: int):
        self._cohort = cohort
        self._member = member
        self._local: Optional[FleetState] = None   # member slice, write-back
        self._epoch = -1

    def _fresh(self) -> FleetState:
        c = self._cohort
        if self._local is None or self._epoch != c.epoch:
            with c.timeline.span("fleet.gather"):
                self._local = _member_gather(c.state,
                                             jnp.int32(self._member), c.cfg)
            self._epoch = c.epoch
        return self._local

    @property
    def cache(self):
        return self._fresh().cache

    @cache.setter
    def cache(self, value):
        self._local = self._fresh()._replace(cache=value)
        self._cohort._dirty.add(self._member)

    @property
    def next_token(self):
        return self._fresh().next_token

    @next_token.setter
    def next_token(self, value):
        self._local = self._fresh()._replace(next_token=value)
        self._cohort._dirty.add(self._member)


class ChunkWork(NamedTuple):
    """One member's share of a decode chunk, ready for host commit."""

    outs: np.ndarray     # (n_f, 3, B) host array (token, emitted, retired)
    n_eff: int           # iterations this member actually commits
    active: Sequence[int]  # slots active at dispatch time


class CohortCounters:
    """Vectorized per-member fleet counters (numpy, host-side).

    ``active``/``queued`` mirror each member engine's slot/queue occupancy
    (synced by the engine on every mutation), so ``ClusterServer`` can
    aggregate load without a Python loop over engines. ``emitted``/
    ``retired`` accumulate from the stacked chunk outputs — one vectorized
    sum per dispatch, no per-engine host pulls."""

    def __init__(self, n_members: int):
        self.active = np.zeros(n_members, np.int64)
        self.queued = np.zeros(n_members, np.int64)
        self.emitted = np.zeros(n_members, np.int64)
        self.retired = np.zeros(n_members, np.int64)
        self.dispatches = 0


class CohortDispatch(NamedTuple):
    """Result of one cohort decode dispatch."""

    work: Dict[int, ChunkWork]   # member -> commit work (empty: no dispatch)
    emitted: np.ndarray          # (M,) tokens emitted this chunk, per member
    retired: np.ndarray          # (M,) slots retired this chunk, per member

    @property
    def participants(self) -> int:
        """Members that rode this stacked dispatch (0 = no dispatch ran) —
        telemetry reads it host-side, no extra device sync."""
        return len(self.work)


class Cohort:
    """A group of engines sharing one (ModelConfig, EngineConfig, params)
    identity whose device state is stacked into a single ``FleetState``.

    Adoption re-homes each engine's decode cache, next-token array and (when
    paged prefix reuse is on) K/V pools into stacked arrays with a leading
    member axis; the engines keep operating on views (``FleetMemberStore``).
    ``dispatch`` advances every participating member in one jitted call.
    ``timeline`` (a ``repro.obs.Timeline``) gets the dispatch's spans and
    row counters; the shared no-op by default."""

    def __init__(self, engines: Sequence, timeline=NOOP_TIMELINE):
        assert engines, "a cohort needs at least one engine"
        e0 = engines[0]
        self.cfg = e0.cfg
        self.ecfg = e0.ecfg
        self.params = e0.params
        for e in engines[1:]:
            assert e.cfg == self.cfg and e.ecfg == self.ecfg, \
                "cohort members must share (ModelConfig, EngineConfig)"
            assert e.params is self.params, \
                "cohort members must share one params pytree"
            assert e.fleet_ok, "engine pattern is not fleet-vectorizable"
        self.members = list(engines)
        self.timeline = timeline
        M = len(self.members)
        self.state = _stack(self.cfg, [
            FleetState(cache=e.cache, next_token=e._next_token)
            for e in self.members])
        self.kv_pools: Optional[FleetKVPools] = None
        if self.ecfg.prefix_cache and all(e.kv is not None
                                          for e in self.members):
            self.kv_pools = FleetKVPools.stack([e.kv for e in self.members])
        self.counters = CohortCounters(M)
        self.host_syncs = 0   # one stacked device->host transfer per dispatch
        self.epoch = 0        # bumps per dispatch: invalidates member views
        self._dirty: set = set()   # members with unflushed local writes
        for m, eng in enumerate(self.members):
            eng._attach_fleet(self, m)

    def __len__(self) -> int:
        return len(self.members)

    def _flush(self) -> None:
        """Write every dirty member's local slice into the stacked state —
        at most one jitted scatter per member per dispatch, however many
        slot writes its admissions made since the last one."""
        for m in sorted(self._dirty):
            self.state = _member_scatter(self.state,
                                         self.members[m]._store._local,
                                         jnp.int32(m), self.cfg)
        self._dirty.clear()

    def dispatch(self, n: int, eligible: Sequence[int]) -> CohortDispatch:
        """One vmapped decode chunk for every participating member.

        ``eligible`` pre-filters members (the scheduler drops crashed
        nodes); participation additionally requires active slots and — for
        ``n > 1``, mirroring ``step_n``'s fallback — an empty admission
        queue, so chunking never skips a mid-chunk admission a per-engine
        ``step()`` would have run. The participant rows are gathered inside
        the jit at a power-of-two-padded bucket size, so a near-idle tick
        (the drain tail of a replay) costs O(participants) decode compute,
        not O(members). Returns per-member ``ChunkWork`` for the host commit
        plus vectorized emit/retire counts straight off the stacked
        (bucket, n, 3, B) output — the single transfer for the whole
        cohort. With a timeline the dispatch is the ``cohort.dispatch`` span,
        with ``cohort.flush``, ``cohort.launch`` (the jitted call) and
        ``cohort.fetch`` (the wait for its outputs) inside, and it counts
        ``cohort.rows_decoded`` (bucket × slots × iterations run) and
        ``cohort.tokens_emitted``."""
        n = max(int(n), 1)
        work_slots: Dict[int, List[int]] = {}
        for m in eligible:
            eng = self.members[m]
            if n > 1 and eng.queue:
                continue   # step_n would fall back: keep per-engine semantics
            active = [i for i, s in enumerate(eng.slots)
                      if s.request_id is not None]
            if active:
                work_slots[m] = active
        M = len(self.members)
        zero = np.zeros(M, np.int64)
        if not work_slots:
            return CohortDispatch({}, zero, zero)
        with self.timeline.span("cohort.dispatch"):
            return self._launch(n, work_slots)

    def _launch(self, n: int, work_slots: Dict[int, List[int]]
                ) -> CohortDispatch:
        tl = self.timeline
        with tl.span("cohort.flush"):
            self._flush()
        M = len(self.members)
        B = self.ecfg.max_slots
        parts = sorted(work_slots)
        k = len(parts)
        # pow2 bucket; one of M rows only when all M ride, so that bucket
        # decodes the whole state in place and a partial one pads below M
        k_b = M if k == M else min(1 << (k - 1).bit_length(), M - 1)
        pads = [m for m in range(M) if m not in work_slots][:k_b - k]
        idx = np.asarray(parts + pads, np.int32)
        valid = np.zeros(k_b, bool)
        valid[:k] = True
        budgets = np.zeros((k_b, B), np.int32)
        alive = np.zeros((k_b, B), bool)
        n_eff: Dict[int, int] = {}
        for r, m in enumerate(parts):
            for i in work_slots[m]:
                s = self.members[m].slots[i]
                budgets[r, i] = s.budget
                alive[r, i] = True
            n_eff[m] = min(n, int(budgets[r, alive[r]].max()))
        n_f = max(n_eff.values())
        with tl.span("cohort.launch"):
            self.state, outs = _cohort_decode_chunk(
                self.params, self.cfg, self.state, jnp.asarray(budgets),
                jnp.asarray(alive), jnp.asarray(idx), jnp.asarray(valid),
                n_f, self.ecfg.eos_token)
        self.epoch += 1
        # non-participants' stacked rows are untouched: their (flushed)
        # local views stay valid across the epoch bump, so an idle member
        # never re-gathers; participants drop their stale copy (device
        # memory) and re-gather lazily on next read
        changed = set(parts)
        for m, eng in enumerate(self.members):
            st = eng._store
            if m in changed:
                st._local = None
            elif st._local is not None and st._epoch == self.epoch - 1:
                st._epoch = self.epoch
        with tl.span("cohort.fetch"):
            outs_np = np.asarray(outs)    # ONE transfer for the whole cohort
        self.host_syncs += 1
        self.counters.dispatches += 1
        # fleet counters straight from the stacked emit/retire masks: rows
        # past a member's n_eff are all-dead (emit == retire == 0), padding
        # rows all-idle, so the vectorized sum is exact
        emitted = np.zeros(M, np.int64)
        retired = np.zeros(M, np.int64)
        emitted[parts] = outs_np[:k, :, 1, :].sum(axis=(1, 2))
        retired[parts] = outs_np[:k, :, 2, :].sum(axis=(1, 2))
        self.counters.emitted += emitted
        self.counters.retired += retired
        if tl.enabled:
            tl.count("cohort.rows_decoded", k_b * B * n_f)
            tl.count("cohort.tokens_emitted", int(emitted.sum()))
            tl.count("cohort.members_gathered", 0 if k_b == M else k_b)
            if outs_np.shape[2] > 3:     # an MoE pattern's picks row
                tl.count("moe.expert_rows", int(outs_np[:k, :, 3, :].sum()))
        work = {m: ChunkWork(outs=outs_np[r], n_eff=n_eff[m],
                             active=tuple(work_slots[m]))
                for r, m in enumerate(parts)}
        return CohortDispatch(work, emitted, retired)


def build_cohorts(engines: Dict[int, object], timeline=NOOP_TIMELINE):
    """Group engines into cohorts by shared (ModelConfig, EngineConfig,
    params-identity); non-vectorizable engines (capacity-MoE patterns) stay
    loose.
    Every cohort records into ``timeline``.

    Returns ``(cohorts, cohort_pairs, pair_to_cohort)`` where
    ``cohort_pairs[c]`` lists the pair ids of cohort ``c`` in pair order and
    ``pair_to_cohort`` maps pair id -> (cohort index, member index)."""
    groups: Dict[tuple, List[int]] = {}
    for pair in sorted(engines):
        eng = engines[pair]
        if not eng.fleet_ok:
            continue
        key = (eng.cfg, eng.ecfg, id(eng.params))
        groups.setdefault(key, []).append(pair)
    cohorts: List[Cohort] = []
    cohort_pairs: List[List[int]] = []
    pair_to_cohort: Dict[int, tuple] = {}
    for pairs in groups.values():
        c = len(cohorts)
        cohorts.append(Cohort([engines[p] for p in pairs], timeline))
        cohort_pairs.append(pairs)
        for m, p in enumerate(pairs):
            pair_to_cohort[p] = (c, m)
    return cohorts, cohort_pairs, pair_to_cohort
