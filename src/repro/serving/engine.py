"""Continuous-batching inference engine for one LLM instance.

Orca-style iteration-level scheduling (the paper's §II-D background) on a
fixed slot pool:

* ``submit`` queues a request; admission runs its prefill (padded to a bucket
  length) and writes the resulting KV/state into a free slot of the batched
  decode cache;
* ``step`` advances *all* active slots by one decode token (one jit'd
  ``decode_step`` call — iteration-level batching), retiring slots that hit
  max_new_tokens or emit EOS and immediately admitting queued requests into
  the freed slots;
* per-slot fill lives in ``cache.kv_len`` so ragged occupancy needs no
  re-padding.

The engine is exact: admission uses the same ``lm.prefill`` the tests
validate against teacher forcing, so a routed request's tokens are identical
to an offline forward pass.

Each result carries **QoE phase accounting** in engine-step units (the
discrete clock advanced by ``step``): ``submit_step``/``first_token_step``/
``finish_step`` timestamps plus the derived ``ttft_steps`` (queue wait until
the prefill emits the first token) and ``tpot_steps`` (decode iterations per
generated token after the first). These are the serving-layer ground truth
the analytical TTFT/TPOT tables in ``core.fitness`` model.

**Prefix reuse** (``EngineConfig.prefix_cache``): admission looks up the
longest cached whole-block prefix of the prompt in a paged KV store
(``serving.kvcache``), runs ``lm.prefill_extend`` on only the uncached
suffix, and caches the freshly computed whole blocks for later requests.
Reuse is exact — output tokens are bit-identical to the non-caching engine —
while ``cache_stats()["prefill_tokens_run"]`` drops with every shared
prefix (multi-turn sessions, shared system prompts).
"""
from __future__ import annotations

import dataclasses
import functools
from collections import deque
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels import ops as kops
from ..models import lm
from ..models.config import ModelConfig
from ..obs.timeline import NOOP_TIMELINE
from .fleet import ChunkWork, FleetMemberStore, decode_chunk_body
from .kvcache import CacheStats, PagedKVStore

# ---------------------------------------------------------------------------
# Module-level jitted entry points, keyed on the (hashable) ModelConfig:
# every engine with the same model shares one compiled executable per shape
# bucket instead of re-jitting per instance, and admission pads prompts /
# suffixes to `EngineConfig.prefill_bucket` multiples so distinct lengths
# stop compiling distinct executables.
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("cfg",))
def _decode_one(params, cfg: ModelConfig, tok, cache):
    return lm.decode_step(params, cfg, tok, cache)


@functools.partial(jax.jit, static_argnames=("cfg", "max_seq"))
def _prefill_bucketed(params, cfg: ModelConfig, tokens, length, max_seq):
    with jax.named_scope("prefill"):
        return lm.prefill(params, cfg, {"tokens": tokens}, max_seq=max_seq,
                          length=length)


@functools.partial(jax.jit, static_argnames=("cfg", "max_seq"))
def _prefill_extend_bucketed(params, cfg: ModelConfig, tokens, length,
                             prefix, prefix_len, max_seq):
    with jax.named_scope("prefill"):
        return lm.prefill_extend(params, cfg, {"tokens": tokens}, prefix,
                                 max_seq=max_seq, prefix_len=prefix_len,
                                 length=length)


@functools.partial(jax.jit, static_argnames=("cfg", "n", "eos"))
def _decode_chunk(params, cfg: ModelConfig, tok, cache, budget, alive,
                  n: int, eos: int):
    """``n`` fused decode iterations with device-side retirement.

    Mirrors ``LLMEngine.step`` state evolution exactly: every iteration
    decodes all slots, budgets decrement for live slots, a live slot retires
    on exhausted budget or EOS (its ``kv_len`` zeroes and its next token
    resets, exactly like ``_release_slot``), and already-dead slots keep
    decoding garbage that nothing reads — so the chunk is bit-identical to
    ``n`` single steps when no admission happens in between. Emits one
    stacked (n, 3, B) int32 tensor (token, emitted-this-iter, retired-this-
    iter; an MoE pattern adds a fourth row of expert picks) so the caller
    needs a single device->host transfer per chunk.

    The scan body lives in ``serving.fleet.decode_chunk_body`` — the same
    code is vmapped over a node axis by ``fleet._cohort_decode_chunk`` so a
    whole cohort of engines decodes in one dispatch."""
    return decode_chunk_body(params, cfg, tok, cache, budget, alive, n, eos)


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    max_slots: int = 4
    max_seq: int = 128
    max_new_tokens: int = 16
    eos_token: int = -1            # -1: never (synthetic vocab)
    prefill_bucket: int = 32       # prompts padded up to a bucket multiple
    # paged prefix reuse (pure-attention patterns only)
    prefix_cache: bool = False
    block_size: int = 8            # tokens per KV block
    cache_blocks: int = 64         # pool capacity (blocks)


@dataclasses.dataclass
class _Slot:
    request_id: Optional[int] = None
    generated: List[int] = dataclasses.field(default_factory=list)
    budget: int = 0
    submit_step: int = 0       # engine step at submit()
    admit_step: int = 0        # engine step when the slot was granted
    first_token_step: int = 0  # engine step when prefill emitted token 0
    block_ids: List[int] = dataclasses.field(default_factory=list)
    prompt_tokens: int = 0     # prompt length at admission
    cached_tokens: int = 0     # whole-block prefix reused from the paged pool


class _LocalStore:
    """Engine-local device state (the standalone, non-fleet backing)."""

    __slots__ = ("cache", "next_token")

    def __init__(self, cache, next_token):
        self.cache = cache
        self.next_token = next_token


class LLMEngine:
    def __init__(self, cfg: ModelConfig, params, ecfg: EngineConfig,
                 timeline=NOOP_TIMELINE):
        """``timeline``: a ``repro.obs.Timeline`` for the admission spans
        (``engine.admit`` and its parts); the shared no-op by default."""
        self.cfg = cfg
        self.params = params
        self.ecfg = ecfg
        self.timeline = timeline
        # decode attention tiles the max_seq cache axis: a length it cannot
        # tile fails here, not mid-serve where it would pass for a node fault
        if any(m.startswith("attn") for m, _ in cfg.pattern):
            kops.check_decode_length(ecfg.max_seq)
        B = ecfg.max_slots
        # device state lives behind a store: engine-local arrays until a
        # fleet Cohort adopts this engine, a member view into the cohort's
        # stacked node-axis pytree afterwards (serving.fleet). The control
        # plane below never knows the difference.
        self._store = _LocalStore(lm.make_cache(cfg, B, ecfg.max_seq),
                                  jnp.zeros((B, 1), jnp.int32))
        self._fleet = None   # (Cohort, member index) once adopted
        self.slots = [_Slot() for _ in range(B)]
        self.queue: deque = deque()
        self.results: Dict[int, dict] = {}
        self._steps = 0
        self.host_syncs = 0   # device->host transfer count (decode path)
        self.decode_dispatches = 0   # jitted decode calls issued by THIS engine
        self.tokens_emitted = 0
        # bucketed prefill is exact where padding cannot reach the prompt's
        # rows or state: attention (causal), Mamba (state-neutral past the
        # true length) and dropless MoE; xLSTM integrates padding tokens
        # into its state, and MoE capacity (GShard-style drop) lets padding
        # tokens displace real tokens from expert slots
        self._bucket_ok = (ecfg.prefill_bucket > 0 and
                           all(m in ("attn", "mamba")
                               and (f != "moe" or cfg.moe.dropless)
                               for m, f in cfg.pattern))
        self.kv: Optional[PagedKVStore] = (
            PagedKVStore(cfg, ecfg.cache_blocks, ecfg.block_size)
            if ecfg.prefix_cache else None)

    # -- device-state views (local or fleet-backed) ---------------------------
    @property
    def cache(self):
        return self._store.cache

    @cache.setter
    def cache(self, value):
        self._store.cache = value

    @property
    def _next_token(self):
        return self._store.next_token

    @_next_token.setter
    def _next_token(self, value):
        self._store.next_token = value

    @property
    def fleet_ok(self) -> bool:
        """Fleet vectorization is exact only when batch rows are independent:
        a cohort dispatch may overrun a member's committed iterations
        (``n_f > n_eff``), mutating dead-slot rows the per-engine path never
        touched — invisible unless MoE expert capacity couples rows. A
        dropless expert layer keeps them independent."""
        return all(f != "moe" or self.cfg.moe.dropless
                   for _, f in self.cfg.pattern)

    def _attach_fleet(self, cohort, member: int) -> None:
        """Adopt this engine into a fleet cohort: device state moves into
        the cohort's stacked pytree (the cohort stacks it before calling
        this) and all reads/writes go through a member view."""
        self._fleet = (cohort, member)
        self._store = FleetMemberStore(cohort, member)
        if self.kv is not None and cohort.kv_pools is not None:
            # Cohort construction stacks the members' pools itself
            # (FleetKVPools.stack attaches them); a flushed store re-attaches
            # through flush_kv instead.
            pass
        self._sync_fleet_counters()

    def _sync_fleet_counters(self) -> None:
        if self._fleet is None:
            return
        cohort, m = self._fleet
        cohort.counters.active[m] = sum(
            s.request_id is not None for s in self.slots)
        cohort.counters.queued[m] = len(self.queue)

    def _decode(self, params, tok, cache):
        return _decode_one(params, self.cfg, tok, cache)

    # -- public API -----------------------------------------------------------
    def submit(self, request_id: int, tokens: np.ndarray,
               max_new_tokens: Optional[int] = None,
               extra: Optional[dict] = None) -> None:
        self.queue.append((request_id, np.asarray(tokens, np.int32),
                           max_new_tokens or self.ecfg.max_new_tokens,
                           extra or {}, self._steps))
        self._admit()
        self._sync_fleet_counters()

    def step(self) -> List[int]:
        """One decode iteration for all active slots. Returns retired ids."""
        active = [i for i, s in enumerate(self.slots) if s.request_id is not None]
        if not active:
            self._admit()
            return []
        logits, self.cache = self._decode(self.params, self._next_token,
                                          self.cache)
        nxt = np.asarray(jnp.argmax(logits, axis=-1), np.int32)
        self.host_syncs += 1
        self.decode_dispatches += 1
        self._next_token = jnp.asarray(nxt[:, None])
        retired = []
        for i in active:
            s = self.slots[i]
            tok = int(nxt[i])
            s.generated.append(tok)
            s.budget -= 1
            self.tokens_emitted += 1
            if s.budget <= 0 or tok == self.ecfg.eos_token:
                self.results[s.request_id] = self._result(s, self._steps + 1)
                retired.append(s.request_id)
                self._release_slot(i)
        self._steps += 1
        if retired:
            self._admit()
        return retired

    def step_n(self, n: int) -> List[int]:
        """Up to ``n`` fused decode iterations with ONE host transfer.

        Host-sync-free stepping: the whole chunk (decode, budget/EOS
        retirement masks, slot bookkeeping) runs device-side via
        ``_decode_chunk``'s ``lax.scan``; the host sees a single stacked
        (token, emitted, retired) tensor per chunk instead of one transfer
        per decoded token. Bit-identical to ``n`` consecutive ``step()``
        calls **when no admission is pending** — with queued work (which
        ``step()`` would admit into freed slots mid-chunk) or ``n <= 1``
        it falls back to a single ``step()``. The chunk is clipped to the
        largest active budget so it never decodes past all retirements.
        Returns all ids retired during the chunk."""
        if n <= 1 or self.queue:
            return self.step()
        active = [i for i, s in enumerate(self.slots)
                  if s.request_id is not None]
        if not active:
            self._admit()
            return []
        budgets = [s.budget if s.request_id is not None else 0
                   for s in self.slots]
        n_eff = min(n, max(budgets[i] for i in active))
        alive = np.asarray([s.request_id is not None for s in self.slots])
        tok, cache, outs = _decode_chunk(
            self.params, self.cfg, self._next_token, self.cache,
            jnp.asarray(budgets, jnp.int32), jnp.asarray(alive),
            n_eff, self.ecfg.eos_token)
        self._next_token = tok
        self.cache = cache
        outs = np.asarray(outs)               # (n_eff, 3, B) — one transfer
        self.host_syncs += 1
        self.decode_dispatches += 1
        return self._commit_chunk(ChunkWork(outs=outs, n_eff=n_eff,
                                            active=tuple(active)))

    def _commit_chunk(self, work: ChunkWork) -> List[int]:
        """Host-side half of a fused decode chunk: token append, budget and
        retirement bookkeeping for ``work.n_eff`` iterations. Shared by
        ``step_n`` (engine-local chunk) and ``fleet.Cohort.dispatch`` (this
        engine's slice of a whole-cohort chunk — the device state was
        already advanced in the stacked dispatch, so only the books move
        here). Admits queued work into freed slots exactly like ``step``."""
        toks, emits, retires = (work.outs[:, 0], work.outs[:, 1],
                                work.outs[:, 2])
        retired: List[int] = []
        for t in range(work.n_eff):
            for i in work.active:
                if not emits[t, i]:
                    continue
                s = self.slots[i]
                s.generated.append(int(toks[t, i]))
                s.budget -= 1
                self.tokens_emitted += 1
                if retires[t, i]:
                    self.results[s.request_id] = self._result(
                        s, self._steps + t + 1)
                    retired.append(s.request_id)
                    # device-side state (kv_len, next token) was already
                    # released inside the chunk
                    self._release_slot_host(i)
        self._steps += work.n_eff
        if retired:
            self._admit()
        return retired

    # -- disaggregated prefill/decode (KV handoff) ----------------------------
    def prefill_only(self, request_id: int, tokens: np.ndarray) -> List[int]:
        """Prefill-role half of a disaggregated request: compute (or reuse)
        the whole-block KV of ``tokens`` in the paged store **without**
        occupying a decode slot, and return the block ids pinned for export.

        The pins keep the blocks alive while the payload is in flight; the
        scheduler must pair every call with :meth:`release_export` (delivery
        or abort) or the blocks leak as permanently-active. May return fewer
        than ``len(tokens) // block_size`` blocks when the pool runs dry —
        the decode side simply re-prefills the uncovered tail, so a short
        export is still exact."""
        assert self.kv is not None, "prefill_only requires prefix_cache=True"
        tokens = np.asarray(tokens, np.int32)
        L = len(tokens)
        assert L <= self.ecfg.max_seq, "request exceeds engine max_seq"
        bs = self.kv.block_size
        st = self.kv.cache.stats
        # uncapped whole-block match: unlike decode admission we need no
        # suffix token here, a fully cached prompt exports with zero compute
        cached = self.kv.cache.index.match(tokens)
        self.kv.cache.acquire(cached)
        prefix_len = len(cached) * bs
        st.lookups += 1
        if cached:
            st.hits += 1
            st.hit_tokens += prefix_len
        st.prefill_tokens_total += L
        st.prefill_tokens_run += L - prefix_len
        n_whole = L // bs
        new_ids: List[int] = []
        if n_whole > len(cached):
            suffix = jnp.asarray(tokens[prefix_len:], jnp.int32)[None]
            if cached:
                _, cache1 = lm.prefill_extend(
                    self.params, self.cfg, {"tokens": suffix},
                    self.kv.gather(cached), max_seq=self.ecfg.max_seq)
            else:
                _, cache1 = lm.prefill(self.params, self.cfg,
                                       {"tokens": suffix},
                                       max_seq=self.ecfg.max_seq)
            for _ in range(len(cached), n_whole):
                bid = self.kv.cache.allocate()
                if bid is None:   # pool exhausted: export what we have
                    break
                new_ids.append(bid)
            if new_ids:
                self.kv.scatter(new_ids, len(cached), cache1.layer)
                n_tok = (len(cached) + len(new_ids)) * bs
                self.kv.cache.commit(tokens[:n_tok], cached + new_ids)
        return cached + new_ids

    def export_kv(self, block_ids: List[int]):
        """Host-copy the pinned blocks of a :meth:`prefill_only` result (the
        wire payload of the KV handoff)."""
        assert self.kv is not None
        return self.kv.export_blocks(block_ids)

    def release_export(self, block_ids: List[int]) -> None:
        """Drop the export pins: committed blocks become evictable-cached,
        uncommitted duplicates return to the free list. Refcounts return to
        their pre-handoff baseline."""
        if self.kv is not None and block_ids:
            self.kv.cache.release(block_ids)

    def import_kv(self, tokens: np.ndarray, slabs) -> bool:
        """Decode-role half of the handoff: land exported slabs covering the
        whole-block prefix ``tokens`` into this engine's pool and index them,
        so the next ``submit`` of the full prompt reuses them bit-identically
        (paged reuse is exact). Returns False — caller falls back to a full
        re-prefill — when this engine has no paged store or its pool cannot
        supply enough blocks."""
        if self.kv is None:
            return False
        tokens = np.asarray(tokens, np.int32)
        bs = self.kv.block_size
        n = len(tokens) // bs
        assert n * bs == len(tokens), "KV import must be whole-block"
        if n == 0:
            return False
        ids: List[int] = []
        for _ in range(n):
            bid = self.kv.cache.allocate()
            if bid is None:
                self.kv.cache.release(ids)   # uncommitted -> free list
                return False
            ids.append(bid)
        self.kv.import_blocks(ids, slabs)
        # commit keeps canonical blocks for chunks already indexed here; our
        # duplicates stay unindexed and free on release, new chunks become
        # evictable-cached — either way no pin outlives this call
        self.kv.cache.commit(tokens, ids)
        self.kv.cache.release(ids)
        return True

    def cancel(self, request_id: int) -> bool:
        """Abort a request wherever it currently lives (active slot or
        admission queue). Frees the slot immediately and admits queued work
        into it; produces no result entry. Returns True if found.

        Used by the cluster scheduler to retire the losing copy of a hedged
        request and to purge zombies from a crashed node's engine."""
        for i, s in enumerate(self.slots):
            if s.request_id == request_id:
                self._release_slot(i)
                self._admit()
                self._sync_fleet_counters()
                return True
        for k, item in enumerate(self.queue):
            if item[0] == request_id:
                del self.queue[k]
                self._sync_fleet_counters()
                return True
        return False

    def run_to_completion(self, max_iters: int = 10000,
                          chunk: int = 1) -> Dict[int, dict]:
        """Drain queue + slots. ``chunk > 1`` decodes via :meth:`step_n`
        whenever no admission is pending (one host sync per chunk)."""
        it = 0
        while (self.queue or any(s.request_id is not None
                                 for s in self.slots)):
            if chunk > 1:
                self.step_n(chunk)
            else:
                self.step()
            it += 1
            if it > max_iters:
                raise RuntimeError("engine did not drain")
        return self.results

    @property
    def active_count(self) -> int:
        return sum(s.request_id is not None for s in self.slots)

    @property
    def queue_len(self) -> int:
        return self.active_count + len(self.queue)

    def cache_stats(self) -> dict:
        """Prefix-cache counters (zeros when paged reuse is disabled)."""
        st = self.kv.cache.stats if self.kv is not None else CacheStats()
        return st.as_dict()

    def flush_kv(self) -> None:
        """Simulated node restart: drop every cached KV block. Active slots
        keep decoding — their contiguous caches own a gathered copy — but
        their block pins go down with the pool, so nothing dangles."""
        if self.kv is None:
            return
        for s in self.slots:
            s.block_ids = []
        self.kv = PagedKVStore(self.cfg, self.ecfg.cache_blocks,
                               self.ecfg.block_size)
        if self._fleet is not None:
            cohort, m = self._fleet
            if cohort.kv_pools is not None:
                # re-home the fresh store onto this member's slab slice
                # (copying the fresh zeros wipes the dead pool's bytes too)
                self.kv.attach(cohort.kv_pools, m)

    # -- internals -------------------------------------------------------------
    def _release_slot_host(self, i: int) -> None:
        """Host-side half of slot retirement: drop KV-block references and
        reset the slot record (``step_n`` chunks already performed the
        device-side release inside the scan)."""
        s = self.slots[i]
        if self.kv is not None and s.block_ids:
            self.kv.cache.release(s.block_ids)
        self.slots[i] = _Slot()
        self._sync_fleet_counters()

    def _release_slot(self, i: int) -> None:
        """Retire/cancel slot ``i``: drop its KV-block references and zero its
        ``kv_len`` so ``decode_step`` stops attending over the dead slot's KV
        (stale lengths previously kept streaming the dead cache until the
        slot's next reuse)."""
        self._release_slot_host(i)
        self.cache = self.cache._replace(
            kv_len=self.cache.kv_len.at[i].set(0))
        self._next_token = self._next_token.at[i, 0].set(0)

    def _result(self, s: "_Slot", finish_step: int) -> dict:
        n_decode = max(len(s.generated) - 1, 0)  # token 0 comes from prefill
        return {
            "tokens": list(s.generated),
            "n_steps": len(s.generated),
            "submit_step": s.submit_step,
            "first_token_step": s.first_token_step,
            "finish_step": finish_step,
            # admission wait: steps queued before a slot was granted. The
            # prefill emits token 0 at admission, so today it equals
            # ttft_steps; the two differ once admission stops emitting
            "queue_steps": s.admit_step - s.submit_step,
            "ttft_steps": s.first_token_step - s.submit_step,
            "tpot_steps": ((finish_step - s.first_token_step) / n_decode
                           if n_decode else 0.0),
            "decode_steps": finish_step - s.first_token_step,
            # realized prefix-cache reuse of this request's admission (the
            # per-request cache-hit observation the obs metrics ingest)
            "prompt_tokens": s.prompt_tokens,
            "cached_tokens": s.cached_tokens,
            "cached_frac": (s.cached_tokens / s.prompt_tokens
                            if s.prompt_tokens else 0.0),
        }

    def qoe_summary(self) -> dict:
        """Mean phase timings (in engine steps) over completed requests."""
        if not self.results:
            return {"avg_ttft_steps": 0.0, "avg_tpot_steps": 0.0}
        rs = list(self.results.values())
        return {"avg_ttft_steps": float(np.mean([r["ttft_steps"] for r in rs])),
                "avg_tpot_steps": float(np.mean([r["tpot_steps"] for r in rs]))}

    def _admit(self):
        while self.queue:
            free = [i for i, s in enumerate(self.slots) if s.request_id is None]
            if not free:
                break
            i = free[0]
            request_id, tokens, budget, extra, submit_step = self.queue.popleft()
            self._prefill_into(i, request_id, tokens, budget, extra,
                               submit_step)
        self._sync_fleet_counters()

    def _bucket_len(self, n: int) -> int:
        """Smallest prefill-bucket multiple >= n, capped at max_seq."""
        b = self.ecfg.prefill_bucket
        return min(-(-n // b) * b, self.ecfg.max_seq)

    def _prefill_into(self, slot: int, request_id: int, tokens: np.ndarray,
                      budget: int, extra: dict, submit_step: int = 0):
        """Admit one queued request into free ``slot``: prefill, splice its
        cache into the slot, read its first token. The ``engine.admit``
        span, with ``admit.prefill``, ``admit.splice`` and
        ``admit.first_token`` (the device sync that yields token 0)."""
        L = len(tokens)
        assert L + budget <= self.ecfg.max_seq, \
            "request exceeds engine max_seq"
        tl = self.timeline
        with tl.span("engine.admit", request_id):
            with tl.span("admit.prefill"):
                logits, cache1, block_ids, prefix_len = self._prefill(
                    tokens, extra)
            with tl.span("admit.splice"):
                self._splice(slot, cache1, L)
            with tl.span("admit.first_token"):
                first = int(jnp.argmax(logits[0]))
            s = self.slots[slot]
            s.request_id = request_id
            s.generated = [first]
            s.budget = budget - 1
            s.submit_step = submit_step
            s.admit_step = self._steps
            s.first_token_step = self._steps
            s.block_ids = block_ids
            s.prompt_tokens = L
            s.cached_tokens = prefix_len
            self._next_token = self._next_token.at[slot, 0].set(first)
            if s.budget <= 0:
                self.results[request_id] = self._result(s, self._steps)
                self._release_slot(slot)

    def _prefill(self, tokens: np.ndarray, extra: dict):
        """The prompt's prefill, reusing and extending the paged prefix
        cache where there is one. Returns ``(logits, single-request cache,
        block ids pinned, cached prefix length)``."""
        e = self.ecfg
        L = len(tokens)
        matched: List[int] = []
        if self.kv is not None:
            matched = self.kv.cache.match(tokens)
            self.kv.cache.acquire(matched)
        prefix_len = len(matched) * (self.kv.block_size if self.kv else 0)
        if prefix_len:
            suffix = tokens[prefix_len:]
            Sn = len(suffix)
            Sn_pad = self._bucket_len(Sn) if self._bucket_ok else Sn
            if self._bucket_ok and prefix_len + Sn_pad <= e.max_seq:
                # compile-once admission: suffix padded to the bucket,
                # prefix gathered at the fixed full-block budget — one
                # executable per suffix bucket instead of one per distinct
                # (matched-blocks, suffix-length) combination
                pad_blocks = e.max_seq // self.kv.block_size
                toks = np.zeros(Sn_pad, np.int32)
                toks[:Sn] = suffix
                logits, cache1 = _prefill_extend_bucketed(
                    self.params, self.cfg, jnp.asarray(toks)[None],
                    jnp.int32(Sn), self.kv.gather(matched, pad_to=pad_blocks),
                    jnp.int32(prefix_len), e.max_seq)
            else:
                logits, cache1 = lm.prefill_extend(
                    self.params, self.cfg,
                    {"tokens": jnp.asarray(suffix, jnp.int32)[None]},
                    self.kv.gather(matched), max_seq=e.max_seq)
        elif self._bucket_ok:
            # pad the prompt to the bucket; logits are read at the true last
            # row and kv_len masks the tail, so outputs match exact-length
            # prefill while all lengths in a bucket share one executable
            L_pad = self._bucket_len(L)
            toks = np.zeros(L_pad, np.int32)
            toks[:L] = tokens
            logits, cache1 = _prefill_bucketed(
                self.params, self.cfg, jnp.asarray(toks)[None],
                jnp.int32(L), e.max_seq)
        else:
            batch = {"tokens": jnp.asarray(tokens, jnp.int32)[None]}
            if self.cfg.family == "audio":
                batch["frames"] = jnp.asarray(
                    extra.get("frames",
                              np.zeros((1, self.cfg.encoder.n_frames,
                                        self.cfg.d_model), np.float32)),
                    jnp.bfloat16)
            if self.cfg.family == "vlm":
                batch["patches"] = jnp.asarray(
                    extra.get("patches",
                              np.zeros((1, self.cfg.cross_kv_tokens,
                                        self.cfg.d_model), np.float32)),
                    jnp.bfloat16)
            logits, cache1 = lm.prefill(self.params, self.cfg, batch,
                                        max_seq=e.max_seq)
        block_ids = matched
        if self.kv is not None:
            st = self.kv.cache.stats
            st.prefill_tokens_total += L
            st.prefill_tokens_run += L - prefix_len
            # cache the freshly prefilled whole-block suffix chunks. Start
            # past every chunk the index already holds, not just the capped
            # match: when the whole prompt is cached, match() drops the last
            # block to leave a suffix to prefill, and re-allocating (possibly
            # evicting a live leaf for) that chunk's duplicate would only be
            # thrown away by commit().
            cached = self.kv.cache.index.match(tokens)
            new_ids: List[int] = []
            for _ in range(len(cached), L // self.kv.block_size):
                bid = self.kv.cache.allocate()
                if bid is None:   # pool exhausted: serve uncached, no caching
                    break
                new_ids.append(bid)
            if new_ids:
                self.kv.scatter(new_ids, len(cached), cache1.layer)
                n_tok = (len(cached) + len(new_ids)) * self.kv.block_size
                self.kv.cache.commit(tokens[:n_tok], cached + new_ids)
            block_ids = matched + new_ids
        return logits, cache1, block_ids, prefix_len

    def _splice(self, slot: int, cache1, length: int) -> None:
        """Write a single-request cache into the batch cache at ``slot``."""
        def splice(full, one):
            if full.ndim < 2:
                return full
            return jax.lax.dynamic_update_slice_in_dim(full, one, slot, 1)

        self.cache = self.cache._replace(
            layer=jax.tree.map(splice, self.cache.layer, cache1.layer),
            cross=jax.tree.map(splice, self.cache.cross, cache1.cross),
            kv_len=self.cache.kv_len.at[slot].set(length),
        )
