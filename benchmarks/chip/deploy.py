"""Builds a configuration's deployment: weights from the seed, the router's
genome, the ``ClusterServer``, and a warm-up of every shape the window uses.

Both configurations route with the threshold policy at the paper's default
genome. Each served architecture's kind (``archs/<kind>.py``) gives the
program's config for its sizes. The weights are the benchmark's, not the
program's: one jitted call per model makes every leaf of the program's
parameter layout on the device, in the type it is served in, from
``--seed``. The reference reads the same arrays.
"""
from __future__ import annotations

import dataclasses
import math
from types import ModuleType
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.cluster.spec import ClusterSpec, paper_testbed
from repro.core.policies.threshold import PAPER_DEFAULTS
from repro.models import lm
from repro.models.config import ModelConfig
from repro.serving import ClusterServer, EngineConfig, ServeRequest
from repro.workload.datasets import Request

from benchmarks.chip.cell import kinds_of
from benchmarks.chip.traffic import generator

TESTBEDS = {"paper_testbed": paper_testbed}
WARM_ID = 1 << 40     # warm-up request ids start here, clear of the window's


def seed_key(seed: int):
    """A PRNG key for any non-negative seed, wider than 32 bits included."""
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)


def make_params(cfg: ModelConfig, key, kind: ModuleType):
    """Every leaf of ``lm.init``'s layout, made in one jitted call: an
    embedding or output table N(0, 0.02^2), a weight matrix N(0, 1/fan_in),
    a norm gain 1 + N(0, 0.1^2), and any other leaf by the kind's rule for
    its name (``kind.LEAVES``), each from the same N(0, 1) draw."""
    shapes = jax.eval_shape(lambda k: lm.init(k, cfg), jax.random.key(0))
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def fill(key):
        out = []
        for i, (path, sd) in enumerate(leaves):
            name = path[-1].key
            z = jax.random.normal(jax.random.fold_in(key, i), sd.shape,
                                  jnp.float32)
            if name == "table":
                v = z * 0.02
            elif name == "w":
                v = z / math.sqrt(sd.shape[-2])
            elif name == "g":
                v = 1.0 + 0.1 * z
            elif name in kind.LEAVES:
                v = kind.LEAVES[name](z, sd)
            else:
                raise ValueError(f"unknown parameter leaf {path}")
            out.append(v.astype(sd.dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(fill)(key)


def to_request(p: generator.Prompt) -> Request:
    return Request(dataset=p.dataset, index=p.index, text=p.text,
                   prompt_tokens=p.prompt_tokens, query_bytes=p.query_bytes,
                   resp_tokens_mean=p.resp_tokens_mean,
                   difficulty=p.difficulty, sentence_count=p.sentence_count,
                   has_constraint=p.has_constraint)


def cluster_of(spec: Dict) -> ClusterSpec:
    tb = TESTBEDS[spec["testbed"]]()
    return ClusterSpec(
        nodes=tuple(n for n in tb.nodes if n.name in spec["nodes"]),
        models=tuple(m for m in tb.models if m.name in spec["models"]))


@dataclasses.dataclass
class Deployment:
    srv: ClusterServer
    archs: Dict[str, Dict]          # served architecture -> sizes
    kinds: Dict[str, ModuleType]    # served architecture -> archs/<kind>.py
    params: Dict[str, object]       # served architecture -> weights
    pair_arch: Dict[int, str]       # routable pair -> architecture
    captured: Dict                  # (pair, request id) -> prompt token ids


def build(config: Dict, seed: int,
          archs: Optional[Dict[str, Dict]] = None) -> Deployment:
    """``archs`` replaces the file's sizes (tests pass small ones)."""
    archs = archs or config["architectures"]
    kinds = kinds_of(archs, f"configuration {config['name']!r}")
    cluster = cluster_of(config["cluster"])
    key = seed_key(seed)
    cfgs, params = {}, {}
    for i, name in enumerate(sorted(archs)):
        cfgs[name] = kinds[name].model_config(name, archs[name])
        params[name] = make_params(cfgs[name], jax.random.fold_in(key, i),
                                   kinds[name])
    builders = {m: (cfgs[a], params[a])
                for m, a in config["served_by"].items()}

    if config["router"] != {"policy": "threshold",
                            "genome": "paper_defaults"}:
        raise ValueError(f"unknown router settings {config['router']}")
    e = config["engine"]
    srv = ClusterServer(cluster, builders, PAPER_DEFAULTS,
                        EngineConfig(max_slots=e["max_slots"],
                                     max_seq=e["max_seq"]),
                        fleet=True)
    pair_arch = {p: config["served_by"][cluster.models[k].name]
                 for p, (_, k) in enumerate(cluster.pairs())}
    dep = Deployment(srv=srv, archs=archs, kinds=kinds, params=params,
                     pair_arch=pair_arch, captured={})
    for p, eng in srv.engines.items():
        eng.submit = _capturing(eng.submit, dep.captured, p)
    return dep


def _capturing(submit, captured: Dict, pair: int):
    """Records the prompt token ids an engine is handed (its public
    ``submit``), so the reference reads the prompt that was served."""
    def wrapped(request_id, tokens, *args, **kwargs):
        captured[(pair, request_id)] = np.asarray(tokens, np.int32).copy()
        return submit(request_id, tokens, *args, **kwargs)
    return wrapped


def _drain(srv: ClusterServer, engines: List, ids: List[int],
           limit: int = 400) -> None:
    for _ in range(limit):
        if all(rid in e.results for e, rid in zip(engines, ids)) \
                and not srv.inflight:
            return
        srv.step()
    raise RuntimeError("warm-up requests did not finish")


def warm_up(dep: Deployment, prompts: List[generator.Prompt]) -> None:
    """Compile (or load from the cache) every program the window runs:
    admission at the prompt bucket, each cohort decode at every participant
    bucket it can reach, and the routed path through ``submit``."""
    srv = dep.srv
    by_arch: Dict[str, List[int]] = {}
    for p in sorted(srv.engines):
        by_arch.setdefault(dep.pair_arch[p], []).append(p)
    rid = WARM_ID
    counts = sorted({min(1 << i, len(ps)) for ps in by_arch.values()
                     for i in range(len(ps).bit_length() + 1)})
    for k in counts:
        engines, ids = [], []
        for arch, pairs in by_arch.items():
            vocab = dep.archs[arch]["vocab"]
            for p in pairs[:min(k, len(pairs))]:
                tokens = (np.arange(24) * 7919 + rid) % vocab
                srv.engines[p].submit(rid, tokens, max_new_tokens=3)
                engines.append(srv.engines[p])
                ids.append(rid)
                rid += 1
        _drain(srv, engines, ids)
    for prompt in prompts:
        srv.submit(ServeRequest(request_id=rid, req=to_request(prompt),
                                max_new_tokens=3))
        rid += 1
    srv.run()
    jax.effects_barrier()
