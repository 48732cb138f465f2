"""Multi-pod dry-run (deliverable e).

For every (architecture × input-shape × mesh) cell: AOT-lower and compile the
appropriate step function (train_step / prefill_step / decode_step) against
ShapeDtypeStruct inputs on the production mesh, then record

  * memory_analysis()  — per-device argument/output/temp/peak bytes,
  * cost_analysis()    — HLO FLOPs / bytes accessed,
  * collective bytes   — parsed from the post-SPMD optimized HLO text,

into results/dryrun/<arch>__<shape>__<mesh>.json. These JSONs are the sole
input to benchmarks/roofline.py (§Roofline) and EXPERIMENTS.md §Dry-run.

``main`` fabricates 512 host devices before jax first initializes its
backends, and only in this entrypoint: importing the module changes nothing,
so tests and benches see the real devices.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch stablelm-3b \
      --shape train_4k --mesh single
  PYTHONPATH=src python -m repro.launch.dryrun --all [--mesh both]
"""

import argparse
import json
import re
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..configs import all_ids, get
from ..models import lm
from ..models import sharding as shard
from ..models.config import SHAPES, ModelConfig, cell_applicable
from ..training.optim import make_optimizer, optimizer_for_arch
from .mesh import make_production_mesh

RESULTS = Path(__file__).resolve().parents[3] / "results" / "dryrun"

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")


# ---------------------------------------------------------------------------
# input specs (ShapeDtypeStruct stand-ins, no allocation)
# ---------------------------------------------------------------------------

def input_specs(cfg: ModelConfig, shape_name: str, mesh) -> dict:
    """Abstract model inputs for a shape cell, with shardings attached."""
    cell = SHAPES[shape_name]
    B = cell.global_batch
    S = cell.seq_len
    # tp_friendly=False archs are pure-DP: batch shards over the whole mesh
    dp = shard.best_dp_prefix(mesh, B, full_dp=not cfg.tp_friendly)

    def sds(shp, dtype, spec):
        return jax.ShapeDtypeStruct(shp, dtype,
                                    sharding=NamedSharding(mesh, spec))

    if cell.kind == "train":
        batch = {"tokens": sds((B, S), jnp.int32, P(dp, None)),
                 "labels": sds((B, S), jnp.int32, P(dp, None))}
    elif cell.kind == "prefill":
        batch = {"tokens": sds((B, S), jnp.int32, P(dp, None))}
    else:  # decode: one new token against an S-deep cache
        batch = {"token": sds((B, 1), jnp.int32, P(dp, None))}
    if cfg.family == "audio" and cell.kind != "decode":
        batch["frames"] = sds((B, cfg.encoder.n_frames, cfg.d_model),
                              jnp.bfloat16, P(dp, None, None))
    if cfg.family == "vlm" and cell.kind != "decode":
        batch["patches"] = sds((B, cfg.cross_kv_tokens, cfg.d_model),
                               jnp.bfloat16, P(dp, None, None))
    return batch


def _abstract(tree):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)


def _with_sharding(tree, specs, mesh):
    return jax.tree.map(
        lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                          sharding=NamedSharding(mesh, s)),
        tree, specs, is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))


def opt_state_specs(opt_name: str, params_abs, param_specs):
    """PartitionSpecs for optimizer state, derived from param specs."""
    P0 = P()

    def last_drop(spec, p):
        axes = tuple(spec)[:max(0, p.ndim - 1)]
        return P(*axes)

    if opt_name == "adamw":
        return {"m": param_specs, "v": param_specs, "step": P0, "gnorm": P0}
    if opt_name == "adamw8bit":
        qspec = jax.tree.map(
            lambda s, p: {"q": s, "s": last_drop(s, p)},
            param_specs, params_abs,
            is_leaf=lambda s: isinstance(s, P))
        return {"m": qspec, "v": qspec, "step": P0, "gnorm": P0}
    if opt_name == "adafactor":
        def fac(s, p):
            axes = tuple(s) + (None,) * (p.ndim - len(tuple(s)))
            if p.ndim >= 2:
                return {"vr": P(*axes[:-1]),
                        "vc": P(*(axes[:-2] + (axes[-1],)))}
            return {"v": P(*axes)}
        return {"f": jax.tree.map(fac, param_specs, params_abs,
                                  is_leaf=lambda s: isinstance(s, P)),
                "step": P0, "gnorm": P0}
    raise ValueError(opt_name)


# ---------------------------------------------------------------------------
# step builders
# ---------------------------------------------------------------------------

def build_cell(cfg: ModelConfig, shape_name: str, mesh, unroll: bool = True):
    """Returns (fn, example_args (abstract, sharded), out_shardings, extra).

    unroll=True gives exact cost_analysis (every period materialized in HLO;
    XLA counts while bodies once — verified empirically); unroll=False is the
    production scan form whose memory_analysis reflects real loop buffer
    reuse. run_cell compiles both and records cost from the former, memory
    from the latter.
    """
    cell = SHAPES[shape_name]
    params_abs = jax.eval_shape(lambda k: lm.init(k, cfg), jax.random.key(0))
    pspecs_train = shard.param_specs(cfg, params_abs, mesh, mode="train")
    pspecs_serve = shard.param_specs(cfg, params_abs, mesh, mode="serve")
    batch = input_specs(cfg, shape_name, mesh)

    if cell.kind == "train":
        opt_name = optimizer_for_arch(cfg.param_counts()["total"])
        opt = make_optimizer(opt_name)
        state_abs = jax.eval_shape(opt.init, params_abs)
        sspecs = opt_state_specs(opt_name, params_abs, pspecs_train)

        def train_step(params, opt_state, batch):
            def loss(p):
                l, aux = lm.loss_fn(p, cfg, batch, unroll=unroll)
                return l, aux
            (l, aux), grads = jax.value_and_grad(loss, has_aux=True)(params)
            new_params, new_state = opt.update(grads, opt_state, params)
            return new_params, new_state, {"loss": l, **aux}

        args = (_with_sharding(params_abs, pspecs_train, mesh),
                _with_sharding(state_abs, sspecs, mesh), batch)
        out_shardings = (shard.to_shardings(mesh, pspecs_train),
                         shard.to_shardings(mesh, sspecs), None)
        return train_step, args, out_shardings, {"optimizer": opt_name}

    if cell.kind == "prefill":
        def prefill_step(params, batch):
            logits, cache = lm.prefill(params, cfg, batch, unroll=unroll)
            return logits, cache

        args = (_with_sharding(params_abs, pspecs_serve, mesh), batch)
        # let GSPMD choose cache/logit layouts from propagation
        return prefill_step, args, None, {}

    # decode
    B = cell.global_batch
    cache_abs = jax.eval_shape(
        lambda: lm.make_cache(cfg, B, cell.seq_len,
                              kv_len=jnp.full((B,), cell.seq_len - 1,
                                              jnp.int32)))
    cspecs = shard.cache_specs(cfg, cache_abs, mesh)

    def decode_step(params, cache, batch):
        return lm.decode_step(params, cfg, batch["token"], cache,
                              unroll=unroll)

    args = (_with_sharding(params_abs, pspecs_serve, mesh),
            _with_sharding(cache_abs, cspecs, mesh), batch)
    out_shardings = (None, shard.to_shardings(mesh, cspecs))
    return decode_step, args, out_shardings, {}


# ---------------------------------------------------------------------------
# analysis
# ---------------------------------------------------------------------------

def collective_bytes(hlo_text: str) -> dict:
    """Sum operand bytes of collective ops in post-SPMD optimized HLO."""
    out = {k: 0.0 for k in _COLLECTIVES}
    count = {k: 0 for k in _COLLECTIVES}
    # lines like:  %x = bf16[16,4096,320]{...} all-gather(...)
    shape_re = re.compile(r"\b(f32|bf16|f16|s32|u32|s8|u8|pred|f64|s64|u64)"
                          r"\[([0-9,]*)\]")
    for line in hlo_text.splitlines():
        stripped = line.strip()
        m = re.search(r"=\s*(?:\([^)]*\)|\S+)\s+(" + "|".join(_COLLECTIVES)
                      + r")\b", stripped)
        if not m:
            continue
        op = m.group(1)
        # result shapes approximate payload (operands ~= result for these ops)
        sm = shape_re.search(stripped)
        if sm is None:
            continue
        dtype, dims = sm.groups()
        bytes_per = {"f64": 8, "s64": 8, "u64": 8, "f32": 4, "s32": 4,
                     "u32": 4, "bf16": 2, "f16": 2, "s8": 1, "u8": 1,
                     "pred": 1}[dtype]
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        out[op] += float(n * bytes_per)
        count[op] += 1
    return {"bytes": out, "count": count,
            "total_bytes": float(sum(out.values()))}


def run_cell(arch: str, shape_name: str, mesh_kind: str,
             force: bool = False) -> dict:
    RESULTS.mkdir(parents=True, exist_ok=True)
    out_path = RESULTS / f"{arch}__{shape_name}__{mesh_kind}.json"
    if out_path.exists() and not force:
        return json.loads(out_path.read_text())

    cfg = get(arch).config()
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
           "family": cfg.family,
           "params_total": cfg.param_counts()["total"],
           "params_active": cfg.param_counts()["active"],
           "time": None, "status": None}

    ok, reason = cell_applicable(cfg, shape_name)
    if not ok:
        rec.update(status="skipped", reason=reason)
        out_path.write_text(json.dumps(rec, indent=1))
        return rec

    t0 = time.time()
    try:
        import dataclasses as _dc
        mesh = make_production_mesh(multi_pod=(mesh_kind == "multipod"))
        n_chips = int(np.prod(list(mesh.shape.values())))

        def compile_variant(cfg_v, unroll):
            fn, args, out_shardings, extra = build_cell(
                cfg_v, shape_name, mesh, unroll=unroll)
            rec.update(extra)
            with shard.activation_mesh(
                    mesh, full_dp=not cfg.tp_friendly), mesh:
                jitted = (jax.jit(fn, out_shardings=out_shardings)
                          if out_shardings is not None else jax.jit(fn))
                return jitted.lower(*args).compile()

        def costs_of(compiled):
            c = compiled.cost_analysis() or {}
            coll = collective_bytes(compiled.as_text())
            return {"flops": float(c.get("flops", 0.0)),
                    "bytes": float(c.get("bytes accessed", 0.0)),
                    "coll": coll}

        # 1) production scan form, full depth: memory analysis (loop
        #    buffers are reused, matching real execution)
        compiled_scan = compile_variant(cfg, False)
        mem = compiled_scan.memory_analysis()

        # 2) cost analysis: XLA counts while bodies once, so costs come from
        #    *unrolled* programs. Unrolling the full depth is prohibitive for
        #    the big MoE archs, but periods are homogeneous, so costs are
        #    exactly linear in the period count: compile unrolled 2- and
        #    4-period variants and extrapolate
        #        total(n) = c2 + (n - 2) · (c4 - c2) / 2.
        plen = len(cfg.pattern)
        n_per = cfg.n_periods
        if n_per <= 4:
            cu = costs_of(compile_variant(cfg, True))
            flops, bytes_acc = cu["flops"], cu["bytes"]
            coll = cu["coll"]
        else:
            c2 = costs_of(compile_variant(
                _dc.replace(cfg, n_layers=2 * plen), True))
            c4 = costs_of(compile_variant(
                _dc.replace(cfg, n_layers=4 * plen), True))
            # guard: XLA occasionally optimizes the 4-period program below
            # the 2-period one (cross-period CSE); clamp the per-period slope
            # at zero so the extrapolation never goes negative
            lin = lambda a2, a4: a2 + (n_per - 2) * max((a4 - a2) / 2.0, 0.0)
            flops = lin(c2["flops"], c4["flops"])
            bytes_acc = lin(c2["bytes"], c4["bytes"])
            coll = {
                "bytes": {k: lin(c2["coll"]["bytes"][k], c4["coll"]["bytes"][k])
                          for k in c2["coll"]["bytes"]},
                "count": {k: int(lin(c2["coll"]["count"][k],
                                     c4["coll"]["count"][k]))
                          for k in c2["coll"]["count"]},
                "total_bytes": lin(c2["coll"]["total_bytes"],
                                   c4["coll"]["total_bytes"]),
            }
        rec.update(
            status="ok",
            n_chips=n_chips,
            flops=flops,
            bytes_accessed=bytes_acc,
            flops_scan=float((compiled_scan.cost_analysis() or {})
                             .get("flops", -1.0)),
            memory={
                "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
                "output_bytes": getattr(mem, "output_size_in_bytes", None),
                "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
                "peak_bytes": getattr(mem, "peak_memory_in_bytes", None),
            },
            collectives=coll,
            time=time.time() - t0,
        )
        print(mem)
        print({"flops": flops, "bytes accessed": bytes_acc})
    except Exception as e:  # noqa: BLE001 — record the failure, keep sweeping
        rec.update(status="error", error=f"{type(e).__name__}: {e}",
                   trace=traceback.format_exc()[-4000:],
                   time=time.time() - t0)
    out_path.write_text(json.dumps(rec, indent=1))
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(SHAPES) + [None])
    ap.add_argument("--mesh", default="single",
                    choices=["single", "multipod", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()
    jax.config.update("jax_num_cpu_devices", 512)

    archs = all_ids() if args.all or args.arch is None else [args.arch]
    shapes = list(SHAPES) if args.all or args.shape is None else [args.shape]
    meshes = (["single", "multipod"] if args.mesh == "both"
              else [args.mesh])

    failures = 0
    for arch in archs:
        for shape_name in shapes:
            for mesh_kind in meshes:
                rec = run_cell(arch, shape_name, mesh_kind, force=args.force)
                status = rec["status"]
                msg = rec.get("error", rec.get("reason", ""))[:100]
                t = rec.get("time")
                print(f"[{status:7s}] {arch:28s} {shape_name:12s} "
                      f"{mesh_kind:8s} {t and f'{t:6.1f}s' or '':8s} {msg}",
                      flush=True)
                failures += status == "error"
    raise SystemExit(1 if failures else 0)


if __name__ == "__main__":
    main()
