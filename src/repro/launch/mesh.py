"""Production mesh definitions.

``make_production_mesh`` is a function (never a module-level constant) so
importing this module touches no jax device state; the dry-run's ``main``
fabricates 512 host devices before jax initializes its backends.

Target hardware: TPU v5e pods — 256 chips/pod in a (16, 16) 2-D ICI torus;
multi-pod spans 2 pods over DCN. Axis roles:
  pod   — pure data parallelism across pods (gradient all-reduce over DCN)
  data  — data parallel + FSDP/ZeRO-3 parameter sharding (intra-pod ICI)
  model — tensor / expert parallelism (intra-pod ICI)
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

# v5e roofline constants (per chip) — used by benchmarks/roofline.py
PEAK_FLOPS_BF16 = 197e12      # FLOP/s
HBM_BW = 819e9                # bytes/s
ICI_BW_PER_LINK = 50e9        # bytes/s/link (~)
ICI_LINKS_2D = 4              # 2-D torus: 4 links/chip on v5e


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, (AxisType.Auto,) * len(axes))


def make_host_mesh(model: int = 2, data: int = None):
    """Small mesh over whatever devices exist (tests / examples)."""
    n = len(jax.devices())
    model = min(model, n)
    data = data or n // model
    return jax.make_mesh((data, model), ("data", "model"),
                         (AxisType.Auto,) * 2)
