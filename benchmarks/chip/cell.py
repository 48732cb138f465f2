"""Finds a cell's files by name.

``BENCHMARK.json`` names each cell's configuration and traffic mix and lists
the metrics. Everything that belongs to one of them lives in a file of its
own, found here by that name:

* ``configs/<config>.json`` (the path ``BENCHMARK.json`` gives), whose
  every entry under ``architectures`` names its ``"kind"``;
* ``archs/<kind>.py``, the code of one kind of served architecture:
  ``model_config(name, sizes)``, the program's config; ``LEAVES``, a rule
  ``(z, shape) -> value`` for each parameter leaf name it adds beyond
  ``table``, ``w`` and ``g`` (``deploy.make_params``); ``forward(params,
  sizes, tokens, mode)``, its plain reference (``reference.py``); and
  ``prefill_flops``, ``decode_flops`` and ``decode_attention_cost``, its
  counts (``flops.py``);
* ``traffic/<mix>.json``, read by ``traffic/generator.py``;
* ``metrics/<metric>.py``, a reader with ``read(rec) -> float | None``;
* ``peaks.json``, the chip's peaks keyed by JAX's ``device_kind``.

A later cell, mix, configuration, kind or metric is added by adding files
and entries, never by editing this module.
"""
from __future__ import annotations

import dataclasses
import functools
import importlib.util
import json
import re
from pathlib import Path
from types import ModuleType
from typing import Callable, Dict, List, Optional

BENCH_DIR = Path(__file__).resolve().parent
REPO = BENCH_DIR.parents[1]


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict
    traffic: Dict
    end_to_end: List[Dict]
    per_layer: List[Dict]


def load_benchmark() -> Dict:
    return json.loads((REPO / "BENCHMARK.json").read_text())


def applies(metric: Dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_config(name: str, bench: Dict) -> Dict:
    entry = next(c for c in bench["configs"] if c["name"] == name)
    path = REPO / entry["file"]
    config = json.loads(path.read_text())
    kinds_of(config["architectures"], str(path))
    return config


@functools.lru_cache(maxsize=None)
def _module(path: Path) -> ModuleType:
    """One module object per file, so that a jitted function that takes one
    of its functions as a static argument compiles once per process."""
    stem = path.stem.replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(
        f"bench_{path.parent.name}_{stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_kind(kind: str) -> ModuleType:
    """The module ``archs/<kind>.py``; a name that is not a file there
    raises, naming the path."""
    path = BENCH_DIR / "archs" / f"{kind}.py"
    if not re.fullmatch(r"[A-Za-z0-9_][A-Za-z0-9_.-]*", kind) \
            or not path.is_file():
        raise FileNotFoundError(f"no module {path} for kind {kind!r}")
    return _module(path)


def kinds_of(archs: Dict[str, Dict], where: str) -> Dict[str, ModuleType]:
    """The kind module of each served architecture; ``where`` names the
    sizes' origin in the error raised for an entry that names no kind, or a
    kind with no module."""
    out = {}
    for name, sizes in archs.items():
        if "kind" not in sizes:
            raise ValueError(f"{where}: architecture {name!r} names no "
                             f"\"kind\" (the module archs/<kind>.py that "
                             f"builds it)")
        try:
            out[name] = load_kind(sizes["kind"])
        except FileNotFoundError as e:
            raise FileNotFoundError(f"{where}: architecture {name!r}: {e}") \
                from None
    return out


def load_traffic(name: str) -> Dict:
    return json.loads((BENCH_DIR / "traffic" / f"{name}.json").read_text())


def load_cell(name: str, bench: Optional[Dict] = None) -> Cell:
    bench = bench or load_benchmark()
    matches = [w for w in bench["workloads"] if w["name"] == name]
    if not matches:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                       f"{[w['name'] for w in bench['workloads']]}")
    w = matches[0]
    return Cell(name=name, chips=int(w["chips"]),
                config=load_config(w["config"], bench),
                traffic=load_traffic(w["traffic"]),
                end_to_end=[m for m in bench["end_to_end"]
                            if applies(m, name)],
                per_layer=[m for m in bench["per_layer"] if applies(m, name)])


def load_reader(metric: str) -> Callable[[Dict], Optional[float]]:
    return _module(BENCH_DIR / "metrics" / f"{metric}.py").read


def load_peak(device_kind: str) -> Dict:
    table = json.loads((BENCH_DIR / "peaks.json").read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"peaks.json (have {sorted(table)})")
    return table[device_kind]
