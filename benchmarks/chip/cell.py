"""Finds a cell's files by name.

``BENCHMARK.json`` names each cell's configuration and traffic mix and lists
the metrics. Everything that belongs to one of them lives in a file of its
own, found here by that name:

* ``configs/<config>.json`` (the path ``BENCHMARK.json`` gives);
* ``traffic/<mix>.json``, read by ``traffic/generator.py``;
* ``metrics/<metric>.py``, a reader with ``read(rec) -> float | None``;
* ``peaks.json``, the chip's peaks keyed by JAX's ``device_kind``.

A later cell, mix, configuration or metric is added by adding files and
entries, never by editing this module.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
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
    return json.loads((REPO / entry["file"]).read_text())


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
    path = BENCH_DIR / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{metric.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def load_peak(device_kind: str) -> Dict:
    table = json.loads((BENCH_DIR / "peaks.json").read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"peaks.json (have {sorted(table)})")
    return table[device_kind]
