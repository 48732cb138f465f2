"""From a profiler trace to the numbers the per-layer metrics read.

``load_xplane`` keeps three kinds of events from a ``.xplane.pb`` file, each
``(kind, name, start_ns, duration_ns)`` on the profiler's clock:

* ``op``: an operation on a TPU (the device planes' ``XLA Ops`` line);
* ``module``: a whole executable on a TPU (``XLA Modules``), named by its
  jit, such as ``jit__cohort_decode_chunk(...)``;
* ``host``: a span the harness wrote with ``jax.profiler.TraceAnnotation``,
  named ``bench.<span>``.

``reduce`` turns them into the busy union, device time per operation and
per executable, and the idle gaps, each named by the innermost harness span
open when it happened (``none`` where no span was open).
"""
from __future__ import annotations

from collections import defaultdict
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

Event = Tuple[str, str, int, int]
HOST_PREFIX = "bench."
WINDOW_SPAN = "bench.window"


def find_xplane(logdir) -> Optional[Path]:
    found = sorted(Path(logdir).rglob("*.xplane.pb"))
    return found[-1] if found else None


def load_xplane(path) -> List[Event]:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(str(path))
    out: List[Event] = []
    for plane in data.planes:
        device = "TPU" in plane.name and plane.name.startswith("/device")
        for line in plane.lines:
            kind = None
            if device and line.name == "XLA Ops":
                kind = "op"
            elif device and line.name == "XLA Modules":
                kind = "module"
            for ev in line.events:
                if kind is not None:
                    out.append((kind, op_name(ev.name), int(ev.start_ns),
                                int(ev.duration_ns)))
                elif not device and ev.name.startswith(HOST_PREFIX):
                    out.append(("host", ev.name, int(ev.start_ns),
                                int(ev.duration_ns)))
    return out


def op_name(name: str) -> str:
    """A TPU op's trace name is its whole HLO instruction
    (``%fusion.3 = bf16[...] fusion(...), ...``); keep its name."""
    return name.split(" = ", 1)[0].lstrip("%")


def union(intervals: Sequence[Tuple[int, int]]) -> List[Tuple[int, int]]:
    """Merge (start, end) intervals into disjoint sorted ones."""
    merged: List[List[int]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def _clip(s: int, e: int, lo: int, hi: int):
    s, e = max(s, lo), min(e, hi)
    return (s, e) if e > s else None


def reduce(events: Sequence[Event]) -> Optional[Dict]:
    """Returns None where the trace holds no device operation."""
    ops = [(n, s, s + d) for k, n, s, d in events if k == "op"]
    if not ops:
        return None
    hosts = [(n[len(HOST_PREFIX):], s, s + d)
             for k, n, s, d in events if k == "host"]
    win = [(s, e) for n, s, e in hosts if "bench." + n == WINDOW_SPAN]
    if win:
        lo, hi = win[0]
    else:
        lo, hi = min(s for _, s, _ in ops), max(e for _, _, e in ops)
    spans = [(n, s, e) for n, s, e in hosts if "bench." + n != WINDOW_SPAN]

    op_time: Dict[str, float] = defaultdict(float)
    busy_in: List[Tuple[int, int]] = []
    for n, s, e in ops:
        c = _clip(s, e, lo, hi)
        if c:
            op_time[n] += (c[1] - c[0]) * 1e-9
            busy_in.append(c)
    busy = union(busy_in)
    busy_ns = sum(e - s for s, e in busy)

    modules: Dict[str, List[float]] = defaultdict(list)
    for k, n, s, d in events:
        if k == "module" and lo <= s and s + d <= hi:
            modules[n].append(d * 1e-9)

    gaps = []
    cursor = lo
    for s, e in busy + [(hi, hi)]:
        if s > cursor:
            gaps.append((cursor, s))
        cursor = max(cursor, e)
    named = []
    for s, e in gaps:
        mid = (s + e) // 2
        open_ = [(se - ss, n) for n, ss, se in spans if ss <= mid < se]
        named.append((min(open_)[1] if open_ else "none", (e - s) * 1e-9))
    by_span: Dict[str, float] = defaultdict(float)
    for n, d in named:
        by_span[n] += d
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": busy_ns * 1e-9,
        "op_time": dict(op_time),
        "modules": dict(modules),
        "gaps": named,
        "idle_by_span": dict(by_span),
    }


def breakdown(red: Dict, top: int = 10) -> Dict:
    """The device operations that took most time, and idle time by what the
    host was doing, at most ``top`` entries each."""
    ops = sorted(red["op_time"].items(), key=lambda kv: -kv[1])[:top]
    idle = sorted(red["idle_by_span"].items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[n, t] for n, t in ops],
            "idle_gaps": [[n, t] for n, t in idle]}
