"""The program's own spans and named device scopes, read from the run.

A server built with a ``repro.obs.Timeline`` records host spans and counters
inside ``submit`` and ``step`` (``repro.obs.timeline``), and while the
profiler runs each span is also a ``repro.<span>`` annotation in the
``.xplane.pb``. The device ops carry the ``jax.named_scope`` path of the code
that made them (``cohort.gather``, ``decode.step``, ``attn.kernel``, ...) as
their ``op_name`` metadata, in the HLO of each executable that the
profiler keeps beside the trace.
This module turns both into what the program's per-layer readers take,
``rec["program"]`` (``program_record``):

* ``spans``, ``counters``, ``dropped``: the timeline's snapshot over the
  window;
* ``scopes``: device self time per (executable, innermost scope), where an
  op's self time is its duration minus what the ops nested inside it cover,
  so a ``while`` and the body it runs are not counted twice;
* ``idle_by_span``: device idle time by the innermost ``repro.`` span open at
  each gap's midpoint (``none`` where none was);
* ``longest_call``: for the longest ``server.submit`` or ``server.step``
  span of the window, its descendants' self times and the garbage
  collections inside it.

The last two are what a traced run prints on its ``program:`` line. The
existing reduction (``trace_reduce``) is left as it is: nothing here changes
what an existing reader sees.
"""
from __future__ import annotations

import bisect
from collections import defaultdict
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from repro.obs.timeline import PREFIX, SCOPE_NAMES, SpanRecord, self_times

from benchmarks.chip.trace_reduce import WINDOW_SPAN, op_name, union

CALLS = ("server.submit", "server.step")
UNSCOPED = "unscoped"
# a copy XLA makes of an argument (a relayout, or the copy a functional
# update of an argument that is not donated needs) carries the argument's
# name, such as ``state.cache.layer[0][0]``, in place of a jit path
ARGUMENT = "argument"

# (line, HLO name, executable, scope, start, end)
Op = Tuple[int, str, str, str, int, int]


def scope_of(path: str) -> str:
    """The innermost named scope in an op_name path; ``argument`` for a
    copy of an argument, ``unscoped`` for anything else."""
    parts = path.split("/")
    for part in reversed(parts):
        if part in SCOPE_NAMES:
            return part
    if path and "(" not in parts[0]:
        return ARGUMENT
    return UNSCOPED


def _varint(buf, pos: int) -> Tuple[int, int]:
    shift = out = 0
    while True:
        b = buf[pos]
        pos += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, pos
        shift += 7


def _fields(buf):
    """The (field number, value) pairs of one protobuf message: integers
    for varints, byte slices for everything else."""
    pos, end = 0, len(buf)
    while pos < end:
        key, pos = _varint(buf, pos)
        wire = key & 7
        if wire == 0:
            v, pos = _varint(buf, pos)
        elif wire == 2:
            n, pos = _varint(buf, pos)
            v, pos = buf[pos:pos + n], pos + n
        else:
            n = {1: 8, 5: 4}[wire]
            v, pos = buf[pos:pos + n], pos + n
        yield key >> 3, v


def _text(v) -> str:
    return bytes(v).decode("utf-8", "replace")


def _ints(v) -> List[int]:
    """A repeated integer field's value: one varint, or a packed run."""
    if isinstance(v, int):
        return [v]
    out, pos = [], 0
    while pos < len(v):
        x, pos = _varint(v, pos)
        out.append(x)
    return out


def _map_values(buf) -> List:
    return [v for k, v in _fields(buf) if k == 2]


def _hlo_paths(hlo) -> Dict[str, str]:
    """HLO instruction name -> its op_name, from a serialized HloProto
    (hlo_module 1; HloModuleProto computations 3; HloComputationProto
    instructions 2; HloInstructionProto name 1, metadata 7, id 35,
    operand_ids 36; OpMetadata op_name 2). An instruction without an
    op_name of its own, such as a copy XLA inserted, takes its first
    operand's."""
    insts: Dict[int, Tuple[str, str, List[int]]] = {}
    for f, module in _fields(hlo):
        for g, comp in (_fields(module) if f == 1 else ()):
            for h, ins in (_fields(comp) if g == 3 else ()):
                if h != 2:
                    continue
                name = path = ""
                iid, operands = 0, []
                for k, v in _fields(ins):
                    if k == 1:
                        name = _text(v)
                    elif k == 7:
                        path = _text(dict(_fields(v)).get(2, b""))
                    elif k == 35:
                        iid = v
                    elif k == 36:
                        operands += _ints(v)
                insts[iid] = (name, path, operands)
    out: Dict[str, str] = {}
    for name, path, operands in insts.values():
        hops = 0
        while not path and operands and hops < len(insts):
            _, path, operands = insts.get(operands[0], ("", "", []))
            hops += 1
        out[name] = path
    return out


def _event_metadata(plane_fields) -> List[Tuple[str, str, Dict]]:
    """(name, display name, stats by name) of each event metadata entry of
    one XPlane. Fields read: XPlane event_metadata 4, stat_metadata 5;
    XEventMetadata name 2, display_name 4, stats 5; XStatMetadata id 1,
    name 2; XStat metadata_id 1, uint64 3, int64 4, str 5, bytes 6,
    ref 7 (a ref names a stat metadata entry whose name is the value)."""
    names: Dict[int, str] = {}
    for f, v in plane_fields:
        for m in (_map_values(v) if f == 5 else ()):
            md = dict(_fields(m))
            names[md.get(1, 0)] = _text(md.get(2, b""))
    out = []
    for f, v in plane_fields:
        for m in (_map_values(v) if f == 4 else ()):
            name = display = ""
            stats: Dict[str, object] = {}
            for g, w in _fields(m):
                if g == 2:
                    name = _text(w)
                elif g == 4:
                    display = _text(w)
                elif g == 5:
                    st = dict(_fields(w))
                    key = names.get(st.get(1, 0), "")
                    if 5 in st:
                        stats[key] = _text(st[5])
                    elif 7 in st:
                        stats[key] = names.get(st[7], "")
                    else:
                        stats[key] = st.get(6, st.get(3, st.get(4, 0)))
            out.append((name, display, stats))
    return out


def op_paths(path) -> Dict[Tuple[int, str], str]:
    """(program id, HLO instruction name) -> the op's op_name path. The TPU
    profiler keeps it as the ``tf_op`` stat of each op's event metadata in
    the raw ``.xplane.pb`` (which ``ProfileData`` does not show); an op
    XLA deduplicated takes its original's. An op with none, such as a copy
    XLA inserted, takes its path from the executable's HLO, which the
    ``/host:metadata`` plane keeps as an ``Hlo Proto`` stat (``_hlo_paths``).
    XSpace planes are field 1, an XPlane's name field 2."""
    data = memoryview(Path(path).read_bytes())
    hlo: Dict[Tuple[int, str], str] = {}
    ops = []
    for k, plane in _fields(data):
        if k != 1:
            continue
        fields = list(_fields(plane))
        name = next((_text(v) for f, v in fields if f == 2), "")
        if name == "/host:metadata":
            for ev_name, _, stats in _event_metadata(fields):
                proto = stats.get("Hlo Proto")
                if isinstance(proto, memoryview):
                    pid = program_id(ev_name)
                    for inst, p in _hlo_paths(proto).items():
                        hlo[(pid, inst)] = p
        elif name.startswith("/device") and "TPU" in name:
            ops += [(int(stats.get("program_id", 0)) % (1 << 64), display,
                     str(stats.get("tf_op", "")).rstrip(":"),
                     str(stats.get("deduplicated_name", "")))
                    for _, display, stats in _event_metadata(fields)]
    own = {(pid, n): p for pid, n, p, _ in ops if p}
    found = {(pid, n): own.get((pid, n)) or own.get((pid, orig))
             or hlo.get((pid, n)) for pid, n, _, orig in ops}
    return {k: p for k, p in found.items() if p}


def module_name(name: str) -> str:
    """``jit__cohort_decode_chunk(12)`` -> ``jit__cohort_decode_chunk``."""
    return op_name(name).split("(", 1)[0]


def program_id(name: str) -> int:
    """``jit__cohort_decode_chunk(12)`` -> 12; -1 without one."""
    inner = op_name(name).partition("(")[2].rstrip(")")
    return int(inner) if inner.isdigit() else -1


def load(path) -> Dict:
    """``ops`` (``Op``) and ``modules`` (name, start, end) of the TPU
    planes, ``spans`` (name, start, end) of the ``repro.`` and
    ``bench.window`` host annotations; all in ns on the profiler's clock.
    An op belongs to the executable running when it starts, and its scope
    is looked up there (``op_paths``)."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(str(path))
    raw_ops = []
    modules: List[Tuple[str, int, int]] = []
    pids: List[int] = []
    spans: List[Tuple[str, int, int]] = []
    line_ids: Dict[Tuple[str, str], int] = {}
    for plane in data.planes:
        device = "TPU" in plane.name and plane.name.startswith("/device")
        for line in plane.lines:
            if device and line.name == "XLA Ops":
                lid = line_ids.setdefault((plane.name, line.name),
                                          len(line_ids))
                for ev in line.events:
                    s = int(ev.start_ns)
                    raw_ops.append((lid, ev.name, s,
                                    s + int(ev.duration_ns)))
            elif device and line.name == "XLA Modules":
                for ev in line.events:
                    s = int(ev.start_ns)
                    modules.append((module_name(ev.name), s,
                                    s + int(ev.duration_ns)))
                    pids.append(program_id(ev.name))
            elif not device:
                for ev in line.events:
                    if ev.name.startswith(PREFIX) or ev.name == WINDOW_SPAN:
                        s = int(ev.start_ns)
                        spans.append((ev.name, s, s + int(ev.duration_ns)))
    paths = op_paths(path) if raw_ops else {}
    order = sorted(range(len(modules)), key=lambda i: modules[i][1])
    starts = [modules[i][1] for i in order]
    ops: List[Op] = []
    for lid, name, s, e in raw_ops:
        k = bisect.bisect_right(starts, s) - 1
        m = order[k] if k >= 0 and s < modules[order[k]][2] else -1
        short = op_name(name)
        ops.append((lid, short, modules[m][0] if m >= 0 else "none",
                    scope_of(paths.get((pids[m] if m >= 0 else -1, short),
                                       "")), s, e))
    return {"ops": ops, "modules": modules, "spans": spans}


def op_self_times(ops: Sequence[Op]) -> List[float]:
    """Each op's self time in ns: its duration minus the durations of the
    ops directly nested inside it on the same line."""
    own = [float(op[5] - op[4]) for op in ops]
    order = sorted(range(len(ops)),
                   key=lambda i: (ops[i][0], ops[i][4], -ops[i][5]))
    stack: List[int] = []
    line = None
    for i in order:
        lid, s, e = ops[i][0], ops[i][4], ops[i][5]
        if lid != line:
            stack, line = [], lid
        while stack and ops[stack[-1]][5] <= s:
            stack.pop()
        if stack and e <= ops[stack[-1]][5]:
            own[stack[-1]] -= e - s
        stack.append(i)
    return own


def _window(ev: Dict) -> Tuple[int, int]:
    win = [(s, e) for n, s, e in ev["spans"] if n == WINDOW_SPAN]
    if win:
        return win[0]
    return (min(op[4] for op in ev["ops"]), max(op[5] for op in ev["ops"]))


def device_by_scope(ev: Dict) -> Dict[str, Dict[str, float]]:
    """Executable -> innermost scope -> device self time (s) of the ops
    that start inside the window."""
    lo, hi = _window(ev)
    out: Dict[str, Dict[str, float]] = defaultdict(lambda: defaultdict(float))
    for (_, _, mod, scope, s, _), own in zip(ev["ops"],
                                             op_self_times(ev["ops"])):
        if lo <= s < hi:
            out[mod][scope] += own * 1e-9
    return {m: dict(v) for m, v in out.items()}


def idle_by_span(ev: Dict) -> Dict[str, float]:
    """Device idle time (s) in the window by the innermost ``repro.`` span
    open at each gap's midpoint, ``none`` where none was."""
    lo, hi = _window(ev)
    busy = union([(max(s, lo), min(e, hi)) for *_, s, e in ev["ops"]
                  if e > lo and s < hi])
    spans = sorted((s, e, n[len(PREFIX):]) for n, s, e in ev["spans"]
                   if n.startswith(PREFIX))
    starts = [s for s, _, _ in spans]
    out: Dict[str, float] = defaultdict(float)
    cursor = lo
    for s, e in busy + [(hi, hi)]:
        if s > cursor:
            mid = (cursor + s) // 2
            best = None
            for j in range(bisect.bisect_right(starts, mid) - 1, -1, -1):
                ss, se, n = spans[j]
                if se > mid and (best is None or se - ss < best[0]):
                    best = (se - ss, n)
            out[best[1] if best else "none"] += (s - cursor) * 1e-9
        cursor = max(cursor, e)
    return dict(out)


def longest_call(records: Iterable) -> Optional[Dict]:
    """The longest ``server.submit`` or ``server.step`` span: its length,
    its own self time, each descendant's self time summed by name, and the
    garbage collections inside it (all ms)."""
    recs = [SpanRecord(*r) for r in records]
    calls = [r for r in recs if r.name in CALLS]
    if not calls:
        return None
    top = max(calls, key=lambda r: r.end_ns - r.start_ns)
    own = self_times(recs)
    parent = {r.id: r.parent for r in recs}
    by_name: Dict[str, float] = defaultdict(float)
    for r in recs:
        p = r.parent
        while p >= 0 and p != top.id:
            p = parent.get(p, -1)
        if p == top.id:
            by_name[r.name] += own[r.id] * 1e-6
    return {"name": top.name, "ms": (top.end_ns - top.start_ns) * 1e-6,
            "self_ms": own[top.id] * 1e-6, "children_self_ms": dict(by_name),
            "gc_ms": by_name.get("python.gc", 0.0)}


def program_record(snapshot: Dict, xplane=None) -> Dict:
    """``rec["program"]``: the timeline's ``snapshot()`` over the window,
    and with a trace its scope and idle reductions."""
    rec = dict(snapshot)
    rec["longest_call"] = longest_call(snapshot["spans"])
    if xplane is not None:
        ev = load(xplane)
        if ev["ops"]:
            rec["scopes"] = device_by_scope(ev)
            rec["idle_by_span"] = idle_by_span(ev)
    return rec
