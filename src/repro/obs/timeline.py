"""Host-clock layer timeline: named spans and integer counters inside the
serving path, on ``time.perf_counter_ns()``.

The ``Tracer`` follows each request on the caller's clock (ticks, simulated
seconds) and never reads wall time. The timeline answers the other question:
where the host's time goes inside one ``submit`` or ``step`` call. Each span
records its name, start and end in ``perf_counter_ns``, the id of the span it
opened inside, and the request id where there is one (a span opened without
one takes its parent's, so the spans of one request share an identifier).

While a span is open it is also a ``jax.profiler.TraceAnnotation`` named
``repro.<span>``: when the profiler runs, every span lands in the
``.xplane.pb`` on the device trace's own clock, beside the device ops it
launched. Python garbage collections, seen through ``gc.callbacks``, are
recorded as ``python.gc`` spans under whatever span they interrupted.

Closed spans go into a bounded ring (``capacity`` newest kept, ``dropped``
counts evictions, as in ``Tracer``). Counters are named integer sums.
``NOOP_TIMELINE`` is the shared default: a span site costs one no-op call
and makes no annotation, record or clock read, and a counter site whose
amount costs anything to compute checks ``enabled`` first.

Spans nest on one stack, so the timeline belongs to one thread: the serving
loop's.
"""
from __future__ import annotations

import gc
import time
import weakref
from collections import deque
from typing import Callable, Deque, Dict, Iterable, List, NamedTuple

import jax

__all__ = ["SpanRecord", "Timeline", "NoopTimeline", "NOOP_TIMELINE",
           "SPAN_NAMES", "COUNTER_NAMES", "SCOPE_NAMES", "PREFIX",
           "self_times"]

PREFIX = "repro."

#: Host spans, by where they open (docs/architecture.md keeps the list).
SPAN_NAMES = (
    "server.submit", "router.route", "server.step", "step.deliver",
    "step.retry", "cohort.dispatch", "cohort.flush", "cohort.launch",
    "cohort.fetch", "step.commit", "step.age", "fleet.gather",
    "engine.admit", "admit.prefill", "admit.splice", "admit.first_token",
    "python.gc")
#: Named integer sums.
COUNTER_NAMES = ("cohort.rows_decoded", "cohort.tokens_emitted",
                 "cohort.members_gathered", "kv.positions_held",
                 "kv.positions_reserved")
#: ``jax.named_scope`` names on the device ops (op_name metadata).
SCOPE_NAMES = ("cohort.gather", "cohort.merge", "decode.step",
               "decode.sample", "attn.kv_write", "attn.kv_layout",
               "attn.kernel", "mlp", "prefill")


class SpanRecord(NamedTuple):
    """One closed span; times in ``perf_counter_ns``."""

    id: int
    name: str
    start_ns: int
    end_ns: int
    parent: int      # id of the enclosing span, -1 at top level
    rid: int         # request id, -1 where none


class _OpenSpan:
    __slots__ = ("tl", "name", "rid", "id", "parent", "start", "ann")

    def __init__(self, tl: "Timeline", name: str, rid: int):
        self.tl, self.name, self.rid = tl, name, rid

    def __enter__(self):
        tl = self.tl
        self.id = tl._next_id
        tl._next_id += 1
        if tl._stack:
            self.parent, parent_rid = tl._stack[-1]
            if self.rid < 0:
                self.rid = parent_rid
        else:
            self.parent = -1
        tl._stack.append((self.id, self.rid))
        self.ann = jax.profiler.TraceAnnotation(PREFIX + self.name)
        self.ann.__enter__()
        self.start = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        end = time.perf_counter_ns()
        self.ann.__exit__(*exc)
        tl = self.tl
        tl._stack.pop()
        tl._append(SpanRecord(self.id, self.name, self.start, end,
                              self.parent, self.rid))
        return False


def _gc_hook(ref) -> Callable:
    """A ``gc.callbacks`` entry that holds the timeline weakly."""
    def hook(phase, info):
        tl = ref()
        if tl is not None:
            tl._on_gc(phase)
    return hook


class Timeline:
    """Wall-clock spans and counters, bounded. ``capacity`` 1 << 18 holds a
    40 s serving window of either benchmark cell many times over (about a
    thousand spans a second)."""

    enabled = True

    def __init__(self, capacity: int = 1 << 18):
        self.capacity = capacity
        self._ring: Deque[SpanRecord] = deque(maxlen=capacity)
        self.dropped = 0
        self.counters: Dict[str, int] = {}
        self._stack: List[tuple] = []
        self._next_id = 0
        self._gc_span = None
        hook = _gc_hook(weakref.ref(self))
        gc.callbacks.append(hook)
        weakref.finalize(self, _remove_hook, hook)

    def span(self, name: str, rid: int = -1) -> _OpenSpan:
        """``with tl.span("step.commit"):`` — a span around the block."""
        return _OpenSpan(self, name, rid)

    def count(self, name: str, n: int) -> None:
        self.counters[name] = self.counters.get(name, 0) + int(n)

    def _append(self, rec: SpanRecord) -> None:
        if len(self._ring) == self.capacity:
            self.dropped += 1
        self._ring.append(rec)

    def _on_gc(self, phase: str) -> None:
        if phase == "start" and self._gc_span is None:
            self._gc_span = self.span("python.gc").__enter__()
        elif phase == "stop" and self._gc_span is not None:
            sp, self._gc_span = self._gc_span, None
            sp.__exit__(None, None, None)

    def spans(self) -> List[SpanRecord]:
        """Closed spans, in the order they closed (a child before its
        parent)."""
        # copy first: a collection while a list is built from the ring
        # would append its python.gc span to the ring mid-iteration
        return list(self._ring.copy())

    def __len__(self) -> int:
        return len(self._ring)

    def clear(self) -> None:
        """Forget closed spans, counters and drops; open spans stay open."""
        self._ring.clear()
        self.counters.clear()
        self.dropped = 0

    def snapshot(self) -> Dict:
        """Plain data: ``spans`` as lists of the record's fields, in
        ``SpanRecord`` order, ``counters``, ``dropped``, ``capacity``."""
        return {"spans": [list(r) for r in self.spans()],
                "counters": dict(self.counters), "dropped": self.dropped,
                "capacity": self.capacity}


def _remove_hook(hook) -> None:
    try:
        gc.callbacks.remove(hook)
    except ValueError:
        pass


class _NullSpan:
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL_SPAN = _NullSpan()


class NoopTimeline:
    """Same surface as ``Timeline``; records nothing. Shared singleton
    ``NOOP_TIMELINE`` is the default everywhere."""

    enabled = False
    capacity = 0
    dropped = 0

    @property
    def counters(self) -> Dict[str, int]:
        return {}

    def span(self, name, rid=-1):
        return _NULL_SPAN

    def count(self, name, n):
        pass

    def spans(self):
        return []

    def __len__(self):
        return 0

    def clear(self):
        pass

    def snapshot(self):
        return {"spans": [], "counters": {}, "dropped": 0, "capacity": 0}


NOOP_TIMELINE = NoopTimeline()


def self_times(records: Iterable) -> Dict[int, int]:
    """Span id -> its self time in ns: its duration minus the durations of
    the spans opened directly inside it. Spans nest on one stack, so the
    children of a span never overlap. ``records`` are ``SpanRecord``s or
    their ``snapshot()`` lists."""
    recs = [SpanRecord(*r) for r in records]
    own = {r.id: r.end_ns - r.start_ns for r in recs}
    for r in recs:
        if r.parent in own:
            own[r.parent] -= r.end_ns - r.start_ns
    return own
