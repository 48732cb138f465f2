"""The open loop, and the arithmetic of the end-to-end metrics.

Before each ``ClusterServer.step()`` every request whose due time has passed
is submitted, whatever the server is doing, and each request is timed from
its due time, so a stall counts against every request it delays. The loop
reads only the server's public surface: ``submit``, ``step``, ``inflight``,
``done``, and the engines' ``results``, ``slots`` and ``tokens_emitted``.

A request's first token arrives at the end of the first call after which it
holds an engine slot or has a result; it finishes at the end of the call
that puts it in ``done``.
"""
from __future__ import annotations

import contextlib
import dataclasses
import math
import time
import traceback
from collections import defaultdict
from typing import Callable, Dict, List, Optional

import jax
import numpy as np

from repro.serving import ServeRequest

from benchmarks.chip import deploy, flops
from benchmarks.chip.traffic.generator import Item


def _is_jax_error(e: BaseException) -> bool:
    return any(c.__module__.split(".")[0] in ("jax", "jaxlib")
               for c in type(e).__mro__)


class Probe:
    """What the traced run records besides the profiler: host-clock spans
    around calls into the program (``route``, ``admit``, ``step``,
    ``submit``), each also written into the profiler's trace, and, while the
    profiler runs, the model FLOPs and the decode-attention work that the
    served tokens needed, as each architecture's kind counts them."""

    def __init__(self, dep: deploy.Deployment, peak: Dict):
        self.dep, self.peak = dep, peak
        self.spans: Dict[str, List[float]] = defaultdict(list)
        self.tracing = False
        self.counts = defaultdict(float)
        srv = dep.srv
        if hasattr(srv, "router") and hasattr(srv.router, "route"):
            srv.router.route = self.wrap("route", srv.router.route)
        for p, eng in srv.engines.items():
            if hasattr(eng, "_prefill_into"):
                eng._prefill_into = self.wrap(
                    "admit", eng._prefill_into,
                    on_call=lambda args, a=dep.pair_arch[p]:
                    self._prefill(a, args))

    def span(self, name: str):
        return _Span(self, name)

    def wrap(self, name: str, fn: Callable, on_call=None) -> Callable:
        def wrapped(*args, **kwargs):
            if on_call is not None and self.tracing:
                on_call(args)
            with self.span(name):
                return fn(*args, **kwargs)
        return wrapped

    def _prefill(self, arch: str, args) -> None:
        if len(args) > 2:
            self.counts["model_flops"] += self.dep.kinds[arch].prefill_flops(
                self.dep.archs[arch], len(args[2]))

    def before_step(self) -> None:
        """Counts the decode step about to run: every live slot decodes one
        token over the positions it holds plus the new one."""
        if not self.tracing:
            return
        kv: Dict[str, List[int]] = defaultdict(list)
        for p, eng in self.dep.srv.engines.items():
            for s in eng.slots:
                if s.request_id is not None:
                    kv[self.dep.pair_arch[p]].append(
                        s.prompt_tokens + len(s.generated))
        for arch, lens in kv.items():
            a, kind = self.dep.archs[arch], self.dep.kinds[arch]
            f, b = kind.decode_attention_cost(a, lens)
            self.counts["model_flops"] += kind.decode_flops(a, lens)
            self.counts["attn_min_s"] += flops.roofline_seconds(f, b,
                                                                self.peak)


class _Span:
    def __init__(self, probe: Probe, name: str):
        self.probe, self.name = probe, name

    def __enter__(self):
        self.ann = (jax.profiler.TraceAnnotation("bench." + self.name)
                    if self.probe.tracing else None)
        if self.ann is not None:
            self.ann.__enter__()
        self.t = time.perf_counter()

    def __exit__(self, *exc):
        self.probe.spans[self.name].append(time.perf_counter() - self.t)
        if self.ann is not None:
            self.ann.__exit__(*exc)
        return False


@dataclasses.dataclass
class Window:
    seconds: float                 # measured: last call's end - window start
    due: np.ndarray                # (n,) seconds after the window opened
    submitted: np.ndarray          # (n,) submit call start, nan if never
    first: np.ndarray              # (n,) first token, nan if none
    finish: np.ndarray             # (n,) entry into done, nan if none
    tokens: np.ndarray             # (n,) tokens served, 0 if unfinished
    failed: int                    # ended as failed or shed, or raised
    decode_tokens: int             # engines' tokens_emitted over the window
    ticks: int
    compiles: int                  # compile or trace events in the window
    hedges: int                    # duplicates the server issued (stats())


class _CompileCounter:
    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/core/compile/jaxpr_trace_duration")

    def __init__(self):
        self.on = False
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, event, duration, **_):
        if self.on and event in self.EVENTS:
            self.n += 1


_COUNTER: Optional[_CompileCounter] = None


def compile_counter() -> _CompileCounter:
    global _COUNTER
    if _COUNTER is None:
        _COUNTER = _CompileCounter()
    return _COUNTER


def drive(dep: deploy.Deployment, items: List[Item], seconds: float,
          rid0: int = 0, probe: Optional[Probe] = None,
          trace: Optional[Dict] = None,
          marks: Optional[Dict[float, Callable]] = None) -> Window:
    """Runs ``seconds`` of the open loop over ``items`` (request ids
    ``rid0 + i``). ``trace`` = {"start": s, "length": s, "dir": path} runs
    the profiler over that part of the window. ``marks`` maps an offset in
    seconds to a callback run once the window passes it."""
    srv = dep.srv
    engines = list(srv.engines.values())
    n = len(items)
    due = np.asarray([it.due for it in items])
    submitted, first, finish = (np.full(n, np.nan) for _ in range(3))
    tokens = np.zeros(n, np.int64)
    failed = 0
    done_seen = len(srv.done)
    pending_marks = sorted((marks or {}).items())
    counter = compile_counter()
    ctx = contextlib.ExitStack()
    step = srv.step if probe is None else probe.wrap("step", srv.step)
    submit = srv.submit if probe is None else probe.wrap("submit", srv.submit)

    def mark_progress(t: float) -> None:
        nonlocal done_seen, failed
        for eng in engines:
            for s in eng.slots:
                i = s.request_id
                if i is not None and 0 <= i - rid0 < n \
                        and math.isnan(first[i - rid0]):
                    first[i - rid0] = t
        if len(srv.done) != done_seen:
            for rid in list(srv.done)[done_seen:]:
                i = rid - rid0
                if not 0 <= i < n:
                    continue
                r = srv.done[rid]
                if "tokens" not in r:
                    failed += 1
                    continue
                finish[i] = t
                tokens[i] = len(r["tokens"])
                if math.isnan(first[i]):
                    first[i] = t
            done_seen = len(srv.done)

    emitted0 = sum(e.tokens_emitted for e in engines)
    hedges0 = srv.stats()["hedges"]
    ticks = 0
    nxt = 0
    trace_on = trace_done = False
    counter.n, counter.on = 0, True
    t0 = time.perf_counter()
    t_end = t0 + seconds
    while True:
        now = time.perf_counter()
        if now >= t_end:
            break
        off = now - t0
        while pending_marks and off >= pending_marks[0][0]:
            pending_marks.pop(0)[1]()
        if trace and not trace_on and not trace_done \
                and off >= trace["start"]:
            jax.profiler.start_trace(trace["dir"])
            ctx.enter_context(jax.profiler.TraceAnnotation("bench.window"))
            probe.tracing = trace_on = True
        elif trace_on and off >= trace["start"] + trace["length"]:
            probe.tracing, trace_on, trace_done = False, False, True
            ctx.close()
            jax.profiler.stop_trace()
        while nxt < n and t0 + due[nxt] <= now:
            submitted[nxt] = time.perf_counter() - t0
            try:
                submit(ServeRequest(request_id=rid0 + nxt,
                                    req=deploy.to_request(items[nxt].prompt),
                                    max_new_tokens=items[nxt].max_new_tokens))
            except Exception as e:
                if _is_jax_error(e):
                    raise
                traceback.print_exc()
                failed += 1
            nxt += 1
            mark_progress(time.perf_counter() - t0)
            now = time.perf_counter()
        if not srv.inflight and not srv.transfers:
            wake = t0 + (due[nxt] if nxt < n else seconds)
            time.sleep(max(0.0, min(wake, t_end) - time.perf_counter()))
            continue
        if probe is not None:
            probe.before_step()
        step()
        ticks += 1
        mark_progress(time.perf_counter() - t0)
    t_stop = time.perf_counter() - t0
    counter.on = False
    if trace_on:
        probe.tracing = False
        ctx.close()
        jax.profiler.stop_trace()
    decode_tokens = sum(e.tokens_emitted for e in engines) - emitted0
    return Window(seconds=t_stop, due=due, submitted=submitted, first=first,
                  finish=finish, tokens=tokens, failed=failed,
                  decode_tokens=decode_tokens, ticks=ticks,
                  compiles=counter.n, hedges=srv.stats()["hedges"] - hedges0)


def pct(xs, q: float) -> float:
    """Linear-interpolated percentile; nan for no samples."""
    xs = np.asarray(xs, np.float64)
    return float(np.percentile(xs, q)) if xs.size else float("nan")


def end_to_end(w: Window) -> Dict[str, float]:
    """The end-to-end metrics of one window, with their sample counts.

    TTFT is over every request due in the window; one with no first token by
    the window's end counts with the time it had waited. TPOT is over the
    requests that finished in the window with two tokens or more. Output
    tokens are the engines' decode tokens plus the first token of every
    request that got one, over the window's length."""
    got_first = ~np.isnan(w.first)
    ttft = np.where(got_first, w.first, w.seconds) - w.due
    fin = ~np.isnan(w.finish) & (w.tokens >= 2)
    tpot = (w.finish[fin] - w.first[fin]) / (w.tokens[fin] - 1)
    return {
        "ttft_p50_ms": pct(ttft, 50) * 1e3,
        "ttft_p95_ms": pct(ttft, 95) * 1e3,
        "ttft_samples": int(ttft.size),
        "ttft_censored": int((~got_first).sum()),
        "tpot_p50_ms": pct(tpot, 50) * 1e3,
        "tpot_p95_ms": pct(tpot, 95) * 1e3,
        "tpot_samples": int(tpot.size),
        "output_tok_s": (w.decode_tokens + int(got_first.sum())) / w.seconds,
    }


def describe(w: Window) -> Dict[str, float]:
    """The run's own health: how late submission ran and what was left."""
    sub = ~np.isnan(w.submitted)
    late = (w.submitted - w.due)[sub]
    return {
        "window_s": w.seconds,
        "attempted": int(w.due.size),
        "submitted": int(sub.sum()),
        "first_tokens": int((~np.isnan(w.first)).sum()),
        "finished": int((~np.isnan(w.finish)).sum()),
        "backlog": int((sub & np.isnan(w.first)).sum()),
        "failed": w.failed,
        "late_p50_ms": pct(late, 50) * 1e3,
        "late_max_ms": float(late.max()) * 1e3 if late.size else float("nan"),
        "ticks": w.ticks,
        "compiles_in_window": w.compiles,
        "hedges": w.hedges,
    }
