"""CPU tests of the reduction of the program's own spans and named device
scopes (``scopes.py``) and of the readers that take ``rec["program"]``:
self time of nested device ops, idle time by the innermost program span,
the longest call's breakdown, and that no existing reader moves when the
program's record is there."""
from __future__ import annotations

import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(REPO), str(REPO / "src")]

from benchmarks.chip import scopes, trace_reduce  # noqa: E402
from benchmarks.chip.cell import load_reader  # noqa: E402

JIT = "jit__cohort_decode_chunk"
OLD = ("route_ms", "admit_ms", "tick_ms", "call_max_ms", "decode_step_ms",
       "step_mfu", "decode_attn_roofline", "idle_share")
NEW = ("queue_wait_ms", "admit_wait_ms", "step_self_ms", "kv_used_share",
       "decode_row_yield", "cohort_copy_share")


def _ops():
    # one line of executable JIT; a scan's while [0, 100] holds a gather, a
    # layer loop [30, 90] (itself holding the kernel and an mlp op) and a
    # merge; then a prefill, and a copy on another line
    J, P = JIT, "jit__prefill_bucketed"
    return [
        (0, "while.39", J, "unscoped", 0, 100),
        (0, "fusion.1", J, "cohort.gather", 5, 25),
        (0, "while.57", J, "decode.step", 30, 90),
        (0, "gqa_decode_attention.4", J, "attn.kernel", 35, 55),
        (0, "fusion.7", J, "mlp", 60, 85),
        (0, "select_select_fusion", J, "cohort.merge", 92, 98),
        (0, "fusion.9", P, "prefill", 110, 120),
        (1, "copy.3", J, "unscoped", 10, 20),   # another line: no nesting
    ]


def test_op_self_time_takes_nested_ops_out_of_loops():
    own = scopes.op_self_times(_ops())
    assert own == [100 - 20 - 60 - 6, 20, 60 - 20 - 25, 20, 25, 6, 10, 10]
    # self times tile the outer loop: nothing counts twice
    assert sum(own[:6]) == 100


def test_device_self_time_by_executable_and_scope():
    ev = {"ops": _ops(), "spans": [("bench.window", 0, 200)],
          "modules": [(JIT, 0, 100), ("jit__prefill_bucketed", 105, 125)]}
    got = scopes.device_by_scope(ev)
    ns = 1e-9
    assert got[JIT] == pytest.approx({
        "unscoped": (14 + 10) * ns, "cohort.gather": 20 * ns,
        "decode.step": 15 * ns, "attn.kernel": 20 * ns, "mlp": 25 * ns,
        "cohort.merge": 6 * ns})
    assert got["jit__prefill_bucketed"] == pytest.approx({"prefill": 10e-9})
    # ops that start outside the window are left out
    ev["spans"] = [("bench.window", 0, 50)]
    assert sum(scopes.device_by_scope(ev)[JIT].values()) == pytest.approx(
        (14 + 20 + 15 + 20 + 10) * ns)


def test_scope_of_takes_the_innermost_named_scope():
    path = ("jit(_cohort_decode_chunk)/vmap()/while/body/closed_call/"
            "decode.step/while/body/closed_call/attn.kernel/dot_general")
    assert scopes.scope_of(path) == "attn.kernel"
    assert scopes.scope_of("jit(f)/cohort.merge/scatter") == "cohort.merge"
    assert scopes.scope_of("jit(f)/while/body/copy") == "unscoped"
    assert scopes.scope_of("") == "unscoped"
    # XLA's copy of an argument carries the argument's name
    assert scopes.scope_of("state.cache.layer[0][0]") == "argument"


def _vi(n: int) -> bytes:
    out = bytearray()
    while True:
        b, n = n & 0x7F, n >> 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _msg(*fields) -> bytes:
    """A protobuf message from (field number, int | str | bytes) pairs."""
    out = b""
    for num, v in fields:
        if isinstance(v, int):
            out += _vi(num << 3) + _vi(v)
        else:
            v = v.encode() if isinstance(v, str) else v
            out += _vi(num << 3 | 2) + _vi(len(v)) + v
    return out


def _entry(key: int, value: bytes) -> bytes:
    return _msg((1, key), (2, value))


def _xspace() -> bytes:
    """One TPU plane running executable 7 (four ops, two of which carry no
    path of their own), its HLO on the metadata plane, and one host span."""
    stat_md = [(1, "program_id"), (2, "tf_op"), (3, "deduplicated_name"),
               (4, "jit(f)/decode.step/mlp/dot_general:")]
    ops = [  # id, short name, stats
        (11, "copy.1", [(1, 3, 7), (2, 5, "jit(f)/cohort.merge/scatter:")]),
        (12, "copy.2", [(1, 3, 7), (3, 5, "copy.1")]),     # deduplicated
        (13, "copy.3", [(1, 3, 7)]),                       # XLA's own copy
        (14, "fusion.4", [(1, 3, 7), (2, 7, 4)]),          # ref-valued path
        (15, "jit__cohort_decode_chunk(7)", [])]
    ev_md = [_entry(i, _msg((1, i), (2, f"%{n} = bf16[2] op()"
                                     if "(" not in n else n), (4, n),
                            *[(5, _msg((1, k), (w, v))) for k, w, v in st]))
             for i, n, st in ops]
    events = lambda *evs: b"".join(  # noqa: E731
        _msg((4, _msg((1, m), (2, o * 1000), (3, d * 1000))))
        for m, o, d in evs)
    device = _msg(
        (2, "/device:TPU:0"),
        (3, _msg((1, 1), (2, "XLA Modules"), (3, 1000))
         + events((15, 0, 100))),
        (3, _msg((1, 2), (2, "XLA Ops"), (3, 1000))
         + events((11, 0, 10), (12, 10, 10), (13, 20, 30), (14, 50, 40))),
        *[(4, e) for e in ev_md],
        *[(5, _entry(i, _msg((1, i), (2, n)))) for i, n in stat_md])
    hlo = _msg((1, _msg((3, _msg(
        (2, _msg((1, "p.1"), (7, _msg((2, "state.cache.layer[0][0]"))),
                 (35, 1))),
        (2, _msg((1, "copy.3"), (35, 2), (36, _vi(1)))))))))
    meta = _msg((2, "/host:metadata"),
                (4, _entry(15, _msg((1, 15),
                                    (2, "jit__cohort_decode_chunk(7)"),
                                    (5, _msg((1, 9), (6, hlo)))))),
                (5, _entry(9, _msg((1, 9), (2, "Hlo Proto")))))
    host = _msg((2, "/host:CPU"),
                (3, _msg((1, 3), (2, "python"), (3, 1000))
                 + events((21, 0, 100))),
                (4, _entry(21, _msg((1, 21), (2, "repro.server.step")))))
    return _msg((1, device), (1, meta), (1, host))


def test_op_paths_read_the_trace_metadata_and_the_hlo(tmp_path):
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_xspace())
    assert scopes.op_paths(path) == {
        (7, "copy.1"): "jit(f)/cohort.merge/scatter",
        (7, "copy.2"): "jit(f)/cohort.merge/scatter",
        # no path of its own: its operand's, from the executable's HLO
        (7, "copy.3"): "state.cache.layer[0][0]",
        (7, "fusion.4"): "jit(f)/decode.step/mlp/dot_general"}
    ev = scopes.load(path)
    assert [op[1:4] for op in ev["ops"]] == [
        ("copy.1", JIT, "cohort.merge"), ("copy.2", JIT, "cohort.merge"),
        ("copy.3", JIT, "argument"), ("fusion.4", JIT, "mlp")]
    assert ev["modules"] == [(JIT, 1000, 1100)]
    assert ev["spans"] == [("repro.server.step", 1000, 1100)]
    assert scopes.device_by_scope(ev)[JIT] == pytest.approx(
        {"cohort.merge": 20e-9, "argument": 30e-9, "mlp": 40e-9})


def test_idle_is_put_down_to_the_innermost_program_span():
    ev = {"spans": [("bench.window", 0, 100),
                    ("repro.server.step", 0, 100),
                    ("repro.step.commit", 20, 60),
                    ("repro.python.gc", 30, 40)],
          "ops": [(0, "a", JIT, "mlp", 0, 10), (0, "b", JIT, "mlp", 45, 50),
                  (0, "c", JIT, "mlp", 90, 120)],
          "modules": []}
    got = scopes.idle_by_span(ev)
    # gap [10, 45]: midpoint 27 lies in step.commit only; gap [50, 90]:
    # midpoint 70 in server.step only
    assert got == pytest.approx({"step.commit": 35e-9,
                                 "server.step": 40e-9})
    ev["spans"] = [("bench.window", 0, 100), ("repro.python.gc", 20, 40)]
    assert scopes.idle_by_span(ev) == pytest.approx({"python.gc": 35e-9,
                                                     "none": 40e-9})


def _records():
    # (id, name, start, end, parent, rid) in ns; a short and a long step,
    # one request admitted inside the long one
    return [
        [1, "router.route", 1_000_000, 2_000_000, 0, 4],
        [0, "server.submit", 0, 3_000_000, -1, 4],
        [3, "step.commit", 11_000_000, 19_000_000, 2, -1],
        [2, "server.step", 10_000_000, 20_000_000, -1, -1],
        [5, "admit.first_token", 24_000_000, 30_000_000, 4, 4],
        [6, "python.gc", 31_000_000, 33_000_000, 4, 4],
        [4, "engine.admit", 23_000_000, 35_000_000, 7, 4],
        [7, "step.commit", 22_000_000, 36_000_000, 8, -1],
        [8, "server.step", 21_000_000, 41_000_000, -1, -1],
    ]


def test_longest_call_breaks_down_into_child_self_times_and_gc():
    got = scopes.longest_call(_records())
    assert got["name"] == "server.step" and got["ms"] == pytest.approx(20)
    assert got["self_ms"] == pytest.approx(20 - 14)
    assert got["children_self_ms"] == pytest.approx({
        "step.commit": 14 - 12, "engine.admit": 12 - 6 - 2,
        "admit.first_token": 6, "python.gc": 2})
    assert got["gc_ms"] == pytest.approx(2)
    assert scopes.longest_call([]) is None


def _rec(program=True):
    red = trace_reduce.reduce([
        ("host", "bench.window", 0, 100), ("host", "bench.step", 5, 35),
        ("op", "gqa_decode_attention.4", 10, 10),
        ("module", "jit__cohort_decode_chunk(1)", 10, 20)])
    rec = {"spans": {"route": [1e-4], "admit": [0.015],
                     "step": [0.02, 0.03], "submit": [0.001]},
           "trace": red, "counts": {"model_flops": 1e6, "attn_min_s": 1e-9},
           "peak": {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}}
    if program:
        rec["program"] = {
            "spans": _records(), "dropped": 0, "capacity": 1 << 18,
            "counters": {"kv.positions_held": 300,
                         "kv.positions_reserved": 1200,
                         "cohort.rows_decoded": 64,
                         "cohort.tokens_emitted": 16},
            "scopes": {JIT: {"cohort.gather": 0.002, "cohort.merge": 0.003,
                             "argument": 0.005, "decode.step": 0.015},
                       "jit__prefill_bucketed": {"prefill": 0.5}}}
    return rec


def test_new_readers_on_a_hand_built_record():
    rec = _rec()
    got = {m: load_reader(m)(rec) for m in NEW}
    assert got == pytest.approx({
        # rid 4: submitted at 0, first admitted at 23 ms
        "queue_wait_ms": 23.0,
        "admit_wait_ms": 6.0,
        # steps' self times: 10 - 8 and 20 - 14
        "step_self_ms": (2.0 + 6.0) / 2,
        "kv_used_share": 25.0,
        "decode_row_yield": 25.0,
        # prefill's executable is not the cohort decode's
        "cohort_copy_share": 100.0 * 0.01 / 0.025})


def test_new_readers_report_nothing_without_the_program_record():
    rec = _rec(program=False)
    assert all(load_reader(m)(rec) is None for m in NEW)
    rec["program"] = {"spans": [], "counters": {}, "dropped": 0}
    assert all(load_reader(m)(rec) is None for m in NEW)


@pytest.mark.parametrize("metric", OLD)
def test_existing_readers_ignore_the_program_record(metric):
    read = load_reader(metric)
    assert read(_rec(program=True)) == read(_rec(program=False))
    assert read(_rec()) is not None


def test_timeline_spans_land_in_a_profiler_trace(tmp_path):
    """Each open timeline span is a ``repro.<span>`` annotation: the CPU
    profiler's trace holds them, and ``scopes.load`` reads them back."""
    import jax

    from repro.obs import Timeline
    tl = Timeline()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with tl.span("server.step"):
            with tl.span("step.commit"):
                jax.numpy.ones(4).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    ev = scopes.load(trace_reduce.find_xplane(tmp_path))
    names = [n for n, _, _ in ev["spans"]]
    assert "repro.server.step" in names and "repro.step.commit" in names
    (s0, e0), = [(s, e) for n, s, e in ev["spans"]
                 if n == "repro.server.step"]
    (s1, e1), = [(s, e) for n, s, e in ev["spans"]
                 if n == "repro.step.commit"]
    assert s0 <= s1 <= e1 <= e0
