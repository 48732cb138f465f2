"""CPU tests of the benchmark's arithmetic: trace reduction, the end-to-end
metrics, operation counts, peaks, discovery of files by name, and the
command's refusal to run without a TPU."""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(REPO), str(REPO / "src")]

from benchmarks.chip import cell as cellmod  # noqa: E402
from benchmarks.chip import flops, openloop, trace_reduce  # noqa: E402


def _events():
    # window 0..100 ns; ops busy over [10, 30] and [50, 60]
    return [
        ("host", "bench.window", 0, 100),
        ("host", "bench.step", 5, 35),
        ("host", "bench.route", 45, 25),
        ("op", "fusion.a", 10, 10),
        ("op", "fusion.b", 15, 15),
        ("op", "fusion.a", 50, 10),
        ("op", "fusion.a", 95, 20),          # runs past the window's end
        ("module", "jit__cohort_decode_chunk(1)", 10, 20),
        ("module", "jit_other(2)", 90, 30),  # ends outside the window
    ]


def test_trace_reduce_busy_union_per_op_and_gaps():
    red = trace_reduce.reduce(_events())
    assert red["window_s"] == pytest.approx(100e-9)
    assert red["busy_s"] == pytest.approx(35e-9)   # 20 + 10 + 5 clipped
    assert red["op_time"] == pytest.approx({"fusion.a": 25e-9,
                                            "fusion.b": 15e-9})
    assert red["modules"] == {"jit__cohort_decode_chunk(1)":
                              [pytest.approx(20e-9)]}
    # gaps [0,10] (mid 5: in step), [30,50] (mid 40: step ended, route not
    # begun), [60,95] (mid 77: none)
    assert [n for n, _ in red["gaps"]] == ["step", "none", "none"]
    assert red["idle_by_span"] == pytest.approx({"step": 10e-9,
                                                 "none": 55e-9})
    b = trace_reduce.breakdown(red, top=1)
    assert b == {"device_ops": [["fusion.a", pytest.approx(25e-9)]],
                 "idle_gaps": [["none", pytest.approx(55e-9)]]}


def test_op_names_keep_the_hlo_instruction_name():
    full = ('%gqa_decode_attention.1 = bf16[4,8,2,128]{3,2,1,0} '
            'custom-call(s32[4]{0} %kl.1), custom_call_target="x"')
    assert trace_reduce.op_name(full) == "gqa_decode_attention.1"
    assert trace_reduce.op_name("jit__lambda(12)") == "jit__lambda(12)"


def test_trace_reduce_without_device_ops_is_none():
    assert trace_reduce.reduce([("host", "bench.window", 0, 10)]) is None
    assert trace_reduce.union([(5, 7), (0, 2), (1, 3), (6, 9)]) == \
        [(0, 3), (5, 9)]


def _window(**kw):
    base = dict(seconds=10.0, due=np.array([0.0, 1.0, 2.0, 3.0]),
                submitted=np.array([0.0, 1.0, 2.0, np.nan]),
                first=np.array([0.1, 1.5, np.nan, np.nan]),
                finish=np.array([1.1, 3.5, np.nan, np.nan]),
                tokens=np.array([11, 21, 0, 0]), failed=0,
                decode_tokens=30, ticks=5, compiles=0, hedges=0)
    base.update(kw)
    return openloop.Window(**base)


def test_end_to_end_censors_ttft_and_counts_samples():
    m = openloop.end_to_end(_window())
    # TTFT 0.1, 0.5, then censored at the window's end: 8 and 7 s
    assert m["ttft_samples"] == 4 and m["ttft_censored"] == 2
    assert m["ttft_p50_ms"] == pytest.approx(3750.0)
    assert m["ttft_p95_ms"] == pytest.approx(7850.0)
    # TPOT (1.1 - 0.1) / 10 and (3.5 - 1.5) / 20
    assert m["tpot_samples"] == 2
    assert m["tpot_p95_ms"] == pytest.approx(100.0)
    # 30 decode tokens and 2 first tokens over 10 s
    assert m["output_tok_s"] == pytest.approx(3.2)
    d = openloop.describe(_window())
    assert (d["attempted"], d["submitted"], d["backlog"]) == (4, 3, 1)


def test_end_to_end_without_finished_requests_gives_no_tpot():
    m = openloop.end_to_end(_window(finish=np.full(4, np.nan)))
    assert m["tpot_samples"] == 0 and np.isnan(m["tpot_p95_ms"])


def _sizes(config: str, arch: str):
    return json.loads((REPO / "benchmarks/chip/configs" / f"{config}.json")
                      .read_text())["architectures"][arch]


def test_flops_match_hand_counts():
    st, qw = _sizes("testbed", "stablelm-3b"), _sizes("testbed",
                                                      "qwen3-1.7b")
    dense = cellmod.load_kind(st["kind"])
    # stablelm-3b: 4 x 2560^2 attention + 3 x 2560 x 6912 SwiGLU per layer
    assert dense.layer_matmul_params(st) == 79_298_560
    assert dense.token_matmul_flops(st) == \
        2 * (32 * 79_298_560 + 50304 * 2560)
    # qwen3-1.7b: q/o 2048x2048, k/v 2048x1024, SwiGLU 3 x 2048 x 6144
    assert dense.layer_matmul_params(qw) == 50_331_648
    assert dense.decode_flops(qw, [100]) == \
        2 * (28 * 50_331_648 + 151936 * 2048) + 4 * 28 * 2048 * 100
    f, b = dense.decode_attention_cost(qw, [100, 50])
    assert f == 4 * 28 * 2048 * 150
    assert b == 2 * 28 * 1024 * 150 * 2 + 2 * 28 * 2 * 16 * 128 * 2
    assert dense.prefill_flops(st, 24) == (2 * 24 * 32 * 79_298_560
                                           + 2 * 50304 * 2560
                                           + 2 * 32 * 2560 * 24 * 25)
    peak = cellmod.load_peak("TPU v5 lite")
    assert flops.roofline_seconds(197e12, 0, peak) == pytest.approx(1.0)
    assert flops.roofline_seconds(0, 819e9, peak) == pytest.approx(1.0)


def test_unknown_device_kind_raises():
    assert cellmod.load_peak("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        cellmod.load_peak("cpu")


def test_every_name_in_benchmark_json_resolves_to_its_files():
    bench = cellmod.load_benchmark()
    for w in bench["workloads"]:
        c = cellmod.load_cell(w["name"], bench)
        assert c.config["name"] == w["config"]
        assert {m["name"] for m in c.end_to_end} >= {"setup_s"}
        assert c.per_layer
    empty = {"spans": {}, "trace": None, "counts": {}, "peak": {}}
    for m in bench["per_layer"]:
        assert m["moves"] in {e["name"] for e in bench["end_to_end"]}
        assert cellmod.load_reader(m["name"])(empty) is None


def test_new_files_are_found_by_name(tmp_path, monkeypatch):
    """A new configuration, mix and metric need only new files and
    BENCHMARK.json entries."""
    bench_dir = tmp_path / "benchmarks" / "chip"
    for sub in ("configs", "traffic", "metrics", "archs"):
        shutil.copytree(cellmod.BENCH_DIR / sub, bench_dir / sub)
    shutil.copy(cellmod.BENCH_DIR / "peaks.json", bench_dir)
    bench = cellmod.load_benchmark()
    cfg = json.loads((bench_dir / "configs/testbed.json").read_text())
    (bench_dir / "configs/edge_only.json").write_text(json.dumps(
        dict(cfg, name="edge_only")))
    mix = json.loads((bench_dir / "traffic/paper_mix.json").read_text())
    (bench_dir / "traffic/slow_mix.json").write_text(json.dumps(
        dict(mix, arrival={"law": "poisson", "rate": 1.0})))
    (bench_dir / "metrics/ticks_seen.py").write_text(
        "def read(rec):\n    return float(len(rec['spans'].get('step', [])))\n")
    bench["configs"].append({"name": "edge_only", "source": "x",
                             "file": "benchmarks/chip/configs/edge_only.json",
                             "reduced": [], "why": "x"})
    bench["workloads"].append({"name": "edge_only.slow", "config":
                               "edge_only", "traffic": "slow_mix",
                               "chips": 1, "why": "x"})
    bench["per_layer"].append({"name": "ticks_seen", "unit": "ticks",
                               "better": "higher", "source": "host_clock",
                               "layer": "scheduler", "moves": "tpot_p95_ms"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    monkeypatch.setattr(cellmod, "REPO", tmp_path)
    monkeypatch.setattr(cellmod, "BENCH_DIR", bench_dir)
    c = cellmod.load_cell("edge_only.slow")
    assert c.config["name"] == "edge_only"
    assert c.traffic["arrival"]["rate"] == 1.0
    assert "ticks_seen" in {m["name"] for m in c.per_layer}
    assert cellmod.load_reader("ticks_seen")({"spans": {"step": [1, 2]}}) \
        == 2.0


def test_command_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(REPO / "benchmarks/chip/run.py"), "--workload",
         "testbed.paper_mix", "--seed", "3", "--seconds", "1", "--trace",
         "0"], env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert not p.stdout.strip()
