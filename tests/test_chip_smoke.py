"""``chip_smoke.py`` on a CPU host: it refuses to run without a TPU, and each
of its phases passes its own checks at ``.smoke()`` widths, with the Pallas
kernels in interpret mode."""
import functools
import importlib.util
import types
from pathlib import Path

import pytest

from repro.cluster.spec import paper_testbed
from repro.configs import get
from repro.kernels import ops
from repro.workload.trace import build_trace

REPO = Path(__file__).resolve().parent.parent


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  REPO / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_refuses_without_tpu(chip_smoke, capsys):
    assert chip_smoke.main([]) != 0
    out = capsys.readouterr()
    assert out.out == "" and "no TPU" in out.err


@pytest.mark.parametrize("spans, covered", [
    ([], 0.0),
    ([(0.0, 4.0), (1.0, 2.0)], 4.0),            # a nested trace counts once
    ([(0.0, 2.0), (1.0, 3.0), (5.0, 6.0)], 4.0),
])
def test_compile_seconds_are_the_union_of_spans(chip_smoke, spans, covered):
    assert chip_smoke._covered(spans) == covered


def test_tune_and_serve_phases_at_smoke_widths(chip_smoke, monkeypatch):
    monkeypatch.setattr(chip_smoke, "get", lambda name: types.SimpleNamespace(
        config=get(name).smoke))
    smoke = chip_smoke.Smoke()
    cluster, trace = paper_testbed(), build_trace(64, seed=0)
    thresholds = smoke.phase("tune", chip_smoke.tune, smoke, cluster, trace)
    smoke.phase("serve", chip_smoke.serve, smoke, cluster, trace, thresholds)
    assert smoke.failures == []


def test_kernel_phase_in_interpret_mode(chip_smoke, monkeypatch):
    def interpreted(fn):
        @functools.wraps(fn)
        def call(*a, mode, **k):
            return fn(*a, mode="interpret" if mode == "pallas" else mode, **k)
        return call
    monkeypatch.setattr(chip_smoke, "ops", types.SimpleNamespace(**{
        name: interpreted(getattr(ops, name)) for name in (
            "gqa_decode_attention", "dominance_matrix",
            "dominance_counts")}))
    smoke = chip_smoke.Smoke()
    smoke.phase("kernels", chip_smoke.kernels, smoke)
    assert smoke.failures == []


def test_sharded_fitness_phase_on_one_device(chip_smoke):
    smoke = chip_smoke.Smoke()
    smoke.phase("sharded_fitness", chip_smoke.sharded_fitness, smoke, 1)
    assert smoke.failures == []
