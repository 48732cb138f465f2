"""The benchmark's copy of the traffic generators equals the program's
today, and a mix gives every seed the same work in another order."""
from __future__ import annotations

import dataclasses
import json
import sys
from pathlib import Path

import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(REPO), str(REPO / "src")]

from benchmarks.chip.traffic import generator  # noqa: E402
from repro.workload import arrivals, datasets  # noqa: E402
from repro.workload.trace import build_trace  # noqa: E402

TRAFFIC = REPO / "benchmarks/chip/traffic"


@pytest.mark.parametrize("name", datasets.DATASETS)
def test_dataset_generators_equal_the_program(name):
    mine = generator.generate(name, 40, seed=7)
    theirs = datasets.generate(name, 40, seed=7)
    for a, b in zip(mine, theirs):
        got = dataclasses.asdict(a)
        assert got == {k: getattr(b, k) for k in got}


def test_paper_mix_equals_build_trace_and_poisson_equals_arrivals():
    mine = generator.paper_mix(64, seed=0)
    theirs = build_trace(64, seed=0).requests
    assert [p.text for p in mine] == [r.text for r in theirs]
    np.testing.assert_array_equal(generator.poisson_arrivals(50, 3.0, 9),
                                  arrivals.poisson_arrivals(50, 3.0, 9))


@pytest.mark.parametrize("mix", ["paper_mix", "long_decode"])
def test_every_seed_gets_the_same_work_in_another_order(mix):
    m = json.loads((TRAFFIC / f"{mix}.json").read_text())
    a = generator.schedule(m, 30, seed=1)
    b = generator.schedule(m, 30, seed=2**31 + 17)
    assert len(a) == round(m["arrival"]["rate"] * 30)
    key = lambda s: sorted((it.prompt.text, it.max_new_tokens) for it in s)
    assert key(a) == key(b)
    assert [it.prompt.text for it in a] != [it.prompt.text for it in b]
    for s in (a, b):
        due = np.asarray([it.due for it in s])
        assert (np.diff(due) >= 0).all() and 0 < due[-1] < 30
    np.testing.assert_array_equal([it.due for it in a], [it.due for it in b])
    lens = [it.max_new_tokens for it in a]
    if m["output"]["law"] == "lognormal":
        assert min(lens) >= 32 and max(lens) <= 480
        law = generator.output_lengths(m["output"], [None] * 4000, seed=0)
        assert 150 <= np.median(law) <= 170
    else:
        assert 4 <= min(lens) and max(lens) <= 36
