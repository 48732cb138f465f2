"""Each served architecture names its kind, and the kind's file
(``archs/<kind>.py``) builds it: config, weights, reference and counts.

The dense kind's numbers are pinned to what the harness gave before kinds
existed (weight checksums, reference logits, Probe counts, recorded on the
CPU at ``cpu_smoke`` widths). A toy kind written to a fresh ``archs/``
directory shows that a new kind needs only new files."""
from __future__ import annotations

import hashlib
import json
import math
import shutil
import sys
from pathlib import Path
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(REPO), str(REPO / "src")]

from benchmarks.chip import cell as cellmod  # noqa: E402
from benchmarks.chip import checks, cpu_smoke, deploy, openloop  # noqa: E402
from repro.models import lm  # noqa: E402

SEEDS = (3, 2**33 + 5)

# Recorded from the harness before kinds existed. ``weights``: the first 16
# hex digits of the SHA-256 of every leaf's bytes in flattening order, per
# seed; ``argmax``/``top``: the reference's first choice and its logit at
# each position of ``_tokens`` (weights of the second seed), ``top8`` the
# same in fp8; ``counts``: ``_probe_counts``.
PARENT = {
    "stablelm-3b": dict(
        weights={3: "acb6f2cb54e4bc96", 2**33 + 5: "d994979b2273baa9"},
        argmax=[[57, 8, 57, 116, 137, 116, 370, 240, 57, 137, 137, 137],
                [15, 71, 345, 320, 345, 57, 345, 57, 15, 209, 57, 79]],
        top=[[0.656594, 0.635111, 0.753618, 0.701463, 0.795616, 0.66611,
              0.693589, 0.560239, 0.663993, 0.861527, 0.776714, 0.915833],
             [0.740806, 0.745757, 0.719457, 0.614224, 0.656737, 0.841905,
              0.71964, 0.621938, 0.69679, 0.623084, 0.702201, 0.716628]],
        top8=[[0.61948, 0.6376, 0.72535, 0.69488, 0.82625, 0.65391,
               0.71735, 0.54209, 0.66727, 0.90318, 0.78286, 0.93869],
              [0.69349, 0.76856, 0.69367, 0.60359, 0.66432, 0.75341,
               0.69223, 0.65393, 0.6714, 0.65222, 0.66814, 0.70055]],
        counts={"model_flops": 42924032.0, "attn_min_s": 1.7408e-06}),
    "qwen3-1.7b": dict(
        weights={3: "037feba93033e6b6", 2**33 + 5: "799d29915baaa236"},
        argmax=[[297, 369, 32, 453, 33, 134, 369, 48, 283, 48, 142, 26],
                [151, 151, 142, 413, 231, 142, 142, 427, 456, 60, 181, 60]],
        top=[[0.659945, 0.706681, 0.612378, 0.653993, 0.809356, 0.58317,
              0.710554, 0.72195, 0.667762, 0.731402, 0.693522, 0.746889],
             [0.84997, 0.857987, 0.671772, 0.766153, 0.800136, 0.684019,
              0.663784, 0.546359, 0.61935, 0.902526, 0.911148, 0.665674]],
        top8=[[0.66461, 0.70536, 0.59457, 0.65022, 0.78321, 0.60607,
               0.68146, 0.69172, 0.68164, 0.72598, 0.69306, 0.7192],
              [0.81519, 0.82444, 0.64283, 0.7215, 0.81162, 0.6889,
               0.72146, 0.61855, 0.65466, 0.89481, 0.8472, 0.68821]],
        counts={"model_flops": 31848448.0, "attn_min_s": 8.9088e-07}),
}


def _digest(params) -> str:
    h = hashlib.sha256()
    for leaf in jax.tree_util.tree_leaves(params):
        h.update(np.asarray(leaf).tobytes())
    return h.hexdigest()[:16]


def _tokens(vocab: int) -> jnp.ndarray:
    return jax.random.randint(jax.random.key(11), (2, 12), 0, vocab)


def _probe_counts(archs, kinds, arch: str):
    """Probe counts of one 24-token admission and one decode step over two
    live slots holding 29 and 54 positions, on a stand-in server."""
    slots = [SimpleNamespace(request_id=1, prompt_tokens=24,
                             generated=[0] * 5),
             SimpleNamespace(request_id=None, prompt_tokens=0, generated=[]),
             SimpleNamespace(request_id=2, prompt_tokens=24,
                             generated=[0] * 30)]
    eng = SimpleNamespace(slots=slots, _prefill_into=lambda *a: None)
    dep = deploy.Deployment(srv=SimpleNamespace(engines={0: eng}),
                            archs=archs, kinds=kinds, params={},
                            pair_arch={0: arch}, captured={})
    probe = openloop.Probe(dep, cpu_smoke.PEAK)
    probe.tracing = True
    eng._prefill_into(0, None, list(range(24)))
    probe.before_step()
    return dict(probe.counts)


@pytest.mark.parametrize("arch", sorted(PARENT))
def test_dense_kind_keeps_weights_reference_and_counts(arch):
    sizes = cpu_smoke.ARCHS[arch]
    kind = cellmod.load_kind(sizes["kind"])
    cfg = kind.model_config(arch, sizes)
    want = PARENT[arch]
    params = {s: deploy.make_params(cfg, deploy.seed_key(s), kind)
              for s in SEEDS}
    assert {s: _digest(p) for s, p in params.items()} == want["weights"]
    tok = _tokens(sizes["vocab"])
    ref = np.asarray(kind.forward(params[SEEDS[1]], sizes, tok, "ref"))
    fp8 = np.asarray(kind.forward(params[SEEDS[1]], sizes, tok, "fp8"))
    assert ref.argmax(-1).tolist() == want["argmax"]
    # the CPU's matrix products round differently with the thread count:
    # the reference by < 1e-6 here, fp8's rounding of it by up to 1e-3
    np.testing.assert_allclose(ref.max(-1), want["top"], atol=2e-6, rtol=0)
    np.testing.assert_allclose(fp8.max(-1), want["top8"], atol=1e-2, rtol=0)
    got = _probe_counts({arch: sizes}, {arch: kind}, arch)
    assert got == want["counts"]


TOY = '''"""A kind for the tests: the program's hybrid smoke pattern (Mamba and
attention mixers, MoE and dense feed-forwards), a rule for each of its
further leaves, and a forward pass and counts that show they were used."""
import dataclasses

import jax
import jax.numpy as jnp

from repro.configs import jamba_52b

LEAVES = {"b": lambda z, sd: 0.25 * z - 4.0,
          "A_log": lambda z, sd: jnp.abs(z),
          "D": lambda z, sd: 1.0 + 0.5 * z}


def model_config(name, sizes):
    return dataclasses.replace(jamba_52b.smoke(), name=name,
                               vocab=sizes["vocab"])


def forward(params, sizes, tokens, mode):
    """Puts all weight on the next token id, or with fp8 the one after."""
    step = 2 if mode == "fp8" else 1
    return jax.nn.one_hot((tokens + step) % sizes["vocab"], sizes["vocab"])


def prefill_flops(a, n_tokens):
    return 1000 * n_tokens


def decode_flops(a, kv_lens):
    return 7 * sum(kv_lens)


def decode_attention_cost(a, kv_lens):
    return 3 * sum(kv_lens), 5 * len(list(kv_lens))
'''


@pytest.fixture
def toy_dir(tmp_path, monkeypatch):
    """A benchmark directory whose ``archs/`` holds the dense kind and the
    toy one."""
    bench_dir = tmp_path / "benchmarks" / "chip"
    shutil.copytree(cellmod.BENCH_DIR / "archs", bench_dir / "archs",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (bench_dir / "archs" / "toy.py").write_text(TOY)
    monkeypatch.setattr(cellmod, "BENCH_DIR", bench_dir)
    return bench_dir


def _toy_config():
    """The cloud-only deployment (one engine) served by the toy kind."""
    c = json.loads((REPO / "benchmarks/chip/configs/cloud_only.json")
                   .read_text())
    c["architectures"] = {"toy-hybrid": {"kind": "toy", "vocab": 512}}
    c["served_by"] = {m: "toy-hybrid" for m in c["served_by"]}
    return c


def test_a_new_kind_needs_only_its_file(toy_dir):
    config = _toy_config()
    seed = 2**31 + 5
    dep = deploy.build(config, seed)
    toy = cellmod.load_kind("toy")
    assert dep.kinds == {"toy-hybrid": toy}

    # every leaf through its rule, from the draw of its index: the three
    # shared rules for table, w and g, the toy's for the rest
    shared = {"table": lambda z, sd: z * 0.02,
              "w": lambda z, sd: z / math.sqrt(sd.shape[-2]),
              "g": lambda z, sd: 1.0 + 0.1 * z}
    cfg = toy.model_config("toy-hybrid", config["architectures"]["toy-hybrid"])
    shapes = jax.eval_shape(lambda k: lm.init(k, cfg), jax.random.key(0))
    leaves = jax.tree_util.tree_flatten_with_path(shapes)[0]
    names = [path[-1].key for path, _ in leaves]
    assert set(names) == {"table", "w", "g", "b", "A_log", "D"}

    @jax.jit
    def expected(key):
        return [(shared.get(n) or toy.LEAVES[n])(
            jax.random.normal(jax.random.fold_in(key, j), sd.shape,
                              jnp.float32), sd).astype(sd.dtype)
                for j, (n, (_, sd)) in enumerate(zip(names, leaves))]

    want = expected(jax.random.fold_in(deploy.seed_key(seed), 0))
    got = jax.tree_util.tree_leaves(dep.params["toy-hybrid"])
    assert len(got) == len(want)
    for (path, sd), g, w in zip(leaves, got, want):
        assert g.dtype == sd.dtype and g.shape == sd.shape
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w),
                                      err_msg=jax.tree_util.keystr(path))

    # Probe counts and the check go through the toy's functions
    counts = _probe_counts(dep.archs, dep.kinds, "toy-hybrid")
    assert counts["model_flops"] == 1000 * 24 + 7 * (29 + 54)
    assert counts["attn_min_s"] == pytest.approx(max(
        3 * 83 / cpu_smoke.PEAK["bf16_flops_per_s"],
        5 * 2 / cpu_smoke.PEAK["hbm_bytes_per_s"]))
    prompt = np.array([5, 9, 2], np.int32)
    rows = [(prompt, [3, 4, 5]), (prompt, [3, 7])]
    spec = {"sample_requests": 2, "pad_to": 8}
    served = checks.gaps(dep, rows, spec, "toy-hybrid")
    # 2 -> 3 -> 4 -> 5 follow the toy's next id; 3 -> 7 does not
    assert served.tolist() == [0, 0, 0, 0, 1]
    assert checks.gaps(dep, rows, spec, "toy-hybrid",
                       control=True).tolist() == [1] * 5
    got = checks.check(dep, {"toy-hybrid": rows}, spec, {"toy-hybrid": 0.5})
    assert got["toy-hybrid"]["value"] == 1.0
    jax.clear_caches()


@pytest.mark.parametrize("kind", [None, "no_such_kind", "../dense"])
def test_an_architecture_without_a_known_kind_raises_naming_the_file(
        tmp_path, monkeypatch, kind):
    bench = cellmod.load_benchmark()
    cfg = json.loads((cellmod.BENCH_DIR / "configs/testbed.json").read_text())
    sizes = cfg["architectures"]["qwen3-1.7b"]
    del sizes["kind"]
    if kind is not None:
        sizes["kind"] = kind
    path = tmp_path / "benchmarks/chip/configs/testbed.json"
    path.parent.mkdir(parents=True)
    path.write_text(json.dumps(cfg))
    monkeypatch.setattr(cellmod, "REPO", tmp_path)
    with pytest.raises((ValueError, FileNotFoundError)) as e:
        cellmod.load_cell("testbed.paper_mix", bench)
    assert str(path) in str(e.value) and "qwen3-1.7b" in str(e.value)
    if kind is not None:
        assert str(cellmod.BENCH_DIR / "archs" / f"{kind}.py") in str(e.value)
    else:
        assert '"kind"' in str(e.value)
