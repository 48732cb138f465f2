"""The Jamba kind (``archs/jamba.py``) and its cells: the reference against
transformers' ``JambaForCausalLM``, the configuration file against the
published config it copies, the counts against the parameter shapes, and
the new cell end to end on the CPU at small widths."""
from __future__ import annotations

import json
import math
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(REPO), str(REPO / "src")]

from benchmarks.chip import checks, cpu_smoke, deploy, harness  # noqa: E402
from benchmarks.chip.cell import load_kind  # noqa: E402
from repro.models import lm  # noqa: E402

KIND = load_kind("jamba")
SEED = 2**31 + 91
# one period at d 64, all four experts held unless a test says otherwise
SMALL = dict(kind="jamba", n_layers=8, d_model=64, n_heads=4, n_kv_heads=2,
             head_dim=16, d_ff=96, n_experts=4, experts_held=[0, 4],
             top_k=2, d_state=16, d_conv=4, expand=2, dt_rank=8, vocab=128,
             norm_eps=1e-6, dtype="float32")
CONFIG = json.loads((REPO / "benchmarks/chip/configs/jamba2_mini.json")
                    .read_text())


def _params(sizes, seed=SEED):
    cfg = KIND.model_config("jamba-small", sizes)
    return cfg, deploy.make_params(cfg, deploy.seed_key(seed), KIND)


def _hf_state(params, sizes):
    """The benchmark's weights under transformers' names and layouts."""
    t = lambda a: np.asarray(a, np.float32).T
    sd = {"model.embed_tokens.weight": params["embed"]["table"],
          "lm_head.weight": params["head"]["table"],
          "model.final_layernorm.weight": params["final_norm"]["g"]}
    for i, (mixer, ffn) in enumerate(KIND.PERIOD):
        p = jax.tree.map(lambda a: a[0], params["blocks"][i])
        pre = f"model.layers.{i}."
        sd[pre + "input_layernorm.weight"] = p["norm1"]["g"]
        sd[pre + "pre_ff_layernorm.weight"] = p["norm2"]["g"]
        m = p["mixer"]
        if mixer == "attn":
            for n in ("q", "k", "v", "o"):
                sd[pre + f"self_attn.{n}_proj.weight"] = t(m[f"w{n}"]["w"])
        else:
            mp = pre + "mamba."
            for n in ("in_proj", "x_proj", "dt_proj", "out_proj"):
                sd[mp + n + ".weight"] = t(m[n]["w"])
            sd[mp + "dt_proj.bias"] = m["dt_proj"]["b"]
            sd[mp + "conv1d.weight"] = t(m["conv"]["w"])[:, None, :]
            sd[mp + "conv1d.bias"] = m["conv"]["b"]
            sd[mp + "A_log"], sd[mp + "D"] = m["A_log"], m["D"]
            for n, hf in (("dt", "dt"), ("b", "b"), ("c", "c")):
                sd[mp + f"{hf}_layernorm.weight"] = m[f"{n}_norm"]["g"]
        f = p["ffn"]
        names = (("wg", "gate_proj"), ("wi", "up_proj"), ("wo", "down_proj"))
        if ffn == "moe":
            sd[pre + "feed_forward.router.weight"] = t(f["router"]["w"])
            for e in range(sizes["n_experts"]):
                for ours, hf in names:
                    sd[pre + f"feed_forward.experts.{e}.{hf}.weight"] = t(
                        f[ours]["w"][e])
        else:
            for ours, hf in names:
                sd[pre + f"feed_forward.{hf}.weight"] = t(f[ours]["w"])
    return {k: np.asarray(v, np.float32) for k, v in sd.items()}


def test_reference_matches_transformers_jamba():
    """The reference's logits equal ``JambaForCausalLM``'s (transformers'
    own Mamba scan, experts and attention) on the same tiny weights, every
    expert held."""
    torch = pytest.importorskip("torch")
    transformers = pytest.importorskip("transformers")
    a = SMALL
    hf_cfg = transformers.JambaConfig(
        vocab_size=a["vocab"], hidden_size=a["d_model"],
        intermediate_size=a["d_ff"], num_hidden_layers=a["n_layers"],
        num_attention_heads=a["n_heads"], num_key_value_heads=a["n_kv_heads"],
        num_experts=a["n_experts"], num_experts_per_tok=a["top_k"],
        attn_layer_offset=4, attn_layer_period=8, expert_layer_offset=1,
        expert_layer_period=2, mamba_d_state=a["d_state"],
        mamba_d_conv=a["d_conv"], mamba_expand=a["expand"],
        mamba_dt_rank=a["dt_rank"], mamba_conv_bias=True,
        mamba_proj_bias=False, rms_norm_eps=a["norm_eps"],
        use_mamba_kernels=False, tie_word_embeddings=False)
    model = transformers.JambaForCausalLM(hf_cfg).float().eval()
    _, params = _params(a)
    sd = _hf_state(params, a)
    assert set(sd) == set(model.state_dict())
    model.load_state_dict({k: torch.from_numpy(v) for k, v in sd.items()})
    tokens = np.asarray(jax.random.randint(jax.random.key(4), (2, 20), 0,
                                           a["vocab"]))
    with torch.no_grad():
        want = model(torch.from_numpy(tokens).long(),
                     use_cache=False).logits.numpy()
    with jax.default_matmul_precision("highest"):
        got = np.asarray(KIND.forward(params, a, jnp.asarray(tokens)))
    assert np.abs(want).max() > 0.1
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=0)


def test_configuration_copies_the_published_config():
    """The file's top level is the catalog's config with only ``reduced``
    changed, the architecture entry says the same, and the kind's period
    puts attention and experts where the offsets say."""
    a = CONFIG["architectures"]["jamba2-mini"]
    published = dict(CONFIG, **CONFIG["published"])
    assert set(CONFIG["reduced"]) - {"served_by"} == set(CONFIG["published"])
    same = {"hidden_size": "d_model", "intermediate_size": "d_ff",
            "num_attention_heads": "n_heads",
            "num_key_value_heads": "n_kv_heads",
            "num_experts_per_tok": "top_k", "mamba_d_state": "d_state",
            "mamba_d_conv": "d_conv", "mamba_expand": "expand",
            "mamba_dt_rank": "dt_rank", "rms_norm_eps": "norm_eps",
            "num_hidden_layers": "n_layers", "vocab_size": "vocab"}
    for key, ours in same.items():
        assert CONFIG[key] == a[ours], key
    assert a["head_dim"] * a["n_heads"] == CONFIG["hidden_size"]
    assert a["n_experts"] == published["num_experts"]
    lo, hi = a["experts_held"]
    assert hi - lo == CONFIG["num_experts"]
    assert CONFIG["vocab_size"] * 8 == published["vocab_size"]
    assert CONFIG["num_hidden_layers"] % CONFIG["attn_layer_period"] == 0
    for i, (mixer, ffn) in enumerate(KIND.PERIOD):
        assert (mixer == "attn") == (i % CONFIG["attn_layer_period"]
                                     == CONFIG["attn_layer_offset"])
        assert (ffn == "moe") == (i % CONFIG["expert_layer_period"]
                                  == CONFIG["expert_layer_offset"])


def test_counts_follow_the_parameter_shapes():
    """A token's FLOPs are twice the matrix weights it meets (an expert
    layer's weighted by top_k × held / n_experts of its held experts, i.e.
    top_k / n_experts of each), plus the conv taps and the recurrence."""
    a = dict(SMALL, experts_held=[0, 2])
    cfg, _ = _params(a)
    shapes = jax.eval_shape(lambda k: lm.init(k, cfg), jax.random.key(0))
    weights = 0.0
    for path, sd in jax.tree_util.tree_flatten_with_path(
            shapes["blocks"])[0]:
        keys = [getattr(k, "key", None) for k in path]
        if keys[-1] != "w" or "conv" in keys:
            continue
        n = math.prod(sd.shape[1:])     # one period
        if "ffn" in keys and cfg.pattern[path[0].idx][1] == "moe" \
                and "router" not in keys:
            n *= a["top_k"] / a["n_experts"]
        weights += n
    di = a["expand"] * a["d_model"]
    recurrence = 7 * (2 * a["d_conv"] * di + 6 * di * a["d_state"])
    assert KIND.token_flops(a) == pytest.approx(2 * weights + recurrence)
    head = 2 * a["vocab"] * a["d_model"]
    kv = [5, 9]
    assert KIND.decode_flops(a, kv) == pytest.approx(
        2 * (KIND.token_flops(a) + head)
        + 4 * a["n_heads"] * a["head_dim"] * sum(kv))
    flops, nbytes = KIND.decode_attention_cost(a, kv)
    assert flops == 4 * a["n_heads"] * a["head_dim"] * sum(kv)
    assert nbytes == (2 * a["n_kv_heads"] * a["head_dim"] * sum(kv) * 2
                      + 2 * 2 * a["n_heads"] * a["head_dim"] * 2)


def _run(name, trace=False, **kwargs):
    c = cpu_smoke.cell(name)
    archs = ({"jamba2-mini": dict(SMALL, dtype="bfloat16",
                                  experts_held=[0, 2])}
             if name.startswith("jamba") else cpu_smoke.archs(c))
    return harness.run_cell(c, SEED, 2.0, trace, peak=cpu_smoke.PEAK,
                            t_start=time.monotonic(), archs=archs, **kwargs)


@pytest.mark.parametrize("name", ["jamba2_mini.long_decode"])
def test_new_cell_reports_its_end_to_end_metrics(name):
    r = _run(name)
    assert r["correct"] is True, r["checks"]
    assert r["failed"] == 0 and r["attempted"] > 0
    want = {m["name"] for m in cpu_smoke.cell(name).end_to_end}
    assert set(r["metrics"]) == want == {"setup_s", "tpot_p95_ms",
                                         "output_tok_s"}
    assert all(m["value"] is not None and m["value"] > 0
               for m in r["metrics"].values())


def test_jamba_cell_traced_and_its_control_fails():
    """A traced run reads only the cell's per-layer metrics (the device
    ones stay silent on the CPU), and the fp8 control of the same served
    requests lies outside the widest sound gap."""
    name = "jamba2_mini.long_decode"
    c = cpu_smoke.cell(name)
    assert {m["name"] for m in c.per_layer} == {"hybrid_decode_step_ms",
                                                "hybrid_step_mfu"}
    r = _run(name, trace=True)
    assert r["correct"] is True
    assert set(r["metrics"]) <= {"hybrid_decode_step_ms", "hybrid_step_mfu"}
    archs = {"jamba2-mini": dict(SMALL, dtype="bfloat16",
                                 experts_held=[0, 2])}
    dep = deploy.build(c.config, SEED, archs)
    eng = next(iter(dep.srv.engines.values()))
    prompts = [np.arange(3 + k, dtype=np.int32) * 7 % 128 for k in range(2)]
    for i, prompt in enumerate(prompts):    # greedy answers, served path
        eng.submit(100 + i, prompt, max_new_tokens=12)
    deploy._drain(dep.srv, [eng, eng], [100, 101])
    rows = [(prompt, list(eng.results[100 + i]["tokens"]))
            for i, prompt in enumerate(prompts)]
    spec = dict(c.config["check"], sample_requests=2, pad_to=32)
    sound = checks.gaps(dep, rows, spec, "jamba2-mini")
    control = checks.gaps(dep, rows, spec, "jamba2-mini", control=True)
    # at d 64 the logits span little: twice the widest sound gap here; the
    # chip's margin at the configuration's widths is in PERF.md
    assert control.max() > 2 * max(sound.max(), 1e-3)
