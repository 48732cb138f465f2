"""The one traffic generator: reads a mix file (``traffic/<mix>.json``) and
makes a cell's requests and arrival times from ``--seed``.

The request text comes from a copy of the paper's four-dataset generators
(MBPP, GSM8K, SQuAD, HellaSwag; ``repro.workload.datasets``) and the arrival
gaps from a copy of ``repro.workload.arrivals.poisson_arrivals``, so that no
later change to the program can change the traffic it is measured with.

Every seed gets the same set of requests and output lengths and the same
arrival times, drawn once from the mix's ``pool_seed``; the seed only deals
the requests onto the arrivals. A run's work is then the same on every seed
and its numbers do not swing with the Poisson count, the clumping of the
arrivals or the sample mean of the output lengths.

A mix file holds:

* ``prompts``: ``"paper_mix"`` - the four datasets in the paper's
  round-robin order (MBPP, GSM8K, HellaSwag, SQuAD), 1/4 each;
* ``arrival``: ``{"law": "poisson", "rate": <requests/s>}``;
* ``output``: ``{"law": "resp_mean_ceil"}`` (the generator's own response
  length, rounded up) or ``{"law": "lognormal", "median": m, "sigma": s,
  "min": lo, "max": hi}``;
* ``pool_seed``: the seed of the fixed pool.
"""
from __future__ import annotations

import dataclasses
import math
import re
from typing import Dict, List

import numpy as np

DATASETS = ("mbpp", "gsm8k", "squad", "hellaswag")
ORDER = ("mbpp", "gsm8k", "hellaswag", "squad")

# ---------------------------------------------------------------------------
# copy of repro.workload.tokenizer (token counting only)
# ---------------------------------------------------------------------------

_WORD_RE = re.compile(r"[A-Za-z]+|\d|[^\sA-Za-z\d]")
_CHARS_PER_SUBWORD = 4


def count_tokens(text: str) -> int:
    n = 0
    for piece in _WORD_RE.findall(text):
        if piece.isalpha():
            n += max(1, (len(piece) + _CHARS_PER_SUBWORD - 1)
                     // _CHARS_PER_SUBWORD)
        else:
            n += 1
    return n


def text_bytes(text: str) -> int:
    return len(text.encode("utf-8"))


# ---------------------------------------------------------------------------
# copy of repro.workload.datasets generators
# ---------------------------------------------------------------------------

_NOUNS = ("list", "string", "matrix", "graph", "tree", "array", "number",
          "interval", "sequence", "dictionary", "window", "queue", "stack",
          "polygon", "vector", "substring", "digit", "prime", "factor", "path")
_VERBS = ("compute", "return", "find", "merge", "sort", "count", "reverse",
          "partition", "validate", "transform", "encode", "filter", "rotate",
          "flatten", "search")
_TOPICS = ("the river festival", "a school fundraiser", "the bake sale",
           "a train journey", "the orchard harvest", "a paint job",
           "the reading challenge", "a cycling trip", "the garden fence",
           "a grocery run")
_ENTITIES = ("the Amazon basin", "the 1896 Olympics", "photosynthesis",
             "the printing press", "plate tectonics", "the Roman senate",
             "migratory birds", "the telegraph", "alpine glaciers",
             "the cotton trade")
_SCENES = ("a man is waxing a car", "a woman ties her climbing harness",
           "two chefs plate a dessert", "a child stacks wooden blocks",
           "a runner stretches at the track", "a barista steams milk",
           "a violinist tunes her strings", "a diver checks his gauge")
_CONSTRAINTS = ("You must output only the final answer.",
                "Output must be a single integer.",
                "Only return the function body.",
                "The answer must be given in meters.",
                "You must respond with the letter of the ending only.")


@dataclasses.dataclass
class Prompt:
    """One generated request, with the fields of the program's ``Request``."""

    dataset: str
    index: int
    text: str
    prompt_tokens: int
    query_bytes: int
    resp_tokens_mean: float
    difficulty: float
    sentence_count: int
    has_constraint: bool


def _mbpp(rng, i):
    difficulty = float(rng.beta(2.6, 2.4))
    n_clauses = 1 + int(round(difficulty * 4)) + int(rng.integers(0, 2))
    body = []
    for _ in range(n_clauses):
        body.append(f"The function should {rng.choice(_VERBS)} the "
                    f"{rng.choice(_NOUNS)} of a given {rng.choice(_NOUNS)}.")
    has_constraint = bool(rng.random() < 0.55)
    text = (f"Write a python function to {rng.choice(_VERBS)} a "
            f"{rng.choice(_NOUNS)}. " + " ".join(body)
            + (" " + str(rng.choice(_CONSTRAINTS)) if has_constraint else "")
            + " Your code should pass these tests: assert f(" +
            ", ".join(str(int(rng.integers(0, 99))) for _ in range(3)) + ")")
    return _pack("mbpp", i, text, 20 + 16 * difficulty, difficulty)


def _gsm8k(rng, i):
    difficulty = float(rng.beta(3.0, 2.2))
    steps = 2 + int(round(difficulty * 5))
    topic = rng.choice(_TOPICS)
    body = [f"For {topic}, Maya buys {int(rng.integers(2, 60))} items at "
            f"{int(rng.integers(1, 15))} dollars each."]
    for _ in range(steps - 1):
        body.append(f"Then she "
                    f"{rng.choice(['sells', 'adds', 'returns', 'splits'])} "
                    f"{int(rng.integers(1, 40))} of them with "
                    f"{int(rng.integers(2, 9))} friends.")
    has_constraint = bool(rng.random() < 0.35)
    text = (" ".join(body) + " How many does she have left?"
            + (" " + str(rng.choice(_CONSTRAINTS)) if has_constraint else ""))
    return _pack("gsm8k", i, text, 18 + 14 * difficulty, difficulty)


def _squad(rng, i):
    difficulty = float(rng.beta(2.0, 3.2))
    ctx_sent = 3 + int(round(difficulty * 6)) + int(rng.integers(0, 3))
    ent = rng.choice(_ENTITIES)
    ctx = []
    for _ in range(ctx_sent):
        ctx.append(f"Historians note that {ent} influenced "
                   f"{rng.choice(_ENTITIES)} during the period of "
                   f"{int(rng.integers(1700, 1990))}.")
    has_constraint = bool(rng.random() < 0.2)
    text = ("Context: " + " ".join(ctx) +
            f" Question: When did {ent} influence the region?"
            + (" " + str(rng.choice(_CONSTRAINTS)) if has_constraint else ""))
    return _pack("squad", i, text, 7 + 8 * difficulty, difficulty)


def _hellaswag(rng, i):
    difficulty = float(rng.beta(2.5, 2.5))
    scene = rng.choice(_SCENES)
    endings = [f"({c}) then {rng.choice(_SCENES)}." for c in "ABCD"]
    has_constraint = bool(rng.random() < 0.6)
    text = (f"Complete the scenario: {scene}. Choose the most plausible "
            "ending: " + " ".join(endings)
            + (" " + str(rng.choice(_CONSTRAINTS)) if has_constraint else ""))
    return _pack("hellaswag", i, text, 3 + 3 * difficulty, difficulty)


def _pack(ds, i, text, resp_mean, difficulty) -> Prompt:
    return Prompt(
        dataset=ds, index=i, text=text, prompt_tokens=count_tokens(text),
        query_bytes=text_bytes(text), resp_tokens_mean=float(resp_mean),
        difficulty=difficulty,
        sentence_count=max(1, text.count(".") + text.count("?")),
        has_constraint=any(k in text
                           for k in ("must", "only", "Output", "output")))


_GENERATORS = {"mbpp": _mbpp, "gsm8k": _gsm8k, "squad": _squad,
               "hellaswag": _hellaswag}


def generate(dataset: str, n: int, seed: int = 0) -> List[Prompt]:
    rng = np.random.default_rng(
        np.random.SeedSequence([seed, DATASETS.index(dataset)]))
    return [_GENERATORS[dataset](rng, i) for i in range(n)]


def paper_mix(n: int, seed: int = 0) -> List[Prompt]:
    """``n`` prompts in the paper's round-robin dataset order."""
    per = (n + len(ORDER) - 1) // len(ORDER)
    pools = {name: generate(name, per, seed=seed) for name in ORDER}
    return [pools[ORDER[i % len(ORDER)]][i // len(ORDER)] for i in range(n)]


# ---------------------------------------------------------------------------
# copy of repro.workload.arrivals.poisson_arrivals
# ---------------------------------------------------------------------------

def poisson_arrivals(n: int, rate: float, seed: int = 0) -> np.ndarray:
    rng = np.random.default_rng(np.random.SeedSequence([seed, 11]))
    gaps = rng.exponential(1.0 / rate, size=n)
    return np.cumsum(gaps).astype(np.float32)


# ---------------------------------------------------------------------------
# the cell's schedule
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Item:
    due: float            # seconds after the window opens
    prompt: Prompt
    max_new_tokens: int


_PROMPTS = {"paper_mix": paper_mix}


def output_lengths(law: Dict, prompts: List[Prompt], seed: int) -> np.ndarray:
    kind = law["law"]
    if kind == "resp_mean_ceil":
        return np.asarray([math.ceil(p.resp_tokens_mean) for p in prompts],
                          np.int64)
    if kind == "lognormal":
        rng = np.random.default_rng(np.random.SeedSequence([seed, 23]))
        x = rng.lognormal(math.log(law["median"]), law["sigma"],
                          size=len(prompts))
        return np.clip(np.round(x), law["min"], law["max"]).astype(np.int64)
    raise ValueError(f"unknown output-length law {kind!r}")


def pool(mix: Dict, seconds: float, rate: float = None):
    """The fixed pool for ``seconds`` of this mix: prompts, output lengths
    and arrival gaps, all from ``pool_seed``. ``rate`` overrides the mix's
    (the knee sweep offers several)."""
    rate = float(mix["arrival"]["rate"] if rate is None else rate)
    if mix["arrival"]["law"] != "poisson":
        raise ValueError(f"unknown arrival law {mix['arrival']['law']!r}")
    n = max(1, int(round(rate * seconds)))
    ps = int(mix["pool_seed"])
    prompts = _PROMPTS[mix["prompts"]](n, seed=ps)
    lengths = output_lengths(mix["output"], prompts, ps)
    gaps = np.diff(poisson_arrivals(n, rate, seed=ps), prepend=0.0)
    return prompts, lengths, gaps.astype(np.float64)


def schedule(mix: Dict, seconds: float, seed: int,
             rate: float = None) -> List[Item]:
    """The requests due in a window of ``seconds``, ordered by ``seed``.

    The arrival times are the pool's on every seed, so that every run meets
    the same bursts; the seed deals the requests onto them. The gaps are
    scaled so that the last request is due half a gap before the window
    closes: all of them are due inside it, at the offered rate."""
    prompts, lengths, gaps = pool(mix, seconds, rate)
    n = len(prompts)
    rng = np.random.default_rng(np.random.SeedSequence([seed, 29]))
    order = rng.permutation(n)
    due = np.cumsum(gaps) * (seconds * (1.0 - 0.5 / n) / gaps.sum())
    return [Item(due=float(due[k]), prompt=prompts[i],
                 max_new_tokens=int(lengths[i]))
            for k, i in enumerate(order)]
