"""Decides ``correct``: what the timed window served, against the reference.

Once the window has closed, a sample of the requests it finished is drawn
from the seed for each served architecture, always with the one that served
the most tokens in it. For each request the reference runs once over its
prompt (as the engine was handed it) followed by its served tokens, in the
forward pass of the architecture's kind (``archs/<kind>.py``), and
reads, at every position that produced a served token, how far that token's
logit lies below the reference's best. The widest such gap per architecture
is held to the configuration's limit (``check.logit_gap_max``).

The served tokens are greedy, so with exact arithmetic every gap is 0; what
is left is the rounding of the bfloat16 path.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip import deploy, reference


def served_by(dep: deploy.Deployment, rid: int) -> Tuple[int, Dict]:
    """The pair whose engine produced the result in ``done`` (a hedged
    request ran on two)."""
    res = dep.srv.done[rid]
    for p, eng in dep.srv.engines.items():
        if eng.results.get(rid) is res:
            return p, res
    raise KeyError(f"no engine holds the result of request {rid}")


def sample(dep: deploy.Deployment, rids: List[int], seed: int, k: int
           ) -> Dict[str, List[Tuple[np.ndarray, List[int]]]]:
    """Per architecture: up to ``k`` (prompt ids, served tokens) pairs."""
    rows: Dict[str, List[Tuple[int, np.ndarray, List[int]]]] = {}
    for rid in rids:
        p, res = served_by(dep, rid)
        rows.setdefault(dep.pair_arch[p], []).append(
            (rid, dep.captured[(p, rid)], list(res["tokens"])))
    rng = np.random.default_rng(np.random.SeedSequence([seed, 31]))
    out = {}
    for arch, rs in sorted(rows.items()):
        rs.sort(key=lambda r: (-len(r[2]), r[0]))
        rest = rs[1:]
        pick = rng.choice(len(rest), size=min(k - 1, len(rest)),
                          replace=False) if rest else []
        out[arch] = [rs[0][1:]] + [rest[i][1:] for i in sorted(pick)]
    return out


def batch(rows, k: int, pad_to: int):
    """(tokens, targets, mask), each (k, pad_to): row r holds the prompt
    then the served tokens; ``targets[r, s]`` is the token served after
    position ``s`` and ``mask`` marks those positions."""
    tokens = np.zeros((k, pad_to), np.int32)
    targets = np.zeros((k, pad_to), np.int32)
    mask = np.zeros((k, pad_to), bool)
    for r, (prompt, served) in enumerate(rows):
        L, n = len(prompt), len(served)
        if L + n > pad_to:
            raise ValueError(f"a request of {L}+{n} tokens exceeds the "
                             f"check's pad_to={pad_to}")
        tokens[r, :L] = prompt
        tokens[r, L:L + n] = served
        targets[r, L - 1:L - 1 + n] = served
        mask[r, L - 1:L - 1 + n] = True
    return tokens, targets, mask


def gaps(dep: deploy.Deployment, rows, spec: Dict, arch: str,
         control: bool = False) -> np.ndarray:
    """The gaps of every compared position of ``rows`` (served tokens, or
    with ``control`` the fp8 control's first choice)."""
    tok, tgt, msk = batch(rows, spec["sample_requests"], spec["pad_to"])
    fwd, sizes = dep.kinds[arch].forward, reference.Sizes(dep.archs[arch])
    p = dep.params[arch]
    if control:
        g = reference.control_gaps(p, fwd, sizes, jnp.asarray(tok),
                                   jnp.asarray(msk))
    else:
        g = reference.served_gaps(p, fwd, sizes, jnp.asarray(tok),
                                  jnp.asarray(tgt), jnp.asarray(msk))
    return np.asarray(jax.device_get(g))[msk]


def check(dep: deploy.Deployment, rows: Dict, spec: Dict,
          limits: Dict[str, float], control: bool = False) -> Dict:
    """{arch: {"value": widest gap, "limit": ..., "tokens": n compared}}
    for every architecture in ``rows`` (``sample``'s result)."""
    out = {}
    for arch, rs in rows.items():
        g = gaps(dep, rs, spec, arch, control)
        out[arch] = {"value": float(g.max()), "limit": limits[arch],
                     "tokens": int(g.size), "requests": len(rs)}
    return out
