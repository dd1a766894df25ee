"""One general traffic generator; each mix is a JSON file of parameters.

A mix file ``bench/traffic/<mix>.json`` holds:

``arrival``   ``{"process": "poisson", "rate_per_s": r}`` — open loop.
``tenants``   ``{"count": n, "zipf_s": s}`` — popularity rank k is drawn
              with weight ``k ** -s``.
``prompt``    ``{"median", "sigma", "min", "max"}`` — clipped lognormal
              token counts of the part of the prompt each request brings.
``output``    the same, for the tokens each request asks to generate.
``system_prompt`` (optional) ``{"tokens": t}`` — every tenant owns one
              prompt of ``t`` tokens that starts each of its requests;
              set-up sends it once per tenant (see ``warm_prefixes``).
``shape_seed`` the seed of the sizes and arrival gaps.

The sizes and the gaps between arrivals come from ``shape_seed`` alone, so
every run seed offers the same work: the run seed only permutes their order
and draws tenants and token ids.  Two runs with different seeds then differ
by the order of the same requests, not by how much there is to do.
"""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path
from typing import Dict, List, Optional

import numpy as np

FIRST_TOKEN_ID = 2  # ids 0 and 1 stay free, as the program's own drivers keep them


@dataclasses.dataclass
class Request:
    due_s: float  # seconds after the window opens
    tenant: int
    prompt: np.ndarray  # int32 token ids, system prompt included
    max_new_tokens: int


def load_mix(path: Path) -> Dict:
    with open(path) as f:
        return json.load(f)


def clipped_lognormal(rng: np.random.Generator, spec: Dict, n: int) -> np.ndarray:
    x = rng.lognormal(np.log(spec["median"]), spec["sigma"], size=n)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def zipf_weights(count: int, s: float) -> np.ndarray:
    w = np.arange(1, count + 1, dtype=np.float64) ** -s
    return w / w.sum()


def shapes(mix: Dict, seconds: float, rate: Optional[float] = None):
    """(gaps, prompt lengths, output lengths) of every request due within
    ``seconds`` — drawn from ``shape_seed`` only."""
    rate = mix["arrival"]["rate_per_s"] if rate is None else rate
    if mix["arrival"]["process"] != "poisson":
        raise ValueError(f"unknown arrival process {mix['arrival']['process']!r}")
    rng = np.random.default_rng(mix["shape_seed"])
    n = max(16, int(rate * seconds * 2) + 64)
    gaps = rng.exponential(1.0 / rate, size=n)
    keep = int(np.searchsorted(np.cumsum(gaps), seconds, side="left"))
    gaps = gaps[:keep]
    return gaps, clipped_lognormal(rng, mix["prompt"], keep), clipped_lognormal(
        rng, mix["output"], keep
    )


def system_prompts(mix: Dict, vocab: int, seed: int) -> Optional[np.ndarray]:
    """(tenants, t) token ids of each tenant's system prompt, or None."""
    sp = mix.get("system_prompt")
    if not sp:
        return None
    rng = np.random.default_rng([seed, 1])
    return rng.integers(
        FIRST_TOKEN_ID, vocab, size=(mix["tenants"]["count"], sp["tokens"])
    ).astype(np.int32)


def generate(mix: Dict, vocab: int, seed: int, seconds: float,
             rate: Optional[float] = None) -> List[Request]:
    """The requests due in a window of ``seconds``, in arrival order."""
    gaps, plen, olen = shapes(mix, seconds, rate)
    n = len(gaps)
    rng = np.random.default_rng([seed, 0])
    gaps = gaps[rng.permutation(n)]
    order = rng.permutation(n)
    plen, olen = plen[order], olen[order]
    w = zipf_weights(mix["tenants"]["count"], mix["tenants"]["zipf_s"])
    # popularity rank → tenant id, permuted per seed so the hot tenants move
    rank_to_tenant = rng.permutation(mix["tenants"]["count"])
    tenants = rank_to_tenant[rng.choice(len(w), size=n, p=w)]
    sysp = system_prompts(mix, vocab, seed)
    due = np.cumsum(gaps) - gaps[0] if n else gaps
    out = []
    for i in range(n):
        user = rng.integers(FIRST_TOKEN_ID, vocab, size=int(plen[i])).astype(np.int32)
        prompt = user if sysp is None else np.concatenate([sysp[tenants[i]], user])
        out.append(Request(float(due[i]), int(tenants[i]), prompt, int(olen[i])))
    return out


def prefix_warmup(mix: Dict, vocab: int, seed: int) -> List[Request]:
    """Set-up traffic that leaves each tenant's system prompt in the prefix
    cache, as a long-running deployment's would be: one request per tenant,
    its system prompt plus the shortest user turn the mix sends, so the
    prompt falls in the same prefill bucket as the window's requests."""
    sysp = system_prompts(mix, vocab, seed)
    if sysp is None:
        return []
    rng = np.random.default_rng([seed, 2])
    out = []
    for t in range(sysp.shape[0]):
        user = rng.integers(
            FIRST_TOKEN_ID, vocab, size=mix["prompt"]["min"]
        ).astype(np.int32)
        out.append(Request(0.0, t, np.concatenate([sysp[t], user]), 1))
    return out
