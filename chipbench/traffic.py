"""The one traffic generator: a mix file's parameters -> seeded requests.

A mix file (``chipbench/traffic/<name>.json``) holds only parameters:

    {"prompt": {"values": [...], "weights": [...]},
     "output": {"median": m, "sigma": s, "min": lo, "max": hi},
     "arrival": {"kind": "saturated"}              # a backlog fills every slot
              | {"kind": "poisson", "rate_per_s": r}}

Requests come in blocks.  A saturated mix's block is ``block`` requests
(the configuration's slot count).  An open-loop block is one ``period``
of arrivals (the window's length): round(rate * period) requests whose
gaps, at the quantiles of the exponential, are scaled to end the block
half a mean gap before the period does, and block b starts at
b * period.  So a window of that length holds the same requests for
every seed.  Every block holds the same multiset of sizes and gaps:
prompt lengths by largest remainder of ``weights * n``, output lengths
at the block's evenly spaced quantiles of the clipped lognormal.  The
seed only permutes each block and draws the token ids, so every seed
serves the same work in another order.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import Iterator, List

import numpy as np


@dataclasses.dataclass
class Item:
    """One generated request: prompt token ids, tokens to serve, and the
    second (from the window's start) at which it is due; saturated mixes
    give every request due 0."""
    idx: int
    prompt: np.ndarray
    gen: int
    due: float


def _counts(weights, n: int) -> List[int]:
    """Largest-remainder apportionment of ``n`` items over ``weights``."""
    w = np.asarray(weights, float) / float(np.sum(weights))
    raw = w * n
    base = np.floor(raw).astype(int)
    order = np.argsort(-(raw - base), kind="stable")
    base[order[:n - int(base.sum())]] += 1
    return base.tolist()


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def block_prompt_lens(mix: dict, n: int) -> np.ndarray:
    p = mix["prompt"]
    return np.repeat(np.asarray(p["values"], int), _counts(p["weights"], n))


def block_output_lens(mix: dict, n: int) -> np.ndarray:
    o = mix["output"]
    nd = NormalDist(math.log(o["median"]), o["sigma"])
    lens = [math.exp(nd.inv_cdf(q)) for q in _quantiles(n)]
    return np.clip(np.rint(lens), o["min"], o["max"]).astype(int)


def block_gaps(mix: dict, n: int, period: float = 0.0) -> np.ndarray:
    """Seconds between consecutive arrivals of a block of ``n`` that
    spans ``period`` seconds (0 for a saturated mix)."""
    a = mix["arrival"]
    if a["kind"] == "saturated":
        return np.zeros(n)
    if a["kind"] == "poisson":
        gaps = -np.log1p(-_quantiles(n)) / float(a["rate_per_s"])
        return gaps * (period * (1 - 0.5 / n) / gaps.sum())
    raise ValueError(f"unknown arrival kind {a['kind']!r}")


def max_len(mix: dict) -> int:
    """Longest prompt + output the mix can send."""
    return max(mix["prompt"]["values"]) + mix["output"]["max"]


def generate(mix: dict, seed: int, block: int, vocab: int,
             period: float = None) -> Iterator[Item]:
    """Endless seeded request stream, a block at a time: ``block``
    requests for a saturated mix; for an open loop the arrivals of one
    ``period`` (seconds; ``block`` arrivals' worth if not given)."""
    rng = np.random.default_rng([seed, 7])
    if mix["arrival"]["kind"] == "saturated":
        period = 0.0
    else:
        rate = float(mix["arrival"]["rate_per_s"])
        if period is None:
            period = block / rate
        block = max(1, round(rate * period))
    prompts = block_prompt_lens(mix, block)
    outs = block_output_lens(mix, block)
    gaps = block_gaps(mix, block, period)
    idx, b = 0, 0
    while True:
        order_p = rng.permutation(block)
        order_o = rng.permutation(block)
        order_g = rng.permutation(block)
        t = b * period
        b += 1
        for k in range(block):
            t += float(gaps[order_g[k]])
            P = int(prompts[order_p[k]])
            yield Item(idx=idx,
                       prompt=rng.integers(2, vocab, (P,)).astype(np.int32),
                       gen=int(outs[order_o[k]]), due=t)
            idx += 1


def warm_prompts(mix: dict, seed: int, vocab: int) -> List[np.ndarray]:
    """One prompt of every length the mix sends, for warming up."""
    rng = np.random.default_rng([seed, 11])
    return [rng.integers(2, vocab, (int(P),)).astype(np.int32)
            for P in sorted(set(mix["prompt"]["values"]))]
