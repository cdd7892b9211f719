"""How ``correct`` is decided: the served tokens against the float32
reference of the cell's architecture file
(``chipbench/architectures/<name>.py``).

After the window, a sample of requests drawn from the seed (always with
the longest request served, and more until ``CHECK_TOKENS`` served
tokens or ``CHECK_REQUESTS`` requests) is run through the reference,
teacher-forced on its prompt and its served tokens.  Every served token
is greedy, so the reference's best logit at its position is where the
program should have been; the number compared is the widest gap by
which a served token's reference logit lies below that best.  The
configuration file states the limit (``check.gap_max``) and where it
came from.  A request that was due and never answered, or ended failed,
is ``missing`` and fails the run whatever the gaps say.
"""
from __future__ import annotations

from types import ModuleType
from typing import Dict, List

import numpy as np

CHECK_TOKENS = 256
CHECK_REQUESTS = 12
MIN_TOKENS = 32


def sample(requests: List, seed: int) -> List:
    """The longest served request, then others in seeded order."""
    served = [r for r in requests if r.tokens is not None and len(r.tokens)]
    if not served:
        return []
    longest = max(served, key=lambda r: (len(r.prompt) + len(r.tokens),
                                         -r.idx))
    rest = [r for r in served if r is not longest]
    order = np.random.default_rng([seed, 5]).permutation(len(rest))
    out, n = [longest], len(longest.tokens)
    for i in order:
        if n >= CHECK_TOKENS or len(out) >= CHECK_REQUESTS:
            break
        out.append(rest[i])
        n += len(rest[i].tokens)
    return out


def _summary(gaps: np.ndarray, prefix: str = "") -> Dict[str, float]:
    return {prefix + "gap_max": float(gaps.max()),
            prefix + "gap_mean": float(gaps.mean()),
            prefix + "flip_share": float((gaps > 0).mean())}


def readings(arch: ModuleType, weights, model: dict, reqs: List,
             control: bool = False) -> Dict[str, float]:
    """Gaps of every served token of ``reqs`` against ``arch``'s
    reference; with ``control`` also those of the fp8 control's first
    choices."""
    if not reqs:
        return {"gap_max": float("nan"), "gap_mean": float("nan"),
                "flip_share": float("nan"), "tokens": 0, "requests": 0}
    t_pad = arch.pad_length(
        [len(r.prompt) + len(r.tokens) - 1 for r in reqs])
    r_pad = arch.pad_rows([len(r.tokens) for r in reqs])
    out = [arch.served_gaps(weights, model, r.prompt, r.tokens,
                            t_pad=t_pad, r_pad=r_pad, control=control)
           for r in reqs]
    if control:
        gaps = np.concatenate([g for g, _ in out])
        ctl = _summary(np.concatenate([c for _, c in out]), "control_")
    else:
        gaps, ctl = np.concatenate(out), {}
    return {**_summary(gaps), **ctl, "tokens": int(gaps.size),
            "requests": len(reqs)}


def verdict(read: Dict[str, float], limits: dict, missing: int):
    """(correct, the numbers compared beside their limits)."""
    limit = limits.get("gap_max")
    check = {
        "gap_max": {"value": read["gap_max"], "limit": limit},
        "missing": {"value": missing, "limit": 0},
        "tokens_compared": {"value": read["tokens"],
                            "at_least": MIN_TOKENS},
    }
    ok = (limit is not None and read["tokens"] >= MIN_TOKENS
          and read["gap_max"] <= limit and missing == 0)
    return bool(ok), check
