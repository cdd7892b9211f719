"""Arithmetic the metric readers share: window counts and percentiles on
the host clock, and program/kernel times from the device trace.

The programs and kernels are found by the names the trace gives them:
the jitted decode step and whole-prompt prefill of ``DecodeEngine``
(``jit_<function>`` on the ``XLA Modules`` line) and a Pallas kernel by
the jitted wrapper it is called through (its custom call's instruction
name on the ``XLA Ops`` line).  A reader that
finds none of them returns None and the metric is left out.  Operations
and bytes come from the cell's architecture file (``rec.arch``).
"""
from __future__ import annotations

from typing import List, Optional

import numpy as np

from chipbench import costs

DECODE_PROGRAM = "jit_sharded_serve_step"
PREFILL_PROGRAM = "jit_prefill_step"
PAGED_DECODE_KERNEL = r"^_vwr_paged_flash_decode"


def pct(xs, q: float) -> Optional[float]:
    """Percentile by linear interpolation; None for no samples."""
    return float(np.percentile(np.asarray(xs, float), q)) if len(xs) else None


# -- host clock ---------------------------------------------------------

def window_tokens(rec) -> int:
    return sum(rec.in_window(t) for r in rec.requests.values()
               for t in r.token_times)


def due_in_window(rec) -> list:
    return [r for r in rec.requests.values() if rec.in_window(r.due)]


def ttft_ms(rec) -> List[float]:
    """Due -> first token, for every request due in the window.  One
    that never got a token counts with its wait up to the end of the
    run (``rec.end``), a bound below its real time."""
    out = []
    for r in due_in_window(rec):
        first = r.token_times[0] if r.token_times else rec.end
        out.append(1e3 * (first - r.due))
    return out


def itl_ms(rec) -> List[float]:
    """Every gap between consecutive tokens of a request that closes
    inside the window."""
    out = []
    for r in rec.requests.values():
        tt = r.token_times
        out += [1e3 * (b - a) for a, b in zip(tt, tt[1:])
                if rec.in_window(b)]
    return out


def queue_wait_ms(rec) -> List[float]:
    """Due -> the admit call that took it, for requests due in the
    window and admitted."""
    return [1e3 * (r.admit_t - r.due) for r in due_in_window(rec)
            if r.admit_t is not None]


# -- device trace -------------------------------------------------------

def decode_programs(rec):
    return rec.trace.modules(DECODE_PROGRAM) if rec.trace else []


def prefill_programs(rec):
    return rec.trace.modules(PREFILL_PROGRAM) if rec.trace else []


def decode_step_ms(rec) -> Optional[float]:
    progs = decode_programs(rec)
    return 1e3 * float(np.mean([p.dur for p in progs])) if progs else None


def _window_steps(rec):
    return [s for s in rec.steps if rec.t0 <= s.t0 and s.t1 <= rec.t1]


def decode_mfu(rec) -> Optional[float]:
    """Model operations of the decode steps over the time their
    programs ran, as a share of the chips' bf16 peak."""
    progs = decode_programs(rec)
    if not progs:
        return None
    m = rec.config["model"]
    flops = sum(rec.arch.decode_flops(m, s.n_active, s.ctx)
                for s in _window_steps(rec))
    t = sum(p.dur for p in progs)
    return 100.0 * flops / (t * rec.peaks["bf16_flops_per_s"]
                            * rec.device["count"])


def decode_step_roofline(rec) -> Optional[float]:
    """Least time the chips could take for the decode steps (weights,
    live KV and operations) over the time their programs ran."""
    progs = decode_programs(rec)
    if not progs:
        return None
    m, n_chips = rec.config["model"], rec.device["count"]
    arch = rec.arch
    least = sum(costs.roofline_s(arch.decode_flops(m, s.n_active, s.ctx),
                                 arch.decode_bytes(m, s.n_active, s.ctx),
                                 rec.peaks) for s in _window_steps(rec))
    return 100.0 * least / (n_chips * sum(p.dur for p in progs))


def kernel_roofline(rec, pattern: str, flops_fn, bytes_fn
                    ) -> Optional[float]:
    """Least time for a kernel's work in the window's decode steps over
    the time its calls ran in the decode programs."""
    progs = decode_programs(rec)
    if not progs:
        return None
    calls = rec.trace.ops_in(pattern, progs)
    if not calls:
        return None
    m = rec.config["model"]
    least = sum(costs.roofline_s(flops_fn(m, s.n_active, s.ctx),
                                 bytes_fn(m, s.n_active, s.ctx), rec.peaks)
                for s in _window_steps(rec))
    return 100.0 * least / (rec.device["count"]
                            * sum(c.dur for c in calls))


def prefill_mfu(rec) -> Optional[float]:
    """Operations of the window's whole-prompt prefills over the time
    their programs ran, as a share of the bf16 peak."""
    progs = prefill_programs(rec)
    if not progs:
        return None
    m = rec.config["model"]
    lens = [P for a in rec.admits if rec.t0 <= a.t0 and a.t1 <= rec.t1
            for P in a.prompt_lens]
    flops = sum(rec.arch.prefill_flops(m, P) for P in lens)
    return 100.0 * flops / (sum(p.dur for p in progs)
                            * rec.peaks["bf16_flops_per_s"]
                            * rec.device["count"])


def idle_share(rec) -> Optional[float]:
    if rec.trace is None:
        return None
    return 100.0 * (1.0 - rec.trace.busy_s() / rec.trace.window_s)
