"""The program's own spans beside the device trace.

``Scheduler`` opens ``repro.*`` spans (``jax.profiler.TraceAnnotation``;
the table is in its docstring) and runs its pool write as the program
``jit_write_prefill_step``.  ``load`` reads a profiler trace as
``trace.load_xplane`` does and adds the ``repro.*`` host events to
``Trace.host``, so ``Trace.breakdown`` names an idle gap by the program
phase open over it.  The readers take such a ``Trace``; each returns
None where it finds nothing to read:

    step_host_ms          mean over the window's ``repro.step`` spans of
                          the span less its ``*.sync`` spans (ms)
    admit_ms_p95          p95 of the window's ``repro.admit.request``
                          spans, device waits included (ms)
    prefill_write_ms_p95  p95 of ``jit_write_prefill_step`` executions
                          on the device (ms)
    idle_by_span          the device's idle seconds by the innermost
                          host span open over them
    clock_offset_ms       bounds on how far the device planes' clock
                          runs behind the host plane's

The profiler puts both on one clock only to about a millisecond or two
(``clock_offset_ms``), so a gap of a few ms is named only as closely.

Run as a script, it makes one traced run of a cell (``run.run`` with
this loader), prints the run's result line with a ``spans`` entry
added, and can cut a few steps of the window out as a fixture.  Given
the directory where XLA dumped this run's optimized HLO, it also splits
each program's device time by the model's named scopes:

    XLA_FLAGS="--xla_dump_to=D --xla_dump_hlo_as_text" \\
    JAX_COMPILATION_CACHE_DIR=<empty dir> \\
    python3 chipbench/spans.py --workload qwen05b.chat.r80 --seed 1 \\
        --seconds 51 [--out DIR] [--fixture PATH] [--hlo-dump D]

(an empty compilation cache, so that the programs compile and are
dumped).
"""
from __future__ import annotations

import bisect
import glob
import json
import re
import sys
from pathlib import Path
from typing import Dict, List, Optional, Tuple

ROOT = Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

import numpy as np  # noqa: E402

from chipbench import measure, trace  # noqa: E402
from chipbench.trace import Span, Trace  # noqa: E402

PREFIX = "repro."
STEP, ADMIT_REQUEST = "repro.step", "repro.admit.request"
SYNC_SUFFIX = ".sync"
WRITE_PROGRAM = "jit_write_prefill_step"
PICK_PROGRAM = "jit__pick"
# the model's named scopes (``models/lm.py``)
SCOPES = ("attn.qkv", "attn.kv_write", "attn.paged", "attn.out", "mlp",
          "lm_head")
UNSCOPED = "(unscoped)"

_load_device = trace.load_xplane


def _xplane(trace_dir: str):
    from jax.profiler import ProfileData
    paths = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(f"expected one xplane under {trace_dir}, "
                                f"found {paths}")
    return ProfileData.from_file(paths[0])


def host_spans(pd) -> List[Span]:
    """The program's ``repro.*`` events, on any host thread."""
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(PREFIX):
                    s = ev.start_ns * 1e-9
                    out.append(Span(ev.name, s, s + ev.duration_ns * 1e-9))
    return out


def load(trace_dir: str) -> Trace:
    """``trace.load_xplane`` plus the program's ``repro.*`` spans."""
    t = _load_device(trace_dir)
    t.host = sorted(t.host + host_spans(_xplane(trace_dir)),
                    key=lambda s: (s.start, -s.end))
    return t


# -- readers ------------------------------------------------------------

def _in_window(t: Trace, name: str) -> List[Span]:
    lo, hi = t.window
    return sorted((s for s in t.host if s.name == name and s.start >= lo
                   and s.end <= hi), key=lambda s: s.start)


def _syncs_within(t: Trace, outer: List[Span]) -> List[float]:
    """Seconds of ``*.sync`` spans inside each span of ``outer``."""
    syncs = sorted((s for s in t.host if s.name.endswith(SYNC_SUFFIX)),
                   key=lambda s: s.start)
    starts = [s.start for s in syncs]
    out = []
    for o in outer:
        i = bisect.bisect_left(starts, o.start)
        j = bisect.bisect_right(starts, o.end)
        out.append(sum(s.dur for s in syncs[i:j] if s.end <= o.end))
    return out


def step_host_ms(t: Optional[Trace]) -> Optional[float]:
    steps = _in_window(t, STEP) if t is not None else []
    if not steps:
        return None
    waits = _syncs_within(t, steps)
    return 1e3 * float(np.mean([s.dur - w for s, w in zip(steps, waits)]))


def admit_ms_p95(t: Optional[Trace]) -> Optional[float]:
    if t is None:
        return None
    return measure.pct([1e3 * s.dur for s in _in_window(t, ADMIT_REQUEST)],
                       95)


def prefill_write_ms_p95(t: Optional[Trace]) -> Optional[float]:
    if t is None:
        return None
    return measure.pct([1e3 * p.dur for p in t.modules(WRITE_PROGRAM)], 95)


def idle_by_span(t: Trace, chip: int = 0) -> Dict[str, float]:
    """Each idle stretch of the device, cut at host span boundaries and
    given to the innermost span open over it (``bench.window`` where
    none is)."""
    spans = sorted((s for s in t.host if s.name != "bench.window"),
                   key=lambda s: s.start)
    starts = [s.start for s in spans]
    longest = max((s.dur for s in spans), default=0.0)
    out: Dict[str, float] = {}
    for a, b in t.idle_gaps(chip):
        i = bisect.bisect_left(starts, a - longest)
        j = bisect.bisect_left(starts, b)
        over = [s for s in spans[i:j] if s.end > a]
        cuts = sorted({a, b} | {x for s in over for x in (s.start, s.end)
                                if a < x < b})
        for lo, hi in zip(cuts, cuts[1:]):
            mid = (lo + hi) / 2
            inner = min((s for s in over if s.start <= mid <= s.end),
                        key=lambda s: s.dur, default=None)
            name = inner.name if inner is not None else "bench.window"
            out[name] = out.get(name, 0.0) + hi - lo
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))


def clock_offset_ms(t: Trace) -> Optional[Tuple[float, float]]:
    """Bounds on how far the device planes' timestamps run behind the
    host plane's (ms): no decode program starts before its step's
    ``repro.step.launch`` does, and no ``repro.step.sync`` ends before
    its step's pick ends.  Steps and programs are paired in order."""
    launches = _in_window(t, "repro.step.launch")
    syncs = _in_window(t, "repro.step.sync")
    progs = sorted(t.modules(measure.DECODE_PROGRAM), key=lambda s: s.start)
    picks = sorted(t.modules(PICK_PROGRAM), key=lambda s: s.start)
    n = len(launches)
    if not n or not (n == len(syncs) == len(progs) == len(picks)):
        return None
    lower = max(a.start - p.start for a, p in zip(launches, progs))
    upper = min(s.end - p.end for s, p in zip(syncs, picks))
    return 1e3 * lower, 1e3 * upper


def shifted(t: Trace, seconds: float) -> Trace:
    """``t`` with its host spans moved ``seconds`` earlier."""
    return Trace(window=t.window, chips=t.chips,
                 host=[Span(s.name, s.start - seconds, s.end - seconds)
                       for s in t.host])


def readings(t: Trace) -> dict:
    """Everything the spans say about one traced window."""
    idle = idle_by_span(t)
    idle_s = sum(idle.values())
    outside_wait = idle_s - idle.get("bench.wait", 0.0)
    under_program = sum(v for k, v in idle.items()
                        if k.startswith(PREFIX))
    lo, hi = t.window
    steps = _in_window(t, STEP)
    offset = clock_offset_ms(t)
    n_program = sum(1 for s in t.host if s.name.startswith(PREFIX)
                    and s.start >= lo and s.end <= hi)
    return {
        "sched.step_host_ms": step_host_ms(t),
        "sched.admit_ms_p95": admit_ms_p95(t),
        "engine.prefill_write_ms_p95": prefill_write_ms_p95(t),
        "steps": len(steps),
        "admissions": len(_in_window(t, ADMIT_REQUEST)),
        "spans_per_step": n_program / len(steps) if steps else None,
        "idle_s": idle_s,
        "idle_by_span_s": idle,
        "idle_outside_wait_under_program": (
            under_program / outside_wait if outside_wait > 0 else None),
        "gc_s": sum(s.dur for s in _in_window(t, "repro.gc")),
        "clock_offset_ms": offset,
        # the same split with the host spans moved by the middle of
        # the offset's bounds
        "idle_by_span_s_offset": (
            idle_by_span(shifted(t, 1e-3 * sum(offset) / 2))
            if offset else None),
        "span_ms": _span_stats(t),
        "programs": _program_stats(t),
    }


def _span_stats(t: Trace) -> dict:
    """Count, mean and largest ms of each ``repro.*`` span in the
    window."""
    lo, hi = t.window
    per: Dict[str, List[float]] = {}
    for s in t.host:
        if s.name.startswith(PREFIX) and s.start >= lo and s.end <= hi:
            per.setdefault(s.name, []).append(1e3 * s.dur)
    return {k: {"n": len(v), "mean_ms": float(np.mean(v)),
                "max_ms": float(np.max(v))} for k, v in sorted(per.items())}


def _program_stats(t: Trace) -> dict:
    """Executions, mean and p95 ms of each program in the window."""
    per: Dict[str, List[float]] = {}
    for s in t.modules(""):
        per.setdefault(s.name.split("(")[0], []).append(1e3 * s.dur)
    return {k: {"n": len(v), "mean_ms": float(np.mean(v)),
                "p95_ms": measure.pct(v, 95), "sum_ms": float(np.sum(v))}
            for k, v in sorted(per.items())}


# -- the model's scopes on the device -----------------------------------

_INSTRUCTION = re.compile(r'^\s*(?:ROOT\s+)?%?([^\s=]+)\s+=\s.*?'
                          r'metadata=\{[^}]*?op_name="([^"]*)"')


def op_names_from_dump(dump_dir: str) -> Dict[str, Dict[str, str]]:
    """``{program: {instruction: op_name}}`` from the optimized HLO text
    that XLA writes with ``--xla_dump_to=DIR --xla_dump_hlo_as_text``
    (the profiler's operation events carry no ``op_name``).  Programs
    compiled at several shapes share their instruction names."""
    out: Dict[str, Dict[str, str]] = {}
    for path in sorted(glob.glob(f"{dump_dir}/**/*after_optimizations.txt",
                                 recursive=True)):
        with open(path) as f:
            head = f.readline()
            m = re.match(r"HloModule (\S+?),", head)
            if not m:
                continue
            names = out.setdefault(m.group(1), {})
            for line in f:
                i = _INSTRUCTION.match(line)
                if i:
                    names[i.group(1)] = i.group(2)
    return out


def _scope(op_name: str) -> str:
    for part in op_name.split("/"):
        if part in SCOPES:
            return part
    return UNSCOPED


def time_by_scope(t: Trace, op_names: Dict[str, str],
                  program: str) -> dict:
    """Device ms per execution of ``program``, by the model's scope (an
    operation's scope read from ``op_names``), and the part of each
    scope spent in ``copy`` operations."""
    progs = sorted(t.modules(program), key=lambda s: s.start)
    if not progs:
        return {}
    starts = [p.start for p in progs]
    by: Dict[str, float] = {}
    copies: Dict[str, float] = {}
    for op in t.chips[0]["ops"]:
        if re.sub(r"\.\d+$", "", op.name) in trace.CONTAINERS:
            continue
        i = bisect.bisect_right(starts, op.start) - 1
        if i < 0 or op.end > progs[i].end + 1e-9:
            continue
        scope = _scope(op_names.get(op.name, ""))
        by[scope] = by.get(scope, 0.0) + op.dur
        if op.name.startswith("copy"):
            copies[scope] = copies.get(scope, 0.0) + op.dur
    n = len(progs)
    return {"executions": n,
            "ms_per_execution": {k: 1e3 * v / n for k, v in
                                 sorted(by.items(), key=lambda kv: -kv[1])},
            "copy_ms_per_execution": {k: 1e3 * v / n
                                      for k, v in copies.items()}}


# -- a fixture ----------------------------------------------------------

def cut(t: Trace, n_steps: int = 2) -> Trace:
    """A few steps of ``t``, from the benchmark step before the first
    admission that ran a pool write to the end of the ``n_steps``-th
    benchmark step after it; times shifted to start at 0."""
    writes = t.modules(WRITE_PROGRAM)
    reqs = [r for r in _in_window(t, ADMIT_REQUEST)
            if any(r.start <= w.start and w.end <= r.end for w in writes)]
    bench_steps = _in_window(t, "bench.step")
    for r in reqs:
        before = [s for s in bench_steps if s.end <= r.start]
        after = [s for s in bench_steps if s.start >= r.end]
        if before and len(after) >= n_steps:
            lo, hi = before[-1].start, after[n_steps - 1].end
            break
    else:
        raise ValueError("no admission with a pool write and "
                         f"{n_steps} steps after it")

    def keep(ss):
        return [Span(s.name, round(s.start - lo, 9), round(s.end - lo, 9))
                for s in ss if s.start >= lo and s.end <= hi]
    host = keep(s for s in t.host if s.name != "bench.window")
    host.append(Span("bench.window", 0.0, round(hi - lo, 9)))
    return Trace(window=(0.0, round(hi - lo, 9)),
                 chips=[{k: keep(v) for k, v in t.chips[0].items()}],
                 host=host)


def main(argv=None) -> int:
    import argparse
    from unittest import mock

    from chipbench import run
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", default=None,
                    help="directory for the readings")
    ap.add_argument("--fixture", default=None,
                    help="write a few steps of the window here")
    ap.add_argument("--hlo-dump", default=None,
                    help="XLA's --xla_dump_to directory of this run, for "
                         "the device time by the model's scopes")
    args = ap.parse_args(argv)
    kept = {}

    def load_kept(trace_dir):
        kept["trace"] = load(trace_dir)
        return kept["trace"]

    with mock.patch.object(trace, "load_xplane", load_kept):
        try:
            result = run.run(ROOT, args.workload, args.seed, args.seconds,
                             1)
        except run.NoChip as e:
            print(f"[spans] cannot measure here: {e}", file=sys.stderr)
            return 2
    t = kept["trace"]
    spans = readings(t)
    if args.hlo_dump:
        names = op_names_from_dump(args.hlo_dump)
        spans["scopes"] = {p: time_by_scope(t, names.get(p, {}), p)
                           for p in (measure.DECODE_PROGRAM,
                                     measure.PREFILL_PROGRAM,
                                     WRITE_PROGRAM)}
        spans["dumped_programs"] = {p: len(v) for p, v in names.items()}
    result["spans"] = spans
    if args.out:
        out = Path(args.out)
        out.mkdir(parents=True, exist_ok=True)
        (out / f"{args.workload}.{args.seed}.json").write_text(
            json.dumps(result, indent=1))
    if args.fixture:
        Path(args.fixture).write_text(json.dumps(cut(t).to_json()))
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
