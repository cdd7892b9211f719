"""Profiler trace of the window -> spans, and the reductions the metric
readers share.

``load_xplane`` keeps, for each device (a ``/device:TPU:<n>`` plane), the
program executions (line ``XLA Modules``, named ``jit_<function>(<id>)``)
and the operations (line ``XLA Ops``; the event name is the HLO text,
of which the instruction name is kept, e.g.
``_vwr_paged_flash_decode_jit.11``), each inside the window, and from
the host only the benchmark's own annotations (``bench.*``: the window,
each admit call, each step, each wait for an arrival).  All times are
seconds on the trace's clock.  ``to_json`` /
``from_json`` keep such a trace as a small file, which is how the tests
hold a recorded one.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import json
import re
from typing import Dict, List, Optional, Tuple

MODULES_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"
# operations that only hold others (a layer loop's ``while``): counted
# in the busy union, left out of the list of operations by time
CONTAINERS = ("while", "conditional", "call")


@dataclasses.dataclass
class Span:
    name: str
    start: float
    end: float

    @property
    def dur(self) -> float:
        return self.end - self.start


@dataclasses.dataclass
class Trace:
    window: Tuple[float, float]
    chips: List[Dict[str, List[Span]]]    # per device: modules, ops
    host: List[Span]

    # -- reductions --------------------------------------------------

    def modules(self, prefix: str, chip: int = 0) -> List[Span]:
        """Executions of the programs whose name starts ``prefix``
        (``jit_<function>``) inside the window."""
        lo, hi = self.window
        return [s for s in self.chips[chip]["modules"]
                if s.name.startswith(prefix) and s.start >= lo
                and s.end <= hi]

    def ops_in(self, pattern: str, within: List[Span],
               chip: int = 0) -> List[Span]:
        """Operations whose name matches ``pattern`` (a regular
        expression) and that run inside one of the spans ``within``."""
        rx = re.compile(pattern)
        spans = sorted(within, key=lambda s: s.start)
        starts = [s.start for s in spans]
        out = []
        for op in self.chips[chip]["ops"]:
            if not rx.search(op.name):
                continue
            i = bisect.bisect_right(starts, op.start) - 1
            if i >= 0 and op.end <= spans[i].end + 1e-9:
                out.append(op)
        return out

    def busy(self, chip: int) -> List[Tuple[float, float]]:
        """Union of the operations' intervals, clipped to the window."""
        lo, hi = self.window
        ivs = sorted((max(s.start, lo), min(s.end, hi))
                     for s in self.chips[chip]["ops"]
                     if s.end > lo and s.start < hi)
        out: List[List[float]] = []
        for a, b in ivs:
            if out and a <= out[-1][1]:
                out[-1][1] = max(out[-1][1], b)
            else:
                out.append([a, b])
        return [(a, b) for a, b in out]

    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over devices."""
        return sum(sum(b - a for a, b in self.busy(c))
                   for c in range(len(self.chips))) / len(self.chips)

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    def idle_gaps(self, chip: int = 0) -> List[Tuple[float, float]]:
        lo, hi = self.window
        gaps, t = [], lo
        for a, b in self.busy(chip):
            if a > t:
                gaps.append((t, a))
            t = max(t, b)
        if hi > t:
            gaps.append((t, hi))
        return gaps

    def host_at(self, t: float) -> str:
        """The innermost benchmark annotation open at ``t``."""
        best: Optional[Span] = None
        for s in self.host:
            if s.start <= t <= s.end and s.name != "bench.window" and (
                    best is None or s.dur < best.dur):
                best = s
        return best.name if best is not None else "bench.window"

    def breakdown(self, top: int = 10) -> dict:
        """The device operations that took most time (summed by name,
        device 0) and the longest idle gaps, each named by what the
        host was doing in its middle."""
        per: Dict[str, float] = {}
        for s in self.chips[0]["ops"]:
            key = re.sub(r"\.\d+$", "", s.name)
            if key not in CONTAINERS:
                per[key] = per.get(key, 0.0) + s.dur
        ops = sorted(per.items(), key=lambda kv: -kv[1])[:top]
        gaps = sorted(self.idle_gaps(0), key=lambda g: g[0] - g[1])[:top]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[self.host_at((a + b) / 2), b - a]
                              for a, b in gaps]}

    # -- files -------------------------------------------------------

    def to_json(self) -> dict:
        enc = lambda ss: [[s.name, s.start, s.end]  # noqa: E731
                          for s in ss]
        return {"window": list(self.window),
                "chips": [{k: enc(v) for k, v in c.items()}
                          for c in self.chips],
                "host": enc(self.host)}

    @classmethod
    def from_json(cls, d: dict) -> "Trace":
        dec = lambda ss: [Span(*s) for s in ss]  # noqa: E731
        return cls(window=tuple(d["window"]),
                   chips=[{k: dec(v) for k, v in c.items()}
                          for c in d["chips"]],
                   host=dec(d["host"]))


def _op_name(hlo_text: str) -> str:
    """``%fusion.3 = bf16[...] fusion(...)`` -> ``fusion.3``."""
    return hlo_text.split(" = ", 1)[0].lstrip("%")


def load_xplane(trace_dir: str) -> Trace:
    """Read the one ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData
    paths = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(f"expected one xplane under {trace_dir}, "
                                f"found {paths}")
    pd = ProfileData.from_file(paths[0])
    host, device_planes = [], []
    for plane in pd.planes:
        m = re.fullmatch(r"/device:TPU:(\d+)", plane.name)
        if m:
            device_planes.append((int(m.group(1)), plane))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("bench."):
                        s = ev.start_ns * 1e-9
                        host.append(Span(ev.name, s,
                                         s + ev.duration_ns * 1e-9))
    windows = [s for s in host if s.name == "bench.window"]
    if not windows or not device_planes:
        raise ValueError(f"{paths[0]}: no bench.window annotation or no "
                         "TPU device plane")
    lo, hi = windows[0].start, windows[0].end
    chips = []
    for _, plane in sorted(device_planes, key=lambda x: x[0]):
        chip = {"modules": [], "ops": []}
        for line in plane.lines:
            key = {MODULES_LINE: "modules", OPS_LINE: "ops"}.get(line.name)
            if key is None:
                continue
            for ev in line.events:
                s = ev.start_ns * 1e-9
                e = s + ev.duration_ns * 1e-9
                if s >= lo and e <= hi:
                    chip[key].append(Span(
                        _op_name(ev.name) if key == "ops" else ev.name,
                        s, e))
        chips.append(chip)
    return Trace(window=(lo, hi), chips=chips, host=host)
