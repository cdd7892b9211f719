"""Chip benchmark: one cell of ``BENCHMARK.json``, one run.

    python chipbench/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

Loads the cell's configuration, its architecture file, mix and metric
readers by name, builds the served path (``DecodeEngine(paged=True)`` +
``Scheduler``) with the architecture's weights made on the device from
the seed, warms every shape the mix uses, measures for ``--seconds``,
then checks the served tokens against the architecture's float32
reference.  The last line of stdout is one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics, or with ``--trace 1`` its per-layer metrics, read
from a profiler trace of the window), ``device`` and, traced,
``breakdown``; ``check`` comes last, each compared number beside its
limit, and the same lines end stderr.

Runs only on a TPU: with no TPU, too few chips, or a device kind that
``chipbench/peaks.json`` does not list, it exits 2 and prints no
result.  JAX's persistent compilation cache lives at
``$JAX_COMPILATION_CACHE_DIR`` if set, else at
``<checkout>/chipbench/.cache/jax``.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)


class NoChip(RuntimeError):
    """The run cannot measure here."""


def _say(msg: str) -> None:
    print(f"[chipbench] {msg}", file=sys.stderr, flush=True)


def enable_compile_cache(root: Path) -> str:
    import jax
    path = (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or str(root / "chipbench" / ".cache" / "jax"))
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def device_info(devices, n: int) -> dict:
    d = devices[0]
    peak = 0
    for dev in devices[:n]:
        stats = dev.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": d.platform, "kind": d.device_kind, "count": n,
            "memory_peak_bytes": peak}


def run(root: Path, workload: str, seed: int, seconds: float, trace: int,
        *, require_tpu: bool = True, peaks: dict = None,
        rate: float = None, control: bool = False,
        t_start: float = None) -> dict:
    """One run of one cell; returns the result object.  Tests pass
    ``require_tpu=False`` and ``peaks`` to drive it on the CPU,
    ``rate`` replaces an open-loop mix's arrival rate (the sweep for the
    knee), and ``control`` also reads the fp8 reference control on the
    same sample (``limits.py``)."""
    from chipbench import spec
    cell = spec.load_cell(root, workload)
    if rate is not None:
        cell.mix["arrival"]["rate_per_s"] = rate
    import jax
    devices = jax.devices()
    if require_tpu:
        if devices[0].platform != "tpu":
            raise NoChip(f"no TPU: JAX sees {len(devices)} "
                         f"{devices[0].platform} device(s)")
        if len(devices) < cell.chips:
            raise NoChip(f"{cell.name} needs {cell.chips} chips, found "
                         f"{len(devices)}")
    if peaks is None:
        try:
            peaks = spec.peaks_for(devices[0].device_kind)
        except KeyError as e:
            raise NoChip(str(e)) from e
    os.environ["REPRO_AUTOTUNE"] = "0"
    cache_dir = enable_compile_cache(root)

    from chipbench import check, measure, serve
    _say(f"{cell.name}: {devices[0].device_kind} x{cell.chips}, seed "
         f"{seed}, {seconds} s, trace {trace}; compile cache {cache_dir}")
    counter = serve.CompileCounter()
    server = serve.Server(cell.arch, cell.config, cell.mix, seed,
                          devices[:cell.chips], period=seconds)
    server.warm()
    saturated = cell.mix["arrival"]["kind"] == "saturated"
    if saturated:
        server.fill()
    rec = serve.Record(cell=cell.name, config=cell.config, arch=cell.arch,
                       mix=cell.mix, seed=seed, peaks=peaks)
    rec.setup_s = time.monotonic() - (t_start if t_start is not None
                                      else T_START)
    trace_dir = None
    if trace:
        trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-")
        jax.profiler.start_trace(trace_dir)
    counter.active = True
    rec.t0, rec.t1 = server.run_window(seconds)
    counter.active = False
    rec.window_compiles = counter.count
    pending_at_end = len(server.sched.pending)
    if trace:
        jax.profiler.stop_trace()
    if not saturated:
        server.drain(rec.t1)
    server.collect()
    rec.end = time.monotonic()
    rec.requests = server.rec_requests
    rec.steps, rec.admits = server.steps, server.admits
    rec.device = device_info(devices, cell.chips)
    preempted = server.sched.stats["preempted"]
    if trace:
        from chipbench import trace as tr
        t = time.monotonic()
        rec.trace = tr.load_xplane(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        rec.device["busy_s"] = rec.trace.busy_s()
        rec.device["window_s"] = rec.trace.window_s
        _say(f"trace read in {time.monotonic() - t:.1f} s: "
             f"{sum(len(c['ops']) for c in rec.trace.chips)} device ops")

    kind = "per_layer" if trace else "end_to_end"
    metrics = {}
    for m in cell.metrics:
        if m.kind != kind:
            continue
        v = m.read(rec)
        if v is not None:
            metrics[m.name] = {"value": float(v), "unit": m.unit}

    # which requests the window served, and which of them failed
    if saturated:
        attempted = [r for r in rec.requests.values()
                     if any(rec.in_window(t) for t in r.token_times)]
    else:
        attempted = measure.due_in_window(rec)
    bad = ("REJECTED", "FAILED", "CANCELLED", "TIMED_OUT")
    failed = [r for r in attempted
              if r.status in bad or not r.token_times]

    # the check: free the program's state, then run the reference
    server.close()
    gc.collect()
    pool = [r for r in rec.requests.values() if r.status not in bad]
    t = time.monotonic()
    read = check.readings(cell.arch, server.weights, cell.config["model"],
                          check.sample(pool, seed), control=control)
    correct, compared = check.verdict(read, cell.config.get("check", {}),
                                      len(failed))
    _say(f"reference over {read['requests']} requests, {read['tokens']} "
         f"served tokens in {time.monotonic() - t:.1f} s: gap max "
         f"{read['gap_max']:.6g}, mean {read['gap_mean']:.6g}, share of "
         f"tokens not the reference's best {read['flip_share']:.6g}")
    window = {
        "seconds": rec.t1 - rec.t0, "steps": len(rec.steps),
        "tokens": measure.window_tokens(rec),
        "compiles_in_window": rec.window_compiles,
        "preempted": preempted,
        "pending_at_end": pending_at_end,
        "queue_wait_p50_ms_by_third": _by_third(rec),
        "generator_late_ms_max": max(
            [1e3 * (r.submit_t - r.due) for r in attempted
             if r.submit_t is not None] or [0.0]),
        "check_readings": read,
    }
    _say(f"window: {json.dumps(window)}")
    result = {"correct": correct, "attempted": len(attempted),
              "failed": len(failed), "metrics": metrics,
              "device": rec.device, "window": window}
    if trace:
        result["breakdown"] = rec.trace.breakdown()
    result["check"] = compared
    for name, c in compared.items():
        lim = {k: v for k, v in c.items() if k != "value"}
        _say(f"check {name}: {c['value']} (limit {lim})")
    return result


def _by_third(rec) -> list:
    """Median queue wait of the requests due in each third of the
    window: a backlog that grows shows as a rising series."""
    from chipbench import measure
    span = (rec.t1 - rec.t0) / 3
    out = []
    for k in range(3):
        lo = rec.t0 + k * span
        waits = [1e3 * (r.admit_t - r.due) for r in rec.requests.values()
                 if lo < r.due <= lo + span and r.admit_t is not None]
        out.append(measure.pct(waits, 50))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = run(ROOT, args.workload, args.seed, args.seconds,
                     args.trace)
    except NoChip as e:
        _say(f"cannot measure here: {e}")
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
