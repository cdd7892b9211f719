"""Chip smoke test: tinyllama-1.1b at its published widths, served on a
TPU through the paged Pallas decode path.

    python chip_smoke.py                 # one chip
    python chip_smoke.py --four-chips    # the sharded path, four chips

One chip: 16 seeded requests (prompts of 128-1024 tokens, 32-64
generated tokens) go through ``DecodeEngine(paged=True,
kernel_impl="pallas")`` and ``Scheduler`` — the path ``serve --stream``
takes — once over bf16 page pools and once over int8 pools.  Every
request must finish with no retry, the compiled decode step must hold
the Mosaic kernels (``tpu_custom_call``: nothing ran interpreted or fell
back to XLA), and the pallas prefill and first decode-step logits must
match the same engine under ``kernel_impl="xla"``.

Four chips (``--four-chips`` runs this and nothing else): the same
requests on a (1, 4) mesh with the page pool sequence-sharded over the
chips (``decode_shard="seq"``), against a one-device engine in the same
process.

The weights are random (``lm.init`` from a fixed seed); nothing is
downloaded.  Wall and compile times printed are smoke timings, not
metrics.  Without a TPU the script exits non-zero and prints no result;
on success the last line of stdout is one JSON object,
``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import os
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.engine import (DecodeEngine, EngineConfig, Request,  # noqa: E402
                          RequestStatus, Scheduler, paged_cache)
from repro.launch import compile_cache  # noqa: E402
from repro.launch.mesh import make_local_mesh  # noqa: E402
from repro.models import lm  # noqa: E402

ARCH = "tinyllama-1.1b"
SEED = 0
# pallas vs xla logits: max |difference| over max |xla logit|.  Both
# paths hold bf16 weights and activations with fp32 accumulation but
# round at different points (fused matmul epilogues, the flash-decode
# online softmax); over 22 layers that compounds to around 1e-2 of
# bf16's 7.8e-3 relative step.
LOGIT_RTOL = 3e-2


@dataclasses.dataclass(frozen=True)
class Workload:
    """The seeded request stream and the engine shape that serves it."""
    slots: int = 8
    n_requests: int = 16
    prompt_lens: tuple = (128, 256, 512, 1024)
    gen_range: tuple = (32, 64)
    page_size: int = 16
    compare_prompt: int = 128
    seed: int = SEED

    @property
    def max_len(self) -> int:
        return max(self.prompt_lens) + self.gen_range[1]


def requests(vocab: int, w: Workload):
    """Fresh seeded requests; prompt lengths come from a few values so
    prefill compiles once per length, not once per request."""
    rng = np.random.default_rng(w.seed)
    return [Request(rid=i,
                    tokens=rng.integers(
                        2, vocab, (int(rng.choice(w.prompt_lens)),)
                    ).astype(np.int32),
                    gen=int(rng.integers(w.gen_range[0],
                                         w.gen_range[1] + 1)),
                    seed=i)
            for i in range(w.n_requests)]


def make_engine(cfg, params, w: Workload, devices, *, kv_dtype="bf16",
                kernel_impl="pallas"):
    """Paged engine over ``devices``: one device, or a (1, n) mesh with
    the page pool sequence-sharded over the n devices."""
    n = len(devices)
    return DecodeEngine(
        cfg, EngineConfig(batch=w.slots, max_len=w.max_len,
                          mesh_shape=(1, n),
                          decode_shard="seq" if n > 1 else "none",
                          kernel_impl=kernel_impl, paged=True,
                          page_size=w.page_size, kv_dtype=kv_dtype),
        params=params, mesh=make_local_mesh(1, n, devices=devices))


def serve_phase(engine, w: Workload) -> dict:
    """Serve the seeded requests through ``Scheduler``.  Returns the
    tokens of each request, counts, and ``problems``: every request
    that did not finish and every nonzero retry or failure counter."""
    sched = Scheduler(engine)
    reqs = requests(engine.cfg.vocab, w)
    for r in reqs:
        sched.submit(r)
    t0 = time.perf_counter()
    done = sched.run()
    wall = time.perf_counter() - t0
    st = sched.stats
    problems = [f"request {i} ended {v.status.value}: {v.error}"
                for i, v in sorted(done.items())
                if v.status is not RequestStatus.FINISHED]
    if len(done) != len(reqs):
        problems.append(f"{len(done)} of {len(reqs)} requests came back")
    problems += [f"{k} = {st[k]}" for k in
                 ("step_retries", "prefill_retries", "failed") if st[k]]
    return {"tokens": {i: np.asarray(v).tolist() for i, v in done.items()},
            "tokens_served": sum(len(v) for v in done.values()),
            "finished": sum(v.status is RequestStatus.FINISHED
                            for v in done.values()),
            "steps": st["steps"], "wall_s": wall, "problems": problems}


def first_logits(engine, prompt):
    """Prefill ``prompt`` into slot 0 of a fresh pool, then take one
    decode step with the other slots idle.  Returns fp32 numpy
    (prefill logits, first decode-step logits) of slot 0."""
    B, P = engine.ecfg.batch, len(prompt)
    logits, caches = engine.prefill_fn(
        engine.params, {"tokens": jnp.asarray(prompt, jnp.int32)[None]})
    n = paged_cache.max_pages(P + 1, engine.page_size)
    table = np.zeros((B, engine.max_pages), np.int32)
    table[0, :n] = np.arange(n)
    cache = paged_cache.write_prefill(engine.cfg, engine.init_paged_cache(),
                                      caches, jnp.asarray(table[:1]))
    tok = np.zeros((B,), np.int32)
    tok[0] = int(jnp.argmax(logits[0]))
    lens = np.zeros((B,), np.int32)
    lens[0] = P
    W = paged_cache.bucket_table_width(n, engine.max_pages)
    step, _ = engine.decode_step(jnp.asarray(tok), jnp.asarray(lens),
                                 cache, jnp.asarray(table[:, :W]))
    return (np.asarray(logits[0], np.float32),
            np.asarray(step[0], np.float32))


def compare_logits(got, want) -> dict:
    """Per stage (prefill, decode): max |got - want|, that over max
    |want|, and whether the greedy tokens agree."""
    out = {}
    for stage, g, r in zip(("prefill", "decode"), got, want):
        diff = float(np.abs(g - r).max())
        ref = float(np.abs(r).max())
        out[stage] = {"max_abs_diff": diff, "max_abs_ref": ref,
                      "rel": diff / max(ref, 1e-30),
                      "greedy_agree": bool(g.argmax() == r.argmax())}
    return out


def logit_problems(report, what: str) -> list:
    return [f"{what} {stage} logits differ by {r['rel']:.3g} of max "
            f"(tolerance {LOGIT_RTOL})"
            for stage, r in report.items() if not r["rel"] <= LOGIT_RTOL]


def compare_phase(pallas_engine, xla_engine, w: Workload) -> dict:
    """pallas vs xla on the same params: one prompt's prefill and first
    decode-step logits."""
    prompt = np.random.default_rng(w.seed + 1).integers(
        2, pallas_engine.cfg.vocab, (w.compare_prompt,)).astype(np.int32)
    report = compare_logits(first_logits(pallas_engine, prompt),
                            first_logits(xla_engine, prompt))
    return {"logits": report,
            "problems": logit_problems(report, "pallas vs xla")}


def decode_hlo(engine) -> str:
    """Compiled HLO of the engine's decode step at full table width."""
    B = engine.ecfg.batch
    dbatch = {"token": jnp.zeros((B,), jnp.int32),
              "cur_len": jnp.zeros((B,), jnp.int32),
              "block_table": jnp.zeros((B, engine.max_pages), jnp.int32),
              "cache": engine.init_paged_cache()}
    return engine.decode_fn.lower(engine.params, dbatch).compile().as_text()


def divergence(want: dict, got: dict) -> list:
    """Per request of ``want``: the index of the first greedy token
    where ``got`` differs, or None where the streams are identical.
    With random weights the top two logits of 32000 often lie closer
    than two bf16 implementations' rounding, so streams that agree on
    their first token can part later; this says where."""
    out = []
    for i, t in sorted(want.items()):
        g = got.get(i, [])
        out.append(next((k for k, (a, b) in enumerate(zip(t, g))
                         if a != b),
                        None if len(g) == len(t) else min(len(g), len(t))))
    return out


def four_chip_phase(cfg, params, w: Workload, devices,
                    report=lambda name, res: None) -> dict:
    """The seeded requests on a (1, 4) seq-sharded mesh against a
    one-device engine over ``devices[0]``: greedy streams compared, and
    one prompt's prefill and first decode-step logits.  ``report(name,
    result)`` runs after each engine's serve."""
    one = make_engine(cfg, params, w, devices[:1])
    want = serve_phase(one, w)
    report("one device", want)
    four = make_engine(cfg, params, w, devices[:4])
    got = serve_phase(four, w)
    report("four chips, seq-sharded pool", got)
    prompt = np.random.default_rng(w.seed + 1).integers(
        2, cfg.vocab, (w.compare_prompt,)).astype(np.int32)
    logits = compare_logits(first_logits(four, prompt),
                            first_logits(one, prompt))
    return {"one": want, "four": got, "logits": logits,
            "diverge_at": divergence(want["tokens"], got["tokens"]),
            "problems": (["one device: " + p for p in want["problems"]]
                         + ["four chips: " + p for p in got["problems"]]
                         + logit_problems(logits, "four chips vs one"))}


class CompileMeter:
    """Seconds spent compiling programs (or loading them from the
    persistent cache), the programs, and the persistent-cache hits,
    since the last ``take()``."""

    def __init__(self):
        self._reset()
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _reset(self):
        self.seconds, self.programs, self.hits = 0.0, 0, 0

    def _dur(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.programs += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def take(self) -> str:
        out = (f"compile {self.seconds:.1f} s over {self.programs} "
               f"programs, {self.hits} from the persistent cache")
        self._reset()
        return out


def _say(msg):
    print(f"[smoke] {msg}", flush=True)


def _report_serve(name, res, meter):
    _say(f"{name}: {res['finished']}/{len(res['tokens'])} FINISHED, "
         f"{res['tokens_served']} tokens served in {res['steps']} decode "
         f"steps; {meter.take()}; wall {res['wall_s']:.1f} s (smoke "
         "timing, not a metric)")


def _report_logits(name, report):
    for stage, r in report.items():
        _say(f"{name} {stage}: max|diff| {r['max_abs_diff']:.4g} of "
             f"max|ref| {r['max_abs_ref']:.4g} (rel {r['rel']:.3g}, "
             f"tolerance {LOGIT_RTOL}); greedy tokens "
             f"{'agree' if r['greedy_agree'] else 'DIFFER'}")


def run_one_chip(cfg, params, w, devices, meter) -> list:
    problems, engines, served = [], {}, {}
    for kv in ("bf16", "int8"):
        engines[kv] = make_engine(cfg, params, w, devices[:1], kv_dtype=kv)
        served[kv] = serve_phase(engines[kv], w)
        _report_serve(f"serve {kv} pools", served[kv], meter)
        problems += [f"{kv}: {p}" for p in served[kv]["problems"]]
        if "tpu_custom_call" not in decode_hlo(engines[kv]):
            problems.append(f"{kv}: no tpu_custom_call in the compiled "
                            "decode step")
        _say(f"{kv} decode-step HLO checked; {meter.take()}")
    xla = make_engine(cfg, params, w, devices[:1], kernel_impl="xla")
    cmp = compare_phase(engines["bf16"], xla, w)
    _report_logits("pallas vs xla", cmp["logits"])
    # how far two bf16 implementations' greedy streams agree on these
    # random weights: the yardstick for the four-chip comparison, not a
    # check
    res = serve_phase(xla, w)
    problems += [f"xla: {p}" for p in res["problems"]]
    _report_streams("pallas vs xla",
                    divergence(res["tokens"], served["bf16"]["tokens"]))
    _say(f"pallas vs xla: {meter.take()}")
    return problems + cmp["problems"]


def _report_streams(name, diverge_at):
    _say(f"{name}: greedy streams identical in "
         f"{diverge_at.count(None)} of {len(diverge_at)} requests; first "
         f"differing token per request {diverge_at}")


def run_four_chips(cfg, params, w, devices, meter) -> list:
    res = four_chip_phase(
        cfg, params, w, devices,
        report=lambda name, r: _report_serve(name, r, meter))
    _report_streams("four chips vs one", res["diverge_at"])
    _report_logits("four chips vs one", res["logits"])
    _say(f"four chips vs one: {meter.take()}")
    return res["problems"]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the seq-sharded four-chip phase")
    args = ap.parse_args(argv)

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"[smoke] no TPU found: JAX sees {len(devices)} "
              f"{devices[0].platform} device(s); this smoke test runs "
              "only on a TPU", file=sys.stderr)
        return 2
    need = 4 if args.four_chips else 1
    if len(devices) < need:
        print(f"[smoke] needs {need} TPU chips, found {len(devices)}",
              file=sys.stderr)
        return 2
    # block sizes from the cost-model prior: nothing is measured and no
    # tuning cache is written outside the checkout
    os.environ["REPRO_AUTOTUNE"] = "0"
    cache_dir = compile_cache.enable()
    meter = CompileMeter()
    kind = devices[0].device_kind
    _say(f"device: {kind} x{len(devices)} (platform "
         f"{devices[0].platform}); compile cache {cache_dir}")

    cfg = get_config(ARCH)
    t0 = time.perf_counter()
    # one jitted program draws every leaf (eagerly, each leaf shape
    # compiles a program of its own)
    params = jax.block_until_ready(
        jax.jit(functools.partial(lm.init, cfg))(jax.random.PRNGKey(SEED)))
    n_params = sum(x.size for x in jax.tree.leaves(params))
    _say(f"{cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
         f"{cfg.n_heads} heads / {cfg.n_kv_heads} KV, d_head "
         f"{cfg.d_head}, d_ff {cfg.d_ff}, vocab {cfg.vocab}; "
         f"{n_params / 1e9:.3f}B {cfg.dtype} params from seed {SEED} in "
         f"{time.perf_counter() - t0:.1f} s; {meter.take()}")

    run = run_four_chips if args.four_chips else run_one_chip
    problems = run(cfg, params, Workload(), devices, meter)
    if problems:
        for p in problems:
            print(f"[smoke] FAILED: {p}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
