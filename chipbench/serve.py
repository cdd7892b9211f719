"""Drive the served path: ``DecodeEngine(paged=True)`` + ``Scheduler``
under a seeded client, and record what each request and step did.

Set-up builds the engine from the configuration file, warms every shape
the mix will use (one request of each prompt length through the real
admission path, and the decode step at every table width the mix can
reach), and, for a saturated mix, fills every slot.  The window then
runs for a fixed number of seconds and ends at a step boundary:

    saturated  admit + step back to back; a backlog keeps every slot full
    poisson    open loop: each request is submitted when it comes due,
               whatever the server is doing; the loop sleeps only when
               nothing is queued or running.  The stream's blocks span
               the window's length, so every seed's window holds the
               same requests; those that came due while the last step
               ran are submitted as the window closes

After the window an open-loop run keeps serving, with no new arrivals,
until every request that came due in the window has its first token
(at most ``DRAIN_S``), so its time to first token counts its whole wait.
"""
from __future__ import annotations

import dataclasses
import time
import typing
from collections import deque
from types import ModuleType
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import traffic

DRAIN_S = 60.0


@dataclasses.dataclass
class Req:
    idx: int
    prompt: np.ndarray
    gen: int
    due: float                      # monotonic seconds
    submit_t: Optional[float] = None
    admit_t: Optional[float] = None  # start of the admit call that took it
    tokens: Optional[np.ndarray] = None
    token_times: List[float] = dataclasses.field(default_factory=list)
    status: str = "PENDING"


@dataclasses.dataclass
class Step:
    t0: float
    t1: float
    n_active: int                   # slots decoding
    ctx: int                        # sum over them of positions attended


@dataclasses.dataclass
class Admit:
    t0: float
    t1: float
    prompt_lens: List[int]


@dataclasses.dataclass
class Record:
    """Everything the metric readers and the check read."""
    cell: str
    config: dict
    arch: ModuleType                # the cell's architecture file
    mix: dict
    seed: int
    t0: float = 0.0                 # window start (monotonic)
    t1: float = 0.0                 # window end, at a step boundary
    end: float = 0.0                # end of serving, drain included
    setup_s: float = 0.0
    requests: Dict[int, Req] = dataclasses.field(default_factory=dict)
    steps: List[Step] = dataclasses.field(default_factory=list)
    admits: List[Admit] = dataclasses.field(default_factory=list)
    window_compiles: int = 0
    device: dict = dataclasses.field(default_factory=dict)
    peaks: dict = dataclasses.field(default_factory=dict)
    trace: object = None            # trace.Trace of the window, or None

    def in_window(self, t: float) -> bool:
        return self.t0 < t <= self.t1


class CompileCounter:
    """Programs compiled or loaded from the persistent cache while
    ``active``."""

    def __init__(self):
        self.active = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, duration, **_):
        if self.active and event == \
                "/jax/core/compile/backend_compile_duration":
            self.count += 1

    def _event(self, event, **_):
        if self.active and event == "/jax/compilation_cache/cache_hits":
            self.count += 1


def model_config(config: dict, kernel_impl: str):
    """The configuration's ``model`` section as a ``ModelConfig``.  An
    object under a key whose field is typed as a dataclass (``moe``,
    ``mla``) becomes that dataclass."""
    from repro.common.config import ModelConfig
    hints = typing.get_type_hints(ModelConfig)
    kw = {}
    for k, v in config["model"].items():
        if isinstance(v, dict):
            t = hints[k]
            cls = next(c for c in (t, *typing.get_args(t))
                       if dataclasses.is_dataclass(c))
            v = cls(**v)
        kw[k] = v
    return ModelConfig(**kw, kernel_impl=kernel_impl)


def table_widths(mix: dict, page_size: int, max_pages: int) -> List[int]:
    """Every block-table width bucket the mix can reach: from one slot
    holding the shortest prompt to one holding the longest request."""
    from repro.engine.paged_cache import bucket_table_width
    lo = -(-(min(mix["prompt"]["values"]) + 1) // page_size)
    hi = -(-traffic.max_len(mix) // page_size)
    return sorted({bucket_table_width(n, max_pages)
                   for n in range(lo, hi + 1)})


class Server:
    """Engine + scheduler for one cell, and the client that drives it."""

    def __init__(self, arch: ModuleType, config: dict, mix: dict,
                 seed: int, devices, period: float = None):
        from repro.dist import sharding as SH
        from repro.engine import DecodeEngine, EngineConfig, Scheduler
        from repro.launch.mesh import make_local_mesh

        dep = config["deployment"]
        self.config, self.mix, self.seed = config, mix, seed
        self.cfg = model_config(config, dep["kernel_impl"])
        data, model = dep["mesh"]
        mesh = make_local_mesh(data, model, devices=devices[:data * model])
        shardings = SH.to_shardings(
            mesh, SH.param_pspecs(self.cfg, mesh, "serve"))
        wkey = jax.random.PRNGKey(
            int(np.random.default_rng([seed, 3]).integers(2**31)))
        self.weights = jax.block_until_ready(
            arch.make(config, wkey, shardings))
        self.engine = DecodeEngine(self.cfg, EngineConfig(
            batch=dep["slots"], max_len=dep["max_len"],
            mesh_shape=(data, model), decode_shard=dep["decode_shard"],
            kernel_impl=dep["kernel_impl"], paged=True,
            page_size=dep["page_size"], n_pages=dep["n_pages"],
            kv_dtype=dep["kv_dtype"],
            prefix_cache=dep["prefix_cache"],
            chunked_prefill=dep["chunked_prefill"]),
            params=self.weights, mesh=mesh)
        self.sched = Scheduler(self.engine,
                               bucket_tables=dep["bucket_tables"])
        self.slots = dep["slots"]
        self.stream = traffic.generate(mix, seed, self.slots,
                                       self.cfg.vocab, period)
        self.rec_requests: Dict[int, Req] = {}
        self._queued: deque = deque()   # submitted, not yet admitted
        self._lookahead: Optional[traffic.Item] = None
        self.steps: List[Step] = []
        self.admits: List[Admit] = []

    # -- set-up ------------------------------------------------------

    def warm(self) -> None:
        """Compile every program the window will call, by calling it."""
        from repro.engine import Request
        s = self.sched
        for i, p in enumerate(traffic.warm_prompts(self.mix, self.seed,
                                                   self.cfg.vocab)):
            s.submit(Request(rid=("warm", i), tokens=p, gen=2))
        while s.pending or s.n_active:
            s.admit()
            s.step()
        B = self.slots
        for W in table_widths(self.mix, self.engine.page_size,
                              self.engine.max_pages):
            logits, cache = self.engine.decode_fn(self.engine.params, {
                "token": jnp.asarray(np.zeros((B,), np.int32)),
                "cur_len": jnp.asarray(np.zeros((B,), np.int32)),
                "block_table": jnp.asarray(np.zeros((B, W), np.int32)),
                "cache": s.cache})
            jax.block_until_ready(logits)
            del logits, cache
        s.finished.clear()

    def fill(self) -> None:
        """Saturated mixes: every slot busy and a backlog behind them."""
        self._top_up(time.monotonic())
        while self.sched.n_active < self.slots:
            before = self.sched.n_active
            self._admit()
            if self.sched.n_active == before:
                break               # the pool is full
        self._top_up(time.monotonic())

    # -- client ------------------------------------------------------

    def _next_item(self) -> traffic.Item:
        if self._lookahead is None:
            self._lookahead = next(self.stream)
        return self._lookahead

    def _submit(self, item: traffic.Item, due: float, now: float) -> None:
        from repro.engine import Request
        self._lookahead = None
        r = Req(idx=item.idx, prompt=item.prompt, gen=item.gen, due=due,
                submit_t=now)
        self.rec_requests[item.idx] = r
        self._queued.append(r)
        self.sched.submit(Request(rid=item.idx, tokens=item.prompt,
                                  gen=item.gen))

    def _top_up(self, now: float) -> None:
        while len(self.sched.pending) < self.slots:
            self._submit(self._next_item(), now, now)

    def _admit(self) -> None:
        t0 = time.monotonic()
        with jax.profiler.TraceAnnotation("bench.admit"):
            self.sched.admit()
        t1 = time.monotonic()
        waiting = {getattr(x, "req", x).rid for x in self.sched.pending}
        lens = []
        while self._queued and self._queued[0].idx not in waiting:
            r = self._queued.popleft()
            r.admit_t = t0
            lens.append(len(r.prompt))
        if lens:
            self.admits.append(Admit(t0, t1, lens))

    def _step(self) -> None:
        s = self.sched
        active = [i for i, sl in enumerate(s.slots) if sl is not None]
        if not active:
            return
        ctx = int(sum(int(s.lens[i]) + 1 for i in active))
        t0 = time.monotonic()
        with jax.profiler.TraceAnnotation("bench.step"):
            s.step()
        self.steps.append(Step(t0, time.monotonic(), len(active), ctx))

    def run_window(self, seconds: float) -> tuple:
        """Serve for ``seconds``; returns (t0, t1)."""
        self.steps, self.admits = [], []
        saturated = self.mix["arrival"]["kind"] == "saturated"
        t0 = time.monotonic()
        end = t0 + seconds
        with jax.profiler.TraceAnnotation("bench.window"):
            while True:
                now = time.monotonic()
                if now >= end:
                    # arrivals that came due while the last step ran
                    while not saturated and \
                            self._next_item().due <= seconds:
                        item = self._next_item()
                        self._submit(item, t0 + item.due, now)
                    break
                if saturated:
                    self._top_up(now)
                else:
                    while t0 + self._next_item().due <= now:
                        item = self._next_item()
                        self._submit(item, t0 + item.due, now)
                if self.sched.pending:
                    self._admit()
                if self.sched.n_active:
                    self._step()
                elif not saturated:
                    wake = min(t0 + self._next_item().due, end)
                    with jax.profiler.TraceAnnotation("bench.wait"):
                        time.sleep(max(0.0, wake - time.monotonic()))
        t1 = time.monotonic()
        return t0, t1

    def drain(self, t1: float) -> None:
        """Serve on, with no arrivals, until every request due by ``t1``
        has its first token, or ``DRAIN_S`` has passed."""
        limit = time.monotonic() + DRAIN_S

        def waiting():
            self.collect()
            return [r for r in self.rec_requests.values()
                    if r.due <= t1 and not r.token_times
                    and r.status not in ("REJECTED", "FAILED",
                                         "CANCELLED", "TIMED_OUT")]

        while waiting() and time.monotonic() < limit:
            if not (self.sched.pending or self.sched.n_active):
                break
            if self.sched.pending:
                self._admit()
            self._step()

    def collect(self) -> None:
        """Copy tokens, token times and status of every request from the
        scheduler into the record."""
        s = self.sched
        for rid, res in s.finished.items():
            r = self.rec_requests.get(rid)
            if r is not None:
                r.tokens = np.asarray(res, np.int32)
                r.token_times = list(res.token_times or [])
                r.status = res.status.value
        for sl in s.slots:
            if sl is None:
                continue
            r = self.rec_requests.get(sl.req.rid)
            if r is not None:
                r.tokens = np.asarray(sl.out, np.int32)
                r.token_times = list(sl.token_times)
                r.status = "RUNNING"

    def close(self) -> None:
        """Free the program's device state; the weights stay."""
        self.sched.cache = None
        self.sched = None
        self.engine = None
