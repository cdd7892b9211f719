"""Continuous batching: request-level serving on the paged DecodeEngine.

The dense ``engine.generate`` admits a whole batch at once and holds
every slot until the longest request finishes — at serving scale most
of the cache and most of the step budget is spent on retired or
not-yet-started requests.  This scheduler runs the engine's jitted
paged decode step as a *slot machine* instead:

  admit    a pending request takes a free slot: its prompt is prefilled
           alone (batch-1 prefill, one jit cache per prompt length) and
           scattered into freshly allocated pages — survivors in other
           slots are untouched (no re-prefill, no cache copy);
  step     ONE decode step advances every active slot through the
           shared jitted step (per-slot lengths + block tables);
           inactive slots ride along masked;
  grow     a slot crossing a page boundary gets one more page from the
           allocator — a request's footprint is ceil(len/page_size)
           pages, never the engine-wide max_len budget;
  preempt  when growth finds the pool dry, the latest-admitted slot is
           evicted back to the pending queue (pages freed now, prompt +
           generated prefix teacher-forced back in at re-admission) —
           an oversubscribed pool degrades to less concurrency instead
           of killing the stream;
  retire   a finished request frees its pages and its slot immediately;
           the next pending request is admitted on the following
           ``admit()`` — short requests stop paying for long ones.

With ``chunked_prefill`` (EngineConfig or the constructor knob),
admission becomes "grant pages + enqueue chunks": a prompt takes a
free slot and ALL its pages immediately but prefills ``chunk_tokens``
tokens at a time INSIDE the shared step (``steps.build_mixed_step``
runs one prompt chunk + the whole decode batch in a single jitted
call), packed by a token-budget rule (``pack_chunk``) that always
runs every decoding slot and fits the chunk into what budget remains
— one long prompt no longer stalls decode (the head-of-line latency
cliff of batch-1 admission).

Request lifecycle (fault tolerance).  Every request walks a status
machine (PREFILLING appears only with chunked prefill; whole-prompt
admission goes straight to RUNNING)::

    PENDING -> [PREFILLING ->] RUNNING -> FINISHED
       |          |               |-> PREEMPTED -> (again)
       |          |               |     (chunked: in-flight chunks
       |          |               |      dropped, completed pages kept)
       |          |               |-> FAILED / TIMED_OUT / CANCELLED
       |          `-> FAILED / TIMED_OUT / CANCELLED
       |-> REJECTED               (over budget, pool can never fit it)
       |-> CANCELLED / TIMED_OUT  (while still queued)

and every terminal state lands in ``finished`` as a ``RequestResult``
— an int32 token array (so existing callers index/compare it exactly
as before) carrying ``status`` / ``error`` / ``latency_s``.  Faults
are contained per-request: a malformed request is REJECTED instead of
raising away the stream, a slot whose logits go NaN/inf is
quarantined (FAILED) while the other slots' token streams stay
bit-identical, a transient step exception is retried with bounded
backoff (``runtime.resilience.RetryPolicy``), and a slot preempted
more than ``max_preemptions`` times is *parked* — kept out of
admission until the pool quiets down — instead of thrashing the
admit→preempt loop.  ``runtime.resilience.StragglerMonitor`` /
``Heartbeat`` can ride the step loop for slow-step flagging and
external hang detection.  Deterministic fault injectors for all of
this live in ``engine.faults``.

Token streams are bit-identical to a solo ``engine.generate`` run of
the same request (first token = argmax of the prefill logits; sampled
step i uses ``fold_in(PRNGKey(seed), i)``), which the paged-vs-dense
tests pin — except across a preemption, where the re-prefilled prefix
reproduces the decode-written cache only to fp rounding (a near-tie
argmax can flip, the usual recompute-preemption caveat).

All bookkeeping (free slots, free pages, per-slot lengths, block
tables) is host-side numpy; the device only ever sees the batch arrays
of the current step.
"""
from __future__ import annotations

import dataclasses
import enum
import gc
import time
from collections import deque
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.dist import sharding as SH
from repro.engine.faults import CrashError
from repro.engine.paged_cache import (PageAllocator, PagePoolExhausted,
                                      bucket_table_width, fork_page,
                                      write_prefill)
from repro.engine.prefix_cache import PrefixCache
from repro.runtime.resilience import (Heartbeat, RetryPolicy,
                                      StragglerMonitor, call_with_retries,
                                      percentiles)


_gc_span: Optional[TraceAnnotation] = None


def _gc_callback(phase: str, info: dict) -> None:
    """``gc.callbacks`` hook: a generation-2 collection runs inside a
    ``repro.gc`` span; younger generations return at once."""
    global _gc_span
    if info["generation"] != 2:
        return
    if phase == "start":
        _gc_span = TraceAnnotation("repro.gc")
        _gc_span.__enter__()
    elif _gc_span is not None:
        span, _gc_span = _gc_span, None
        span.__exit__(None, None, None)


class RequestStatus(str, enum.Enum):
    """Request lifecycle states (terminal: FINISHED / REJECTED /
    FAILED / CANCELLED / TIMED_OUT)."""
    PENDING = "PENDING"
    PREFILLING = "PREFILLING"   # chunked prefill in flight
    RUNNING = "RUNNING"
    PREEMPTED = "PREEMPTED"
    FINISHED = "FINISHED"
    REJECTED = "REJECTED"
    FAILED = "FAILED"
    CANCELLED = "CANCELLED"
    TIMED_OUT = "TIMED_OUT"


TERMINAL_STATES = frozenset({
    RequestStatus.FINISHED, RequestStatus.REJECTED, RequestStatus.FAILED,
    RequestStatus.CANCELLED, RequestStatus.TIMED_OUT})


class RequestResult(np.ndarray):
    """The tokens of a terminal request, plus how it ended.

    An int32 ndarray view, so every pre-lifecycle caller keeps working
    (``len(result)``, ``result[:k]``, ``assert_array_equal``), with
    ``status`` (RequestStatus), ``error`` (reason string for
    non-FINISHED terminals), ``latency_s`` (submit -> terminal wall
    time) and ``token_times`` (monotonic wall timestamp per emitted
    token, ITL = np.diff of it) riding along."""

    def __new__(cls, tokens, status: RequestStatus,
                error: Optional[str] = None,
                latency_s: Optional[float] = None,
                token_times: Optional[List[float]] = None):
        obj = np.asarray(tokens, np.int32).view(cls)
        obj.status = status
        obj.error = error
        obj.latency_s = latency_s
        obj.token_times = token_times
        return obj

    def __array_finalize__(self, obj):
        if obj is None:
            return
        self.status = getattr(obj, "status", None)
        self.error = getattr(obj, "error", None)
        self.latency_s = getattr(obj, "latency_s", None)
        self.token_times = getattr(obj, "token_times", None)

    @property
    def tokens(self) -> np.ndarray:
        return np.asarray(self)

    @property
    def ok(self) -> bool:
        return self.status is RequestStatus.FINISHED

    def __repr__(self):
        st = getattr(self, "status", None)
        err = getattr(self, "error", None)
        return (f"RequestResult({np.asarray(self).tolist()}, "
                f"status={getattr(st, 'value', st)}"
                + (f", error={err!r}" if err else "") + ")")


@dataclasses.dataclass
class Request:
    """One generation request.  ``tokens`` is the (P,) int32 prompt;
    ``gen`` counts generated tokens (prefill argmax included);
    ``frontend_emb`` feeds the vlm/audio modality frontends.

    ``deadline_s`` (wall seconds from ``submit()``) and ``max_steps``
    (decode steps) bound the request; crossing either ends it
    TIMED_OUT with the tokens generated so far.  ``status`` / ``error``
    are scheduler-owned lifecycle fields."""
    rid: Any
    tokens: np.ndarray
    gen: int
    temperature: float = 0.0
    seed: int = 0
    frontend_emb: Optional[np.ndarray] = None
    deadline_s: Optional[float] = None
    max_steps: Optional[int] = None
    status: RequestStatus = RequestStatus.PENDING
    error: Optional[str] = None
    submit_t: Optional[float] = None


@dataclasses.dataclass
class _Slot:
    req: Request
    length: int                     # valid cache positions
    pages: List[int]                # physical pages owned
    out: List[int]                  # generated tokens so far
    steps: int = 0                  # decode steps taken (RNG fold_in)
    order: int = 0                  # admission sequence (LIFO preempt)
    preempts: int = 0               # times evicted (livelock watchdog)
    prefilled: int = 0              # chunked: prompt positions resident
    token_times: List[float] = dataclasses.field(default_factory=list)


def pack_chunk(remaining: int, n_decode: int, budget: int,
               chunk_tokens: int, page_size: int) -> int:
    """Token-budget packing rule for one mixed step: how many prompt
    tokens of the head in-flight prefill ride along with ``n_decode``
    decoding slots under a ``budget``-token step.

    Decode is never starved: every decoding slot always runs (the
    chunk takes only ``budget - n_decode`` tokens, down to zero), and
    the chunk never exceeds ``chunk_tokens``.  A non-final chunk is
    floored to a whole-page multiple so the NEXT chunk's resident
    prefix is whole pages (exactly the suffix-prefill contract); the
    final chunk takes ``remaining`` exactly, page-aligned or not.
    Returns 0 when no chunk fits this step."""
    room = min(budget - n_decode, chunk_tokens)
    if room <= 0:
        return 0
    if room >= remaining:
        return remaining            # final chunk (may be unaligned)
    return (room // page_size) * page_size


class Scheduler:
    """Admit / step / retire requests over a paged ``DecodeEngine``.

    ``enc_len`` budgets the audio cross-attention cache (frames per
    slot); it defaults to the engine's decoder ``max_len``, which is
    usually too SHORT for speech — encoder frame counts routinely
    exceed the decoder token budget, so audio streams should size it
    to the longest expected ``frontend_emb``.

    ``bucket_tables`` (default on) slices the block table each step to
    the power-of-two width bucket covering the longest active slot's
    live page count (``paged_cache.bucket_table_width``), so a step
    stages only live pages instead of ``max_pages`` columns; the
    jitted step compiles once per bucket (at most log2(max_pages)+1
    shapes).  Admission / growth / retirement semantics and the token
    streams are identical either way — only the staged table width
    changes.

    Fault-tolerance knobs:

    ``retry``            RetryPolicy for transient prefill/decode step
                         exceptions (bounded, linear backoff; the last
                         exception re-raises once spent).
    ``max_preemptions``  a slot evicted more than this many times is
                         parked (kept out of admission until the pool
                         quiets) instead of thrashing admit→preempt.
    ``guard_nonfinite``  batched isfinite guard on the step logits:
                         a slot producing NaN/inf is quarantined
                         (FAILED) alone; survivors are untouched.
    ``straggler`` / ``heartbeat``  optional
                         ``runtime.resilience`` monitors wired into
                         every ``step()``.

    ``prefix_cache`` (None = inherit ``EngineConfig.prefix_cache``)
    turns on prompt-prefix sharing (``engine.prefix_cache``):
    admission matches the longest cached whole-page prefix, increfs
    and aliases those pages into the slot's block table, and prefills
    only the suffix; retire/preempt decref instead of free, and when
    an allocation would exhaust the pool, refcount-1 LRU trie leaves
    are evicted BEFORE any slot is preempted.  Greedy token streams
    are bit-identical to the cache-off scheduler for model-dtype
    pools (the suffix prefill reads exactly the KV blocks the cold
    prefill would recompute).  int8 pools dequantize the prefix
    through the same per-page scales decode reads, but a HIT's suffix
    prefill sees the quantized prefix where a cold prefill saw full
    precision, so a near-tie argmax in the hit's own stream can flip
    — miss streams (and every decode step) are unaffected.

    ``chunked_prefill`` / ``chunk_tokens`` / ``token_budget`` (None =
    inherit the first two from EngineConfig; budget defaults to
    ``batch + chunk_tokens``) turn on chunked admission: prompts
    prefill ``chunk_tokens`` at a time inside the shared mixed step
    (see the module docstring), packed under ``token_budget`` by
    ``pack_chunk`` so decode slots are never starved.  Greedy token
    streams stay bit-identical to the non-chunked scheduler for
    model-dtype pools (each chunk is suffix-prefill math over the same
    kv block boundaries as the whole prefill; extra fully-masked kv
    blocks are exact no-ops for online softmax); int8 pools carry the
    same near-tie caveat as a prefix-cache hit, since chunks after the
    first read the earlier chunks' KV through the quantized pages.

    Spans.  ``admit`` and ``step`` open ``jax.profiler.TraceAnnotation``
    spans, which a profiler trace records on its host plane, on the
    device planes' timeline to within a millisecond or two; with no
    profiler running each costs about a microsecond.  A ``*.sync`` span is the host waiting on the device,
    so a span's host time is its length less its ``*.sync`` children.
    The pool write runs as the program ``jit_write_prefill_step``.

    ======================  ===========================================
    span                    covers
    ======================  ===========================================
    ``repro.step``          all of ``step()``
    ``repro.step.prepare``  deadlines, page growth, copy-on-write,
                            chunk packing, width bucket, batch arrays
    ``repro.step.launch``   the decode (or mixed) step and the pick
                            dispatched
    ``repro.promote.sync``  chunked prefill: the final chunk's isfinite
                            guard and first-token read (in ``launch``)
    ``repro.step.sync``     the one device->host read of the picks
    ``repro.step.retire``   tokens appended, retirements, straggler /
                            heartbeat / snapshot hooks
    ``repro.admit``         all of ``admit()``
    ``repro.admit.request`` one request taking a slot: prefill and pool
                            write dispatched, first token read
    ``repro.admit.sync``    the read of that first token
    ``repro.gc``            a generation-2 garbage collection
    ======================  ===========================================

    ``chipbench/spans.py`` reads them beside the device trace: host time
    per step (``repro.step`` less its syncs, ``sched.step_host_ms``),
    p95 of ``repro.admit.request`` (``sched.admit_ms_p95``), p95 of
    ``jit_write_prefill_step`` on the device
    (``engine.prefill_write_ms_p95``), and the device's idle time split
    by the innermost span open over it.
    """

    def __init__(self, engine, enc_len: Optional[int] = None,
                 bucket_tables: bool = True,
                 retry: Optional[RetryPolicy] = None,
                 max_preemptions: int = 3,
                 guard_nonfinite: bool = True,
                 straggler: Optional[StragglerMonitor] = None,
                 heartbeat: Optional[Heartbeat] = None,
                 prefix_cache: Optional[bool] = None,
                 chunked_prefill: Optional[bool] = None,
                 chunk_tokens: Optional[int] = None,
                 token_budget: Optional[int] = None,
                 journal=None, snapshotter=None):
        if not engine.ecfg.paged:
            raise ValueError(
                "Scheduler needs a paged engine: EngineConfig("
                "paged=True, page_size=..., n_pages=...)")
        self.eng = engine
        self.cfg = engine.cfg
        B, J = engine.ecfg.batch, engine.max_pages
        self.page_size = engine.page_size
        self.allocator = PageAllocator(engine.n_pages)
        # durability hooks (engine.journal / engine.snapshot): every
        # submit/cancel/terminal is write-ahead logged, and the
        # snapshotter cuts the full serving state every N steps off
        # the step path
        self.journal = journal
        self.snapshotter = snapshotter
        self.slots: List[Optional[_Slot]] = [None] * B
        self.table = np.zeros((B, J), np.int32)
        self.lens = np.zeros((B,), np.int32)
        self.tokens = np.zeros((B,), np.int32)
        self.enc_lens = np.zeros((B,), np.int32)
        self.cache = engine.init_paged_cache(enc_len=enc_len)
        self.enc_budget = (self.cache["cross_k"].shape[2]
                           if self.cfg.family == "audio" else 0)
        self.bucket_tables = bucket_tables
        # default policy: transient step faults retry, a simulated
        # process death (CrashError) surfaces immediately — a crash is
        # the restart loop's problem, not the step retry's
        self.retry = retry if retry is not None else RetryPolicy(
            fatal=(CrashError,))
        self.max_preemptions = max_preemptions
        self.guard_nonfinite = guard_nonfinite
        self.straggler = straggler
        self.heartbeat = heartbeat
        self.pending: deque = deque()   # Request | preempted _Slot
        self.parked: deque = deque()    # watchdog-parked _Slots
        self.finished: Dict[Any, RequestResult] = {}
        if prefix_cache is None:
            prefix_cache = engine.ecfg.prefix_cache
        self.prefix: Optional[PrefixCache] = None
        if prefix_cache:
            if self.cfg.family not in ("dense", "moe"):
                raise ValueError(
                    f"prefix_cache supports the token-only families "
                    f"('dense', 'moe'); family {self.cfg.family!r} "
                    "prepends frontend positions a token-keyed prefix "
                    "index cannot match")
            if engine.suffix_prefill_fn is None:
                raise ValueError("engine has no suffix_prefill_fn — "
                                 "construct a paged dense/moe engine")
            self.prefix = PrefixCache(self.page_size, self.allocator)
        if chunked_prefill is None:
            chunked_prefill = engine.ecfg.chunked_prefill
        self.chunked = bool(chunked_prefill)
        self.chunk_tokens = 0
        self.token_budget = 0
        if self.chunked:
            if self.cfg.family not in ("dense", "moe"):
                raise ValueError(
                    f"chunked_prefill supports the token-only families "
                    f"('dense', 'moe'); got family {self.cfg.family!r}")
            if getattr(engine, "mixed_fn", None) is None:
                raise ValueError("engine has no mixed_fn — construct "
                                 "a paged dense/moe engine")
            ct = (chunk_tokens if chunk_tokens is not None
                  else engine.ecfg.chunk_tokens)
            if ct < 1 or ct % self.page_size:
                raise ValueError(
                    f"chunk_tokens must be a positive multiple of "
                    f"page_size {self.page_size}; got {ct} (a non-final "
                    "chunk must end page-aligned so the next chunk's "
                    "prefix is whole pages)")
            self.chunk_tokens = ct
            # default budget: every slot decodes AND a full chunk fits
            self.token_budget = (token_budget if token_budget is not None
                                 else B + ct)
            if self.token_budget < 1:
                raise ValueError("token_budget must be >= 1")
        self._prefilling: deque = deque()   # slot ids, chunking order
        self.stats = {"prefills": 0, "admitted": 0, "retired": 0,
                      "steps": 0, "peak_pages": 0, "preempted": 0,
                      "table_widths": {},   # width -> steps at it
                      # table entries the decode kernel stages, summed
                      # over steps: decoding slots' pages, and B x W
                      "decode_pages_live": 0, "decode_pages_table": 0,
                      "rejected": 0, "failed": 0, "cancelled": 0,
                      "timed_out": 0, "step_retries": 0,
                      "prefill_retries": 0, "parked": 0,
                      "straggler_flags": 0,
                      # prefix-cache counters (zero when it's off)
                      "prefix_hits": 0, "prefix_misses": 0,
                      "prefix_hit_tokens": 0, "prefix_evictions": 0,
                      "shared_pages": 0,     # peak pages refcount > 1
                      "cow_forks": 0,
                      # chunked-prefill counters (zero when it's off)
                      "mixed_steps": 0, "chunks": 0,
                      "chunked_tokens": 0}
        # a page pool split into slabs over model shards
        # (decode_shard='seq'): ``decode_pages_live_by_shard[s]`` sums
        # the decoding slots' table entries that land in slab s, over
        # steps
        self.pool_shards = (SH.page_slabs(engine.mesh, engine.n_pages)
                            if engine.ecfg.decode_shard == "seq" else 1)
        if self.pool_shards > 1:
            self.stats["decode_pages_live_by_shard"] = \
                [0] * self.pool_shards
        self._latencies: List[float] = []
        self._itl: List[float] = []     # inter-token latency samples
        self._order = 0
        if _gc_callback not in gc.callbacks:
            gc.callbacks.append(_gc_callback)

        # jitted prefill->pages scatter with the pool DONATED (where
        # the backend supports donation): the eager .at[].set would
        # copy every full pool leaf per admission.  Named, so its runs
        # show as ``jit_write_prefill_step`` in a profile.
        def write_prefill_step(cache, caches, table, slots):
            return write_prefill(self.cfg, cache, caches, table,
                                 enc_caches_slots=slots)

        self._write_prefill = jax.jit(
            write_prefill_step,
            donate_argnums=(() if jax.default_backend() == "cpu"
                            else (0,)))
        # jitted copy-on-write page fork (src/dst ride as traced
        # scalars: one compile serves every pair); donation for the
        # same reason as _write_prefill
        self._fork_page = jax.jit(
            lambda cache, src, dst: fork_page(self.cfg, cache, src, dst),
            donate_argnums=(() if jax.default_backend() == "cpu"
                            else (0,)))
        # one jitted pick for the whole batch: greedy argmax, per-slot
        # fold_in-keyed categorical, and the isfinite guard, packed
        # into a single (3, B) int32 array -> ONE device->host transfer
        # per step (the per-slot categorical used to sync once per
        # sampled slot)
        self._pick_fn = jax.jit(self._pick)

    @staticmethod
    def _pick(logits, seeds, steps, temps):
        keys = jax.vmap(lambda s, i: jax.random.fold_in(
            jax.random.PRNGKey(s), i))(seeds, steps)
        safe_t = jnp.where(temps > 0, temps, 1.0)
        sampled = jax.vmap(lambda k, l, t: jax.random.categorical(
            k, l / t))(keys, logits, safe_t)
        greedy = jnp.argmax(logits, -1)
        finite = jnp.all(jnp.isfinite(logits), -1)
        return jnp.stack([greedy.astype(jnp.int32),
                          sampled.astype(jnp.int32),
                          finite.astype(jnp.int32)])

    # ------------------------------------------------------------------

    @property
    def n_active(self) -> int:
        return sum(s is not None for s in self.slots)

    def submit(self, req: Request) -> None:
        req.status = RequestStatus.PENDING
        req.submit_t = time.monotonic()
        if self.journal is not None:
            # write-ahead: the submit is on disk (fsynced) before the
            # scheduler can act on it — an acknowledged request
            # survives a crash even if no snapshot ever sees it
            self.journal.submit(req)
        self.pending.append(req)

    def results(self) -> Dict[Any, RequestResult]:
        return dict(self.finished)

    def latency_percentiles(self, qs=(50, 90, 99)) -> Dict[str, float]:
        """Submit -> terminal wall-latency percentiles over every
        terminal request so far."""
        return percentiles(self._latencies, qs)

    def itl_percentiles(self, qs=(50, 90, 99)) -> Dict[str, float]:
        """Inter-token-latency percentiles (seconds between consecutive
        emitted tokens, per request) aggregated over every terminal
        request so far — the tail (p99) is what a long prompt's
        monopolized prefill inflates, and what chunked prefill pins."""
        return percentiles(self._itl, qs)

    # ------------------------------------------------------------------
    # terminal transitions
    # ------------------------------------------------------------------

    def _terminal(self, req: Request, tokens, status: RequestStatus,
                  error: Optional[str] = None, *,
                  token_times: Optional[List[float]] = None
                  ) -> RequestResult:
        lat = (time.monotonic() - req.submit_t
               if req.submit_t is not None else None)
        req.status = status
        req.error = error
        res = RequestResult(np.asarray(list(tokens), np.int32), status,
                            error=error, latency_s=lat,
                            token_times=(list(token_times)
                                         if token_times else None))
        self.finished[req.rid] = res
        if self.journal is not None:
            self.journal.terminal(req.rid, res)
        if lat is not None:
            self._latencies.append(lat)
        if token_times and len(token_times) > 1:
            self._itl.extend(
                np.diff(np.asarray(token_times, np.float64)).tolist())
        key = {RequestStatus.FINISHED: "retired",
               RequestStatus.REJECTED: "rejected",
               RequestStatus.FAILED: "failed",
               RequestStatus.CANCELLED: "cancelled",
               RequestStatus.TIMED_OUT: "timed_out"}[status]
        self.stats[key] += 1
        return res

    def _evict(self, slot_id: int) -> _Slot:
        """Release a slot's pages + batch-row state (no terminal
        record).  Pages are DECREF'd, not freed: with the prefix cache
        on, a slot's row may alias pages the trie (or another slot)
        still holds — the old unconditional ``free`` double-freed
        exactly those, pulling live prefixes out from under survivors."""
        slot = self.slots[slot_id]
        if slot.pages:
            self.allocator.decref(slot.pages)
            slot.pages = []
        slot.prefilled = 0
        if slot_id in self._prefilling:
            self._prefilling.remove(slot_id)
        self.slots[slot_id] = None
        self.lens[slot_id] = 0
        self.tokens[slot_id] = 0
        self.enc_lens[slot_id] = 0
        return slot

    def _retire(self, slot_id: int) -> None:
        slot = self.slots[slot_id]
        if self.prefix is not None:
            # index the retiring request's whole pages — prompt AND
            # generated tokens (multi-turn reuse: a follow-up prompt
            # that extends this conversation hits the whole history).
            # The cache holds positions [0, length) = prompt + out[:-1]
            # (the last picked token's KV is written by the step that
            # never came).
            toks = np.concatenate([
                np.asarray(slot.req.tokens, np.int32),
                np.asarray(slot.out[:-1], np.int32)])
            self.prefix.insert(toks, slot.pages)
        slot = self._evict(slot_id)
        self._terminal(slot.req, slot.out, RequestStatus.FINISHED,
                       token_times=slot.token_times)

    def _fail_slot(self, slot_id: int, reason: str) -> None:
        slot = self._evict(slot_id)
        self._terminal(slot.req, slot.out, RequestStatus.FAILED, reason,
                       token_times=slot.token_times)

    def _preempt(self, slot_id: int) -> None:
        """Evict an active slot back to the FRONT of the pending queue
        (vLLM-style recompute preemption): its pages free immediately
        and its prompt + generated prefix is teacher-forced back in at
        re-admission, so no tokens are lost — only the prefix compute
        is redone.  A slot past ``max_preemptions`` is parked instead:
        re-admitting it just feeds the same thrash, so it waits out the
        pool pressure (re-admitted when nothing else is runnable).

        A PREFILLING slot (chunked prefill in flight) drops only its
        in-flight chunk: the whole pages its completed chunks already
        wrote stay WITH the slot across the queue, so re-admission
        grants the missing tail and resumes chunking where it left off
        instead of re-prefilling from scratch.  ``prefilled`` is always
        page-aligned while PREFILLING (non-final chunks end on page
        boundaries), so the kept prefix is exactly whole pages — and at
        least one tail page frees (the grant covers the next unwritten
        position), so pool-pressure preemption still makes progress."""
        slot = self.slots[slot_id]
        if slot.req.status is RequestStatus.PREFILLING:
            keep = slot.prefilled // self.page_size
            tail = slot.pages[keep:]
            if tail:
                self.allocator.decref(tail)
            slot.pages = slot.pages[:keep]
            if slot_id in self._prefilling:
                self._prefilling.remove(slot_id)
            self.slots[slot_id] = None
            self.lens[slot_id] = 0
            self.tokens[slot_id] = 0
            self.enc_lens[slot_id] = 0
        else:
            slot = self._evict(slot_id)
        slot.preempts += 1
        slot.req.status = RequestStatus.PREEMPTED
        if slot.preempts > self.max_preemptions:
            self.parked.append(slot)
            self.stats["parked"] += 1
        else:
            self.pending.appendleft(slot)
        self.stats["preempted"] += 1

    def cancel(self, rid: Any) -> bool:
        """Cancel a request wherever it is: mid-flight (slot + pages
        freed immediately, partial tokens attached), pending, or
        parked.  Returns False if ``rid`` is unknown or already
        terminal."""
        if self.journal is not None:
            # intent record — the terminal event that follows is what
            # replay treats as authoritative
            self.journal.cancel(rid)
        for slot_id, slot in enumerate(self.slots):
            if slot is not None and slot.req.rid == rid:
                slot = self._evict(slot_id)
                self._terminal(slot.req, slot.out,
                               RequestStatus.CANCELLED,
                               "cancelled mid-flight",
                               token_times=slot.token_times)
                return True
        for q, where in ((self.pending, "pending"),
                         (self.parked, "parked")):
            for item in list(q):
                req = item.req if isinstance(item, _Slot) else item
                if req.rid == rid:
                    q.remove(item)
                    self._release_queued(item)
                    toks = item.out if isinstance(item, _Slot) else []
                    self._terminal(req, toks, RequestStatus.CANCELLED,
                                   f"cancelled while {where}",
                                   token_times=getattr(
                                       item, "token_times", None))
                    return True
        return False

    def _release_queued(self, item) -> None:
        """Drop the pages a queued item still holds.  A chunk-preempted
        slot keeps its completed prefix pages across the queue (so
        re-admission resumes chunking instead of restarting); if the
        item goes terminal while queued, those pages must be released
        here or they leak."""
        if isinstance(item, _Slot) and item.pages:
            self.allocator.decref(item.pages)
            item.pages = []
            item.prefilled = 0

    # ------------------------------------------------------------------
    # admission
    # ------------------------------------------------------------------

    def _prefill_positions(self, req: Request) -> int:
        P = len(req.tokens)
        if self.cfg.family == "vlm":
            P += self.cfg.frontend_tokens
        return P

    @staticmethod
    def _teacher_tokens(item) -> np.ndarray:
        """Every token position the admission prefill must occupy: the
        prompt, plus — for a preempted slot being re-admitted — the
        generated prefix except the last token (that one is the slot's
        pending input, written by the next step)."""
        req = item.req if isinstance(item, _Slot) else item
        tokens = np.asarray(req.tokens, np.int32)
        if isinstance(item, _Slot):
            tokens = np.concatenate(
                [tokens, np.asarray(item.out[:-1], np.int32)])
        return tokens

    def _pages_needed(self, positions: int, more_writes: bool) -> int:
        """Pages covering ``positions`` occupied slots — plus the page
        the next decode token writes to, but only when one is coming
        (a gen-exhausted request must not ask for a page beyond its
        block-table row)."""
        last = positions + 1 if more_writes else positions
        return -(-last // self.page_size)

    def _deadline_expired(self, req: Request) -> bool:
        return (req.deadline_s is not None
                and req.submit_t is not None
                and time.monotonic() - req.submit_t > req.deadline_s)

    def _validate(self, req: Request) -> Optional[str]:
        """Admission-blocking fault in ``req``, or None if admissible."""
        P = self._prefill_positions(req)
        if P + req.gen - 1 > self.eng.ecfg.max_len:
            return (f"prompt {P} + gen {req.gen} exceeds engine "
                    f"max_len {self.eng.ecfg.max_len}")
        if (self.cfg.family == "audio"
                and req.frontend_emb is not None
                and req.frontend_emb.shape[0] > self.enc_budget):
            return (f"{req.frontend_emb.shape[0]} encoder frames "
                    f"exceed the cross-cache budget {self.enc_budget} "
                    "— construct the Scheduler with enc_len >= the "
                    "longest expected frontend_emb")
        return None

    def admit(self) -> int:
        """Admit pending requests (or preempted slots) into free slots
        while pages allow.  Returns the number admitted (0 = no free
        slot, nothing pending, or the pool is momentarily too full —
        retiring slots frees pages, so admission retries on the next
        call).

        Malformed requests (over-budget prompt, encoder frames beyond
        the cross-cache budget, a single request larger than the whole
        pool) are REJECTED individually — the stream keeps serving —
        and a request whose deadline lapsed while queued ends
        TIMED_OUT here instead of wasting a prefill."""
        with TraceAnnotation("repro.admit"):
            return self._admit_pending()

    def _admit_pending(self) -> int:
        if (self.n_active == 0 and not self.pending and self.parked):
            # nothing else runnable: the parked slots get their turn
            while self.parked:
                self.pending.append(self.parked.popleft())
        admitted = 0
        while self.pending:
            try:
                slot_id = self.slots.index(None)
            except ValueError:
                break
            item = self.pending[0]
            req = item.req if isinstance(item, _Slot) else item
            partial = item.out if isinstance(item, _Slot) else []
            if self._deadline_expired(req):
                self.pending.popleft()
                self._release_queued(item)
                self._terminal(req, partial, RequestStatus.TIMED_OUT,
                               f"deadline_s={req.deadline_s} lapsed "
                               "while queued",
                               token_times=getattr(
                                   item, "token_times", None))
                continue
            fault = self._validate(req)
            if fault is not None:
                self.pending.popleft()
                self._release_queued(item)
                self._terminal(req, partial, RequestStatus.REJECTED,
                               fault)
                continue
            P = self._prefill_positions(req)
            # a chunk-preempted slot can be re-queued with out == []
            # (it never finished prefilling): it behaves like a fresh
            # request here — the prefill emits its first token
            done = (max(len(item.out), 1)
                    if isinstance(item, _Slot) else 1)
            positions = P + (max(len(item.out) - 1, 0)
                             if isinstance(item, _Slot) else 0)
            need = self._pages_needed(positions, done < req.gen)
            if need > self.allocator.n_pages:
                self.pending.popleft()
                self._release_queued(item)
                self._terminal(
                    req, partial, RequestStatus.REJECTED,
                    f"needs {need} pages but the pool only has "
                    f"{self.allocator.n_pages} in total — raise "
                    "EngineConfig.n_pages or page_size")
                continue
            # pages a chunk-preempted slot kept across the queue: its
            # completed prefix is already resident, so prefix matching
            # is skipped (the slot holds its own refs) and only the
            # missing tail is allocated
            held = (list(item.pages)
                    if isinstance(item, _Slot) else [])
            # prefix-cache match: alias the longest cached whole-page
            # prefix (incref'd NOW, so eviction below can't reclaim it)
            # and only allocate private pages for the suffix + growth
            matched: List[int] = []
            if self.prefix is not None and not held:
                matched = self.prefix.match(self._teacher_tokens(item))
                if matched:
                    self.allocator.incref(matched)
            resident = held or matched
            private = need - len(resident)
            if private > self.allocator.free_pages \
                    and self.prefix is not None:
                # refcount-1 LRU trie leaves go before any preemption
                # (the matched pages just took a slot ref, so eviction
                # cannot reclaim them out from under this admission)
                self.stats["prefix_evictions"] += self.prefix.evict(
                    private - self.allocator.free_pages)
            if private > self.allocator.free_pages:
                if matched:
                    self.allocator.decref(matched)
                break               # wait for a retirement
            self.pending.popleft()
            if self.prefix is not None and not held:
                if matched:
                    self.stats["prefix_hits"] += 1
                    self.stats["prefix_hit_tokens"] += \
                        len(matched) * self.page_size
                else:
                    self.stats["prefix_misses"] += 1
                self.stats["shared_pages"] = max(
                    self.stats["shared_pages"],
                    self.allocator.shared_pages)
            pages = resident + self.allocator.alloc(private)
            with TraceAnnotation("repro.admit.request"):
                if self.chunked:
                    ok = self._admit_chunked(slot_id, item, pages,
                                             n_resident=len(resident))
                else:
                    ok = self._admit_into(slot_id, item, pages,
                                          n_shared=len(matched))
            if ok:
                admitted += 1
        return admitted

    def _admit_into(self, slot_id: int, item, pages: List[int],
                    n_shared: int = 0) -> bool:
        """Prefill ``item`` (a fresh Request, or a preempted _Slot whose
        prompt + generated prefix is teacher-forced back in) into the
        allocated pages of ``slot_id``.  The first ``n_shared`` pages
        are prefix-cache aliases already resident in the pool: the
        prefill runs suffix-only over the remaining tokens (attending
        to the shared pages read-only) and the scatter touches only the
        private suffix pages.  A prefill that keeps failing past the
        retry budget FAILs the request (pages decref'd) rather than the
        stream.  Returns True if the slot went active."""
        resumed = isinstance(item, _Slot)
        req = item.req if resumed else item
        tokens = self._teacher_tokens(item)
        M = n_shared * self.page_size   # cached positions (page-whole)
        batch = {"tokens": jnp.asarray(tokens[M:])[None]}
        if req.frontend_emb is not None:
            batch["frontend_emb"] = jnp.asarray(req.frontend_emb)[None]
        if n_shared:
            batch["pages"] = jnp.asarray(pages[:n_shared], jnp.int32)
            batch["cache"] = self.cache
            prefill_fn = self.eng.suffix_prefill_fn
        else:
            prefill_fn = self.eng.prefill_fn

        def _count_retry(attempt, exc):
            self.stats["prefill_retries"] += 1

        try:
            logits, caches = call_with_retries(
                prefill_fn, self.eng.params, batch,
                policy=self.retry, on_retry=_count_retry)
        except Exception as e:                      # noqa: BLE001
            self.allocator.decref(pages)
            self._terminal(req, item.out if resumed else [],
                           RequestStatus.FAILED,
                           f"prefill failed after "
                           f"{self.retry.max_retries} retries: {e}")
            return False
        self.stats["prefills"] += 1
        row = np.zeros((1, self.table.shape[1]), np.int32)
        row[0, :len(pages)] = pages
        # scatter ONLY the suffix caches into the private suffix pages:
        # the shared prefix pages already hold their KV (that is the
        # whole point of the hit) and must never be written through
        srow = np.zeros((1, self.table.shape[1]), np.int32)
        srow[0, :len(pages) - n_shared] = pages[n_shared:]
        self.cache = self._write_prefill(self.cache, caches,
                                         jnp.asarray(srow),
                                         jnp.asarray([slot_id]))
        if resumed:
            slot = _Slot(req=req, length=self._prefill_positions(req)
                         + len(item.out) - 1,
                         pages=list(pages), out=list(item.out),
                         steps=item.steps, order=self._order,
                         preempts=item.preempts,
                         token_times=list(item.token_times))
            tok = item.out[-1]
        else:
            # engine convention: the first generated token is the
            # argmax of the prefill logits; sampled steps start at
            # fold_in(key, 0)
            with TraceAnnotation("repro.admit.sync"):
                tok = int(jnp.argmax(logits[0]))
            slot = _Slot(req=req, length=self._prefill_positions(req),
                         pages=list(pages), out=[tok],
                         order=self._order,
                         token_times=[time.monotonic()])
        self._order += 1
        req.status = RequestStatus.RUNNING
        self.slots[slot_id] = slot
        self.table[slot_id] = row[0]
        self.lens[slot_id] = slot.length
        self.tokens[slot_id] = tok
        self.enc_lens[slot_id] = (req.frontend_emb.shape[0]
                                  if self.cfg.family == "audio"
                                  and req.frontend_emb is not None else 0)
        self.stats["admitted"] += 1
        self.stats["peak_pages"] = max(self.stats["peak_pages"],
                                       self.allocator.used_pages)
        if self.prefix is not None:
            # index the freshly prefilled whole pages NOW (not just at
            # retirement) so concurrent requests sharing this prompt
            # hit while it is still in flight; the trie increfs only
            # nodes it creates, so re-inserting a matched prefix is a
            # no-op walk
            self.prefix.insert(tokens, slot.pages)
        if len(slot.out) >= req.gen:
            self._retire(slot_id)   # gen=1: the prefill already ends it
        return True

    def _admit_chunked(self, slot_id: int, item, pages: List[int],
                       n_resident: int = 0) -> bool:
        """Grant pages + enqueue chunks — the chunked-admission
        counterpart of ``_admit_into``.  NO model call happens here:
        the slot goes PREFILLING with all ``need`` pages granted up
        front, and subsequent mixed steps prefill ``chunk_tokens`` at a
        time (``step()`` packs them under the token budget).  The first
        ``n_resident`` pages already hold KV — a prefix-cache match, or
        the completed pages a chunk-preempted slot kept — so chunking
        starts at position ``n_resident * page_size``.  The slot rides
        the decode batch inactive meanwhile (cur_len == 0: write
        dropped, attention masked, logits discarded)."""
        resumed = isinstance(item, _Slot)
        req = item.req if resumed else item
        if resumed:
            slot = item             # keep out/steps/preempts/times
        else:
            slot = _Slot(req=req, length=0, pages=[], out=[])
        slot.pages = list(pages)
        slot.prefilled = n_resident * self.page_size
        slot.length = 0
        slot.order = self._order
        self._order += 1
        req.status = RequestStatus.PREFILLING
        self.slots[slot_id] = slot
        row = np.zeros((self.table.shape[1],), np.int32)
        row[:len(pages)] = pages
        self.table[slot_id] = row
        self.lens[slot_id] = 0
        self.tokens[slot_id] = 0
        self.enc_lens[slot_id] = 0
        self._prefilling.append(slot_id)
        self.stats["admitted"] += 1
        self.stats["peak_pages"] = max(self.stats["peak_pages"],
                                       self.allocator.used_pages)
        return True

    # ------------------------------------------------------------------
    # stepping
    # ------------------------------------------------------------------

    def _grow_pages(self) -> None:
        """A slot whose next write position opens a new page gets one
        more from the pool (the only mid-flight allocation).  When the
        pool is dry, refcount-1 LRU trie leaves are evicted first (a
        cached-but-unreferenced prefix is the cheapest thing to drop);
        only once the trie has nothing reclaimable is the
        LATEST-admitted active slot preempted (decref'ing its pages)
        until the allocation fits — the stream degrades to less
        concurrency instead of dying with every in-flight request
        lost.  A preempted slot's trie-held prefix pages stay resident
        (refcount 1, trie) and become evictable next iteration, so the
        loop still terminates: the final victim is the needy slot
        itself."""
        for slot_id, slot in enumerate(self.slots):
            if slot is None:
                continue
            page_idx = slot.length // self.page_size
            if page_idx < len(slot.pages):
                continue
            while self.allocator.free_pages < 1:
                if self.prefix is not None:
                    self.stats["prefix_evictions"] += \
                        self.prefix.evict(1)
                    if self.allocator.free_pages >= 1:
                        break
                victim = max(
                    (s for s, sl in enumerate(self.slots)
                     if sl is not None),
                    key=lambda s: self.slots[s].order)
                self._preempt(victim)
                if victim == slot_id:
                    break           # the needy slot itself backed off
            if self.slots[slot_id] is None:
                continue
            (page,) = self.allocator.alloc(1)
            slot.pages.append(page)
            self.table[slot_id, page_idx] = page
            self.stats["peak_pages"] = max(
                self.stats["peak_pages"], self.allocator.used_pages)

    def _cow_guard(self) -> None:
        """Copy-on-write: fork any slot's WRITE page (the page its next
        decode token lands in) that is shared (refcount > 1), so the
        write cannot corrupt another reader's prefix.  On the normal
        scheduler path this never fires — matched prefixes are
        whole-page and the partial tail / growth pages are always
        privately allocated — but external incref'ing (snapshots,
        speculative forks, tests) makes a write page shared, and this
        guard is what keeps the aliasing safe rather than silently
        corrupting."""
        for slot_id, slot in enumerate(self.slots):
            if slot is None:
                continue
            if slot.req.status is RequestStatus.PREFILLING:
                # no decode write while chunking (cur_len == 0 drops
                # it), and chunk writes only touch private suffix pages
                # — pages[0] may be a shared prefix alias, which is
                # exactly NOT a reason to fork
                continue
            wp = slot.length // self.page_size
            page = slot.pages[wp]
            if self.allocator.refcount(page) <= 1:
                continue
            if self.allocator.free_pages < 1:
                self.stats["prefix_evictions"] += self.prefix.evict(1)
            if self.allocator.free_pages < 1:
                # no page to fork into: back this slot off rather than
                # write through a shared page
                self._preempt(slot_id)
                continue
            (new,) = self.allocator.alloc(1)
            self.cache = self._fork_page(self.cache, jnp.int32(page),
                                         jnp.int32(new))
            slot.pages[wp] = new
            self.table[slot_id, wp] = new
            self.allocator.decref([page])
            self.stats["cow_forks"] += 1
            self.stats["peak_pages"] = max(
                self.stats["peak_pages"], self.allocator.used_pages)

    def _expire_deadlines(self) -> None:
        for slot_id, slot in enumerate(self.slots):
            if slot is None:
                continue
            req = slot.req
            if (req.max_steps is not None
                    and slot.steps >= req.max_steps):
                slot = self._evict(slot_id)
                self._terminal(slot.req, slot.out,
                               RequestStatus.TIMED_OUT,
                               f"max_steps={req.max_steps} reached",
                               token_times=slot.token_times)
            elif self._deadline_expired(req):
                slot = self._evict(slot_id)
                self._terminal(slot.req, slot.out,
                               RequestStatus.TIMED_OUT,
                               f"deadline_s={req.deadline_s} lapsed",
                               token_times=slot.token_times)

    def _run_decode(self, dbatch):
        def _count_retry(attempt, exc):
            self.stats["step_retries"] += 1
        # the jitted step is functional (the new cache is returned, the
        # old one untouched), so re-running it after a transient fault
        # is safe — nothing was mutated
        return call_with_retries(self.eng.decode_fn, self.eng.params,
                                 dbatch, policy=self.retry,
                                 on_retry=_count_retry)

    def _run_mixed(self, mbatch):
        def _count_retry(attempt, exc):
            self.stats["step_retries"] += 1
        # functional like the decode step: a transient-fault retry
        # re-runs only the CURRENT chunk + decode step against the
        # untouched previous cache — completed chunks stay resident
        return call_with_retries(self.eng.mixed_fn, self.eng.params,
                                 mbatch, policy=self.retry,
                                 on_retry=_count_retry)

    def _pack_chunk_for_step(self, n_decode: int):
        """(slot_id, C) for the head in-flight prefill's next chunk
        under the token budget, or (None, 0) when nothing chunks this
        step."""
        if not (self.chunked and self._prefilling):
            return None, 0
        sid = self._prefilling[0]
        slot = self.slots[sid]
        remaining = len(self._teacher_tokens(slot)) - slot.prefilled
        C = pack_chunk(remaining, n_decode, self.token_budget,
                       self.chunk_tokens, self.page_size)
        return (sid, C) if C > 0 else (None, 0)

    def _promote(self, slot_id: int, chunk_logits) -> None:
        """Final chunk done: the slot leaves PREFILLING and joins the
        decode batch next step.  Mirrors the end of ``_admit_into``:
        first token = argmax of the (final-chunk) prefill logits for a
        fresh request, the pending generated token for a resumed one;
        the whole prefilled prefix is indexed into the prefix trie."""
        slot = self.slots[slot_id]
        req = slot.req
        if self.guard_nonfinite:
            with TraceAnnotation("repro.promote.sync"):
                finite = bool(jnp.all(jnp.isfinite(chunk_logits)))
            if not finite:
                self._fail_slot(slot_id, "non-finite logits in chunked "
                                "prefill (final chunk)")
                return
        self.stats["prefills"] += 1
        if slot.out:
            tok = slot.out[-1]
            slot.length = (self._prefill_positions(req)
                           + len(slot.out) - 1)
        else:
            with TraceAnnotation("repro.promote.sync"):
                tok = int(jnp.argmax(chunk_logits[0]))
            slot.out = [tok]
            slot.length = self._prefill_positions(req)
            slot.token_times.append(time.monotonic())
        req.status = RequestStatus.RUNNING
        self.lens[slot_id] = slot.length
        self.tokens[slot_id] = tok
        if self.prefix is not None:
            self.prefix.insert(self._teacher_tokens(slot), slot.pages)
        if len(slot.out) >= req.gen:
            self._retire(slot_id)   # gen=1: the prefill already ends it

    def step(self) -> None:
        """One decode step for every RUNNING slot — plus, in chunked
        mode, up to ``chunk_tokens`` of the head in-flight prompt
        packed into the SAME jitted call (``engine.mixed_fn``) under
        the token budget — then retirement.

        Fault handling per step: deadlines expire first (TIMED_OUT with
        partial tokens), a transient step exception is retried up to
        ``retry.max_retries`` times (a mixed-step retry redoes only the
        current chunk — earlier chunks are already resident), and —
        with ``guard_nonfinite`` — any slot whose logits contain
        NaN/inf is quarantined (FAILED) alone while every other slot's
        stream is untouched (a PREFILLING slot is guarded at its final
        chunk, where its logits first matter)."""
        with TraceAnnotation("repro.step"):
            with TraceAnnotation("repro.step.prepare"):
                plan = self._prepare_step()
            if plan is None:
                return
            with TraceAnnotation("repro.step.launch"):
                picked = self._launch_step(*plan)
            # ONE device->host transfer for the whole step
            with TraceAnnotation("repro.step.sync"):
                picked = np.asarray(picked)
            with TraceAnnotation("repro.step.retire"):
                self._retire_step(plan[0], picked)

    def _prepare_step(self):
        """Host work before the step's launch: deadlines, page growth,
        copy-on-write, chunk packing and the batch arrays.  Returns
        ``(was_running, chunk slot, chunk length, batch)``, or None
        when nothing decodes and nothing chunks."""
        if self.n_active == 0:
            return None
        self._expire_deadlines()
        if self.n_active == 0:
            return None
        self._grow_pages()
        if self.n_active == 0:      # growth preempted everything
            return None
        if self.prefix is not None:
            self._cow_guard()
            if self.n_active == 0:
                return None
        # snapshot who decodes THIS step: PREFILLING slots ride the
        # batch masked (cur_len == 0), and a slot promoted after the
        # mixed call must not consume this step's (garbage) logits row
        was_running = [sid for sid, s in enumerate(self.slots)
                       if s is not None
                       and s.req.status is not RequestStatus.PREFILLING]
        c_slot, C = self._pack_chunk_for_step(len(was_running))
        if c_slot is None and not was_running:
            return None             # nothing decodable, nothing chunks
        if self.straggler is not None:
            self.straggler.start_step()
        # table-width bucketing: stage only live pages.  After
        # _grow_pages every active slot owns the page its next write
        # lands in, so the max live page count bounds every per-slot
        # index the step takes into the table row.
        W = self.table.shape[1]
        if self.bucket_tables:
            live = max(len(s.pages) for s in self.slots if s is not None)
            W = bucket_table_width(live, self.table.shape[1])
        self.stats["table_widths"][W] = \
            self.stats["table_widths"].get(W, 0) + 1
        self.stats["decode_pages_live"] += sum(
            len(self.slots[sid].pages) for sid in was_running)
        self.stats["decode_pages_table"] += len(self.slots) * W
        if self.pool_shards > 1 and was_running:
            live = np.concatenate(
                [self.table[sid, :len(self.slots[sid].pages)]
                 for sid in was_running])
            per = np.bincount(
                SH.page_slab_of(live, self.eng.n_pages, self.pool_shards),
                minlength=self.pool_shards)
            self.stats["decode_pages_live_by_shard"] = [
                a + b for a, b in zip(self.stats["decode_pages_live_by_shard"],
                                      per.tolist())]
        dbatch = {"token": jnp.asarray(self.tokens),
                  "cur_len": jnp.asarray(self.lens),
                  "block_table": jnp.asarray(self.table[:, :W]),
                  "cache": self.cache}
        if self.cfg.family == "audio":
            dbatch["enc_lens"] = jnp.asarray(self.enc_lens)
        if c_slot is not None:
            slot = self.slots[c_slot]
            toks = self._teacher_tokens(slot)
            p0 = slot.prefilled
            jp = p0 // self.page_size           # whole prefix pages
            jw = -(-(p0 + C) // self.page_size)  # end page (excl)
            dbatch["chunk_tokens"] = jnp.asarray(
                toks[p0:p0 + C], jnp.int32)[None]
            dbatch["chunk_pages"] = jnp.asarray(
                slot.pages[:jp], jnp.int32)
            dbatch["chunk_write_pages"] = jnp.asarray(
                slot.pages[jp:jw], jnp.int32)
        return was_running, c_slot, C, dbatch

    def _launch_step(self, was_running, c_slot, C, dbatch):
        """Dispatch the decode (or mixed) step and the pick over its
        logits; returns the picks, still on the device."""
        if c_slot is not None:
            slot = self.slots[c_slot]
            logits, chunk_logits, self.cache = self._run_mixed(dbatch)
            self.stats["mixed_steps"] += 1
            self.stats["chunks"] += 1
            self.stats["chunked_tokens"] += C
            slot.prefilled += C
            if slot.prefilled >= len(self._teacher_tokens(slot)):
                self._prefilling.popleft()
                self._promote(c_slot, chunk_logits)
        else:
            logits, self.cache = self._run_decode(dbatch)
        self.stats["steps"] += 1
        # one jitted pick (batched argmax + per-slot fold_in keys +
        # batched categorical + isfinite guard) for the whole step
        B = len(self.slots)
        seeds = np.zeros((B,), np.int32)
        steps = np.zeros((B,), np.int32)
        temps = np.zeros((B,), np.float32)
        for slot_id in was_running:
            slot = self.slots[slot_id]
            seeds[slot_id] = slot.req.seed
            steps[slot_id] = slot.steps
            temps[slot_id] = slot.req.temperature
        return self._pick_fn(logits, jnp.asarray(seeds),
                             jnp.asarray(steps), jnp.asarray(temps))

    def _retire_step(self, was_running, picked) -> None:
        """Append each slot's picked token, retire finished requests
        and run the per-step hooks."""
        greedy, sampled, finite = picked[0], picked[1], picked[2]
        now = time.monotonic()
        for slot_id in was_running:
            slot = self.slots[slot_id]
            if self.guard_nonfinite and not finite[slot_id]:
                # quarantine ONLY this slot: its pages free, its
                # partial stream is attached, survivors untouched
                self._fail_slot(
                    slot_id,
                    f"non-finite logits at decode step {slot.steps}")
                continue
            tok = int(sampled[slot_id] if slot.req.temperature > 0
                      else greedy[slot_id])
            slot.steps += 1
            slot.length += 1
            slot.out.append(tok)
            slot.token_times.append(now)
            self.lens[slot_id] = slot.length
            self.tokens[slot_id] = tok
            if len(slot.out) >= slot.req.gen:
                self._retire(slot_id)
        if self.straggler is not None:
            if self.straggler.end_step() is not None:
                self.stats["straggler_flags"] += 1
        if self.heartbeat is not None:
            self.heartbeat.beat(self.stats["steps"], extra={
                "active": self.n_active,
                "pending": len(self.pending),
                "finished": len(self.finished),
                "failed": self.stats["failed"],
                "retries": self.stats["step_retries"]})
        if self.snapshotter is not None:
            # async cadence: the host cut happens here, the disk
            # writes on the store's background pool
            self.snapshotter.on_step(self)

    def run(self) -> Dict[Any, RequestResult]:
        """Drain the pending queue: admit / step until every request is
        terminal.  A stream deadlock (pending work, no active slot, and
        still not enough pages) REJECTS the blocking request and keeps
        going — already-finished results are never lost; everything the
        scheduler ever saw comes back with a status."""
        while self.pending or self.parked or self.n_active:
            self.admit()
            if self.n_active == 0:
                if not (self.pending or self.parked):
                    break
                if not self.pending:
                    # only parked work left: admit() unparks on the
                    # next call now that nothing is runnable
                    continue
                # deadlock: nothing active to retire, head unadmittable
                item = self.pending.popleft()
                req = item.req if isinstance(item, _Slot) else item
                toks = item.out if isinstance(item, _Slot) else []
                self._release_queued(item)
                self._terminal(
                    req, toks, RequestStatus.REJECTED,
                    f"page pool exhausted: cannot admit with "
                    f"{self.allocator.free_pages} free page(s) of "
                    f"{self.allocator.n_pages} and no active request "
                    "left to retire — raise EngineConfig.n_pages")
                continue
            self.step()
        if self.snapshotter is not None:
            # surface a failed background snapshot at run end instead
            # of silently dropping it with the drained queue
            self.snapshotter.wait()
        return dict(self.finished)
