"""Engine snapshots: the full serving state, crash-consistently on disk.

The scheduler's state is exactly the bytes the paper's memory
hierarchy exists to keep resident — the paged KV pool (int8 scale
sidecars included) plus the host bookkeeping that makes those pages
mean something: the ``PageAllocator`` free/owned/refcount partition,
per-slot block tables, request lifecycle (PENDING/PREFILLING/RUNNING,
chunked-prefill progress, generated prefixes, per-request RNG
seed+step), the pending queue and parked set, the ``PrefixCache``
radix trie, finished results and counters.  ``snapshot`` serializes
ALL of it through ``checkpoint.CheckpointStore``'s shard format — the
device pools as ``.npy`` shards, the host state as one JSON blob
riding as a uint8 leaf — so a snapshot inherits the store's
crash-consistency discipline verbatim: written into ``step_N.tmp``,
committed with a fsynced ``_COMPLETE`` marker, atomically renamed.  A
crash mid-snapshot leaves the previous snapshot intact; keep-k GC
bounds disk.

``restore`` rebuilds a ``Scheduler`` over a live engine (the engine —
params, jitted step functions — is NOT part of the snapshot; params
belong to training checkpoints) and resumes decode bit-exactly:

  * the pool bytes round-trip exactly (npy preserves bf16/int8 bits),
  * the allocator free list is restored IN ORDER (``alloc`` pops from
    the end — order is what makes post-restore page assignment, and
    thus block tables, replay deterministically),
  * each slot's ``steps`` counter is its RNG state (sampled step i
    uses ``fold_in(PRNGKey(seed), i)``), so sampling resumes on the
    same key sequence,
  * monotonic timestamps (submit times, token times) are rebased by
    the snapshot→restore clock delta, so deadlines and ITL stats stay
    meaningful across a process restart.

``EngineSnapshotter`` adds the async cadence: every ``every`` steps
the scheduler's step path hands the state off to the store's
background writer (device→host copy is synchronous — the functional
step never mutates a published cache, so the copied tree is a
consistent cut — while the ``.npy`` writes happen off the step path).
``wait()``/``close()`` join the writer and re-raise its failure, so a
dying disk is never silently dropped.

Greedy token streams are pinned bit-identical crash+recover vs
crash-free (gqa/mla × bf16/int8 × prefix-cache × chunked-prefill in
``tests/test_snapshot.py``).  One caveat rides along from the prefix
cache: recovery re-indexes a FINISHED slot's prefix only up to its
snapshot-time length, so a post-recovery admission may match a
SHORTER cached prefix than it would have pre-crash — bit-identical
for model-dtype pools either way, but on int8 pools a near-tie argmax
in a hit's own stream can flip (the same caveat a cache hit already
carries vs a cold prefill).
"""
from __future__ import annotations

import json
import os
import time
from collections import deque
from typing import Any, Dict, List, Optional

import jax
import numpy as np

from repro.checkpoint.store import CheckpointStore

# 2: GQA page pools are head-major (L, n_pages, KV, page_size, Dh); a
# version-1 pool (L, n_pages, page_size, KV, Dh) would be misread
SNAPSHOT_VERSION = 2


# ----------------------------------------------------------------------
# host-state (de)serialization
# ----------------------------------------------------------------------

def _req_state(req) -> Dict[str, Any]:
    d = {"rid": req.rid,
         "tokens": np.asarray(req.tokens, np.int32).tolist(),
         "gen": int(req.gen),
         "temperature": float(req.temperature),
         "seed": int(req.seed),
         "deadline_s": req.deadline_s,
         "max_steps": req.max_steps,
         "status": req.status.value,
         "error": req.error,
         "submit_t": req.submit_t}
    if req.frontend_emb is not None:
        emb = np.asarray(req.frontend_emb)
        d["frontend_emb"] = {"data": emb.tolist(),
                             "dtype": str(emb.dtype)}
    return d


def _req_from_state(d: Dict[str, Any], delta: float):
    from repro.engine.scheduler import Request, RequestStatus
    emb = None
    if d.get("frontend_emb") is not None:
        rec = d["frontend_emb"]
        emb = np.asarray(rec["data"], np.dtype(rec["dtype"]))
    return Request(rid=d["rid"],
                   tokens=np.asarray(d["tokens"], np.int32),
                   gen=int(d["gen"]),
                   temperature=float(d["temperature"]),
                   seed=int(d["seed"]),
                   frontend_emb=emb,
                   deadline_s=d.get("deadline_s"),
                   max_steps=d.get("max_steps"),
                   status=RequestStatus(d["status"]),
                   error=d.get("error"),
                   submit_t=(d["submit_t"] + delta
                             if d.get("submit_t") is not None else None))


def _slot_state(slot) -> Dict[str, Any]:
    return {"req": _req_state(slot.req),
            "length": int(slot.length),
            "pages": [int(p) for p in slot.pages],
            "out": [int(t) for t in slot.out],
            "steps": int(slot.steps),
            "order": int(slot.order),
            "preempts": int(slot.preempts),
            "prefilled": int(slot.prefilled),
            "token_times": list(slot.token_times)}


def _slot_from_state(d: Dict[str, Any], delta: float):
    from repro.engine.scheduler import _Slot
    return _Slot(req=_req_from_state(d["req"], delta),
                 length=int(d["length"]),
                 pages=[int(p) for p in d["pages"]],
                 out=[int(t) for t in d["out"]],
                 steps=int(d["steps"]),
                 order=int(d["order"]),
                 preempts=int(d["preempts"]),
                 prefilled=int(d["prefilled"]),
                 token_times=[t + delta for t in d["token_times"]])


def _queue_state(q) -> List[Dict[str, Any]]:
    from repro.engine.scheduler import _Slot
    out = []
    for item in q:
        if isinstance(item, _Slot):
            out.append({"kind": "slot", **_slot_state(item)})
        else:
            out.append({"kind": "req", **_req_state(item)})
    return out


def _queue_from_state(items, delta: float) -> deque:
    q: deque = deque()
    for d in items:
        if d["kind"] == "slot":
            q.append(_slot_from_state(d, delta))
        else:
            q.append(_req_from_state(d, delta))
    return q


def _result_state(res) -> Dict[str, Any]:
    return {"tokens": np.asarray(res, np.int32).tolist(),
            "status": res.status.value,
            "error": res.error,
            "latency_s": res.latency_s,
            "token_times": res.token_times}


def _result_from_state(d: Dict[str, Any]):
    from repro.engine.scheduler import RequestResult, RequestStatus
    return RequestResult(np.asarray(d["tokens"], np.int32),
                         RequestStatus(d["status"]),
                         error=d.get("error"),
                         latency_s=d.get("latency_s"),
                         token_times=d.get("token_times"))


def scheduler_state(sched) -> Dict[str, Any]:
    """The scheduler's complete host-side state as one JSON-able dict
    (the device pools ride separately as npy shards)."""
    ecfg = sched.eng.ecfg
    state = {
        "version": SNAPSHOT_VERSION,
        "step": int(sched.stats["steps"]),
        "mono": time.monotonic(),
        "engine": {"page_size": int(sched.page_size),
                   "n_pages": int(sched.allocator.n_pages),
                   "batch": int(ecfg.batch),
                   "max_len": int(ecfg.max_len),
                   "family": sched.cfg.family,
                   "kv_dtype": getattr(ecfg, "kv_dtype", None)},
        "sched": {"bucket_tables": bool(sched.bucket_tables),
                  "max_preemptions": int(sched.max_preemptions),
                  "guard_nonfinite": bool(sched.guard_nonfinite),
                  "prefix_cache": sched.prefix is not None,
                  "chunked_prefill": bool(sched.chunked),
                  "chunk_tokens": int(sched.chunk_tokens) or None,
                  "token_budget": int(sched.token_budget) or None,
                  "enc_len": (int(sched.enc_budget)
                              if sched.enc_budget else None)},
        "allocator": sched.allocator.to_state(),
        "table": sched.table.tolist(),
        "lens": sched.lens.tolist(),
        "tokens": sched.tokens.tolist(),
        "enc_lens": sched.enc_lens.tolist(),
        "slots": [None if s is None else _slot_state(s)
                  for s in sched.slots],
        "pending": _queue_state(sched.pending),
        "parked": _queue_state(sched.parked),
        "prefilling": [int(s) for s in sched._prefilling],
        "finished": [[rid, _result_state(res)]
                     for rid, res in sched.finished.items()],
        "prefix": (sched.prefix.to_state()
                   if sched.prefix is not None else None),
        "stats": {**sched.stats,
                  "table_widths": [[int(w), int(n)] for w, n in
                                   sched.stats["table_widths"].items()]},
        "latencies": list(sched._latencies),
        "itl": list(sched._itl),
        "order": int(sched._order),
    }
    return state


def snapshot_tree(sched) -> Dict[str, Any]:
    """The pytree one snapshot save writes: the device cache plus the
    host state as a uint8 JSON leaf (so the whole snapshot commits —
    or doesn't — as ONE atomic store step)."""
    blob = json.dumps(scheduler_state(sched)).encode("utf-8")
    return {"cache": sched.cache,
            "host": np.frombuffer(blob, np.uint8)}


# ----------------------------------------------------------------------
# snapshot / restore
# ----------------------------------------------------------------------

def _as_store(directory_or_store, keep: int = 3) -> CheckpointStore:
    if isinstance(directory_or_store, CheckpointStore):
        return directory_or_store
    if isinstance(directory_or_store, EngineSnapshotter):
        return directory_or_store.store
    return CheckpointStore(str(directory_or_store), keep=keep)


def snapshot(sched, directory_or_store, step: Optional[int] = None,
             *, async_: bool = False, keep: int = 3) -> int:
    """Write one snapshot of ``sched`` (device pools + host state)
    into the store at ``step`` (default: the scheduler's current step
    count).  Returns the step id.  ``async_`` hands the disk writes to
    the store's background pool — the device→host copy still happens
    here, synchronously, so the cut is consistent no matter how the
    scheduler mutates on."""
    store = _as_store(directory_or_store, keep=keep)
    if step is None:
        step = int(sched.stats["steps"])
    store.save(step, snapshot_tree(sched), async_=async_)
    return step


def _read_host_state(store: CheckpointStore, step: int) -> Dict[str, Any]:
    d = os.path.join(store.dir, f"step_{step}")
    with open(os.path.join(d, "index.json")) as f:
        index = json.load(f)
    if "host" not in index:
        raise ValueError(
            f"{d} is not an engine snapshot (no 'host' leaf — a "
            "training checkpoint?)")
    shard = index["host"]["shards"][0]
    blob = np.load(os.path.join(d, shard["file"]))
    return json.loads(bytes(bytearray(np.asarray(blob, np.uint8))))


def restore(directory_or_store, engine, step: Optional[int] = None,
            *, journal=None, snapshotter=None, **sched_overrides):
    """Rebuild a ``Scheduler`` over ``engine`` from the snapshot at
    ``step`` (default: the latest complete one).  With no snapshot on
    disk a FRESH scheduler is returned — recovery before the first
    cadence is just "replay the whole journal into an empty engine".

    The engine must match the snapshot's geometry (page_size, n_pages,
    batch, family, kv_dtype); scheduler knobs (bucketing, chunking,
    prefix cache, budgets) are restored from the snapshot and can be
    overridden via ``sched_overrides``."""
    from repro.engine.scheduler import Scheduler

    store = _as_store(directory_or_store)
    if step is None:
        step = store.latest_step()
    kw = dict(sched_overrides)
    if step is None:
        return Scheduler(engine, journal=journal,
                         snapshotter=snapshotter, **kw)

    state = _read_host_state(store, step)
    if state.get("version") != SNAPSHOT_VERSION:
        raise ValueError(f"snapshot version {state.get('version')} != "
                         f"supported {SNAPSHOT_VERSION}")
    geo = state["engine"]
    ecfg = engine.ecfg
    mine = {"page_size": int(engine.page_size),
            "n_pages": int(engine.n_pages),
            "batch": int(ecfg.batch),
            "max_len": int(ecfg.max_len),
            "family": engine.cfg.family,
            "kv_dtype": getattr(ecfg, "kv_dtype", None)}
    if geo != mine:
        raise ValueError(
            f"snapshot geometry {geo} does not match the engine "
            f"{mine} — restore needs the same engine config the "
            "snapshot was taken under")

    sk = state["sched"]
    for key in ("bucket_tables", "max_preemptions", "guard_nonfinite",
                "prefix_cache", "chunked_prefill", "chunk_tokens",
                "token_budget", "enc_len"):
        kw.setdefault(key, sk[key])
    sched = Scheduler(engine, journal=journal, snapshotter=snapshotter,
                      **kw)

    # device pools: restore the npy shards against the fresh cache's
    # own specs/shardings (same engine config -> same tree), then
    # device_put leaf-by-leaf so sharded pools land where the engine
    # expects them
    target = {"cache": jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), sched.cache)}
    restored = store.restore(step, target)["cache"]
    sched.cache = jax.tree.map(
        lambda r, c: jax.device_put(np.asarray(r), c.sharding),
        restored, sched.cache)

    # host bookkeeping
    delta = time.monotonic() - state["mono"]
    sched.allocator.load_state(state["allocator"])
    sched.table = np.asarray(state["table"], np.int32)
    sched.lens = np.asarray(state["lens"], np.int32)
    sched.tokens = np.asarray(state["tokens"], np.int32)
    sched.enc_lens = np.asarray(state["enc_lens"], np.int32)
    sched.slots = [None if s is None else _slot_from_state(s, delta)
                   for s in state["slots"]]
    sched.pending = _queue_from_state(state["pending"], delta)
    sched.parked = _queue_from_state(state["parked"], delta)
    sched._prefilling = deque(int(s) for s in state["prefilling"])
    sched.finished = {rid: _result_from_state(res)
                      for rid, res in state["finished"]}
    if state["prefix"] is not None:
        if sched.prefix is None:
            raise ValueError("snapshot carries a prefix-cache trie but "
                             "the restored scheduler has prefix_cache "
                             "disabled")
        sched.prefix.load_state(state["prefix"])
    stats = dict(state["stats"])
    stats["table_widths"] = {int(w): int(n)
                             for w, n in stats["table_widths"]}
    sched.stats.update(stats)
    sched._latencies = list(state["latencies"])
    sched._itl = list(state["itl"])
    sched._order = int(state["order"])
    sched.allocator.check()
    if sched.prefix is not None:
        sched.prefix.check()
    return sched


class EngineSnapshotter:
    """Snapshot cadence riding the scheduler's step path.

    Construct with ``every=N`` and hand to the ``Scheduler``
    (``snapshotter=``): after every N-th step the scheduler calls
    ``on_step``, which cuts the state synchronously (host copy) and
    writes it on the store's background pool — decode is never blocked
    on disk.  Exposes ``latest_step()`` so it plugs directly into
    ``runtime.resilience.run_with_restarts`` as the resume store.
    ``wait()``/``close()`` join the background writer and re-raise its
    failure (the snapshot-cadence teardown the ``CheckpointStore.
    wait`` satellite exists for); the scheduler also calls ``wait()``
    when its drain loop ends."""

    def __init__(self, directory: str, *, every: int = 0, keep: int = 3):
        self.store = CheckpointStore(directory, keep=keep)
        self.every = int(every)
        self.saved = 0
        self._last: Optional[int] = None

    def latest_step(self) -> Optional[int]:
        return self.store.latest_step()

    def save(self, sched, *, async_: bool = True) -> int:
        step = snapshot(sched, self.store, async_=async_)
        self._last = step
        self.saved += 1
        return step

    def on_step(self, sched) -> None:
        step = int(sched.stats["steps"])
        if self.every and step != self._last and step % self.every == 0:
            self.save(sched, async_=True)

    def wait(self) -> None:
        self.store.wait()

    def close(self) -> None:
        self.store.wait()
