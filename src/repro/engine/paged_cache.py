"""Paged KV cache: a shared page pool + per-slot block tables.

The dense decode cache pads every request to the engine's full
``(B, max_len)`` budget, so at serving scale most cache bytes are
*dead* — allocated, streamed around, never read.  Paging replaces the
per-slot budget with a shared pool of fixed-width pages:

  * each family cache leaf becomes a **page pool** with the (B, T)
    dims replaced by ``(n_pages, page_size)`` — the GQA leaf
    ``(L, B, T, KV, Dh)`` becomes the head-major ``(L, n_pages, KV,
    page_size, Dh)`` (every head of one page is a contiguous ``(KV,
    page_size, Dh)`` block, which the bf16 decode kernel stages whole;
    the int8 and chunk kernels stage one head's ``(page_size, Dh)``
    tile), the MLA latent leaf
    ``(L, B, T, r)`` becomes ``(L, n_pages, page_size, r)``;
  * a ``(B_slots, max_pages)`` int32 **block table** maps each slot's
    logical page j to a physical page id (the allocator hands pages
    out on demand, so a slot only ever owns ``ceil(len/page_size)``
    pages).

The page is the software analogue of the paper's intermediate-tier
transaction: a fixed-width unit staged whole into the kernel (the
block-table scalar prefetch in ``kernels.vwr_decode`` resolves the
page id before the DMA fires), so reclaiming dead bytes costs no
transaction width.  Recurrent families (hybrid/ssm) carry O(1) state
per slot — nothing to page — and are rejected here.

With ``kv_dtype='int8'`` each pool stores symmetric int8 pages with an
fp32 **scale sidecar** — per page per KV head for GQA
(``k_scale``/``v_scale`` (L, n_pages, KV)), per page for the flat MLA
latent pools (``ckv_scale``/``krope_scale`` (L, n_pages)).  The
sidecar rides the same block-table indirection as the pools and the
flash-decode kernels dequantize INSIDE the staged block, so the HBM
bytes streamed per token drop ~2x vs bf16 (~4x vs fp32) at identical
transaction geometry.  One scale per whole page keeps the sidecar
O(n_pages) and, because a per-block-constant scale commutes with the
dot products, the q8 kernels are exact in fp32 arithmetic up to the
int8 rounding itself.

This module owns the *layout* (pool specs, zero-init, prefill
scatter, the per-step quantized token write) and the host-side page
allocator; request-level admission / eviction policy lives in
``engine.scheduler``.
"""
from __future__ import annotations

from typing import Any, Dict, List, Sequence

import jax
import jax.numpy as jnp

from repro.kernels.quant import int8_scale, quantize_int8

PAGED_FAMILIES = ("dense", "vlm", "moe", "audio")


def check_family(cfg) -> None:
    if cfg.family not in PAGED_FAMILIES:
        raise ValueError(
            f"paged KV cache supports the KV-cache families "
            f"{PAGED_FAMILIES}; family {cfg.family!r} carries O(1) "
            "recurrent state per slot (nothing to page) — serve it "
            "with the dense engine")


def max_pages(max_len: int, page_size: int) -> int:
    return -(-max_len // page_size)


def bucket_table_width(live_pages: int, max_pages: int) -> int:
    """Block-table width bucket covering ``live_pages`` columns.

    Fixed-width ``(B, max_pages)`` tables keep the jitted decode step
    at one shape, but every step then stages (or at least masks)
    ``max_pages`` pages per slot even when the longest slot only owns
    a handful — dead table columns are the table-side analogue of the
    dense cache's dead bytes.  Bucketing rounds the live width up to
    the next power of two (capped at ``max_pages``): the step is
    compiled once per bucket — at most log2(max_pages)+1 shapes over a
    stream's lifetime — and a step stages at most the bucket width of
    pages per slot instead of ``max_pages``.
    """
    if live_pages >= max_pages:
        return max_pages
    w = 1
    while w < max(live_pages, 1):
        w *= 2
    return min(w, max_pages)


def paged_cache_spec(cfg, n_pages: int, page_size: int,
                     batch_slots: int, enc_len: int = 0,
                     kv_dtype: str = None):
    """ShapeDtypeStruct tree for the paged decode cache.

    KV leaves become ``(L, n_pages, KV, page_size, Dh)`` (GQA) or
    ``(L, n_pages, page_size, r)`` (MLA latents) pools.  The audio
    cross-attention cache stays slot-dense ``(L, B_slots, enc_len_p,
    KV, Dh)`` — it is written once at admission and sized exactly by
    the encoder length (no dead bytes to reclaim); ``lm`` *views* it as
    an identity-paged pool at attend time, so ``enc_len`` is padded up
    to a page multiple here.

    ``kv_dtype``: None/'bf16' keeps the pools at the model dtype;
    'int8' stores int8 pools plus fp32 per-page scale sidecars
    (``k_scale``/``v_scale`` (L, n_pages, KV) for GQA,
    ``ckv_scale``/``krope_scale`` (L, n_pages) for MLA latents).
    """
    check_family(cfg)
    fam = cfg.family
    dt_ = jnp.dtype(cfg.dtype)
    if kv_dtype not in (None, "bf16", "int8"):
        raise ValueError(f"kv_dtype must be 'bf16' or 'int8', got "
                         f"{kv_dtype!r}")
    q8 = kv_dtype == "int8"
    if q8 and fam == "audio":
        raise ValueError(
            "kv_dtype='int8' is unsupported for the audio family: the "
            "slot-dense cross cache is written once at admission and "
            "stays at the model dtype — serve audio with kv_dtype="
            "'bf16'")
    pool_dt = jnp.dtype(jnp.int8) if q8 else dt_

    def sds(shape, dtype=dt_):
        return jax.ShapeDtypeStruct(shape, dtype)

    def gqa_pool(L):
        sh = (L, n_pages, cfg.n_kv_heads, page_size, cfg.d_head)
        pool = {"k": sds(sh, pool_dt), "v": sds(sh, pool_dt)}
        if q8:
            ssh = (L, n_pages, cfg.n_kv_heads)
            pool["k_scale"] = sds(ssh, jnp.float32)
            pool["v_scale"] = sds(ssh, jnp.float32)
        return pool

    def mla_pool(L):
        m = cfg.mla
        pool = {"ckv": sds((L, n_pages, page_size, m.kv_lora_rank),
                           pool_dt),
                "krope": sds((L, n_pages, page_size, m.rope_head_dim),
                             pool_dt)}
        if q8:
            pool["ckv_scale"] = sds((L, n_pages), jnp.float32)
            pool["krope_scale"] = sds((L, n_pages), jnp.float32)
        return pool

    if fam in ("dense", "vlm"):
        return mla_pool(cfg.n_layers) if cfg.mla is not None \
            else gqa_pool(cfg.n_layers)

    if fam == "moe":
        m = cfg.moe
        n_moe = cfg.n_layers - m.first_k_dense
        mk = mla_pool if cfg.mla is not None else gqa_pool
        return {"dense": mk(m.first_k_dense) if m.first_k_dense else None,
                "moe": mk(n_moe)}

    # audio: paged self-attention pool + slot-dense cross cache padded
    # to a page multiple (lm reshapes it into an identity-paged view)
    enc_p = max_pages(max(enc_len, 1), page_size) * page_size
    xh = (cfg.n_layers, batch_slots, enc_p, cfg.n_kv_heads, cfg.d_head)
    pool = gqa_pool(cfg.n_layers)
    return {"self_k": pool["k"], "self_v": pool["v"],
            "cross_k": sds(xh), "cross_v": sds(xh)}


def init_paged_cache(cfg, n_pages: int, page_size: int,
                     batch_slots: int, enc_len: int = 0,
                     kv_dtype: str = None, *, shardings):
    """Zeroed pools, every leaf made by one jitted program straight into
    its layout under ``shardings`` (a matching tree), so no device ever
    holds a whole leaf of a sharded pool."""
    spec = paged_cache_spec(cfg, n_pages, page_size, batch_slots,
                            enc_len, kv_dtype)

    def zeros():
        return jax.tree.map(
            lambda s: jnp.zeros(s.shape, s.dtype), spec,
            is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))

    return jax.jit(zeros, out_shardings=shardings)()


# ----------------------------------------------------------------------
# prefill -> pages
# ----------------------------------------------------------------------

def _to_pages(kv, ps):
    """Prefill material (L, B', S, ...) -> (L, B', J, *page) in the
    pool's page layout: S padded up to a page multiple (the zero pad
    also scrubs stale bytes from reused pages) and a GQA page turned
    head-major, (ps, KV, Dh) -> (KV, ps, Dh)."""
    L, Bp, S = kv.shape[:3]
    pad = (-S) % ps
    if pad:
        kv = jnp.pad(kv, ((0, 0), (0, 0), (0, pad))
                     + ((0, 0),) * (kv.ndim - 3))
    J = kv.shape[2] // ps
    kv = kv.reshape(L, Bp, J, ps, *kv.shape[3:])
    return jnp.swapaxes(kv, 3, 4) if kv.ndim == 6 else kv


def _scatter_pages(pool, kv, table):
    """pool (L, n_pages, *page) <- kv (L, B', S, ...) at the pages of
    ``table`` (B', max_pages)."""
    pages = _to_pages(kv, pool.shape[-2])
    J = pages.shape[2]
    return pool.at[:, table[:, :J]].set(pages.astype(pool.dtype))


def _scatter_pages_q8(pool, scales, kv, table):
    """Quantize-on-write prefill scatter into an int8 pool + sidecar.

    Same layout contract as ``_scatter_pages`` but the page material is
    symmetric-int8 quantized per page — per KV head when the pool
    carries a head axis (GQA (L, n_pages, KV, ps, Dh), scale group =
    (ps, Dh) per head), per whole page for the flat MLA latents
    (L, n_pages, ps, r).  The zero pad of a partial last page rides
    inside the scale group, so it both scrubs stale bytes and leaves
    the amax untouched.  Returns (pool, scales)."""
    pages = _to_pages(kv, pool.shape[-2])
    L, Bp, J = pages.shape[:3]
    # GQA (L, B', J, KV, ps, Dh): per-page per-head; MLA (L, B', J, ps,
    # r): per-page
    q, s = quantize_int8(pages, axis=(pages.ndim - 2, pages.ndim - 1))
    s = s.reshape(pages.shape[:pages.ndim - 2])
    return (pool.at[:, table[:, :J]].set(q),
            scales.at[:, table[:, :J]].set(s))


def _scatter_family(sub, kvs, keys, table):
    """Scatter one family's prefill material (``kvs`` aligned with
    ``keys``) into its pool dict ``sub``, routing through the q8
    quantize-on-write path when the dict carries scale sidecars."""
    sub = dict(sub)
    q8 = keys[0] + "_scale" in sub
    for kk, kv in zip(keys, kvs):
        if q8:
            sub[kk], sub[kk + "_scale"] = _scatter_pages_q8(
                sub[kk], sub[kk + "_scale"], kv, table)
        else:
            sub[kk] = _scatter_pages(sub[kk], kv, table)
    return sub


def quantized_page_write(pool, scales, pages, offs, x):
    """One decode token per slot into an int8 pool + scale sidecar.

    pool: (n_pages, KV, ps, Dh) or (n_pages, ps, r) int8 (one layer's
    slice); scales: (n_pages, KV) or (n_pages,) fp32; pages/offs: (B,)
    from ``models.lm._page_write_ids`` (page id ``n_pages`` = inactive
    slot, dropped); x: (B, KV, Dh) or (B, r) new-token material.

    Page scales only ever *grow* while a page fills: the write at
    offset 0 resets the scale to the token's own amax (the device-side
    scrub of a reused page — the rest of the page is zeroed, no
    allocator hook needed), and later writes take ``max(s_old,
    s_tok)`` and requantize the already-resident rows of the touched
    page onto the new grid before inserting the token.  One whole-page
    scatter per step, mirroring the bf16 path's single
    ``at[pages, offs].set``."""
    n_pages = pool.shape[0]
    B = x.shape[0]
    per_head = pool.ndim == 4               # (n_pages, KV, ps, Dh)
    xf = x.astype(jnp.float32)
    s_tok = int8_scale(jnp.max(jnp.abs(xf), axis=-1))  # (B, KV) | (B,)
    pidx = jnp.clip(pages, 0, n_pages - 1)
    s_old = scales[pidx]
    fresh = offs == 0
    s_new = jnp.where(fresh[:, None] if per_head else fresh,
                      s_tok, jnp.maximum(s_old, s_tok))

    def ex(s):                              # scale -> page broadcast
        return s[:, :, None, None] if per_head else s[:, None, None]

    page_f = pool[pidx].astype(jnp.float32) * ex(s_old)
    keep = ~fresh.reshape((B,) + (1,) * (page_f.ndim - 1))
    page_f = jnp.where(keep, page_f, 0.0)
    qpage = jnp.clip(jnp.round(page_f / ex(s_new)),
                     -127, 127).astype(jnp.int8)
    qtok = jnp.clip(jnp.round(xf / s_new[..., None]),
                    -127, 127).astype(jnp.int8)
    qpage = (qpage.at[jnp.arange(B), :, offs] if per_head
             else qpage.at[jnp.arange(B), offs]).set(qtok)
    return (pool.at[pages].set(qpage, mode="drop"),
            scales.at[pages].set(s_new, mode="drop"))


def write_prefill(cfg, cache, caches, table, *, enc_caches_slots=None):
    """Scatter prefill KV material into the page pools.

    ``caches`` is the raw ``lm.prefill`` cache material for B' requests
    (B' = full slot count for whole-batch prefill, or 1 for the
    scheduler's admit-into-slot path); ``table`` holds those requests'
    block-table rows (B', max_pages).  For audio,
    ``enc_caches_slots`` is the list of slot indices receiving the
    slot-dense cross cache rows.  Returns the updated cache tree.
    """
    check_family(cfg)
    fam = cfg.family
    cache = dict(cache)
    keys = ("ckv", "krope") if cfg.mla is not None else ("k", "v")

    if fam in ("dense", "vlm"):
        return _scatter_family(cache, caches, keys, table)

    if fam == "moe":
        kv_d, kv_m = caches
        if cfg.moe.first_k_dense and kv_d is not None:
            cache["dense"] = _scatter_family(cache["dense"], kv_d,
                                             keys, table)
        cache["moe"] = _scatter_family(cache["moe"], kv_m, keys, table)
        return cache

    # audio
    kv, cross = caches
    cache["self_k"] = _scatter_pages(cache["self_k"], kv[0], table)
    cache["self_v"] = _scatter_pages(cache["self_v"], kv[1], table)
    slots = jnp.asarray(
        enc_caches_slots if enc_caches_slots is not None
        else range(kv[0].shape[1]), jnp.int32)
    enc_p = cache["cross_k"].shape[2]
    for kk, xkv in (("cross_k", cross[0]), ("cross_v", cross[1])):
        pad = enc_p - xkv.shape[2]
        if pad:
            xkv = jnp.pad(xkv, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
        cache[kk] = cache[kk].at[:, slots].set(
            xkv.astype(cache[kk].dtype))
    return cache


# ----------------------------------------------------------------------
# host-side page allocator
# ----------------------------------------------------------------------

class PagePoolExhausted(RuntimeError):
    """Raised when an admit/step needs more pages than the pool has
    free — evict a request, shrink the stream, or raise ``n_pages``."""


class PageAllocator:
    """Refcounted free-list over physical page ids [0, n_pages).  Pure
    host state: the device only ever sees the resulting block tables.

    ``alloc`` hands a page out at refcount 1; every additional holder
    (a prefix-cache trie node, a second slot aliasing the page through
    its block table) takes a ref with ``incref`` and releases it with
    ``decref`` — the page returns to the free list only when the last
    ref drops.  The legacy ``free`` keeps its exclusive-owner contract
    (it rejects a shared page: freeing under another holder is exactly
    the preempt/retire double-free the prefix cache must not hit), so
    pre-refcount callers and their double-free diagnostics keep
    working.  ``check()`` asserts the pool invariant at any point
    (owned ∪ free == all pages, owned ∩ free == ∅, every owned page
    holds refcount >= 1, no free page holds a ref) — the chaos /
    property tests call it after every scheduler transition."""

    def __init__(self, n_pages: int):
        self.n_pages = int(n_pages)
        self._free: List[int] = list(range(self.n_pages - 1, -1, -1))
        self._owned: set = set()
        self._refs: Dict[int, int] = {}

    @property
    def free_pages(self) -> int:
        return len(self._free)

    @property
    def used_pages(self) -> int:
        return self.n_pages - len(self._free)

    @property
    def shared_pages(self) -> int:
        """Pages currently held by more than one holder."""
        return sum(1 for r in self._refs.values() if r > 1)

    def refcount(self, page: int) -> int:
        """Current holders of ``page`` (0 = free)."""
        return self._refs.get(page, 0)

    def alloc(self, n: int) -> List[int]:
        if n > len(self._free):
            raise PagePoolExhausted(
                f"page pool exhausted: need {n} page(s), "
                f"{len(self._free)} free of {self.n_pages} "
                f"(evict a request or raise n_pages / EngineConfig."
                f"page_size)")
        out = [self._free.pop() for _ in range(n)]
        self._owned.update(out)
        for p in out:
            self._refs[p] = 1
        return out

    def _validate_owned(self, pages: Sequence[int], verb: str) -> None:
        for p in pages:
            if not 0 <= p < self.n_pages:
                raise ValueError(f"{verb} invalid page id {p}")
            if p not in self._owned:
                raise ValueError(
                    f"{verb} page {p}: not currently handed out "
                    "(already freed, or never allocated)")

    def incref(self, pages: Sequence[int]) -> None:
        """Take one more ref on each page (pages must be handed out)."""
        self._validate_owned(pages, "incref of")
        for p in pages:
            self._refs[p] += 1

    def decref(self, pages: Sequence[int]) -> None:
        """Drop one ref per page; a page whose last ref drops returns
        to the free list.  The same page may appear more than once (it
        then loses one ref per occurrence)."""
        self._validate_owned(pages, "decref of")
        counts: Dict[int, int] = {}
        for p in pages:
            counts[p] = counts.get(p, 0) + 1
        for p, n in counts.items():
            if self._refs[p] < n:
                raise ValueError(
                    f"decref of page {p} by {n} holder(s) but only "
                    f"{self._refs[p]} ref(s) held")
        released = []
        for p, n in counts.items():
            self._refs[p] -= n
            if self._refs[p] == 0:
                del self._refs[p]
                self._owned.discard(p)
                released.append(p)
        self._free.extend(released)

    def free(self, pages: Sequence[int]) -> None:
        """Exclusive-owner release: every page must be held by exactly
        one ref.  A shared page raises — the caller is about to pull a
        page out from under the prefix cache / another slot; route
        shared ownership through ``decref`` instead."""
        seen: set = set()
        for p in pages:
            if not 0 <= p < self.n_pages:
                raise ValueError(f"freeing invalid page id {p}")
            if p in seen:
                raise ValueError(f"double free of page {p} within one "
                                 "free() call")
            if p not in self._owned:
                raise ValueError(
                    f"double free of page {p}: not currently handed "
                    "out (already freed, or never allocated)")
            if self._refs.get(p, 0) != 1:
                raise ValueError(
                    f"free of shared page {p} (refcount "
                    f"{self._refs.get(p, 0)}): another holder still "
                    "references it — decref instead")
            seen.add(p)
        for p in pages:
            self._owned.discard(p)
            del self._refs[p]
        self._free.extend(pages)

    def check(self) -> bool:
        """Validate the pool invariant; raises ``ValueError`` on any
        violation, returns True otherwise."""
        free = set(self._free)
        if len(free) != len(self._free):
            raise ValueError("free list contains duplicate page ids")
        overlap = free & self._owned
        if overlap:
            raise ValueError(f"pages both free and owned: "
                             f"{sorted(overlap)}")
        universe = free | self._owned
        if universe != set(range(self.n_pages)):
            raise ValueError(
                f"page leak: owned ∪ free covers {len(universe)} of "
                f"{self.n_pages} pages "
                f"(missing {sorted(set(range(self.n_pages)) - universe)})")
        unref = self._owned - set(self._refs)
        if unref:
            raise ValueError(f"owned pages with no refcount: "
                             f"{sorted(unref)}")
        bad = [p for p, r in self._refs.items() if r < 1]
        if bad:
            raise ValueError(f"refcount < 1 on owned pages: {sorted(bad)}")
        ghost = set(self._refs) - self._owned
        if ghost:
            raise ValueError(f"refcounts on pages not handed out: "
                             f"{sorted(ghost)}")
        return True

    def to_state(self) -> Dict[str, Any]:
        """JSON-serializable snapshot of the pool partition.  The free
        LIST (not set) is captured in order: ``alloc`` pops from the
        end, so reproducing the exact order is what makes page
        assignment — and therefore block tables — deterministic across
        a snapshot/restore cycle."""
        return {"n_pages": self.n_pages,
                "free": list(self._free),
                "refs": [[int(p), int(r)]
                         for p, r in sorted(self._refs.items())]}

    def load_state(self, state: Dict[str, Any]) -> None:
        """Restore a ``to_state`` snapshot (validates the partition)."""
        if int(state["n_pages"]) != self.n_pages:
            raise ValueError(
                f"allocator snapshot covers {state['n_pages']} pages "
                f"but this pool has {self.n_pages}")
        self._free = [int(p) for p in state["free"]]
        self._refs = {int(p): int(r) for p, r in state["refs"]}
        self._owned = set(self._refs)
        self.check()


# ----------------------------------------------------------------------
# copy-on-write page fork
# ----------------------------------------------------------------------

def fork_page(cfg, cache, src, dst):
    """Device-side page fork: copy physical page ``src`` onto ``dst``
    across every pool leaf — including the int8 scale sidecar rows,
    which are part of page identity (a forked page must dequantize
    exactly like its original until the divergent write lands).

    ``src``/``dst`` may be traced int32 scalars, so one jitted copy
    serves every (src, dst) pair.  Every leaf of the dense/moe paged
    cache carries the page dim at axis 1 (pools ``(L, n_pages, ...)``,
    sidecars ``(L, n_pages[, KV])``); the audio family's
    slot-dense cross cache breaks that contract and is rejected.
    """
    check_family(cfg)
    if cfg.family == "audio":
        raise ValueError(
            "fork_page does not support the audio family: the "
            "slot-dense cross cache carries slots, not pages, at "
            "axis 1")
    return jax.tree.map(lambda leaf: leaf.at[:, dst].set(leaf[:, src]),
                        cache)
