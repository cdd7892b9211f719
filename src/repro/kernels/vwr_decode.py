"""Flash-decode kernels: one query token (or one prompt chunk) vs a
dense, paged or int8 KV cache.

Decode is the zero-reuse end of the paper's streaming spectrum — every
cached (T, Dh) K/V element is read exactly once per generated token, so
the only lever is transaction width: each grid step stages one wide
cache block (a ``bkv x Dh`` slab, or one whole page) in VMEM and the
whole head group consumes it before the next fetch.  GQA is zero-copy
as in ``vwr_attention``: the q block is the *group* (G query heads that
share one KV head), so the staged cache bytes per group are 1/G of the
head-expanded layout.

Unlike the prefill kernel these return the **unnormalized** online-
softmax partials (o_tilde, m, l) rather than the normalized context:
that is the combine contract of distributed FlashDecoding
(``dist.decode``), where each model shard holds a slab of the cache
starting at global position ``pos0`` and only the (B, H) statistics
cross the interconnect.  Single-device callers normalize with
``o_tilde / max(l, eps)``.

Mosaic tiling contract (what the TPU compiler accepts): the last two
dims of every block equal the array's own, or divide by (8, 128).  So

  * paged GQA pools are **head-major**, ``(n_pages, KV, page_size,
    Dh)``: each grid step of the bf16 paged kernel (one block of a
    slot's live pages) stages ``ppb`` whole pages, every head of a page
    one contiguous block ``(1, KV, ps, Dh)``; the int8 and chunk-prefix
    kernels' (row, page) step stages one head's ``(page_size, Dh)``
    tile, a block ``(1, 1, ps, Dh)``;
  * the (m, l) statistics leave the kernel as ``(rows, G, 1)`` columns
    (block ``(1, G, 1)``) — exactly the ``(G, 1)`` VMEM scratch they
    accumulate in — and the wrappers drop the trailing 1;
  * dense-cache lengths ``[cur_len, pos0]`` ride as a scalar-prefetch
    operand (SMEM), never as a vector block.

Every kernel shares one online-softmax step (``_flash_update``); the
int8 variants stage int8 blocks and fold their per-block scales into
the scores and values (``q.(k*s) == (q.k)*s``), so dequantization costs
one multiply per staged block, not one per element.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
F32 = jnp.float32
EXACT = jax.lax.Precision.HIGHEST


def _params(*dimension_semantics):
    return pltpu.CompilerParams(dimension_semantics=dimension_semantics)


# ----------------------------------------------------------------------
# the shared online-softmax step
# ----------------------------------------------------------------------

def _flash_init(j, acc_ref, ms_ref, ls_ref):
    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        ms_ref[...] = jnp.full_like(ms_ref, NEG_INF)
        ls_ref[...] = jnp.zeros_like(ls_ref)


def _scores(q, k, precision=None):
    """(..., R, D) x (..., T, D) -> (..., R, T) fp32, leading dims
    batched."""
    n = q.ndim - 2
    lead = tuple(range(n))
    return jax.lax.dot_general(q, k, (((n + 1,), (n + 1,)), (lead, lead)),
                               precision=precision,
                               preferred_element_type=F32)


def _pv(p, v, precision=None):
    """(..., R, T) x (..., T, Dv) -> (..., R, Dv) fp32, leading dims
    batched."""
    n = p.ndim - 2
    lead = tuple(range(n))
    return jax.lax.dot_general(p, v, (((n + 1,), (n,)), (lead, lead)),
                               precision=precision,
                               preferred_element_type=F32)


def _flash_update(s, valid, v, acc_ref, ms_ref, ls_ref, v_scale=None,
                  precision=None):
    """Fold one staged block into the running (acc, m, l): ``s`` (R, T)
    scores, ``valid`` (R, T) mask, ``v`` (T, Dv) values; the scratch
    refs are (R, Dv), (R, 1), (R, 1).  Leading dims (a block of heads)
    ride along batched; ``precision`` is that of the ``p.v`` dot."""
    s = jnp.where(valid, s, NEG_INF)
    m_prev = ms_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    p = jnp.where(m_new > NEG_INF / 2, p, 0.0)
    corr = jnp.exp(m_prev - m_new)
    ls_ref[...] = ls_ref[...] * corr + p.sum(axis=-1, keepdims=True)
    pv = _pv(p, v, precision)
    if v_scale is not None:
        pv = pv * v_scale
    acc_ref[...] = acc_ref[...] * corr + pv
    ms_ref[...] = m_new


def _flash_store(j, last, ot_ref, m_ref, l_ref, acc_ref, ms_ref, ls_ref):
    @pl.when(j == last)
    def _store():
        ot_ref[0] = acc_ref[...]
        m_ref[0] = ms_ref[...]
        l_ref[0] = ls_ref[...]


def _dense_valid(s, j, bkv, t_valid, lens_ref):
    """Mask of a dense-cache block: ``idx < t_valid`` drops the
    block-multiple padding, ``pos0 + idx < cur`` positions not yet
    written (sharded, other shards' positions never appear here)."""
    idx = j * bkv + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    return (idx < t_valid) & (lens_ref[0, 1] + idx < lens_ref[0, 0])


def _count_valid(s, count):
    """Mask of a page: its first ``count`` positions are valid (0 masks
    a page past the slot's length, an unallocated table entry, or —
    sharded — a page another shard owns)."""
    return jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) < count


def _scratch(rows, width):
    return [pltpu.VMEM((rows, width), F32), pltpu.VMEM((rows, 1), F32),
            pltpu.VMEM((rows, 1), F32)]


def _out_shapes(n, rows, width):
    return [jax.ShapeDtypeStruct((n, rows, width), F32),
            jax.ShapeDtypeStruct((n, rows, 1), F32),
            jax.ShapeDtypeStruct((n, rows, 1), F32)]


def _out_specs(rows, width, index_map):
    """o_tilde (1, rows, width) + m, l (1, rows, 1) blocks, all at
    ``index_map``'s leading index."""
    return [pl.BlockSpec((1, rows, width), index_map),
            pl.BlockSpec((1, rows, 1), index_map),
            pl.BlockSpec((1, rows, 1), index_map)]


def _squeeze_stats(o_t, m, l):
    return o_t, m[..., 0], l[..., 0]


# ----------------------------------------------------------------------
# GQA: dense cache, paged pool, chunk prefix — bf16 and int8
# ----------------------------------------------------------------------

def _decode_kernel(lens_ref, q_ref, k_ref, v_ref, ot_ref, m_ref, l_ref,
                   acc_ref, ms_ref, ls_ref, *, scale, bkv, t_valid, n_kv):
    j = pl.program_id(1)
    _flash_init(j, acc_ref, ms_ref, ls_ref)
    s = _scores(q_ref[0].astype(F32) * scale, k_ref[0].astype(F32))
    _flash_update(s, _dense_valid(s, j, bkv, t_valid, lens_ref),
                  v_ref[0].astype(F32), acc_ref, ms_ref, ls_ref)
    _flash_store(j, n_kv - 1, ot_ref, m_ref, l_ref, acc_ref, ms_ref,
                 ls_ref)


def vwr_flash_decode_p(q: jax.Array, k: jax.Array, v: jax.Array,
                       lens: jax.Array, *, bkv: int, t_valid: int,
                       interpret: bool = False):
    """q: (B*KV, G, Dh); k, v: (B*KV, Tp, Dh) flattened kv heads, Tp
    padded to a bkv multiple; lens: (1, 2) int32 [cur_len, pos0]
    (dynamic — decode runs inside a jitted generation loop).  Grid:
    (B*KV, kv-blocks), kv innermost (sequential).  Returns (o_tilde
    (BKV, G, Dh) f32, m (BKV, G) f32, l (BKV, G) f32)."""
    BKV, G, D = q.shape
    Tp = k.shape[1]
    assert k.shape == (BKV, Tp, D) and v.shape == k.shape
    assert Tp % bkv == 0, (Tp, bkv)
    n_kv = Tp // bkv
    kernel = functools.partial(_decode_kernel, scale=1.0 / (D ** 0.5),
                               bkv=bkv, t_valid=t_valid, n_kv=n_kv)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,              # lens
        grid=(BKV, n_kv),
        in_specs=[
            pl.BlockSpec((1, G, D), lambda b, j, ln: (b, 0, 0)),
            pl.BlockSpec((1, bkv, D), lambda b, j, ln: (b, j, 0)),
            pl.BlockSpec((1, bkv, D), lambda b, j, ln: (b, j, 0)),
        ],
        out_specs=_out_specs(G, D, lambda b, j, ln: (b, 0, 0)),
        scratch_shapes=_scratch(G, D),
    )
    return _squeeze_stats(*pl.pallas_call(
        kernel, grid_spec=grid_spec, out_shape=_out_shapes(BKV, G, D),
        compiler_params=_params("parallel", "arbitrary"),
        interpret=interpret,
    )(lens, q, k, v))


def _decode_kernel_q8(lens_ref, ks_ref, vs_ref, q_ref, k_ref, v_ref,
                      ot_ref, m_ref, l_ref, acc_ref, ms_ref, ls_ref, *,
                      scale, bkv, t_valid, n_kv):
    b = pl.program_id(0)
    j = pl.program_id(1)
    _flash_init(j, acc_ref, ms_ref, ls_ref)
    s = _scores(q_ref[0].astype(F32) * scale,
                k_ref[0].astype(F32)) * ks_ref[b]
    _flash_update(s, _dense_valid(s, j, bkv, t_valid, lens_ref),
                  v_ref[0].astype(F32), acc_ref, ms_ref, ls_ref,
                  v_scale=vs_ref[b])
    _flash_store(j, n_kv - 1, ot_ref, m_ref, l_ref, acc_ref, ms_ref,
                 ls_ref)


def vwr_flash_decode_q8_p(q: jax.Array, k: jax.Array, v: jax.Array,
                          k_scale: jax.Array, v_scale: jax.Array,
                          lens: jax.Array, *, bkv: int, t_valid: int,
                          interpret: bool = False):
    """int8 dense flash decode: k, v int8 (BKV, Tp, Dh); k_scale,
    v_scale (BKV,) fp32 per flattened kv-head row.  Same unnormalized
    (o_tilde, m, l) fp32 contract as ``vwr_flash_decode_p``."""
    BKV, G, D = q.shape
    Tp = k.shape[1]
    assert k.shape == (BKV, Tp, D) and v.shape == k.shape
    assert k_scale.shape == (BKV,) and v_scale.shape == (BKV,)
    assert Tp % bkv == 0, (Tp, bkv)
    n_kv = Tp // bkv
    kernel = functools.partial(_decode_kernel_q8, scale=1.0 / (D ** 0.5),
                               bkv=bkv, t_valid=t_valid, n_kv=n_kv)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,              # lens, k_scale, v_scale
        grid=(BKV, n_kv),
        in_specs=[
            pl.BlockSpec((1, G, D), lambda b, j, *_: (b, 0, 0)),
            pl.BlockSpec((1, bkv, D), lambda b, j, *_: (b, j, 0)),
            pl.BlockSpec((1, bkv, D), lambda b, j, *_: (b, j, 0)),
        ],
        out_specs=_out_specs(G, D, lambda b, j, *_: (b, 0, 0)),
        scratch_shapes=_scratch(G, D),
    )
    return _squeeze_stats(*pl.pallas_call(
        kernel, grid_spec=grid_spec, out_shape=_out_shapes(BKV, G, D),
        compiler_params=_params("parallel", "arbitrary"),
        interpret=interpret,
    )(lens, k_scale, v_scale, q, k, v))


# the bf16 paged kernel stages whole pages (every KV head) several at a
# time; one staged buffer of K + V pages stays under this many bytes
PAGE_BLOCK_BYTES = 512 * 1024


def pages_per_block(J: int, page_bytes: int) -> int:
    """Pages staged per DMA block: the largest power of two that is at
    most ``J`` and keeps ``ppb * page_bytes`` (K + V of one page, every
    head) under ``PAGE_BLOCK_BYTES``."""
    ppb = 1
    while 2 * ppb <= J and 2 * ppb * page_bytes <= PAGE_BLOCK_BYTES:
        ppb *= 2
    return ppb


# flags of a grid step's block: the first and the last of its slot
FIRST_BLOCK, LAST_BLOCK = 1, 2


def _paged_decode_kernel(slot_ref, flag_ref, page_ref, cnt_ref, q_ref,
                         *refs, scale, ppb, ps):
    k_refs, v_refs = refs[:ppb], refs[ppb:2 * ppb]
    ot_ref, m_ref, l_ref, acc_ref, ms_ref, ls_ref = refs[2 * ppb:]
    w = pl.program_id(0)

    @pl.when(flag_ref[w] & FIRST_BLOCK != 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        ms_ref[...] = jnp.full_like(ms_ref, NEG_INF)
        ls_ref[...] = jnp.zeros_like(ls_ref)

    # the block's pages side by side: (KV, ppb * ps, Dh)
    k = jnp.concatenate([r[0] for r in k_refs], axis=1)
    v = jnp.concatenate([r[0] for r in v_refs], axis=1)
    q = q_ref[0]                                    # (KV, G, Dh)
    if q.dtype != k.dtype:
        q, k = q.astype(F32), k.astype(F32)
    # bf16 products are exact in the fp32 accumulator; fp32 operands
    # (and p below) need HIGHEST, as the MXU's default rounds them to
    # bf16
    s = _scores(q, k, EXACT if k.dtype == F32 else None) * scale
    t = jax.lax.broadcasted_iota(jnp.int32, s.shape[1:], 1)
    valid = jnp.zeros(t.shape, jnp.bool_)
    for p in range(ppb):
        lo = p * ps
        valid |= (t >= lo) & (t < lo + cnt_ref[w * ppb + p])
    _flash_update(s, valid[None], v.astype(F32), acc_ref, ms_ref, ls_ref,
                  precision=EXACT)

    @pl.when(flag_ref[w] & LAST_BLOCK != 0)
    def _store():
        ot_ref[0] = acc_ref[...]
        m_ref[0] = ms_ref[...]
        l_ref[0] = ls_ref[...]


def _keep_last_staged(a, b):
    """Scan step: the later page where its operand stages one, else
    the one before it."""
    (page_a, staged_a), (page_b, staged_b) = a, b
    return jnp.where(staged_b, page_b, page_a), staged_a | staged_b


def _work_list(table, counts, ppb):
    """The grid's work: each slot's live pages (nonzero count) moved to
    the front of its row in order — ``dist.decode`` zeroes foreign
    pages anywhere in a row — then one item per block of ``ppb`` pages
    that holds a live one, slot by slot.  Returns the pages live per
    slot, the item count, and per item (padded to every block of the
    row) its slot, its FIRST_BLOCK | LAST_BLOCK flags, and per page its
    physical page and valid count, flat.  A page past the slot's live
    ones (count 0) names the page its operand staged last, so the
    pipeline, seeing the block index unchanged, issues no DMA for it."""
    B, J = table.shape
    pad = ((0, 0), (0, (-J) % ppb))
    table, counts = jnp.pad(table, pad), jnp.pad(counts, pad)
    dead = (counts == 0).astype(jnp.int32)
    _, table, counts = jax.lax.sort((dead, table, counts), dimension=1,
                                    is_stable=True, num_keys=1)
    n_live = jnp.sum(1 - dead, axis=1, dtype=jnp.int32)
    NB = table.shape[1] // ppb
    n_blk = (n_live + ppb - 1) // ppb
    blk = jnp.arange(NB, dtype=jnp.int32)[None, :]
    flags = (jnp.where(blk == 0, FIRST_BLOCK, 0)
             | jnp.where(blk == n_blk[:, None] - 1, LAST_BLOCK, 0))
    # the blocks that hold live pages first, slot by slot
    unused = (blk >= n_blk[:, None]).astype(jnp.int32).reshape(-1)
    _, order = jax.lax.sort((unused, jnp.arange(B * NB, dtype=jnp.int32)),
                            is_stable=True, num_keys=1)
    pages = table.reshape(B * NB, ppb)[order]
    cnt = counts.reshape(B * NB, ppb)[order]
    pages, _ = jax.lax.associative_scan(_keep_last_staged,
                                        (pages, cnt > 0), axis=0)
    return (n_live, jnp.sum(n_blk), order // NB, flags.reshape(-1)[order],
            pages.reshape(-1), cnt.reshape(-1))


def _page_spec(ps, D, KV, page_of):
    """One head's (ps, Dh) tile of the physical page ``page_of(b, j,
    tbl)`` of a head-major (n_pages, KV, ps, Dh) pool, for grid row
    b = slot * KV + head."""
    return pl.BlockSpec(
        (1, 1, ps, D),
        lambda b, j, tbl, *_: (page_of(b, j, tbl), b % KV, 0, 0))


def vwr_paged_flash_decode_p(q: jax.Array, k_pool: jax.Array,
                             v_pool: jax.Array, table: jax.Array,
                             counts: jax.Array, *, ppb: int,
                             interpret: bool = False):
    """Flash-decode over a paged KV pool, ``ppb`` whole pages per grid
    step, over live pages only.

    The block table IS the transaction schedule: it rides in as a
    scalar-prefetch operand, and each grid step's ``ppb`` page operands
    resolve their physical pages in their index maps *before* the DMAs
    fire, each staging one page's whole ``(KV, page_size, Dh)`` block
    (every head: one contiguous run of the head-major pool) — the
    gather never materializes in HBM, and the pipeline fetches the next
    step's pages while this step computes.  The grid is dynamic: one
    step per block of ``ppb`` live pages (``_work_list``), so no step
    is spent on a page of count 0, a slot with none, or padding.

    q: (B, KV, G, Dh); k_pool, v_pool: head-major (n_pages, KV,
    page_size, Dh); table, counts: (B, max_pages) int32, table within
    [0, n_pages).  Returns (o_tilde (B, KV, G, Dh), m (B, KV, G),
    l (B, KV, G)) fp32 — the ``vwr_flash_decode_p`` contract, an
    inactive slot reading (0, NEG_INF, 0).
    """
    B, KV, G, D = q.shape
    n_pages, KVp, ps, Dp = k_pool.shape
    assert v_pool.shape == k_pool.shape and (KVp, Dp) == (KV, D)
    assert table.shape == counts.shape and table.shape[0] == B
    n_live, n_items, *work = _work_list(table, counts, ppb)
    kernel = functools.partial(_paged_decode_kernel,
                               scale=1.0 / (D ** 0.5), ppb=ppb, ps=ps)

    def page(p):
        return pl.BlockSpec(
            (1, KV, ps, D), lambda w, slot, flag, pg, *_: (
                pg[w * ppb + p], 0, 0, 0))

    slot = lambda w, slot, *_: (slot[w], 0, 0, 0)           # noqa: E731
    pages = [page(p) for p in range(ppb)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,          # slot, flags, pages, counts
        # one step per live block (at least one, which all-inactive
        # slots leave masked)
        grid=(jnp.maximum(n_items, 1),),
        in_specs=[pl.BlockSpec((1, KV, G, D), slot)] + pages + pages,
        out_specs=[pl.BlockSpec((1, KV, G, D), slot),
                   pl.BlockSpec((1, KV, G, 1), slot),
                   pl.BlockSpec((1, KV, G, 1), slot)],
        scratch_shapes=[pltpu.VMEM((KV, G, D), F32),
                        pltpu.VMEM((KV, G, 1), F32),
                        pltpu.VMEM((KV, G, 1), F32)],
    )
    out_shape = [jax.ShapeDtypeStruct((B, KV, G, D), F32),
                 jax.ShapeDtypeStruct((B, KV, G, 1), F32),
                 jax.ShapeDtypeStruct((B, KV, G, 1), F32)]
    # "arbitrary": a slot's blocks accumulate over consecutive steps
    o_t, m, l = pl.pallas_call(
        kernel, grid_spec=grid_spec, out_shape=out_shape,
        compiler_params=_params("arbitrary"),
        interpret=interpret,
    )(*work, q, *[k_pool] * ppb, *[v_pool] * ppb)
    # a slot with no live page gets no step: write its contract here
    live = (n_live > 0)[:, None, None]
    return (jnp.where(live[..., None], o_t, 0.0),
            jnp.where(live, m[..., 0], NEG_INF),
            jnp.where(live, l[..., 0], 0.0))


def _paged_decode_kernel_q8(tbl_ref, cnt_ref, ks_ref, vs_ref, q_ref,
                            k_ref, v_ref, ot_ref, m_ref, l_ref, acc_ref,
                            ms_ref, ls_ref, *, scale, n_logical,
                            kv_heads):
    b = pl.program_id(0)
    j = pl.program_id(1)
    _flash_init(j, acc_ref, ms_ref, ls_ref)
    page = tbl_ref[b // kv_heads, j]
    head = b % kv_heads
    s = _scores(q_ref[0].astype(F32) * scale,
                k_ref[0, 0].astype(F32)) * ks_ref[page, head]
    _flash_update(s, _count_valid(s, cnt_ref[b // kv_heads, j]),
                  v_ref[0, 0].astype(F32), acc_ref, ms_ref, ls_ref,
                  v_scale=vs_ref[page, head])
    _flash_store(j, n_logical - 1, ot_ref, m_ref, l_ref, acc_ref, ms_ref,
                 ls_ref)


def vwr_paged_flash_decode_q8_p(q: jax.Array, k_pool: jax.Array,
                                v_pool: jax.Array, k_scale: jax.Array,
                                v_scale: jax.Array, table: jax.Array,
                                counts: jax.Array, *,
                                interpret: bool = False):
    """Flash decode over int8 page pools with per-page per-head scales.

    k_pool, v_pool: int8 head-major (n_pages, KV, page_size, Dh);
    k_scale, v_scale: fp32 (n_pages, KV) sidecars resolved through the
    SAME ``table[slot, j]`` scalar-prefetch indirection as the page
    DMA.  Everything else matches ``vwr_paged_flash_decode_p``.
    """
    BKV, G, D = q.shape
    n_pages, KV, ps, Dp = k_pool.shape
    assert v_pool.shape == k_pool.shape and Dp == D
    assert k_scale.shape == (n_pages, KV), (k_scale.shape, k_pool.shape)
    assert v_scale.shape == (n_pages, KV)
    B, J = table.shape
    assert counts.shape == (B, J) and B * KV == BKV, (table.shape, BKV)
    kernel = functools.partial(_paged_decode_kernel_q8,
                               scale=1.0 / (D ** 0.5), n_logical=J,
                               kv_heads=KV)
    page_of = lambda b, j, tbl: tbl[b // KV, j]             # noqa: E731
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,          # table, counts, k_scale, v_scale
        grid=(BKV, J),
        in_specs=[
            pl.BlockSpec((1, G, D), lambda b, j, *_: (b, 0, 0)),
            _page_spec(ps, D, KV, page_of),
            _page_spec(ps, D, KV, page_of),
        ],
        out_specs=_out_specs(G, D, lambda b, j, *_: (b, 0, 0)),
        scratch_shapes=_scratch(G, D),
    )
    return _squeeze_stats(*pl.pallas_call(
        kernel, grid_spec=grid_spec, out_shape=_out_shapes(BKV, G, D),
        compiler_params=_params("parallel", "arbitrary"),
        interpret=interpret,
    )(table, counts, k_scale, v_scale, q, k_pool, v_pool))


# chunked prefill: a (C, d) query chunk against the paged pool.
#
# Chunked prefill attends C chunk queries (one in-flight prompt's next
# slice) against the PRIOR pages of that prompt — earlier chunks and
# prefix-cache hits already resident in the pool via the block table.
# The payoff vs replaying the decode kernel C times: each prior page is
# staged from HBM ONCE for all C queries (C·G rows ride the VMEM
# resident block), so staged bytes per chunk are ~1/C of the per-row
# decode cost.  The within-chunk causal self-attention block is a tiny
# (C, C) problem handled outside (models.attention combines the two
# partials with the flash merge), so these kernels mask only by the
# per-page valid counts — which also lets dist.decode zero out pages a
# shard does not own.

def _chunk_prefix_kernel(tbl_ref, cnt_ref, q_ref, k_ref, v_ref,
                         ot_ref, m_ref, l_ref, acc_ref, ms_ref, ls_ref,
                         *, scale, n_logical):
    j = pl.program_id(1)
    _flash_init(j, acc_ref, ms_ref, ls_ref)
    s = _scores(q_ref[0].astype(F32) * scale, k_ref[0, 0].astype(F32))
    _flash_update(s, _count_valid(s, cnt_ref[j]),
                  v_ref[0, 0].astype(F32), acc_ref, ms_ref, ls_ref)
    _flash_store(j, n_logical - 1, ot_ref, m_ref, l_ref, acc_ref, ms_ref,
                 ls_ref)


def vwr_chunk_prefix_attend_p(q: jax.Array, k_pool: jax.Array,
                              v_pool: jax.Array, table: jax.Array,
                              counts: jax.Array, *,
                              interpret: bool = False):
    """Chunk-prefix flash attention over the paged pool.

    q: (KV, C*G, D) chunk queries flattened per KV head (C = chunk
    tokens, G = H // KV); k_pool, v_pool: head-major (n_pages, KV, ps,
    D); table: (J,) physical page ids of the chunk's PRIOR pages in
    prefix order; counts: (J,) valid tokens per page (page_size for
    full prior pages, 0 for pages a shard does not own).  Returns fp32
    partials (o_tilde (KV, C*G, D), m (KV, C*G), l (KV, C*G)) under the
    shared flash combine contract.
    """
    KV, CG, D = q.shape
    n_pages, KVp, ps, _ = k_pool.shape
    assert KVp == KV, (KVp, KV)
    J, = table.shape
    assert counts.shape == (J,), (counts.shape, J)
    kernel = functools.partial(_chunk_prefix_kernel,
                               scale=1.0 / (D ** 0.5), n_logical=J)
    page_of = lambda kv, j, tbl: tbl[j]                     # noqa: E731
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                  # table, counts
        grid=(KV, J),
        in_specs=[
            pl.BlockSpec((1, CG, D), lambda kv, j, *_: (kv, 0, 0)),
            _page_spec(ps, D, KV, page_of),
            _page_spec(ps, D, KV, page_of),
        ],
        out_specs=_out_specs(CG, D, lambda kv, j, *_: (kv, 0, 0)),
        scratch_shapes=_scratch(CG, D),
    )
    return _squeeze_stats(*pl.pallas_call(
        kernel, grid_spec=grid_spec, out_shape=_out_shapes(KV, CG, D),
        compiler_params=_params("parallel", "arbitrary"),
        interpret=interpret,
    )(table, counts, q, k_pool, v_pool))


def _chunk_prefix_kernel_q8(tbl_ref, cnt_ref, ks_ref, vs_ref, q_ref,
                            k_ref, v_ref, ot_ref, m_ref, l_ref,
                            acc_ref, ms_ref, ls_ref, *, scale,
                            n_logical):
    kv = pl.program_id(0)
    j = pl.program_id(1)
    _flash_init(j, acc_ref, ms_ref, ls_ref)
    page = tbl_ref[j]
    s = _scores(q_ref[0].astype(F32) * scale,
                k_ref[0, 0].astype(F32)) * ks_ref[page, kv]
    _flash_update(s, _count_valid(s, cnt_ref[j]),
                  v_ref[0, 0].astype(F32), acc_ref, ms_ref, ls_ref,
                  v_scale=vs_ref[page, kv])
    _flash_store(j, n_logical - 1, ot_ref, m_ref, l_ref, acc_ref, ms_ref,
                 ls_ref)


def vwr_chunk_prefix_attend_q8_p(q: jax.Array, k_pool: jax.Array,
                                 v_pool: jax.Array, k_scale: jax.Array,
                                 v_scale: jax.Array, table: jax.Array,
                                 counts: jax.Array, *,
                                 interpret: bool = False):
    """``vwr_chunk_prefix_attend_p`` over int8 page pools with fp32
    (n_pages, KV) scale sidecars, dequantized on the staged block."""
    KV, CG, D = q.shape
    n_pages, KVp, ps, _ = k_pool.shape
    assert KVp == KV, (KVp, KV)
    assert k_scale.shape == (n_pages, KV) and \
        v_scale.shape == (n_pages, KV)
    J, = table.shape
    assert counts.shape == (J,), (counts.shape, J)
    kernel = functools.partial(_chunk_prefix_kernel_q8,
                               scale=1.0 / (D ** 0.5), n_logical=J)
    page_of = lambda kv, j, tbl: tbl[j]                     # noqa: E731
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,      # table, counts, k_scale, v_scale
        grid=(KV, J),
        in_specs=[
            pl.BlockSpec((1, CG, D), lambda kv, j, *_: (kv, 0, 0)),
            _page_spec(ps, D, KV, page_of),
            _page_spec(ps, D, KV, page_of),
        ],
        out_specs=_out_specs(CG, D, lambda kv, j, *_: (kv, 0, 0)),
        scratch_shapes=_scratch(CG, D),
    )
    return _squeeze_stats(*pl.pallas_call(
        kernel, grid_spec=grid_spec, out_shape=_out_shapes(KV, CG, D),
        compiler_params=_params("parallel", "arbitrary"),
        interpret=interpret,
    )(table, counts, k_scale, v_scale, q, k_pool, v_pool))


# ----------------------------------------------------------------------
# split-operand absorbed MLA (the MQA KV=1 problem): dense cache, paged
# pool, chunk prefix — bf16 and int8
# ----------------------------------------------------------------------
#
# The latent and rope-key caches ride in as SEPARATE BlockSpec
# operands: each grid step stages one (rows x r) latent block and one
# (rows x rope) rope block, computes ``s = q_abs.c_kv + q_rope.k_rope``
# and takes values directly from the latent block — so the staged cache
# bytes per token are exactly ``r + rope`` features/position, vs the
# concatenated-MQA view's ``2*(r + rope)`` (one k_cat copy + one
# zero-padded v_cat copy of the cache, rebuilt every step).

def _mla_scores(qa, qr, ckv, kr, scale, cs=None, rs=None):
    """Split-operand scores of one staged block: qa (rows, r) and qr
    (rows, rope) queries against the (T, r) latent and (T, rope) rope
    blocks; ``cs``/``rs`` the int8 blocks' scales."""
    s_nope = _scores(qa.astype(F32) * scale, ckv)
    s_rope = _scores(qr.astype(F32) * scale, kr)
    if cs is not None:
        s_nope, s_rope = s_nope * cs, s_rope * rs
    return s_nope + s_rope


def _mla_decode_kernel(lens_ref, qa_ref, qr_ref, ckv_ref, kr_ref,
                       ot_ref, m_ref, l_ref, acc_ref, ms_ref, ls_ref,
                       *, scale, bkv, t_valid, n_kv):
    j = pl.program_id(1)
    _flash_init(j, acc_ref, ms_ref, ls_ref)
    ckv = ckv_ref[0].astype(F32)                        # (bkv, r)
    s = _mla_scores(qa_ref[0], qr_ref[0], ckv, kr_ref[0].astype(F32),
                    scale)
    _flash_update(s, _dense_valid(s, j, bkv, t_valid, lens_ref), ckv,
                  acc_ref, ms_ref, ls_ref)
    _flash_store(j, n_kv - 1, ot_ref, m_ref, l_ref, acc_ref, ms_ref,
                 ls_ref)


def vwr_mla_flash_decode_p(q_abs: jax.Array, q_rope: jax.Array,
                           c_kv: jax.Array, k_rope: jax.Array,
                           lens: jax.Array, *, scale: float, bkv: int,
                           t_valid: int, interpret: bool = False):
    """Split-operand absorbed-MLA flash decode over a dense cache.

    q_abs: (B, H, r) nope queries folded through wk_b; q_rope: (B, H,
    rope); c_kv: (B, Tp, r); k_rope: (B, Tp, rope), Tp padded to a bkv
    multiple; lens: (1, 2) int32 [cur_len, pos0]; ``scale`` the
    absorbed-MLA 1/sqrt(nope+rope).  Returns fp32 (o_tilde (B, H, r),
    m (B, H), l (B, H)) — the ``vwr_flash_decode_p`` contract.
    """
    B, H, r = q_abs.shape
    rope = q_rope.shape[2]
    Tp = c_kv.shape[1]
    assert q_rope.shape == (B, H, rope)
    assert c_kv.shape == (B, Tp, r) and k_rope.shape == (B, Tp, rope)
    assert Tp % bkv == 0, (Tp, bkv)
    n_kv = Tp // bkv
    kernel = functools.partial(_mla_decode_kernel, scale=scale, bkv=bkv,
                               t_valid=t_valid, n_kv=n_kv)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,              # lens
        grid=(B, n_kv),
        in_specs=[
            pl.BlockSpec((1, H, r), lambda b, j, ln: (b, 0, 0)),
            pl.BlockSpec((1, H, rope), lambda b, j, ln: (b, 0, 0)),
            pl.BlockSpec((1, bkv, r), lambda b, j, ln: (b, j, 0)),
            pl.BlockSpec((1, bkv, rope), lambda b, j, ln: (b, j, 0)),
        ],
        out_specs=_out_specs(H, r, lambda b, j, ln: (b, 0, 0)),
        scratch_shapes=_scratch(H, r),
    )
    return _squeeze_stats(*pl.pallas_call(
        kernel, grid_spec=grid_spec, out_shape=_out_shapes(B, H, r),
        compiler_params=_params("parallel", "arbitrary"),
        interpret=interpret,
    )(lens, q_abs, q_rope, c_kv, k_rope))


def _mla_decode_kernel_q8(lens_ref, cs_ref, rs_ref, qa_ref, qr_ref,
                          ckv_ref, kr_ref, ot_ref, m_ref, l_ref,
                          acc_ref, ms_ref, ls_ref, *, scale, bkv,
                          t_valid, n_kv):
    b = pl.program_id(0)
    j = pl.program_id(1)
    _flash_init(j, acc_ref, ms_ref, ls_ref)
    cs = cs_ref[b]                                      # latent scale
    ckv = ckv_ref[0].astype(F32)                        # (bkv, r) int8
    s = _mla_scores(qa_ref[0], qr_ref[0], ckv, kr_ref[0].astype(F32),
                    scale, cs, rs_ref[b])
    _flash_update(s, _dense_valid(s, j, bkv, t_valid, lens_ref), ckv,
                  acc_ref, ms_ref, ls_ref, v_scale=cs)
    _flash_store(j, n_kv - 1, ot_ref, m_ref, l_ref, acc_ref, ms_ref,
                 ls_ref)


def vwr_mla_flash_decode_q8_p(q_abs: jax.Array, q_rope: jax.Array,
                              c_kv: jax.Array, k_rope: jax.Array,
                              ckv_scale: jax.Array,
                              krope_scale: jax.Array, lens: jax.Array,
                              *, scale: float, bkv: int, t_valid: int,
                              interpret: bool = False):
    """int8 split-operand MLA flash decode: c_kv, k_rope int8
    (B, Tp, .); ckv_scale, krope_scale (B,) fp32.  Same contract as
    ``vwr_mla_flash_decode_p``."""
    B, H, r = q_abs.shape
    rope = q_rope.shape[2]
    Tp = c_kv.shape[1]
    assert q_rope.shape == (B, H, rope)
    assert c_kv.shape == (B, Tp, r) and k_rope.shape == (B, Tp, rope)
    assert ckv_scale.shape == (B,) and krope_scale.shape == (B,)
    assert Tp % bkv == 0, (Tp, bkv)
    n_kv = Tp // bkv
    kernel = functools.partial(_mla_decode_kernel_q8, scale=scale,
                               bkv=bkv, t_valid=t_valid, n_kv=n_kv)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,              # lens, ckv_scale, krope_scale
        grid=(B, n_kv),
        in_specs=[
            pl.BlockSpec((1, H, r), lambda b, j, *_: (b, 0, 0)),
            pl.BlockSpec((1, H, rope), lambda b, j, *_: (b, 0, 0)),
            pl.BlockSpec((1, bkv, r), lambda b, j, *_: (b, j, 0)),
            pl.BlockSpec((1, bkv, rope), lambda b, j, *_: (b, j, 0)),
        ],
        out_specs=_out_specs(H, r, lambda b, j, *_: (b, 0, 0)),
        scratch_shapes=_scratch(H, r),
    )
    return _squeeze_stats(*pl.pallas_call(
        kernel, grid_spec=grid_spec, out_shape=_out_shapes(B, H, r),
        compiler_params=_params("parallel", "arbitrary"),
        interpret=interpret,
    )(lens, ckv_scale, krope_scale, q_abs, q_rope, c_kv, k_rope))


def _mla_paged_decode_kernel(tbl_ref, cnt_ref, qa_ref, qr_ref, ckv_ref,
                             kr_ref, ot_ref, m_ref, l_ref, acc_ref,
                             ms_ref, ls_ref, *, scale, n_logical):
    b = pl.program_id(0)
    j = pl.program_id(1)
    _flash_init(j, acc_ref, ms_ref, ls_ref)
    ckv = ckv_ref[0].astype(F32)                        # (ps, r)
    s = _mla_scores(qa_ref[0], qr_ref[0], ckv, kr_ref[0].astype(F32),
                    scale)
    _flash_update(s, _count_valid(s, cnt_ref[b, j]), ckv, acc_ref,
                  ms_ref, ls_ref)
    _flash_store(j, n_logical - 1, ot_ref, m_ref, l_ref, acc_ref, ms_ref,
                 ls_ref)


def vwr_mla_paged_flash_decode_p(q_abs: jax.Array, q_rope: jax.Array,
                                 ckv_pool: jax.Array,
                                 krope_pool: jax.Array,
                                 table: jax.Array, counts: jax.Array, *,
                                 scale: float, interpret: bool = False):
    """Split-operand absorbed-MLA flash decode over paged latent pools.

    The block table rides in as a scalar-prefetch operand and each
    (slot, logical-page) grid step stages ONE physical latent page
    (page_size x r) plus its rope page (page_size x rope).

    q_abs: (B, H, r); q_rope: (B, H, rope); ckv_pool: (n_pages,
    page_size, r); krope_pool: (n_pages, page_size, rope); table,
    counts: (B, max_pages) int32, table pre-clamped to [0, n_pages).
    Returns fp32 (o_tilde (B, H, r), m (B, H), l (B, H)).
    """
    B, H, r = q_abs.shape
    rope = q_rope.shape[2]
    n_pages, ps, _ = ckv_pool.shape
    assert krope_pool.shape == (n_pages, ps, rope)
    Bt, J = table.shape
    assert Bt == B and counts.shape == (B, J), (table.shape, B)
    kernel = functools.partial(_mla_paged_decode_kernel, scale=scale,
                               n_logical=J)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,          # table, counts
        grid=(B, J),
        in_specs=[
            pl.BlockSpec((1, H, r), lambda b, j, *_: (b, 0, 0)),
            pl.BlockSpec((1, H, rope), lambda b, j, *_: (b, 0, 0)),
            pl.BlockSpec((1, ps, r), lambda b, j, tbl, *_: (tbl[b, j], 0, 0)),
            pl.BlockSpec((1, ps, rope),
                         lambda b, j, tbl, *_: (tbl[b, j], 0, 0)),
        ],
        out_specs=_out_specs(H, r, lambda b, j, *_: (b, 0, 0)),
        scratch_shapes=_scratch(H, r),
    )
    return _squeeze_stats(*pl.pallas_call(
        kernel, grid_spec=grid_spec, out_shape=_out_shapes(B, H, r),
        compiler_params=_params("parallel", "arbitrary"),
        interpret=interpret,
    )(table, counts, q_abs, q_rope, ckv_pool, krope_pool))


def _mla_paged_decode_kernel_q8(tbl_ref, cnt_ref, cs_ref, rs_ref,
                                qa_ref, qr_ref, ckv_ref, kr_ref, ot_ref,
                                m_ref, l_ref, acc_ref, ms_ref, ls_ref,
                                *, scale, n_logical):
    b = pl.program_id(0)
    j = pl.program_id(1)
    _flash_init(j, acc_ref, ms_ref, ls_ref)
    page = tbl_ref[b, j]
    cs = cs_ref[page]                                   # per-page scales
    ckv = ckv_ref[0].astype(F32)                        # (ps, r) int8
    s = _mla_scores(qa_ref[0], qr_ref[0], ckv, kr_ref[0].astype(F32),
                    scale, cs, rs_ref[page])
    _flash_update(s, _count_valid(s, cnt_ref[b, j]), ckv, acc_ref,
                  ms_ref, ls_ref, v_scale=cs)
    _flash_store(j, n_logical - 1, ot_ref, m_ref, l_ref, acc_ref, ms_ref,
                 ls_ref)


def vwr_mla_paged_flash_decode_q8_p(q_abs: jax.Array, q_rope: jax.Array,
                                    ckv_pool: jax.Array,
                                    krope_pool: jax.Array,
                                    ckv_scale: jax.Array,
                                    krope_scale: jax.Array,
                                    table: jax.Array, counts: jax.Array,
                                    *, scale: float,
                                    interpret: bool = False):
    """Split-operand MLA flash decode over int8 latent page pools.

    ckv_pool: int8 (n_pages, page_size, r); krope_pool: int8 (n_pages,
    page_size, rope); ckv_scale, krope_scale: fp32 (n_pages,) sidecars
    resolved through ``table[b, j]``.  Same contract as
    ``vwr_mla_paged_flash_decode_p``.
    """
    B, H, r = q_abs.shape
    rope = q_rope.shape[2]
    n_pages, ps, _ = ckv_pool.shape
    assert krope_pool.shape == (n_pages, ps, rope)
    assert ckv_scale.shape == (n_pages,) and \
        krope_scale.shape == (n_pages,)
    Bt, J = table.shape
    assert Bt == B and counts.shape == (B, J), (table.shape, B)
    kernel = functools.partial(_mla_paged_decode_kernel_q8, scale=scale,
                               n_logical=J)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,      # table, counts, ckv_scale, kr_scale
        grid=(B, J),
        in_specs=[
            pl.BlockSpec((1, H, r), lambda b, j, *_: (b, 0, 0)),
            pl.BlockSpec((1, H, rope), lambda b, j, *_: (b, 0, 0)),
            pl.BlockSpec((1, ps, r), lambda b, j, tbl, *_: (tbl[b, j], 0, 0)),
            pl.BlockSpec((1, ps, rope),
                         lambda b, j, tbl, *_: (tbl[b, j], 0, 0)),
        ],
        out_specs=_out_specs(H, r, lambda b, j, *_: (b, 0, 0)),
        scratch_shapes=_scratch(H, r),
    )
    return _squeeze_stats(*pl.pallas_call(
        kernel, grid_spec=grid_spec, out_shape=_out_shapes(B, H, r),
        compiler_params=_params("parallel", "arbitrary"),
        interpret=interpret,
    )(table, counts, ckv_scale, krope_scale, q_abs, q_rope, ckv_pool,
      krope_pool))


def _mla_chunk_prefix_kernel(tbl_ref, cnt_ref, qa_ref, qr_ref, ckv_ref,
                             kr_ref, ot_ref, m_ref, l_ref, acc_ref,
                             ms_ref, ls_ref, *, scale, n_logical):
    j = pl.program_id(0)
    _flash_init(j, acc_ref, ms_ref, ls_ref)
    ckv = ckv_ref[0].astype(F32)                        # (ps, r)
    s = _mla_scores(qa_ref[...], qr_ref[...], ckv,
                    kr_ref[0].astype(F32), scale)
    _flash_update(s, _count_valid(s, cnt_ref[j]), ckv, acc_ref, ms_ref,
                  ls_ref)
    _flash_store(j, n_logical - 1, ot_ref, m_ref, l_ref, acc_ref, ms_ref,
                 ls_ref)


def vwr_mla_chunk_prefix_attend_p(q_abs: jax.Array, q_rope: jax.Array,
                                  ckv_pool: jax.Array,
                                  krope_pool: jax.Array,
                                  table: jax.Array, counts: jax.Array,
                                  *, scale: float,
                                  interpret: bool = False):
    """Split-operand MLA chunk-prefix attention over latent page pools.

    q_abs: (C*H, r) absorbed chunk queries; q_rope: (C*H, rope);
    table/counts: (J,) prior pages + per-page valid counts.  Returns
    fp32 partials (o_tilde (1, C*H, r), m (1, C*H), l (1, C*H)).
    """
    CH, r = q_abs.shape
    rope = q_rope.shape[1]
    n_pages, ps, _ = ckv_pool.shape
    assert krope_pool.shape == (n_pages, ps, rope)
    J, = table.shape
    assert counts.shape == (J,), (counts.shape, J)
    kernel = functools.partial(_mla_chunk_prefix_kernel, scale=scale,
                               n_logical=J)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(J,),
        in_specs=[
            pl.BlockSpec((CH, r), lambda j, *_: (0, 0)),
            pl.BlockSpec((CH, rope), lambda j, *_: (0, 0)),
            pl.BlockSpec((1, ps, r), lambda j, tbl, *_: (tbl[j], 0, 0)),
            pl.BlockSpec((1, ps, rope), lambda j, tbl, *_: (tbl[j], 0, 0)),
        ],
        out_specs=_out_specs(CH, r, lambda j, *_: (0, 0, 0)),
        scratch_shapes=_scratch(CH, r),
    )
    return _squeeze_stats(*pl.pallas_call(
        kernel, grid_spec=grid_spec, out_shape=_out_shapes(1, CH, r),
        compiler_params=_params("arbitrary"),
        interpret=interpret,
    )(table, counts, q_abs, q_rope, ckv_pool, krope_pool))


def _mla_chunk_prefix_kernel_q8(tbl_ref, cnt_ref, cs_ref, rs_ref,
                                qa_ref, qr_ref, ckv_ref, kr_ref,
                                ot_ref, m_ref, l_ref, acc_ref, ms_ref,
                                ls_ref, *, scale, n_logical):
    j = pl.program_id(0)
    _flash_init(j, acc_ref, ms_ref, ls_ref)
    page = tbl_ref[j]
    cs = cs_ref[page]                                   # per-page scales
    ckv = ckv_ref[0].astype(F32)                        # (ps, r) int8
    s = _mla_scores(qa_ref[...], qr_ref[...], ckv,
                    kr_ref[0].astype(F32), scale, cs, rs_ref[page])
    _flash_update(s, _count_valid(s, cnt_ref[j]), ckv, acc_ref, ms_ref,
                  ls_ref, v_scale=cs)
    _flash_store(j, n_logical - 1, ot_ref, m_ref, l_ref, acc_ref, ms_ref,
                 ls_ref)


def vwr_mla_chunk_prefix_attend_q8_p(q_abs: jax.Array,
                                     q_rope: jax.Array,
                                     ckv_pool: jax.Array,
                                     krope_pool: jax.Array,
                                     ckv_scale: jax.Array,
                                     krope_scale: jax.Array,
                                     table: jax.Array,
                                     counts: jax.Array, *,
                                     scale: float,
                                     interpret: bool = False):
    """``vwr_mla_chunk_prefix_attend_p`` over int8 latent pools with
    fp32 per-page scale sidecars."""
    CH, r = q_abs.shape
    rope = q_rope.shape[1]
    n_pages, ps, _ = ckv_pool.shape
    assert krope_pool.shape == (n_pages, ps, rope)
    assert ckv_scale.shape == (n_pages,) and \
        krope_scale.shape == (n_pages,)
    J, = table.shape
    assert counts.shape == (J,), (counts.shape, J)
    kernel = functools.partial(_mla_chunk_prefix_kernel_q8, scale=scale,
                               n_logical=J)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,      # table, counts, ckv_scale, kr_scale
        grid=(J,),
        in_specs=[
            pl.BlockSpec((CH, r), lambda j, *_: (0, 0)),
            pl.BlockSpec((CH, rope), lambda j, *_: (0, 0)),
            pl.BlockSpec((1, ps, r), lambda j, tbl, *_: (tbl[j], 0, 0)),
            pl.BlockSpec((1, ps, rope), lambda j, tbl, *_: (tbl[j], 0, 0)),
        ],
        out_specs=_out_specs(CH, r, lambda j, *_: (0, 0, 0)),
        scratch_shapes=_scratch(CH, r),
    )
    return _squeeze_stats(*pl.pallas_call(
        kernel, grid_spec=grid_spec, out_shape=_out_shapes(1, CH, r),
        compiler_params=_params("arbitrary"),
        interpret=interpret,
    )(table, counts, ckv_scale, krope_scale, q_abs, q_rope, ckv_pool,
      krope_pool))
