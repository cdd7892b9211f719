"""Public wrappers for the VWR Pallas kernels.

Handles shape padding to block multiples, zero-copy GQA head routing,
fused epilogues (bias / activation / residual inside the matmul's
final-K store), block-size autotuning (``repro.kernels.autotune``, a
JSON cache keyed by op/shape/dtype/backend consulted on every call
when block sizes are not pinned), and interpret-mode selection (CPU
containers validate kernels with ``interpret=True``; on real TPU the
same calls compile to Mosaic).

Each public op is a thin Python wrapper (block-size resolution happens
at trace time) around a jitted implementation, so calls from inside
jitted model code inline cleanly.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import autotune
from repro.kernels.vwr_attention import vwr_attention_p
from repro.kernels.vwr_conv2d import vwr_conv2d_p
from repro.kernels.vwr_decode import (pages_per_block,
                                      vwr_chunk_prefix_attend_p,
                                      vwr_chunk_prefix_attend_q8_p,
                                      vwr_flash_decode_p,
                                      vwr_flash_decode_q8_p,
                                      vwr_mla_chunk_prefix_attend_p,
                                      vwr_mla_chunk_prefix_attend_q8_p,
                                      vwr_mla_flash_decode_p,
                                      vwr_mla_flash_decode_q8_p,
                                      vwr_mla_paged_flash_decode_p,
                                      vwr_mla_paged_flash_decode_q8_p,
                                      vwr_paged_flash_decode_p,
                                      vwr_paged_flash_decode_q8_p)
from repro.kernels.vwr_depthwise import vwr_depthwise_p
from repro.kernels.vwr_matmul import vwr_matmul_p, vwr_swiglu_p


def _auto_interpret(interpret):
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret


def _backend_tag(interpret: bool) -> str:
    """Cache key component: measured winners are per-hardware, so the
    tag carries the device kind (v5e vs v6e tune differently), not
    just the platform name."""
    if interpret:
        return "interp"
    kind = jax.devices()[0].device_kind.replace(" ", "_")
    return f"{jax.default_backend()}:{kind}"


def _pad_dim(x, axis, mult):
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


# ======================================================================
# matmul (+ fused epilogue)
# ======================================================================

@functools.partial(jax.jit, static_argnames=("bm", "bk", "bn",
                                             "activation", "interpret"))
def _vwr_matmul_jit(x, w, bias, residual, *, bm, bk, bn, activation,
                    interpret):
    M, K = x.shape
    N = w.shape[1]
    bm_, bk_, bn_ = (min(bm, M) if M else bm, min(bk, K), min(bn, N))
    xp = _pad_dim(_pad_dim(x, 0, bm_), 1, bk_)
    wp = _pad_dim(_pad_dim(w, 0, bk_), 1, bn_)
    bp = None if bias is None else _pad_dim(bias.reshape(1, N), 1, bn_)
    rp = None if residual is None else _pad_dim(
        _pad_dim(residual, 0, bm_), 1, bn_)
    out = vwr_matmul_p(xp, wp, bp, rp, bm=bm_, bk=bk_, bn=bn_,
                       activation=activation, interpret=interpret)
    return out[:M, :N]


def vwr_matmul(x, w, bias=None, residual=None, *, activation=None,
               bm=None, bk=None, bn=None, interpret=None):
    """``act(x @ w + bias) + residual`` in one kernel pass.

    x: (M, K) @ w: (K, N), arbitrary shapes (padded internally).
    bias: (N,) or (1, N); residual: (M, N); activation in
    {None, 'relu', 'gelu', 'silu'} — all applied to the fp32
    accumulator inside the final-K store (no extra HBM round-trip).
    With all of bm/bk/bn unspecified the autotuner resolves them
    (cost-model prior + measured winners cached in a JSON file);
    pinning any subset keeps the pins and fills the rest from the
    static defaults (a pinned knob is a deliberate experiment — the
    tuner must not override it)."""
    interpret = _auto_interpret(interpret)
    M, K = x.shape
    N = w.shape[1]
    if bm is None and bk is None and bn is None:
        bm, bk, bn = _matmul_blocks(M, K, N, str(x.dtype), interpret)
    else:
        d_bm, d_bk, d_bn = autotune.DEFAULT_BLOCKS["matmul"]
        bm = d_bm if bm is None else bm
        bk = d_bk if bk is None else bk
        bn = d_bn if bn is None else bn
    return _vwr_matmul_jit(x, w, bias, residual, bm=bm, bk=bk, bn=bn,
                           activation=activation, interpret=interpret)


def _matmul_blocks(M, K, N, dtype, interpret):
    backend = _backend_tag(interpret)

    def runner(cand):
        bm, bk, bn = cand
        xz = jnp.ones((M, K), jnp.dtype(dtype))
        wz = jnp.ones((K, N), jnp.dtype(dtype))

        def run():
            jax.block_until_ready(_vwr_matmul_jit(
                xz, wz, None, None, bm=bm, bk=bk, bn=bn,
                activation=None, interpret=interpret))
        return run

    return autotune.get_blocks(
        "matmul", (M, K, N), dtype, backend,
        candidates=autotune.matmul_candidates(M, K, N, dtype),
        prior=lambda c: autotune.matmul_prior(M, K, N, dtype, c),
        runner=runner if autotune.enabled() else None)


# ======================================================================
# fused swiglu (dual matmul, shared LHS staging)
# ======================================================================

@functools.partial(jax.jit, static_argnames=("bm", "bk", "bn",
                                             "interpret"))
def _vwr_swiglu_jit(x, wg, wi, *, bm, bk, bn, interpret):
    M, K = x.shape
    N = wg.shape[1]
    bm_, bk_, bn_ = (min(bm, M) if M else bm, min(bk, K), min(bn, N))
    xp = _pad_dim(_pad_dim(x, 0, bm_), 1, bk_)
    wgp = _pad_dim(_pad_dim(wg, 0, bk_), 1, bn_)
    wip = _pad_dim(_pad_dim(wi, 0, bk_), 1, bn_)
    out = vwr_swiglu_p(xp, wgp, wip, bm=bm_, bk=bk_, bn=bn_,
                       interpret=interpret)
    return out[:M, :N]


def vwr_swiglu(x, wg, wi, *, bm=None, bk=None, bn=None, interpret=None):
    """``silu(x @ wg) * (x @ wi)`` in one kernel pass.

    x: (M, K); wg, wi: (K, N), arbitrary shapes (padded internally).
    The staged x block feeds both matmuls and the gate product is
    applied to the two fp32 accumulators inside the final-K store, so
    the swiglu hidden activation costs one HBM round-trip total — no
    separate ``g * h`` elementwise pass.  Block resolution follows the
    matmul convention (autotuner when unpinned, defaults fill a
    partial pin)."""
    interpret = _auto_interpret(interpret)
    M, K = x.shape
    N = wg.shape[1]
    if bm is None and bk is None and bn is None:
        bm, bk, bn = _swiglu_blocks(M, K, N, str(x.dtype), interpret)
    else:
        d_bm, d_bk, d_bn = autotune.DEFAULT_BLOCKS["swiglu"]
        bm = d_bm if bm is None else bm
        bk = d_bk if bk is None else bk
        bn = d_bn if bn is None else bn
    return _vwr_swiglu_jit(x, wg, wi, bm=bm, bk=bk, bn=bn,
                           interpret=interpret)


def _swiglu_blocks(M, K, N, dtype, interpret):
    backend = _backend_tag(interpret)

    def runner(cand):
        bm, bk, bn = cand
        xz = jnp.ones((M, K), jnp.dtype(dtype))
        wz = jnp.ones((K, N), jnp.dtype(dtype))

        def run():
            jax.block_until_ready(_vwr_swiglu_jit(
                xz, wz, wz, bm=bm, bk=bk, bn=bn, interpret=interpret))
        return run

    return autotune.get_blocks(
        "swiglu", (M, K, N), dtype, backend,
        candidates=autotune.swiglu_candidates(M, K, N, dtype),
        prior=lambda c: autotune.swiglu_prior(M, K, N, dtype, c),
        runner=runner if autotune.enabled() else None)


# ======================================================================
# conv
# ======================================================================

@functools.partial(jax.jit, static_argnames=("bh", "bf", "activation",
                                             "interpret"))
def _vwr_conv2d_jit(x, w, bias, *, bh, bf, activation, interpret):
    KH = w.shape[0]
    F = w.shape[3]
    H_out = x.shape[1] - KH + 1
    bh_ = min(bh, H_out)
    bf_ = min(bf, F)
    # pad H so H_out divides bh (extra rows are discarded)
    pad_h = (-H_out) % bh_
    xp = _pad_dim(x, 1, 1) if pad_h == 0 else jnp.pad(
        x, ((0, 0), (0, pad_h), (0, 0), (0, 0)))
    wp = _pad_dim(w, 3, bf_)
    bp = None if bias is None else _pad_dim(bias.reshape(1, F), 1, bf_)
    out = vwr_conv2d_p(xp, wp, bp, bh=bh_, bf=bf_, activation=activation,
                       interpret=interpret)
    return out[:, :H_out, :, :F]


def vwr_conv2d(x, w, bias=None, *, activation=None, bh=None, bf=None,
               interpret=None):
    """``act(conv2d(x, w) + bias)`` in one kernel pass.

    x: (N,H,W,C); w: (KH,KW,C,F); stride 1, VALID.  bias: (F,) and
    activation in {None,'relu','gelu','silu'} are fused into the fp32
    accumulator before the single output store (no extra elementwise
    HBM pass).  With both bh/bf unspecified the autotuner resolves them
    via the shared staging-energy prior; pinning any subset keeps the
    pins and fills the rest from the static defaults."""
    interpret = _auto_interpret(interpret)
    if bh is None and bf is None:
        bh, bf = _conv_blocks(x.shape, w.shape, str(x.dtype), interpret)
    else:
        d_bh, d_bf = autotune.DEFAULT_BLOCKS["conv"]
        bh = d_bh if bh is None else bh
        bf = d_bf if bf is None else bf
    return _vwr_conv2d_jit(x, w, bias, bh=bh, bf=bf,
                           activation=activation, interpret=interpret)


def _conv_blocks(xshape, wshape, dtype, interpret):
    N, H, W, C = xshape
    KH, KW, _, F = wshape
    backend = _backend_tag(interpret)

    def runner(cand):
        bh, bf = cand
        xz = jnp.ones(xshape, jnp.dtype(dtype))
        wz = jnp.ones(wshape, jnp.dtype(dtype))

        def run():
            jax.block_until_ready(_vwr_conv2d_jit(
                xz, wz, None, bh=bh, bf=bf, activation=None,
                interpret=interpret))
        return run

    return autotune.get_blocks(
        "conv", (N, H, W, C, KH, KW, F), dtype, backend,
        candidates=autotune.conv_candidates(N, H, W, C, KH, KW, F,
                                            dtype),
        prior=lambda c: autotune.conv_prior(N, H, W, C, KH, KW, F,
                                            dtype, c),
        runner=runner if autotune.enabled() else None)


@functools.partial(jax.jit, static_argnames=("bh", "interpret"))
def vwr_depthwise(x, w, *, bh=8, interpret=None):
    """x: (N,H,W,C); w: (KH,KW,C); stride 1, VALID."""
    interpret = _auto_interpret(interpret)
    KH = w.shape[0]
    H_out = x.shape[1] - KH + 1
    bh_ = min(bh, H_out)
    pad_h = (-H_out) % bh_
    xp = x if pad_h == 0 else jnp.pad(
        x, ((0, 0), (0, pad_h), (0, 0), (0, 0)))
    out = vwr_depthwise_p(xp, w, bh=bh_, interpret=interpret)
    return out[:, :H_out]


# ======================================================================
# attention (zero-copy GQA)
# ======================================================================

@functools.partial(jax.jit,
                   static_argnames=("causal", "bq", "bkv", "interpret"))
def _vwr_attention_jit(q, k, v, *, causal, bq, bkv, interpret):
    B, S, H, D = q.shape
    KV = k.shape[2]
    G = H // KV
    # zero-copy GQA: K/V keep their native KV-head count; the kernel's
    # BlockSpec index map (b // G) routes each query head to its
    # group's shared KV head — no jnp.repeat materialization, so the
    # staged / resident K/V bytes are 1/G of the head-expanded layout
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, S, D)
    kf = k.transpose(0, 2, 1, 3).reshape(B * KV, S, D)
    vf = v.transpose(0, 2, 1, 3).reshape(B * KV, S, D)

    bq_ = min(bq, S)
    bkv_ = min(bkv, S)
    big = max(bq_, bkv_)
    assert big % min(bq_, bkv_) == 0, "bq and bkv must nest"
    if not causal:
        assert S % big == 0, "non-causal path needs S % block == 0"
    # pad to a common block multiple; padded kv rows sit at positions
    # beyond every real query position, so causal masking removes them
    qf = _pad_dim(qf, 1, big)
    kf = _pad_dim(kf, 1, big)
    vf = _pad_dim(vf, 1, big)

    out = vwr_attention_p(qf, kf, vf, causal=causal, bq=bq_, bkv=bkv_,
                          g=G, interpret=interpret)
    out = out[:, :S].reshape(B, H, S, D).transpose(0, 2, 1, 3)
    return out


def vwr_attention(q, k, v, *, causal=True, bq=None, bkv=None,
                  interpret=None):
    """q: (B,S,H,D); k,v: (B,S,KV,D) (GQA: KV divides H, served
    zero-copy).  Causal masks use true positions, so KV-padding to
    block multiples is masked out by construction for causal=True; for
    causal=False we require S % block == 0 (asserted).  With both
    bq/bkv unspecified the autotuner resolves them; pinning one keeps
    the pin and mirrors it onto the other (equal blocks always satisfy
    the nesting constraint, whatever S clamps them to)."""
    interpret = _auto_interpret(interpret)
    B, S, H, D = q.shape
    KV = k.shape[2]
    if bq is None and bkv is None:
        bq, bkv = _attention_blocks(B, S, H, KV, D, str(q.dtype), causal,
                                    interpret)
    elif bq is None:
        bq = bkv          # mirror the pin: equal blocks always nest,
    elif bkv is None:     # whatever S clamps them to
        bkv = bq
    return _vwr_attention_jit(q, k, v, causal=causal, bq=bq, bkv=bkv,
                              interpret=interpret)


# ======================================================================
# flash decode (one token vs a cache shard; unnormalized partials)
# ======================================================================

@functools.partial(jax.jit, static_argnames=("bkv", "interpret"))
def _vwr_flash_decode_jit(q, k, v, lens, *, bkv, interpret):
    B, H, D = q.shape
    T, KV = k.shape[1], k.shape[2]
    G = H // KV
    # zero-copy GQA: the q "block" is the whole group sharing one KV
    # head (heads are kv-major: h = kv * G + g, matching
    # models.attention.flash_decode_partial)
    qf = q.reshape(B * KV, G, D)
    kf = k.transpose(0, 2, 1, 3).reshape(B * KV, T, D)
    vf = v.transpose(0, 2, 1, 3).reshape(B * KV, T, D)
    bkv_ = min(bkv, T)
    kf = _pad_dim(kf, 1, bkv_)
    vf = _pad_dim(vf, 1, bkv_)
    o_t, m, l = vwr_flash_decode_p(qf, kf, vf, lens, bkv=bkv_,
                                   t_valid=T, interpret=interpret)
    return (o_t.reshape(B, H, D), m.reshape(B, H), l.reshape(B, H))


def vwr_flash_decode(q, k, v, cur_len, pos0=0, *, bkv=None,
                     interpret=None):
    """Unnormalized flash-decode partials for one new token.

    q: (B, H, Dh); k, v: (B, T, KV, Dh) — a KV-cache (shard) whose
    first position has *global* index ``pos0``; ``cur_len`` counts the
    globally valid positions (both may be traced scalars: decode runs
    inside a jitted generation loop).  Returns fp32
    (o_tilde (B,H,Dh), m (B,H), l (B,H)) — the distributed-
    FlashDecoding combine contract (``dist.decode``); single-shard
    callers normalize with ``o_tilde / max(l, eps)``.  ``bkv``
    unspecified resolves via the autotuner."""
    interpret = _auto_interpret(interpret)
    B, T = q.shape[0], k.shape[1]
    H, KV, D = q.shape[1], k.shape[2], q.shape[2]
    if bkv is None:
        bkv = _decode_blocks(B, T, H, KV, D, str(q.dtype), interpret)[0]
    lens = jnp.stack([jnp.asarray(cur_len, jnp.int32).reshape(()),
                      jnp.asarray(pos0, jnp.int32).reshape(())]
                     ).reshape(1, 2)
    return _vwr_flash_decode_jit(q, k, v, lens, bkv=bkv,
                                 interpret=interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _vwr_paged_flash_decode_jit(q, k_pool, v_pool, table, counts, *,
                                interpret):
    B, H, D = q.shape
    n_pages, KV, ps, _ = k_pool.shape
    G = H // KV
    ppb = pages_per_block(table.shape[1],
                          2 * KV * ps * D * k_pool.dtype.itemsize)
    # unallocated / foreign table entries carry count 0: the kernel never
    # stages them, but any index it reads must be a legal page
    tbl = jnp.clip(table, 0, n_pages - 1).astype(jnp.int32)
    o_t, m, l = vwr_paged_flash_decode_p(
        q.reshape(B, KV, G, D), k_pool, v_pool, tbl,
        counts.astype(jnp.int32), ppb=ppb, interpret=interpret)
    return (o_t.reshape(B, H, D), m.reshape(B, H), l.reshape(B, H))


def vwr_paged_flash_decode(q, k_pool, v_pool, table, counts, *,
                           interpret=None):
    """Unnormalized flash-decode partials against a paged KV pool.

    q: (B, H, Dh); k_pool, v_pool: head-major (n_pages, KV, page_size,
    Dh) — the shared page pool (possibly one shard's slab of it);
    table: (B, max_pages) int32 physical page per (slot, logical page);
    counts:
    (B, max_pages) int32 valid tokens per (slot, logical page) — 0
    masks a page completely, wherever it lies in the row.  The kernel
    stages ``pages_per_block`` whole pages (every KV head) per grid
    step, a count fixed by the page's bytes and ``max_pages``, so there
    is no block autotuning; the 'auto' dispatch backend still measures
    this wrapper against the XLA gather reference per shape.  Returns
    fp32 (o_tilde (B,H,Dh), m (B,H), l (B,H)), the ``dist.decode``
    combine contract."""
    interpret = _auto_interpret(interpret)
    return _vwr_paged_flash_decode_jit(q, k_pool, v_pool, table, counts,
                                       interpret=interpret)


# ======================================================================
# split-operand MLA flash decode (latent + rope caches as separate
# operands; values taken from the latent block — no concat copies)
# ======================================================================

@functools.partial(jax.jit, static_argnames=("scale", "bkv", "interpret"))
def _vwr_mla_flash_decode_jit(q_abs, q_rope, c_kv, k_rope, lens, *,
                              scale, bkv, interpret):
    T = c_kv.shape[1]
    bkv_ = min(bkv, T)
    ckv = _pad_dim(c_kv, 1, bkv_)
    kr = _pad_dim(k_rope, 1, bkv_)
    return vwr_mla_flash_decode_p(q_abs, q_rope, ckv, kr, lens,
                                  scale=scale, bkv=bkv_, t_valid=T,
                                  interpret=interpret)


def vwr_mla_flash_decode(q_abs, q_rope, c_kv, k_rope, cur_len, pos0=0, *,
                         scale, bkv=None, interpret=None):
    """Unnormalized split-operand absorbed-MLA flash-decode partials.

    q_abs: (B, H, r) nope queries pre-folded through wk_b; q_rope:
    (B, H, rope); c_kv: (B, T, r) latent cache (shard); k_rope: (B, T,
    rope) rope-key cache; ``scale`` the absorbed 1/sqrt(nope+rope).
    The caches stay SEPARATE all the way into the kernel's BlockSpecs —
    staged cache bytes per token are (r + rope) features/position
    instead of the concatenated view's 2*(r + rope) (k_cat + zero-
    padded v_cat copies).  Returns fp32 (o_tilde (B, H, r), m (B, H),
    l (B, H)), the ``dist.decode`` combine contract.  ``bkv``
    unspecified resolves via the autotuner."""
    interpret = _auto_interpret(interpret)
    B, H, r = q_abs.shape
    T, rope = c_kv.shape[1], q_rope.shape[2]
    if bkv is None:
        bkv = _mla_decode_blocks(B, T, H, r, rope, str(c_kv.dtype),
                                 interpret)[0]
    lens = jnp.stack([jnp.asarray(cur_len, jnp.int32).reshape(()),
                      jnp.asarray(pos0, jnp.int32).reshape(())]
                     ).reshape(1, 2)
    return _vwr_mla_flash_decode_jit(q_abs, q_rope, c_kv, k_rope, lens,
                                     scale=scale, bkv=bkv,
                                     interpret=interpret)


def _mla_decode_blocks(B, T, H, r, rope, dtype, interpret):
    backend = _backend_tag(interpret)

    def runner(cand):
        bkv, = cand
        qa = jnp.ones((B, H, r), jnp.float32)
        qr = jnp.ones((B, H, rope), jnp.float32)
        ckv = jnp.ones((B, T, r), jnp.dtype(dtype))
        kr = jnp.ones((B, T, rope), jnp.dtype(dtype))
        lens = jnp.asarray([[T, 0]], jnp.int32)

        def run():
            jax.block_until_ready(_vwr_mla_flash_decode_jit(
                qa, qr, ckv, kr, lens, scale=1.0, bkv=bkv,
                interpret=interpret))
        return run

    # candidates/prior: the decode cost model with the staged feature
    # width r + rope (one latent + one rope block per grid step) and a
    # single shared KV head (the absorbed-MQA view)
    return autotune.get_blocks(
        "decode_mla", (B, T, H, r, rope), dtype, backend,
        candidates=autotune.decode_candidates(T, r + rope, dtype),
        prior=lambda c: autotune.decode_prior(B, T, H, 1, r + rope,
                                              dtype, c),
        runner=runner if autotune.enabled() else None)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def _vwr_mla_paged_flash_decode_jit(q_abs, q_rope, ckv_pool, krope_pool,
                                    table, counts, *, scale, interpret):
    n_pages = ckv_pool.shape[0]
    tbl = jnp.clip(table, 0, n_pages - 1).astype(jnp.int32)
    return vwr_mla_paged_flash_decode_p(
        q_abs, q_rope, ckv_pool, krope_pool, tbl,
        counts.astype(jnp.int32), scale=scale, interpret=interpret)


def vwr_mla_paged_flash_decode(q_abs, q_rope, ckv_pool, krope_pool,
                               table, counts, *, scale, interpret=None):
    """Unnormalized split-operand absorbed-MLA partials over paged
    latent pools.

    q_abs: (B, H, r); q_rope: (B, H, rope); ckv_pool: (n_pages,
    page_size, r); krope_pool: (n_pages, page_size, rope); table,
    counts: (B, max_pages) int32 (count 0 masks a page completely).
    The pools stay separate into the kernel — no pool-wide k_cat/v_cat
    copies.  Page size is the transaction width (the engine owns it),
    so there is no block autotuning; 'auto' dispatch still measures
    this wrapper against the XLA gather reference per shape/geometry.
    Returns fp32 (o_tilde (B, H, r), m (B, H), l (B, H))."""
    interpret = _auto_interpret(interpret)
    return _vwr_mla_paged_flash_decode_jit(
        q_abs, q_rope, ckv_pool, krope_pool, table, counts, scale=scale,
        interpret=interpret)


def _decode_blocks(B, T, H, KV, D, dtype, interpret):
    backend = _backend_tag(interpret)

    def runner(cand):
        bkv, = cand
        qz = jnp.ones((B, H, D), jnp.dtype(dtype))
        kz = jnp.ones((B, T, KV, D), jnp.dtype(dtype))
        lens = jnp.asarray([[T, 0]], jnp.int32)

        def run():
            jax.block_until_ready(_vwr_flash_decode_jit(
                qz, kz, kz, lens, bkv=bkv, interpret=interpret))
        return run

    return autotune.get_blocks(
        "decode", (B, T, H, KV, D), dtype, backend,
        candidates=autotune.decode_candidates(T, D, dtype),
        prior=lambda c: autotune.decode_prior(B, T, H, KV, D, dtype, c),
        runner=runner if autotune.enabled() else None)


# ======================================================================
# q8 flash decode: int8 caches / page pools, fp32 scale sidecars,
# dequantized in-kernel on the staged block
# ======================================================================

@functools.partial(jax.jit, static_argnames=("bkv", "interpret"))
def _vwr_flash_decode_q8_jit(q, k, v, k_scale, v_scale, lens, *, bkv,
                             interpret):
    B, H, D = q.shape
    T, KV = k.shape[1], k.shape[2]
    G = H // KV
    qf = q.reshape(B * KV, G, D)
    kf = k.transpose(0, 2, 1, 3).reshape(B * KV, T, D)
    vf = v.transpose(0, 2, 1, 3).reshape(B * KV, T, D)
    ksf = k_scale.astype(jnp.float32).reshape(B * KV)
    vsf = v_scale.astype(jnp.float32).reshape(B * KV)
    bkv_ = min(bkv, T)
    kf = _pad_dim(kf, 1, bkv_)
    vf = _pad_dim(vf, 1, bkv_)
    o_t, m, l = vwr_flash_decode_q8_p(qf, kf, vf, ksf, vsf, lens,
                                      bkv=bkv_, t_valid=T,
                                      interpret=interpret)
    return (o_t.reshape(B, H, D), m.reshape(B, H), l.reshape(B, H))


def vwr_flash_decode_q8(q, k, v, k_scale, v_scale, cur_len, pos0=0, *,
                        bkv=None, interpret=None):
    """``vwr_flash_decode`` over an int8 cache with per-(B, KV) fp32
    scales: the staged cache block is 1 byte/feature in HBM and is
    dequantized in-kernel (scores/values rescaled after the int8
    dots).  Same (o_tilde, m, l) fp32 combine contract."""
    interpret = _auto_interpret(interpret)
    B, T = q.shape[0], k.shape[1]
    H, KV, D = q.shape[1], k.shape[2], q.shape[2]
    if bkv is None:
        bkv = _decode_blocks_q8(B, T, H, KV, D, interpret)[0]
    lens = jnp.stack([jnp.asarray(cur_len, jnp.int32).reshape(()),
                      jnp.asarray(pos0, jnp.int32).reshape(())]
                     ).reshape(1, 2)
    return _vwr_flash_decode_q8_jit(q, k, v, k_scale, v_scale, lens,
                                    bkv=bkv, interpret=interpret)


def _decode_blocks_q8(B, T, H, KV, D, interpret):
    backend = _backend_tag(interpret)

    def runner(cand):
        bkv, = cand
        qz = jnp.ones((B, H, D), jnp.float32)
        kz = jnp.ones((B, T, KV, D), jnp.int8)
        sz = jnp.ones((B, KV), jnp.float32)
        lens = jnp.asarray([[T, 0]], jnp.int32)

        def run():
            jax.block_until_ready(_vwr_flash_decode_q8_jit(
                qz, kz, kz, sz, sz, lens, bkv=bkv, interpret=interpret))
        return run

    # same op name as the bf16 path: the cache key's dtype field
    # ("int8") separates the entries, and _dtype_bytes(int8) == 1 feeds
    # the staged-bytes prior the halved traffic
    return autotune.get_blocks(
        "decode", (B, T, H, KV, D), "int8", backend,
        candidates=autotune.decode_candidates(T, D, "int8"),
        prior=lambda c: autotune.decode_prior(B, T, H, KV, D, "int8", c),
        runner=runner if autotune.enabled() else None)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _vwr_paged_flash_decode_q8_jit(q, k_pool, v_pool, k_scale, v_scale,
                                   table, counts, *, interpret):
    B, H, D = q.shape
    n_pages, KV, ps, _ = k_pool.shape
    G = H // KV
    qf = q.reshape(B * KV, G, D)
    tbl = jnp.clip(table, 0, n_pages - 1).astype(jnp.int32)
    o_t, m, l = vwr_paged_flash_decode_q8_p(
        qf, k_pool, v_pool, k_scale.astype(jnp.float32),
        v_scale.astype(jnp.float32), tbl, counts.astype(jnp.int32),
        interpret=interpret)
    return (o_t.reshape(B, H, D), m.reshape(B, H), l.reshape(B, H))


def vwr_paged_flash_decode_q8(q, k_pool, v_pool, k_scale, v_scale,
                              table, counts, *, interpret=None):
    """``vwr_paged_flash_decode`` over int8 page pools.

    k_pool, v_pool: int8 (n_pages, KV, page_size, Dh); k_scale,
    v_scale: fp32 (n_pages, KV) sidecars riding the same block-table
    indirection as the pages (scalar-prefetch, resolved per grid
    step).  Staged cache traffic per token is halved vs bf16 pools;
    softmax math stays fp32."""
    interpret = _auto_interpret(interpret)
    return _vwr_paged_flash_decode_q8_jit(
        q, k_pool, v_pool, k_scale, v_scale, table, counts,
        interpret=interpret)


@functools.partial(jax.jit, static_argnames=("scale", "bkv", "interpret"))
def _vwr_mla_flash_decode_q8_jit(q_abs, q_rope, c_kv, k_rope, ckv_scale,
                                 krope_scale, lens, *, scale, bkv,
                                 interpret):
    T = c_kv.shape[1]
    bkv_ = min(bkv, T)
    ckv = _pad_dim(c_kv, 1, bkv_)
    kr = _pad_dim(k_rope, 1, bkv_)
    return vwr_mla_flash_decode_q8_p(
        q_abs, q_rope, ckv, kr, ckv_scale.astype(jnp.float32),
        krope_scale.astype(jnp.float32), lens, scale=scale, bkv=bkv_,
        t_valid=T, interpret=interpret)


def vwr_mla_flash_decode_q8(q_abs, q_rope, c_kv, k_rope, ckv_scale,
                            krope_scale, cur_len, pos0=0, *, scale,
                            bkv=None, interpret=None):
    """``vwr_mla_flash_decode`` over int8 latent/rope caches with
    per-(B,) fp32 scales.  Same combine contract."""
    interpret = _auto_interpret(interpret)
    B, H, r = q_abs.shape
    T, rope = c_kv.shape[1], q_rope.shape[2]
    if bkv is None:
        bkv = _mla_decode_blocks_q8(B, T, H, r, rope, interpret)[0]
    lens = jnp.stack([jnp.asarray(cur_len, jnp.int32).reshape(()),
                      jnp.asarray(pos0, jnp.int32).reshape(())]
                     ).reshape(1, 2)
    return _vwr_mla_flash_decode_q8_jit(
        q_abs, q_rope, c_kv, k_rope, ckv_scale, krope_scale, lens,
        scale=scale, bkv=bkv, interpret=interpret)


def _mla_decode_blocks_q8(B, T, H, r, rope, interpret):
    backend = _backend_tag(interpret)

    def runner(cand):
        bkv, = cand
        qa = jnp.ones((B, H, r), jnp.float32)
        qr = jnp.ones((B, H, rope), jnp.float32)
        ckv = jnp.ones((B, T, r), jnp.int8)
        kr = jnp.ones((B, T, rope), jnp.int8)
        sz = jnp.ones((B,), jnp.float32)
        lens = jnp.asarray([[T, 0]], jnp.int32)

        def run():
            jax.block_until_ready(_vwr_mla_flash_decode_q8_jit(
                qa, qr, ckv, kr, sz, sz, lens, scale=1.0, bkv=bkv,
                interpret=interpret))
        return run

    return autotune.get_blocks(
        "decode_mla", (B, T, H, r, rope), "int8", backend,
        candidates=autotune.decode_candidates(T, r + rope, "int8"),
        prior=lambda c: autotune.decode_prior(B, T, H, 1, r + rope,
                                              "int8", c),
        runner=runner if autotune.enabled() else None)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def _vwr_mla_paged_flash_decode_q8_jit(q_abs, q_rope, ckv_pool,
                                       krope_pool, ckv_scale,
                                       krope_scale, table, counts, *,
                                       scale, interpret):
    n_pages = ckv_pool.shape[0]
    tbl = jnp.clip(table, 0, n_pages - 1).astype(jnp.int32)
    return vwr_mla_paged_flash_decode_q8_p(
        q_abs, q_rope, ckv_pool, krope_pool,
        ckv_scale.astype(jnp.float32), krope_scale.astype(jnp.float32),
        tbl, counts.astype(jnp.int32), scale=scale, interpret=interpret)


def vwr_mla_paged_flash_decode_q8(q_abs, q_rope, ckv_pool, krope_pool,
                                  ckv_scale, krope_scale, table, counts,
                                  *, scale, interpret=None):
    """``vwr_mla_paged_flash_decode`` over int8 latent page pools with
    per-page fp32 scales riding the block-table indirection."""
    interpret = _auto_interpret(interpret)
    return _vwr_mla_paged_flash_decode_q8_jit(
        q_abs, q_rope, ckv_pool, krope_pool, ckv_scale, krope_scale,
        table, counts, scale=scale, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _vwr_chunk_prefix_attend_jit(q, k_pool, v_pool, table, counts, *,
                                 interpret):
    C, H, D = q.shape
    n_pages, KV, ps, _ = k_pool.shape
    G = H // KV
    qf = jnp.transpose(q.reshape(C, KV, G, D),
                       (1, 0, 2, 3)).reshape(KV, C * G, D)
    tbl = jnp.clip(table, 0, n_pages - 1).astype(jnp.int32)
    o_t, m, l = vwr_chunk_prefix_attend_p(
        qf, k_pool, v_pool, tbl, counts.astype(jnp.int32),
        interpret=interpret)
    o_t = jnp.transpose(o_t.reshape(KV, C, G, D),
                        (1, 0, 2, 3)).reshape(C, H, D)
    m = jnp.transpose(m.reshape(KV, C, G), (1, 0, 2)).reshape(C, H)
    l = jnp.transpose(l.reshape(KV, C, G), (1, 0, 2)).reshape(C, H)
    return o_t, m, l


def vwr_chunk_prefix_attend(q, k_pool, v_pool, table, counts, *,
                            interpret=None):
    """Chunked-prefill prefix attention: a (C, H, Dh) query chunk
    against its prompt's PRIOR pages (earlier chunks / prefix-cache
    hits), each page staged once for all C queries.  table/counts:
    (J,) page ids + per-page valid token counts (0 masks a page
    entirely — e.g. pages another sequence shard owns).  Returns fp32
    partials (o_tilde (C,H,Dh), m (C,H), l (C,H)); the within-chunk
    causal block is combined downstream via the flash merge.  No block
    autotuning: page size is the transaction width (the engine owns
    it)."""
    interpret = _auto_interpret(interpret)
    return _vwr_chunk_prefix_attend_jit(q, k_pool, v_pool, table,
                                        counts, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _vwr_chunk_prefix_attend_q8_jit(q, k_pool, v_pool, k_scale, v_scale,
                                    table, counts, *, interpret):
    C, H, D = q.shape
    n_pages, KV, ps, _ = k_pool.shape
    G = H // KV
    qf = jnp.transpose(q.reshape(C, KV, G, D),
                       (1, 0, 2, 3)).reshape(KV, C * G, D)
    tbl = jnp.clip(table, 0, n_pages - 1).astype(jnp.int32)
    o_t, m, l = vwr_chunk_prefix_attend_q8_p(
        qf, k_pool, v_pool, k_scale.astype(jnp.float32),
        v_scale.astype(jnp.float32), tbl, counts.astype(jnp.int32),
        interpret=interpret)
    o_t = jnp.transpose(o_t.reshape(KV, C, G, D),
                        (1, 0, 2, 3)).reshape(C, H, D)
    m = jnp.transpose(m.reshape(KV, C, G), (1, 0, 2)).reshape(C, H)
    l = jnp.transpose(l.reshape(KV, C, G), (1, 0, 2)).reshape(C, H)
    return o_t, m, l


def vwr_chunk_prefix_attend_q8(q, k_pool, v_pool, k_scale, v_scale,
                               table, counts, *, interpret=None):
    """``vwr_chunk_prefix_attend`` over int8 page pools with fp32
    (n_pages, KV) scale sidecars dequantized on the staged block."""
    interpret = _auto_interpret(interpret)
    return _vwr_chunk_prefix_attend_q8_jit(
        q, k_pool, v_pool, k_scale, v_scale, table, counts,
        interpret=interpret)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def _vwr_mla_chunk_prefix_attend_jit(q_abs, q_rope, ckv_pool,
                                     krope_pool, table, counts, *,
                                     scale, interpret):
    C, H, r = q_abs.shape
    rope = q_rope.shape[2]
    n_pages = ckv_pool.shape[0]
    tbl = jnp.clip(table, 0, n_pages - 1).astype(jnp.int32)
    o_t, m, l = vwr_mla_chunk_prefix_attend_p(
        q_abs.reshape(C * H, r), q_rope.reshape(C * H, rope),
        ckv_pool, krope_pool, tbl, counts.astype(jnp.int32),
        scale=scale, interpret=interpret)
    return (o_t.reshape(C, H, r), m.reshape(C, H), l.reshape(C, H))


def vwr_mla_chunk_prefix_attend(q_abs, q_rope, ckv_pool, krope_pool,
                                table, counts, *, scale,
                                interpret=None):
    """Split-operand MLA chunk-prefix attention: absorbed chunk
    queries q_abs (C,H,r) + q_rope (C,H,rope) against the latent page
    pools over the chunk's prior pages.  Same partial contract as
    ``vwr_chunk_prefix_attend``."""
    interpret = _auto_interpret(interpret)
    return _vwr_mla_chunk_prefix_attend_jit(
        q_abs, q_rope, ckv_pool, krope_pool, table, counts,
        scale=scale, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def _vwr_mla_chunk_prefix_attend_q8_jit(q_abs, q_rope, ckv_pool,
                                        krope_pool, ckv_scale,
                                        krope_scale, table, counts, *,
                                        scale, interpret):
    C, H, r = q_abs.shape
    rope = q_rope.shape[2]
    n_pages = ckv_pool.shape[0]
    tbl = jnp.clip(table, 0, n_pages - 1).astype(jnp.int32)
    o_t, m, l = vwr_mla_chunk_prefix_attend_q8_p(
        q_abs.reshape(C * H, r), q_rope.reshape(C * H, rope),
        ckv_pool, krope_pool, ckv_scale.astype(jnp.float32),
        krope_scale.astype(jnp.float32), tbl,
        counts.astype(jnp.int32), scale=scale, interpret=interpret)
    return (o_t.reshape(C, H, r), m.reshape(C, H), l.reshape(C, H))


def vwr_mla_chunk_prefix_attend_q8(q_abs, q_rope, ckv_pool, krope_pool,
                                   ckv_scale, krope_scale, table,
                                   counts, *, scale, interpret=None):
    """``vwr_mla_chunk_prefix_attend`` over int8 latent pools with
    fp32 per-page scale sidecars."""
    interpret = _auto_interpret(interpret)
    return _vwr_mla_chunk_prefix_attend_q8_jit(
        q_abs, q_rope, ckv_pool, krope_pool, ckv_scale, krope_scale,
        table, counts, scale=scale, interpret=interpret)


def _attention_blocks(B, S, H, KV, D, dtype, causal, interpret):
    backend = _backend_tag(interpret)
    op = "attention_causal" if causal else "attention_full"

    def runner(cand):
        bq, bkv = cand
        qz = jnp.ones((B, S, H, D), jnp.dtype(dtype))
        kz = jnp.ones((B, S, KV, D), jnp.dtype(dtype))

        def run():
            jax.block_until_ready(_vwr_attention_jit(
                qz, kz, kz, causal=causal, bq=bq, bkv=bkv,
                interpret=interpret))
        return run

    return autotune.get_blocks(
        op, (B, S, H, KV, D), dtype, backend,
        candidates=autotune.attention_candidates(S, D, dtype, causal),
        prior=lambda c: autotune.attention_prior(B, S, H, KV, D, dtype, c),
        runner=runner if autotune.enabled() else None)
