"""GQA attention with blockwise (flash-style) streaming + KV caches.

The blockwise kernel is the pure-JAX realization of the paper's VWR
streaming discipline applied to attention: KV is consumed in wide blocks
(one "wide transaction"), each block feeding many MXU steps, with fp32
running-softmax accumulators in registers (the R1..R4 analogue).  The
Pallas TPU version lives in ``repro.kernels.vwr_attention``; this module
is the XLA reference path the dry-run lowers.

Decode attention returns *unnormalized* partial results (o_tilde, lse) so
the distribution layer can combine sequence-sharded cache shards with a
psum — distributed FlashDecoding (see dist/decode.py).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as PS

from repro.common.hints import shard_hint
from repro.common.module import (ParamDef, head_out_init, head_proj_init,
                                 zeros_init)
from repro.kernels import dispatch as D
from repro.models.layers import apply_rope

NEG_INF = -1e30


# ---------------- projections ----------------

def gqa_spec(cfg):
    d, H, KV, Dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    dtype = jnp.dtype(cfg.dtype)
    spec = {
        "wq": ParamDef((d, H, Dh), dtype, ("embed", "heads", "head_dim"),
                       head_proj_init),
        "wk": ParamDef((d, KV, Dh), dtype, ("embed", "kv", "head_dim"),
                       head_proj_init),
        "wv": ParamDef((d, KV, Dh), dtype, ("embed", "kv", "head_dim"),
                       head_proj_init),
        "wo": ParamDef((H, Dh, d), dtype, ("heads", "head_dim", "embed"),
                       head_out_init),
    }
    if cfg.qkv_bias:
        spec["bq"] = ParamDef((H, Dh), dtype, ("heads", "head_dim"), zeros_init)
        spec["bk"] = ParamDef((KV, Dh), dtype, ("kv", "head_dim"), zeros_init)
        spec["bv"] = ParamDef((KV, Dh), dtype, ("kv", "head_dim"), zeros_init)
    return spec


@D.register("qkv_proj", "xla")
def _qkv_proj_xla(p, x, mesh=None):
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    if "bq" in p:
        q = q + p["bq"]
        k = k + p["bk"]
        v = v + p["bv"]
    return q, k, v


def _qkv_kernels(p, x):
    from repro.kernels import ops
    B, S, d = x.shape
    x2 = x.reshape(B * S, d)

    def proj(w, b):
        nh, dh = w.shape[1], w.shape[2]
        bias = None if b is None else b.reshape(1, nh * dh)
        out = ops.vwr_matmul(x2, w.reshape(d, nh * dh), bias)
        return out.reshape(B, S, nh, dh)

    return (proj(p["wq"], p.get("bq")),    # qkv bias fused in-kernel
            proj(p["wk"], p.get("bk")),
            proj(p["wv"], p.get("bv")))


@D.register("qkv_proj", "pallas")
def _qkv_proj_pallas(p, x, mesh=None):
    from repro.dist import sharding as SH
    if not SH.multi_device(mesh):
        return _qkv_kernels(p, x)
    # column-parallel over 'model': each device projects its own heads
    hq = SH.tp_entry(mesh, p["wq"].shape[1])
    hk = SH.tp_entry(mesh, p["wk"].shape[1])
    dp = SH.batch_entry(mesh, x.shape[0])
    sub = {k: p[k] for k in ("wq", "wk", "wv", "bq", "bk", "bv")
           if k in p}

    def spec(name):                     # w* (d, heads, Dh), b* (heads, Dh)
        ax = hq if name[1] == "q" else hk
        return PS(None, ax, None) if name[0] == "w" else PS(ax, None)

    return SH.per_device(
        _qkv_kernels, mesh, ({k: spec(k) for k in sub}, PS(dp, None, None)),
        (PS(dp, None, hq, None), PS(dp, None, hk, None),
         PS(dp, None, hk, None)))(sub, x)


def qkv_proj(p, x, positions, rope_theta, backend="xla", *,
             kernel_impl=None, mesh=None):
    """QKV projection (+rope) via the dispatch registry.  ``backend``
    is a backend string or a ModelConfig; the legacy ``kernel_impl=``
    kwarg still works but is deprecated."""
    if kernel_impl is not None:
        D.warn_kernel_impl_kwarg("attention.qkv_proj")
        backend = kernel_impl
    q, k, v = D.dispatch("qkv_proj", backend, p, x, mesh=mesh)
    if rope_theta:
        q = apply_rope(q, positions, rope_theta)
        k = apply_rope(k, positions, rope_theta)
    return q, k, v


@D.register("o_proj", "xla")
def _o_proj_xla(p, o, residual=None, mesh=None):
    out = jnp.einsum("bshk,hkd->bsd", o, p["wo"])
    return out if residual is None else residual + out


def _o_kernel(wo, o, residual=None):
    from repro.kernels import ops
    B, S, H, Dh = o.shape
    d = wo.shape[-1]
    r2 = None if residual is None else residual.reshape(B * S, d)
    out = ops.vwr_matmul(o.reshape(B * S, H * Dh), wo.reshape(H * Dh, d),
                         residual=r2)
    return out.reshape(B, S, d)


@D.register("o_proj", "pallas")
def _o_proj_pallas(p, o, residual=None, mesh=None):
    from repro.dist import sharding as SH
    if not SH.multi_device(mesh):
        return _o_kernel(p["wo"], o, residual)
    # row-parallel over 'model': per-device heads, one psum
    hq = SH.tp_entry(mesh, p["wo"].shape[0])
    dp = SH.batch_entry(mesh, o.shape[0])

    def local(wo, o):
        out = _o_kernel(wo, o)
        return out if hq is None else jax.lax.psum(out, hq)

    out = SH.per_device(local, mesh,
                        (PS(hq, None, None), PS(dp, None, hq, None)),
                        PS(dp, None, None))(p["wo"], o)
    return out if residual is None else residual + out


def o_proj(p, o, backend="xla", residual=None, *, kernel_impl=None,
           mesh=None):
    """Output projection; with ``residual`` returns residual + o@wo —
    fused into the matmul's final-K store on the pallas path.  The
    legacy ``kernel_impl=`` kwarg still works but is deprecated."""
    if kernel_impl is not None:
        D.warn_kernel_impl_kwarg("attention.o_proj")
        backend = kernel_impl
    return D.dispatch("o_proj", backend, p, o, residual=residual,
                      mesh=mesh)


# ---------------- blockwise flash attention (training / prefill) ----------------

def _pad_to(x, mult, axis):
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x, n
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths), n


def blockwise_attn(
    q: jax.Array,                 # (B, Sq, H, Dh)
    k: jax.Array,                 # (B, Skv, KV, Dh)
    v: jax.Array,                 # (B, Skv, KV, Dh)
    *,
    causal: bool,
    q_positions: Optional[jax.Array] = None,    # (Sq,) global positions
    kv_positions: Optional[jax.Array] = None,   # (Skv,)
    kv_valid: Optional[jax.Array] = None,       # (Skv,) bool padding mask
    block_q: int = 512,
    block_kv: int = 1024,
    head_axis=None,
    mesh=None,
) -> jax.Array:
    """Streaming softmax attention; peak memory O(block_q * block_kv).

    head_axis: mesh axis carrying the kv-head dim.  GSPMD loses the
    head sharding through the block reshapes and then ALL-REDUCES the
    fp32 score tensor per (q,kv) block pair in the remat'd backward
    (measured 825 GB/device/step on qwen train_4k — EXPERIMENTS.md
    §Perf H2a); explicit hints on the blocked operands and the running
    stats keep every block head-sharded."""
    B, Sq, H, Dh = q.shape
    KV = k.shape[2]
    G = H // KV
    orig_dtype = q.dtype
    scale = 1.0 / (Dh ** 0.5)

    if q_positions is None:
        q_positions = jnp.arange(Sq)
    if kv_positions is None:
        kv_positions = jnp.arange(k.shape[1])
    if kv_valid is None:
        kv_valid = jnp.ones((k.shape[1],), jnp.bool_)

    block_q = min(block_q, Sq)
    block_kv = min(block_kv, k.shape[1])

    q, _ = _pad_to(q, block_q, 1)
    qpos, _ = _pad_to(q_positions, block_q, 0)
    k, _ = _pad_to(k, block_kv, 1)
    v, _ = _pad_to(v, block_kv, 1)
    kpos, _ = _pad_to(kv_positions, block_kv, 0)
    kval, _ = _pad_to(kv_valid, block_kv, 0)

    nq = q.shape[1] // block_q
    nk = k.shape[1] // block_kv

    qb = q.reshape(B, nq, block_q, KV, G, Dh)
    kb = k.reshape(B, nk, block_kv, KV, Dh)
    vb = v.reshape(B, nk, block_kv, KV, Dh)
    if head_axis is not None:
        qb = shard_hint(qb, PS(None, None, None, head_axis, None, None),
                        mesh=mesh)
        kb = shard_hint(kb, PS(None, None, None, head_axis, None),
                        mesh=mesh)
        vb = shard_hint(vb, PS(None, None, None, head_axis, None),
                        mesh=mesh)
    qposb = qpos.reshape(nq, block_q)
    kposb = kpos.reshape(nk, block_kv)
    kvalb = kval.reshape(nk, block_kv)

    def q_step(_, qi):
        q_i, qp_i = qi                                  # (B,bq,KV,G,Dh),(bq,)
        q_i = (q_i.astype(jnp.float32) * scale)

        def kv_step(carry, ki):
            acc, m, l = carry
            k_j, v_j, kp_j, km_j = ki
            s = jnp.einsum(
                "bqhgd,bkhd->bhgqk", q_i, k_j.astype(jnp.float32)
            )                                            # (B,KV,G,bq,bkv)
            if head_axis is not None:
                s = shard_hint(s, PS(None, head_axis, None, None, None),
                               mesh=mesh)
            mask = km_j[None, None, None, None, :]
            if causal:
                mask = mask & (kp_j[None, :] <= qp_i[:, None])[None, None, None]
            s = jnp.where(mask, s, NEG_INF)
            m_new = jnp.maximum(m, s.max(axis=-1))       # (B,KV,G,bq)
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l = l * corr + p.sum(axis=-1)
            pv = jnp.einsum("bhgqk,bkhd->bhgqd", p, v_j.astype(jnp.float32))
            acc = acc * corr[..., None] + pv
            return (acc, m_new, l), None

        acc0 = jnp.zeros((B, KV, G, block_q, Dh), jnp.float32)
        m0 = jnp.full((B, KV, G, block_q), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, KV, G, block_q), jnp.float32)
        (acc, m, l), _ = jax.lax.scan(
            kv_step, (acc0, m0, l0),
            (kb.swapaxes(0, 1), vb.swapaxes(0, 1), kposb, kvalb),
        )
        out = acc / jnp.maximum(l[..., None], 1e-30)
        out = out.transpose(0, 3, 1, 2, 4)               # (B,bq,KV,G,Dh)
        return None, out.astype(orig_dtype)

    _, ob = jax.lax.scan(q_step, None, (qb.swapaxes(0, 1), qposb))
    out = ob.swapaxes(0, 1).reshape(B, nq * block_q, H, Dh)
    return out[:, :Sq]


@D.register("attention", "xla")
def _attention_xla(q, k, v, *, causal, q_positions=None, kv_positions=None,
                   block_q=512, block_kv=1024, head_axis=None, mesh=None):
    return blockwise_attn(q, k, v, causal=causal, q_positions=q_positions,
                          kv_positions=kv_positions, block_q=block_q,
                          block_kv=block_kv, head_axis=head_axis,
                          mesh=mesh)


@D.register("attention", "pallas")
def _attention_pallas(q, k, v, *, causal, q_positions=None,
                      kv_positions=None, block_q=512, block_kv=1024,
                      head_axis=None, mesh=None):
    """Zero-copy GQA flash kernel (blocks autotuned).  The non-causal
    (encoder) path keeps the blockwise formulation, whose kv-padding
    masks don't require S % block == 0."""
    if causal:
        from repro.dist import sharding as SH
        from repro.kernels import ops

        def kernel(q, k, v):
            return ops.vwr_attention(q, k, v, causal=True)

        if not SH.multi_device(mesh):
            return kernel(q, k, v)
        # per-device heads: a kv head and its whole query group stay on
        # one device (kv-major head order h = kv * G + g)
        hk = SH.tp_entry(mesh, k.shape[2])
        spec = PS(SH.batch_entry(mesh, q.shape[0]), None, hk, None)
        return SH.per_device(kernel, mesh, (spec, spec, spec),
                             spec)(q, k, v)
    return _attention_xla(q, k, v, causal=causal, q_positions=q_positions,
                          kv_positions=kv_positions, block_q=block_q,
                          block_kv=block_kv, head_axis=head_axis,
                          mesh=mesh)


def full_attn_ref(q, k, v, *, causal, q_positions=None, kv_positions=None,
                  kv_valid=None):
    """Dense oracle used by tests."""
    B, Sq, H, Dh = q.shape
    KV = k.shape[2]
    G = H // KV
    if q_positions is None:
        q_positions = jnp.arange(Sq)
    if kv_positions is None:
        kv_positions = jnp.arange(k.shape[1])
    qf = q.astype(jnp.float32).reshape(B, Sq, KV, G, Dh) / (Dh ** 0.5)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qf, k.astype(jnp.float32))
    mask = jnp.ones((Sq, k.shape[1]), jnp.bool_)
    if causal:
        mask = kv_positions[None, :] <= q_positions[:, None]
    if kv_valid is not None:
        mask = mask & kv_valid[None, :]
    s = jnp.where(mask[None, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", p, v.astype(jnp.float32))
    return o.reshape(B, Sq, H, Dh).astype(q.dtype)


# ---------------- decode (single new token against a cache) ----------------

def flash_decode_partial(
    q: jax.Array,          # (B, H, Dh) — one new token
    cache_k: jax.Array,    # (B, T, KV, Dh) — local shard of the cache
    cache_v: jax.Array,
    kv_positions: jax.Array,  # (T,) global positions of the shard
    cur_len: jax.Array,       # scalar: tokens valid so far (global)
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Returns (o_tilde, m, l) with o_tilde = sum(exp(s - m) * v).

    Combining shards i (distributed FlashDecoding, dist/decode.py):
        m* = max_i m_i            (pmax over the cache-sharded axis)
        o  = sum_i o_tilde_i * exp(m_i - m*) / sum_i l_i * exp(m_i - m*)
    """
    B, H, Dh = q.shape
    KV = cache_k.shape[2]
    G = H // KV
    qf = q.astype(jnp.float32).reshape(B, KV, G, Dh) / (Dh ** 0.5)
    s = jnp.einsum("bhgd,bthd->bhgt", qf, cache_k.astype(jnp.float32))
    valid = kv_positions < cur_len                           # (T,)
    s = jnp.where(valid[None, None, None, :], s, NEG_INF)
    m = s.max(axis=-1)                                       # (B,KV,G)
    p = jnp.exp(s - m[..., None])
    # rows with no valid key (m == NEG_INF) contribute l = 0
    p = jnp.where((m > NEG_INF / 2)[..., None], p, 0.0)
    l = p.sum(axis=-1)
    o_t = jnp.einsum("bhgt,bthd->bhgd", p, cache_v.astype(jnp.float32))
    return (o_t.reshape(B, H, Dh), m.reshape(B, H), l.reshape(B, H))


def decode_attend_local(q, cache_k, cache_v, kv_positions, cur_len):
    """Single-shard decode attention (normalized)."""
    o_t, m, l = flash_decode_partial(q, cache_k, cache_v, kv_positions, cur_len)
    return (o_t / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype)


# Registered decode-partial contract (shared by GQA, MLA-absorbed and
# cross-attention decode — dist.decode combines the partials across
# sequence shards): (q (B,H,Dh), k/v (B,T,KV,Dh) slab starting at
# global position pos0, cur_len) -> fp32 (o_tilde, m, l).

@D.register("decode_partial", "xla")
def _decode_partial_xla(q, k, v, cur_len, pos0=0, *, tune=True):
    T = k.shape[1]
    return flash_decode_partial(q, k, v, pos0 + jnp.arange(T), cur_len)


# ---------------- paged decode (block-table-indexed page pool) ----------------

def paged_flash_decode_partial(
    q: jax.Array,            # (B, H, Dh) — one new token per slot
    k_pool: jax.Array,       # (n_pages, KV, page_size, Dh) shared pool
    v_pool: jax.Array,
    block_table: jax.Array,  # (B, max_pages) int32 physical page ids
    page_counts: jax.Array,  # (B, max_pages) int32 valid tokens per page
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """XLA gather reference for the paged decode contract.

    Gathers each slot's pages from the pool (``pool[block_table]``) and
    runs the same online-softmax partial as ``flash_decode_partial``,
    masked per (slot, page) by ``page_counts`` (0 = page fully masked:
    past the slot's length, unallocated, or owned by another shard).
    Returns fp32 (o_tilde (B,H,Dh), m (B,H), l (B,H)).
    """
    B, H, Dh = q.shape
    n_pages, KV, ps, _ = k_pool.shape
    G = H // KV
    J = block_table.shape[1]
    tbl = jnp.clip(block_table, 0, n_pages - 1)

    def gather(pool):                            # -> (B, J*ps, KV, Dh)
        return jnp.swapaxes(pool[tbl], 2, 3).reshape(B, J * ps, KV, Dh)

    k, v = gather(k_pool), gather(v_pool)
    valid = (jnp.arange(ps)[None, None, :]
             < page_counts[..., None]).reshape(B, J * ps)
    qf = q.astype(jnp.float32).reshape(B, KV, G, Dh) / (Dh ** 0.5)
    s = jnp.einsum("bhgd,bthd->bhgt", qf, k.astype(jnp.float32))
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    m = s.max(axis=-1)
    p = jnp.exp(s - m[..., None])
    p = jnp.where((m > NEG_INF / 2)[..., None], p, 0.0)
    l = p.sum(axis=-1)
    o_t = jnp.einsum("bhgt,bthd->bhgd", p, v.astype(jnp.float32))
    return (o_t.reshape(B, H, Dh), m.reshape(B, H), l.reshape(B, H))


# page_size/max_pages ride as STATIC kwargs folded into the dispatch
# cache key (dispatch._arg_signature).  Today they duplicate the
# pool/table dims already keyed via the operand shapes; carrying them
# explicitly pins the geometry even for a future caller that reshapes
# or pads operands before dispatching, and makes the persisted cache
# entries self-describing.

@D.register("decode_partial_paged", "xla")
def _decode_partial_paged_xla(q, k_pool, v_pool, table, counts, *,
                              page_size=None, max_pages=None,
                              tune=True):
    return paged_flash_decode_partial(q, k_pool, v_pool, table, counts)


@D.register("decode_partial_paged", "pallas")
def _decode_partial_paged_pallas(q, k_pool, v_pool, table, counts, *,
                                 page_size=None, max_pages=None,
                                 tune=True):
    from repro.kernels import ops
    return ops.vwr_paged_flash_decode(q, k_pool, v_pool, table, counts)


@D.register("decode_partial", "pallas")
def _decode_partial_pallas(q, k, v, cur_len, pos0=0, *, tune=True):
    from repro.kernels import autotune, ops
    if tune:
        return ops.vwr_flash_decode(q, k, v, cur_len, pos0=pos0)
    # tune=False (shard_map tracing): block size from the cost-model
    # prior only — the measuring tuner must not fire inside shard_map
    T = k.shape[1]
    cands = autotune.decode_candidates(T, q.shape[-1], str(q.dtype))
    bkv = min(cands, key=lambda c: autotune.decode_prior(
        q.shape[0], T, q.shape[1], k.shape[2], q.shape[-1],
        str(q.dtype), c))[0]
    return ops.vwr_flash_decode(q, k, v, cur_len, pos0=pos0, bkv=bkv)


# ---------------- q8 decode (int8 caches, fp32 scale sidecars) ----------------
#
# Same partial contracts with the cache/pool operands stored int8 and
# fp32 scales alongside: per flattened (B, KV) row for the dense cache,
# per (page, KV head) for the pool.  The XLA references dequantize up
# front (reference clarity); the pallas backends stage the int8 block
# and dequantize in-kernel, which is the whole point — staged HBM
# bytes per token drop 2x vs bf16.  The pool dtype is folded into the
# dispatch cache key (all operand dtypes are), so a bf16-pool 'auto'
# winner never replays for an int8 pool of the same geometry.

@D.register("decode_partial_q8", "xla")
def _decode_partial_q8_xla(q, k, v, k_scale, v_scale, cur_len, pos0=0,
                           *, tune=True):
    T = k.shape[1]
    kf = k.astype(jnp.float32) * k_scale[:, None, :, None]
    vf = v.astype(jnp.float32) * v_scale[:, None, :, None]
    return flash_decode_partial(q, kf, vf, pos0 + jnp.arange(T), cur_len)


@D.register("decode_partial_q8", "pallas")
def _decode_partial_q8_pallas(q, k, v, k_scale, v_scale, cur_len,
                              pos0=0, *, tune=True):
    from repro.kernels import autotune, ops
    if tune:
        return ops.vwr_flash_decode_q8(q, k, v, k_scale, v_scale,
                                       cur_len, pos0=pos0)
    T = k.shape[1]
    cands = autotune.decode_candidates(T, q.shape[-1], "int8")
    bkv = min(cands, key=lambda c: autotune.decode_prior(
        q.shape[0], T, q.shape[1], k.shape[2], q.shape[-1],
        "int8", c))[0]
    return ops.vwr_flash_decode_q8(q, k, v, k_scale, v_scale, cur_len,
                                   pos0=pos0, bkv=bkv)


@D.register("decode_partial_paged_q8", "xla")
def _decode_partial_paged_q8_xla(q, k_pool, v_pool, k_scale, v_scale,
                                 table, counts, *, page_size=None,
                                 max_pages=None, tune=True):
    # dequantize the whole pool: honest reference semantics (and the
    # honest cost of NOT dequantizing in-kernel)
    kf = k_pool.astype(jnp.float32) * k_scale[:, :, None, None]
    vf = v_pool.astype(jnp.float32) * v_scale[:, :, None, None]
    return paged_flash_decode_partial(q, kf, vf, table, counts)


@D.register("decode_partial_paged_q8", "pallas")
def _decode_partial_paged_q8_pallas(q, k_pool, v_pool, k_scale, v_scale,
                                    table, counts, *, page_size=None,
                                    max_pages=None, tune=True):
    from repro.kernels import ops
    return ops.vwr_paged_flash_decode_q8(q, k_pool, v_pool, k_scale,
                                         v_scale, table, counts)


# ---------------- chunked prefill (query chunk vs the paged pool) -------------
#
# Chunked prefill splits a prompt into fixed-token slices that ride
# inside the shared decode step.  Chunk k's attention decomposes into
# two partials under the flash combine contract:
#
#   * a PREFIX partial — the (C, d) query chunk against the prompt's
#     prior pages (earlier chunks + prefix-cache hits resident via the
#     block table), masked per page by valid counts.  This is the
#     registered op below: the pallas backend stages each prior page
#     once for all C queries.
#   * a SELF partial — the C x C causal block over the chunk's own
#     freshly computed KV (``chunk_self_attn_partial``).
#
# ``merge_partials`` folds the two (and, in dist.decode, per-shard
# prefix partials) into one normalized output.

def merge_partials(a, b):
    """Flash-combine two (o_tilde, m, l) partials over the same
    queries.  Exact: a fully masked partial (m = NEG_INF, l = 0)
    contributes nothing."""
    o1, m1, l1 = a
    o2, m2, l2 = b
    m = jnp.maximum(m1, m2)
    s1 = jnp.where(m1 > NEG_INF / 2, jnp.exp(m1 - m), 0.0)
    s2 = jnp.where(m2 > NEG_INF / 2, jnp.exp(m2 - m), 0.0)
    return (o1 * s1[..., None] + o2 * s2[..., None], m,
            l1 * s1 + l2 * s2)


def normalize_partial(o_t, l, dtype):
    return (o_t / jnp.maximum(l, 1e-30)[..., None]).astype(dtype)


def chunk_prefix_attend_partial(
    q: jax.Array,            # (C, H, Dh) — one prompt's query chunk
    k_pool: jax.Array,       # (n_pages, KV, page_size, Dh) shared pool
    v_pool: jax.Array,
    table: jax.Array,        # (J,) int32 the chunk's PRIOR pages
    counts: jax.Array,       # (J,) int32 valid tokens per page
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """XLA gather reference for the chunk-prefix contract.  Returns
    fp32 (o_tilde (C,H,Dh), m (C,H), l (C,H))."""
    C, H, Dh = q.shape
    n_pages, KV, ps, _ = k_pool.shape
    G = H // KV
    J = table.shape[0]
    tbl = jnp.clip(table, 0, n_pages - 1)
    k = jnp.swapaxes(k_pool[tbl], 1, 2).reshape(J * ps, KV, Dh)
    v = jnp.swapaxes(v_pool[tbl], 1, 2).reshape(J * ps, KV, Dh)
    valid = (jnp.arange(ps)[None, :] < counts[:, None]).reshape(J * ps)
    qf = q.astype(jnp.float32).reshape(C, KV, G, Dh) / (Dh ** 0.5)
    s = jnp.einsum("chgd,thd->chgt", qf, k.astype(jnp.float32))
    s = jnp.where(valid[None, None, None, :], s, NEG_INF)
    m = s.max(axis=-1)
    p = jnp.exp(s - m[..., None])
    p = jnp.where((m > NEG_INF / 2)[..., None], p, 0.0)
    l = p.sum(axis=-1)
    o_t = jnp.einsum("chgt,thd->chgd", p, v.astype(jnp.float32))
    return (o_t.reshape(C, H, Dh), m.reshape(C, H), l.reshape(C, H))


def chunk_self_attn_partial(q, k, v):
    """Causal partial over the chunk's OWN KV: q (C,H,Dh) against
    k/v (C,KV,Dh), position i attending keys [0, i].  A small dense
    (C, C) block — stays XLA."""
    C, H, Dh = q.shape
    KV = k.shape[1]
    G = H // KV
    qf = q.astype(jnp.float32).reshape(C, KV, G, Dh) / (Dh ** 0.5)
    s = jnp.einsum("chgd,thd->chgt", qf, k.astype(jnp.float32))
    causal = jnp.arange(C)[:, None] >= jnp.arange(C)[None, :]
    s = jnp.where(causal[:, None, None, :], s, NEG_INF)
    m = s.max(axis=-1)
    p = jnp.exp(s - m[..., None])
    p = jnp.where((m > NEG_INF / 2)[..., None], p, 0.0)
    l = p.sum(axis=-1)
    o_t = jnp.einsum("chgt,thd->chgd", p, v.astype(jnp.float32))
    return (o_t.reshape(C, H, Dh), m.reshape(C, H), l.reshape(C, H))


def chunk_prefill_attend(q, chunk_k, chunk_v, k_pool, v_pool, table,
                         counts, *, backend="xla"):
    """Full chunked-prefill attention for one chunk: prefix partial
    (registered op ``chunk_prefix_paged``, q8 routed by the pool's
    scale sidecars being passed as ``k_pool``/``v_pool`` dequantized
    upstream) merged with the within-chunk causal partial, normalized.
    Returns (C, H, Dh) in q's dtype."""
    ps = k_pool.shape[2]
    J = table.shape[0]
    prefix = D.dispatch("chunk_prefix_paged", backend, q, k_pool,
                        v_pool, table, counts, page_size=ps,
                        max_pages=J)
    self_p = chunk_self_attn_partial(q, chunk_k, chunk_v)
    o_t, _, l = merge_partials(prefix, self_p)
    return normalize_partial(o_t, l, q.dtype)


@D.register("chunk_prefix_paged", "xla")
def _chunk_prefix_paged_xla(q, k_pool, v_pool, table, counts, *,
                            page_size=None, max_pages=None, tune=True):
    return chunk_prefix_attend_partial(q, k_pool, v_pool, table, counts)


@D.register("chunk_prefix_paged", "pallas")
def _chunk_prefix_paged_pallas(q, k_pool, v_pool, table, counts, *,
                               page_size=None, max_pages=None,
                               tune=True):
    from repro.kernels import ops
    return ops.vwr_chunk_prefix_attend(q, k_pool, v_pool, table, counts)


@D.register("chunk_prefix_paged_q8", "xla")
def _chunk_prefix_paged_q8_xla(q, k_pool, v_pool, k_scale, v_scale,
                               table, counts, *, page_size=None,
                               max_pages=None, tune=True):
    kf = k_pool.astype(jnp.float32) * k_scale[:, :, None, None]
    vf = v_pool.astype(jnp.float32) * v_scale[:, :, None, None]
    return chunk_prefix_attend_partial(q, kf, vf, table, counts)


@D.register("chunk_prefix_paged_q8", "pallas")
def _chunk_prefix_paged_q8_pallas(q, k_pool, v_pool, k_scale, v_scale,
                                  table, counts, *, page_size=None,
                                  max_pages=None, tune=True):
    from repro.kernels import ops
    return ops.vwr_chunk_prefix_attend_q8(q, k_pool, v_pool, k_scale,
                                          v_scale, table, counts)
