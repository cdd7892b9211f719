"""Multi-head Latent Attention (DeepSeek-V2/V3).

Cache stores the compressed latent (c_kv, kv_lora_rank) + shared rope key
(rope_head_dim) per token — 576 values/token for V3 — the reason MLA is
the bandwidth-friendliest full-attention cache and a natural fit for the
paper's wide-streaming discipline.

Prefill/train use the expanded (non-absorbed) form (compute-bound);
decode uses the *absorbed* form: q_nope is folded through wk_b so scores
and values are taken directly against the latent cache
(O(T * kv_lora) per head instead of O(T * expand)).
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as PS

from repro.common.hints import shard_hint
from repro.common.module import ParamDef, head_out_init, head_proj_init
from repro.kernels import dispatch as D
from repro.models.attention import NEG_INF, blockwise_attn
from repro.models.layers import apply_rope, rmsnorm, rmsnorm_spec


def mla_spec(cfg):
    m = cfg.mla
    d, H = cfg.d_model, cfg.n_heads
    dtype = jnp.dtype(cfg.dtype)
    qd = m.nope_head_dim + m.rope_head_dim
    return {
        "wq_a": ParamDef((d, m.q_lora_rank), dtype, ("embed", "q_lora")),
        "q_norm": rmsnorm_spec(m.q_lora_rank, dtype),
        "wq_b": ParamDef((m.q_lora_rank, H, qd), dtype,
                         ("q_lora", "heads", "head_dim"), head_proj_init),
        "wkv_a": ParamDef(
            (d, m.kv_lora_rank + m.rope_head_dim), dtype, ("embed", "kv_lora")
        ),
        "kv_norm": rmsnorm_spec(m.kv_lora_rank, dtype),
        "wk_b": ParamDef((m.kv_lora_rank, H, m.nope_head_dim), dtype,
                         ("kv_lora", "heads", "head_dim"), head_proj_init),
        "wv_b": ParamDef((m.kv_lora_rank, H, m.v_head_dim), dtype,
                         ("kv_lora", "heads", "head_dim"), head_proj_init),
        "wo": ParamDef((H, m.v_head_dim, d), dtype,
                       ("heads", "head_dim", "embed"), head_out_init),
    }


def mla_latent(p, x, positions, cfg):
    """x -> (c_kv normalized, k_rope with rope applied). Cache contents."""
    m = cfg.mla
    kv_a = jnp.einsum("bsd,dr->bsr", x, p["wkv_a"])
    c_kv = rmsnorm(p["kv_norm"], kv_a[..., : m.kv_lora_rank], cfg.norm_eps)
    k_rope = kv_a[..., m.kv_lora_rank:][:, :, None, :]        # (B,S,1,rope)
    k_rope = apply_rope(k_rope, positions, cfg.rope_theta)
    return c_kv, k_rope[:, :, 0, :]


def mla_queries(p, x, positions, cfg):
    m = cfg.mla
    q_a = rmsnorm(p["q_norm"], jnp.einsum("bsd,dr->bsr", x, p["wq_a"]), cfg.norm_eps)
    q = jnp.einsum("bsr,rhk->bshk", q_a, p["wq_b"])
    q_nope = q[..., : m.nope_head_dim]
    q_rope = apply_rope(q[..., m.nope_head_dim:], positions, cfg.rope_theta)
    return q_nope, q_rope


def mla_attention(p, x, positions, cfg, *, causal=True, dense=False,
                  head_axis=None, mesh=None):
    """Expanded-form attention for train/prefill. Returns (out, (c_kv, k_rope))."""
    m = cfg.mla
    H = cfg.n_heads
    q_nope, q_rope = mla_queries(p, x, positions, cfg)
    c_kv, k_rope = mla_latent(p, x, positions, cfg)

    # H2d (latent/projection hints) was measured NEUTRAL here and is
    # reverted — see EXPERIMENTS.md §Perf; the blockwise head hints
    # (H2b) below carry the gain.
    k_nope = jnp.einsum("bsr,rhk->bshk", c_kv, p["wk_b"])
    v = jnp.einsum("bsr,rhk->bshk", c_kv, p["wv_b"])
    k_rope_h = jnp.broadcast_to(
        k_rope[:, :, None, :], (*k_rope.shape[:2], H, m.rope_head_dim)
    )
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate([k_nope, k_rope_h], axis=-1)

    if dense:
        from repro.models.attention import full_attn_ref
        o = full_attn_ref(q, k, v_pad(v, q.shape[-1]), causal=causal,
                          q_positions=positions, kv_positions=positions)
        o = o[..., : m.v_head_dim]
    else:
        o = blockwise_attn(
            q, k, v_pad(v, q.shape[-1]), causal=causal,
            q_positions=positions, kv_positions=positions,
            block_q=cfg.attn_block_q, block_kv=cfg.attn_block_kv,
            head_axis=head_axis, mesh=mesh,
        )[..., : m.v_head_dim]
    out = jnp.einsum("bshk,hkd->bsd", o, p["wo"])
    return out, (c_kv, k_rope)


def mla_attention_suffix(p, x, q_positions, kv_positions, cfg,
                         prefix_ckv, prefix_krope, *, head_axis=None,
                         mesh=None):
    """Expanded-form attention for suffix-only prefill (prefix cache).

    ``x`` holds only the SUFFIX tokens at global ``q_positions``
    (arange(M, M+S) for a matched prefix of M tokens);
    ``prefix_ckv`` (B, M, r) / ``prefix_krope`` (B, M, rope) are the
    prefix latents gathered back out of the page pools (already
    normalized / rope'd — exactly what ``mla_latent`` cached).  Keys
    and values are reconstructed from the concatenated latents through
    wk_b / wv_b just as the full prefill does, so each suffix row's
    output is bit-identical to the same row of a whole-prompt
    ``mla_attention`` when the pools store the model dtype.  Returns
    (out, (c_kv, k_rope)) covering the suffix only — the prefix is
    already paged."""
    m = cfg.mla
    H = cfg.n_heads
    q_nope, q_rope = mla_queries(p, x, q_positions, cfg)
    c_kv, k_rope = mla_latent(p, x, q_positions, cfg)

    ckv_all = jnp.concatenate([prefix_ckv.astype(c_kv.dtype), c_kv], 1)
    krope_all = jnp.concatenate(
        [prefix_krope.astype(k_rope.dtype), k_rope], 1)
    k_nope = jnp.einsum("bsr,rhk->bshk", ckv_all, p["wk_b"])
    v = jnp.einsum("bsr,rhk->bshk", ckv_all, p["wv_b"])
    k_rope_h = jnp.broadcast_to(
        krope_all[:, :, None, :],
        (*krope_all.shape[:2], H, m.rope_head_dim))
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate([k_nope, k_rope_h], axis=-1)

    if cfg.accounting:
        from repro.models.attention import full_attn_ref
        o = full_attn_ref(q, k, v_pad(v, q.shape[-1]), causal=True,
                          q_positions=q_positions,
                          kv_positions=kv_positions)[..., : m.v_head_dim]
    else:
        o = blockwise_attn(
            q, k, v_pad(v, q.shape[-1]), causal=True,
            q_positions=q_positions, kv_positions=kv_positions,
            block_q=cfg.attn_block_q, block_kv=cfg.attn_block_kv,
            head_axis=head_axis, mesh=mesh,
        )[..., : m.v_head_dim]
    out = jnp.einsum("bshk,hkd->bsd", o, p["wo"])
    return out, (c_kv, k_rope)


def v_pad(v, d):
    """Pad V head dim up to QK head dim so the streaming kernel is uniform."""
    pad = d - v.shape[-1]
    if pad == 0:
        return v
    return jnp.pad(v, ((0, 0), (0, 0), (0, 0), (0, pad)))


# ---------------- absorbed decode ----------------

def mla_absorbed_queries(p, q_nope, q_rope, cfg
                         ) -> Tuple[jax.Array, jax.Array, float]:
    """Fold q_nope through wk_b: the split-operand decode queries.

    q_nope: (B,H,nope); q_rope: (B,H,rope).  Returns (q_abs (B,H,r)
    fp32, q_rope fp32, scale) with scale the absorbed-MLA
    1/sqrt(nope+rope) — the query triple every ``decode_partial_mla``
    backend consumes.  No cache-side concat is involved: scores are
    ``(q_abs . c_kv + q_rope . k_rope) * scale`` and values come
    straight from the latent cache."""
    m = cfg.mla
    q_abs = jnp.einsum("bhk,rhk->bhr", q_nope.astype(jnp.float32),
                       p["wk_b"].astype(jnp.float32))
    scale = 1.0 / ((m.nope_head_dim + m.rope_head_dim) ** 0.5)
    return q_abs, q_rope.astype(jnp.float32), scale


def mla_flash_decode_partial(
    q_abs, q_rope, cache_ckv, cache_krope, kv_positions, cur_len, *,
    scale: float,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Split-operand absorbed-form partial decode (XLA reference).

    q_abs: (B,H,r) fp32 (pre-folded through wk_b — see
    ``mla_absorbed_queries``); q_rope: (B,H,rope); cache_ckv: (B,T,r);
    cache_krope: (B,T,rope).  The latent cache carries both the nope
    part of the keys and the values, so the cache is read ONCE with no
    k_cat/v_cat copies and no rope zero-pad in the value stream.
    Returns fp32 (o_tilde (B,H,r), m (B,H), l (B,H)) — the
    ``dist.decode`` pmax/psum combine contract.
    """
    s = jnp.einsum("bhr,btr->bht", q_abs, cache_ckv.astype(jnp.float32))
    s = s + jnp.einsum("bhk,btk->bht", q_rope.astype(jnp.float32),
                       cache_krope.astype(jnp.float32))
    s = s * scale
    valid = kv_positions < cur_len
    s = jnp.where(valid[None, None, :], s, NEG_INF)
    m = s.max(axis=-1)
    ptab = jnp.exp(s - m[..., None])
    ptab = jnp.where((m > NEG_INF / 2)[..., None], ptab, 0.0)
    l = ptab.sum(axis=-1)
    o_t = jnp.einsum("bht,btr->bhr", ptab, cache_ckv.astype(jnp.float32))
    return o_t, m, l


def mla_paged_flash_decode_partial(
    q_abs, q_rope, ckv_pool, krope_pool, block_table, page_counts, *,
    scale: float,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Split-operand paged partial decode (XLA gather reference).

    q_abs: (B,H,r) fp32; q_rope: (B,H,rope); ckv_pool: (n_pages, ps,
    r); krope_pool: (n_pages, ps, rope); block_table / page_counts:
    (B, max_pages) int32 (count 0 masks a page completely — length
    overrun, unallocated entry, or a page owned by another shard).
    Gathers ONLY the tables' pages of the two pools — the concat-MQA
    view instead copied the whole pool into k_cat/v_cat every step.
    Returns fp32 (o_tilde (B,H,r), m (B,H), l (B,H)).
    """
    B, H, r = q_abs.shape
    n_pages, ps, _ = ckv_pool.shape
    J = block_table.shape[1]
    tbl = jnp.clip(block_table, 0, n_pages - 1)
    ckv = ckv_pool[tbl].reshape(B, J * ps, r)
    kr = krope_pool[tbl].reshape(B, J * ps, krope_pool.shape[2])
    valid = (jnp.arange(ps)[None, None, :]
             < page_counts[..., None]).reshape(B, J * ps)
    s = jnp.einsum("bhr,btr->bht", q_abs, ckv.astype(jnp.float32))
    s = s + jnp.einsum("bhk,btk->bht", q_rope.astype(jnp.float32),
                       kr.astype(jnp.float32))
    s = s * scale
    s = jnp.where(valid[:, None, :], s, NEG_INF)
    m = s.max(axis=-1)
    ptab = jnp.exp(s - m[..., None])
    ptab = jnp.where((m > NEG_INF / 2)[..., None], ptab, 0.0)
    l = ptab.sum(axis=-1)
    o_t = jnp.einsum("bht,btr->bhr", ptab, ckv.astype(jnp.float32))
    return o_t, m, l


def mla_decode_partial(
    p, q_nope, q_rope, cache_ckv, cache_krope, kv_positions, cur_len, cfg
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Absorbed-form partial decode vs a (possibly sharded) latent cache.

    q_nope: (B,H,nope); q_rope: (B,H,rope)
    cache_ckv: (B,T,r); cache_krope: (B,T,rope)
    Returns (o_tilde (B,H,r), m (B,H), l (B,H)) — combined via pmax/psum.
    This is the ``decode_partial_mla`` registry op's reference
    formulation with the wk_b fold applied — no longer a private path.
    """
    q_abs, q_rope, scale = mla_absorbed_queries(p, q_nope, q_rope, cfg)
    return mla_flash_decode_partial(q_abs, q_rope, cache_ckv,
                                    cache_krope, kv_positions, cur_len,
                                    scale=scale)


# Registered split-operand decode contract (dist.decode combines the
# partials across sequence shards): (q_abs (B,H,r) fp32, q_rope
# (B,H,rope), c_kv/k_rope caches, cur_len) -> fp32 (o_tilde, m, l).

@D.register("decode_partial_mla", "xla")
def _decode_partial_mla_xla(q_abs, q_rope, c_kv, k_rope, cur_len,
                            pos0=0, *, scale, tune=True):
    T = c_kv.shape[1]
    return mla_flash_decode_partial(q_abs, q_rope, c_kv, k_rope,
                                    pos0 + jnp.arange(T), cur_len,
                                    scale=scale)


@D.register("decode_partial_mla", "pallas")
def _decode_partial_mla_pallas(q_abs, q_rope, c_kv, k_rope, cur_len,
                               pos0=0, *, scale, tune=True):
    from repro.kernels import autotune, ops
    if tune:
        return ops.vwr_mla_flash_decode(q_abs, q_rope, c_kv, k_rope,
                                        cur_len, pos0=pos0, scale=scale)
    # tune=False (shard_map tracing): block size from the cost-model
    # prior only — the measuring tuner must not fire inside shard_map
    T, r = c_kv.shape[1], c_kv.shape[2]
    rope = k_rope.shape[2]
    dtype = str(c_kv.dtype)
    cands = autotune.decode_candidates(T, r + rope, dtype)
    bkv = min(cands, key=lambda c: autotune.decode_prior(
        q_abs.shape[0], T, q_abs.shape[1], 1, r + rope, dtype, c))[0]
    return ops.vwr_mla_flash_decode(q_abs, q_rope, c_kv, k_rope,
                                    cur_len, pos0=pos0, scale=scale,
                                    bkv=bkv)


@D.register("decode_partial_mla_paged", "xla")
def _decode_partial_mla_paged_xla(q_abs, q_rope, ckv_pool, krope_pool,
                                  table, counts, *, scale,
                                  page_size=None, max_pages=None,
                                  tune=True):
    return mla_paged_flash_decode_partial(q_abs, q_rope, ckv_pool,
                                          krope_pool, table, counts,
                                          scale=scale)


@D.register("decode_partial_mla_paged", "pallas")
def _decode_partial_mla_paged_pallas(q_abs, q_rope, ckv_pool,
                                     krope_pool, table, counts, *,
                                     scale, page_size=None,
                                     max_pages=None, tune=True):
    from repro.kernels import ops
    return ops.vwr_mla_paged_flash_decode(q_abs, q_rope, ckv_pool,
                                          krope_pool, table, counts,
                                          scale=scale)


# q8 split-operand decode: int8 latent caches with fp32 scale
# sidecars.  Per-sequence scales for the dense cache (ckv_scale /
# krope_scale (B,)), per-page scales for the pools ((n_pages,)).  The
# latent channel and the rope channel quantize independently — their
# dynamic ranges differ by the rope rotation — and both dots hoist
# the scale out of the int8 contraction exactly (per-block-constant
# scale commutes with the reduction), so drift vs the bf16 path is
# rounding-only.

@D.register("decode_partial_mla_q8", "xla")
def _decode_partial_mla_q8_xla(q_abs, q_rope, c_kv, k_rope, ckv_scale,
                               krope_scale, cur_len, pos0=0, *, scale,
                               tune=True):
    T = c_kv.shape[1]
    ckv = c_kv.astype(jnp.float32) * ckv_scale[:, None, None]
    kr = k_rope.astype(jnp.float32) * krope_scale[:, None, None]
    return mla_flash_decode_partial(q_abs, q_rope, ckv, kr,
                                    pos0 + jnp.arange(T), cur_len,
                                    scale=scale)


@D.register("decode_partial_mla_q8", "pallas")
def _decode_partial_mla_q8_pallas(q_abs, q_rope, c_kv, k_rope,
                                  ckv_scale, krope_scale, cur_len,
                                  pos0=0, *, scale, tune=True):
    from repro.kernels import autotune, ops
    if tune:
        return ops.vwr_mla_flash_decode_q8(q_abs, q_rope, c_kv, k_rope,
                                           ckv_scale, krope_scale,
                                           cur_len, pos0=pos0,
                                           scale=scale)
    T, r = c_kv.shape[1], c_kv.shape[2]
    rope = k_rope.shape[2]
    cands = autotune.decode_candidates(T, r + rope, "int8")
    bkv = min(cands, key=lambda c: autotune.decode_prior(
        q_abs.shape[0], T, q_abs.shape[1], 1, r + rope, "int8", c))[0]
    return ops.vwr_mla_flash_decode_q8(q_abs, q_rope, c_kv, k_rope,
                                       ckv_scale, krope_scale, cur_len,
                                       pos0=pos0, scale=scale, bkv=bkv)


@D.register("decode_partial_mla_paged_q8", "xla")
def _decode_partial_mla_paged_q8_xla(q_abs, q_rope, ckv_pool,
                                     krope_pool, ckv_scale,
                                     krope_scale, table, counts, *,
                                     scale, page_size=None,
                                     max_pages=None, tune=True):
    ckv = ckv_pool.astype(jnp.float32) * ckv_scale[:, None, None]
    kr = krope_pool.astype(jnp.float32) * krope_scale[:, None, None]
    return mla_paged_flash_decode_partial(q_abs, q_rope, ckv, kr,
                                          table, counts, scale=scale)


@D.register("decode_partial_mla_paged_q8", "pallas")
def _decode_partial_mla_paged_q8_pallas(q_abs, q_rope, ckv_pool,
                                        krope_pool, ckv_scale,
                                        krope_scale, table, counts, *,
                                        scale, page_size=None,
                                        max_pages=None, tune=True):
    from repro.kernels import ops
    return ops.vwr_mla_paged_flash_decode_q8(q_abs, q_rope, ckv_pool,
                                             krope_pool, ckv_scale,
                                             krope_scale, table,
                                             counts, scale=scale)


# ---------------- chunked prefill (absorbed chunk vs latent pools) ------------
#
# The MLA sibling of ``attention.chunk_prefix_attend_partial``: an
# absorbed (C, H, r) query chunk against the latent page pools over the
# chunk's PRIOR pages.  Returns latent-space fp32 partials
# (o_tilde (C,H,r), m (C,H), l (C,H)); the within-chunk causal block
# and ``mla_decode_finish`` live downstream.

def mla_chunk_prefix_attend_partial(q_abs, q_rope, ckv_pool,
                                    krope_pool, table, counts, *,
                                    scale):
    """XLA gather reference for the MLA chunk-prefix contract.
    table/counts: (J,) prior pages + per-page valid counts."""
    C, H, r = q_abs.shape
    n_pages, ps, _ = ckv_pool.shape
    J = table.shape[0]
    tbl = jnp.clip(table, 0, n_pages - 1)
    ckv = ckv_pool[tbl].reshape(J * ps, r)
    kr = krope_pool[tbl].reshape(J * ps, krope_pool.shape[2])
    valid = (jnp.arange(ps)[None, :] < counts[:, None]).reshape(J * ps)
    qa = q_abs.astype(jnp.float32) * scale
    qr = q_rope.astype(jnp.float32) * scale
    s = jnp.einsum("chr,tr->cht", qa, ckv.astype(jnp.float32))
    s = s + jnp.einsum("chr,tr->cht", qr, kr.astype(jnp.float32))
    s = jnp.where(valid[None, None, :], s, NEG_INF)
    m = s.max(axis=-1)
    p = jnp.exp(s - m[..., None])
    p = jnp.where((m > NEG_INF / 2)[..., None], p, 0.0)
    l = p.sum(axis=-1)
    o_t = jnp.einsum("cht,tr->chr", p, ckv.astype(jnp.float32))
    return o_t, m, l


@D.register("chunk_prefix_mla_paged", "xla")
def _chunk_prefix_mla_paged_xla(q_abs, q_rope, ckv_pool, krope_pool,
                                table, counts, *, scale,
                                page_size=None, max_pages=None,
                                tune=True):
    return mla_chunk_prefix_attend_partial(q_abs, q_rope, ckv_pool,
                                           krope_pool, table, counts,
                                           scale=scale)


@D.register("chunk_prefix_mla_paged", "pallas")
def _chunk_prefix_mla_paged_pallas(q_abs, q_rope, ckv_pool, krope_pool,
                                   table, counts, *, scale,
                                   page_size=None, max_pages=None,
                                   tune=True):
    from repro.kernels import ops
    return ops.vwr_mla_chunk_prefix_attend(q_abs, q_rope, ckv_pool,
                                           krope_pool, table, counts,
                                           scale=scale)


@D.register("chunk_prefix_mla_paged_q8", "xla")
def _chunk_prefix_mla_paged_q8_xla(q_abs, q_rope, ckv_pool, krope_pool,
                                   ckv_scale, krope_scale, table,
                                   counts, *, scale, page_size=None,
                                   max_pages=None, tune=True):
    ckv = ckv_pool.astype(jnp.float32) * ckv_scale[:, None, None]
    kr = krope_pool.astype(jnp.float32) * krope_scale[:, None, None]
    return mla_chunk_prefix_attend_partial(q_abs, q_rope, ckv, kr,
                                           table, counts, scale=scale)


@D.register("chunk_prefix_mla_paged_q8", "pallas")
def _chunk_prefix_mla_paged_q8_pallas(q_abs, q_rope, ckv_pool,
                                      krope_pool, ckv_scale,
                                      krope_scale, table, counts, *,
                                      scale, page_size=None,
                                      max_pages=None, tune=True):
    from repro.kernels import ops
    return ops.vwr_mla_chunk_prefix_attend_q8(q_abs, q_rope, ckv_pool,
                                              krope_pool, ckv_scale,
                                              krope_scale, table,
                                              counts, scale=scale)


def mla_absorbed_mqa(p, q_nope, q_rope, cache_ckv, cache_krope, cfg):
    """Absorbed MLA decode as an MQA flash-decode problem.

    Folding q_nope through wk_b makes scores a plain dot product
    against the latent cache, and concatenating the latent and rope-key
    caches along the feature dim makes it *literally* the GQA decode
    contract with KV=1:

        s   = [q_abs, q_rope] . [c_kv, k_rope]   (one shared KV head)
        o~  = p . [c_kv, 0]                       (values = latent part)

    so MLA decode *can* run the very same ``decode_partial`` registry
    op and ``dist.decode`` combine as GQA.  The price of the uniform
    surface: the value stream is zero-padded by rope_head_dim (64/576
    ≈ 11% for V3), and the two concats *materialize* k_cat/v_cat
    copies of the cache each step (the concat operands feeding
    pallas_call/shard_map are not fusion-eliminated), so per-token
    cache bytes are 2*(r+rope) features/position instead of r+rope.

    The production decode path therefore no longer uses this view: the
    split-operand ``decode_partial_mla`` / ``decode_partial_mla_paged``
    ops take the latent and rope caches as SEPARATE operands and stage
    only live bytes.  This concatenated view is kept as the equivalence
    reference — the split-vs-concat bit-exactness tests and the
    ``mla_concat`` benchmark rows are built on it.

    q_nope: (B,H,nope); q_rope: (B,H,rope); cache_ckv: (B,T,r);
    cache_krope: (B,T,rope).  Returns (q_cat (B,H,r+rope) f32 —
    pre-scaled so the kernel's 1/sqrt(Dh) equals the absorbed-MLA
    1/sqrt(nope+rope) — k_cat, v_cat (B,T,1,r+rope), r).
    """
    m = cfg.mla
    r = m.kv_lora_rank
    q_abs = jnp.einsum("bhk,rhk->bhr", q_nope.astype(jnp.float32),
                       p["wk_b"].astype(jnp.float32))
    Dc = r + m.rope_head_dim
    scale_fix = (Dc ** 0.5) / ((m.nope_head_dim + m.rope_head_dim) ** 0.5)
    q_cat = jnp.concatenate([q_abs, q_rope.astype(jnp.float32)],
                            axis=-1) * scale_fix
    k_cat = jnp.concatenate([cache_ckv, cache_krope], axis=-1)[:, :, None]
    v_cat = jnp.concatenate([cache_ckv, jnp.zeros_like(cache_krope)],
                            axis=-1)[:, :, None]
    return q_cat, k_cat, v_cat, r


def mla_concat_view(q_abs, q_rope, c_kv, k_rope, scale: float, *,
                    paged: bool = False):
    """Concatenated k_cat/v_cat view of the SPLIT decode operands —
    equivalence reference only (tests, ``mla_concat`` benchmark rows).

    q_abs: (B,H,r) fp32; q_rope: (B,H,rope); c_kv / k_rope: the latent
    and rope caches with trailing feature dims — dense ``(B,T,...)``
    or paged ``(n_pages, ps, ...)`` (``paged=True``).  Returns
    (q_cat, k_cat, v_cat, r): q_cat is pre-scaled by
    ``scale * sqrt(Dc)`` so the plain decode ops' 1/sqrt(Dc) nets to
    the absorbed-MLA ``scale``; k_cat/v_cat grow a KV=1 head axis —
    ``(B, T, 1, Dc)`` dense, head-major ``(n_pages, 1, ps, Dc)`` paged
    — and v_cat zero-pads the rope features.  Every site pinning
    split-vs-concat equivalence must build the concat side HERE so the
    baselines cannot drift apart."""
    r = c_kv.shape[-1]
    Dc = r + k_rope.shape[-1]
    q_cat = jnp.concatenate([q_abs, q_rope], -1) * (scale * Dc ** 0.5)
    head = 1 if paged else -2
    k_cat = jnp.expand_dims(jnp.concatenate([c_kv, k_rope], -1), head)
    v_cat = jnp.expand_dims(
        jnp.concatenate([c_kv, jnp.zeros_like(k_rope)], -1), head)
    return q_cat, k_cat, v_cat, r


def mla_decode_finish(p, o_latent, cfg):
    """(B,H,r) normalized latent attention output -> (B,d_model)."""
    o = jnp.einsum("bhr,rhk->bhk", o_latent, p["wv_b"].astype(o_latent.dtype))
    return jnp.einsum("bhk,hkd->bd", o, p["wo"].astype(o_latent.dtype))
