"""Distributed FlashDecoding: sequence-sharded KV cache decode.

Decode attention at long context is the purest form of the paper's
streaming workload: the KV cache is read once per generated token with
zero reuse, so the byte path — not FLOPs — sets the latency.  Moving
the cache across the interconnect would put those bytes on the *global*
tier; instead each model shard keeps a contiguous slab of the context
resident in its own HBM, computes an **unnormalized** online-softmax
partial against its slab, and only the (B, H)-sized running statistics
cross the wire:

    m* = pmax_i m_i
    o  = sum_i o~_i * exp(m_i - m*)  /  sum_i l_i * exp(m_i - m*)

(`models.attention.flash_decode_partial` documents the same contract
from the single-shard side.)  Collective bytes per token are
O(B * H * (Dh + 2)) — independent of context length.

Per shard the partial comes from the kernel-dispatch registry
(``repro.kernels.dispatch``): backend 'xla' is the einsum reference,
'pallas' the VWR flash-decode kernel staging the local slab in wide
(bkv x Dh) VMEM blocks, 'auto' the measured winner.  GQA and encoder
cross-attention decode through ``decode_partial`` /
``decode_partial_paged``; absorbed MLA decodes through the
split-operand ``decode_partial_mla`` / ``decode_partial_mla_paged``
ops (latent + rope caches as separate operands — no k_cat/v_cat
copies, no rope zero-pad in the value stream), all sharing the one
pmax/psum statistics combine.

The mesh is an **explicit argument** everywhere here; ``decode_attend``
falls back to the ambient ``with mesh:`` context only through the
deprecated ``hints.resolve_mesh`` shim.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as PS

from repro.common.hints import resolve_mesh
from repro.kernels import dispatch as D
from repro.models.attention import decode_attend_local  # noqa: F401  (re-export)


def _normalize(o_t, l, dtype):
    return (o_t / jnp.maximum(l, 1e-30)[..., None]).astype(dtype)


def _combine_partials(o_t, m, l, axis_name, dtype):
    """pmax/psum statistics combine shared by every sharded decode
    route (must run inside shard_map over ``axis_name``); named scope
    ``attn.combine``."""
    with jax.named_scope("attn.combine"):
        m_star = jax.lax.pmax(m, axis_name)
        s = jnp.exp(m - m_star)                           # (B, H)
        o = jax.lax.psum(o_t * s[..., None], axis_name)
        l = jax.lax.psum(l * s, axis_name)
        return _normalize(o, l, dtype)


def local_decode_attend(q, cache_k, cache_v, cur_len, *,
                        k_scale=None, v_scale=None,
                        backend="xla") -> jax.Array:
    """Single-shard decode attention (normalized) through the dispatch
    registry.

    Passing ``k_scale``/``v_scale`` ((B, KV) fp32) selects the q8 op:
    ``cache_k``/``cache_v`` are int8 and dequantize inside the kernel.
    """
    if k_scale is not None:
        o_t, m, l = D.dispatch("decode_partial_q8", backend, q, cache_k,
                               cache_v, k_scale, v_scale, cur_len)
    else:
        o_t, m, l = D.dispatch("decode_partial", backend, q, cache_k,
                               cache_v, cur_len)
    return _normalize(o_t, l, q.dtype)


def sharded_flash_decode(mesh, q, cache_k, cache_v, cur_len, *,
                         k_scale=None, v_scale=None,
                         backend: str = "xla",
                         data_axis: str = "data",
                         model_axis: str = "model",
                         kernel_impl: Optional[str] = None):
    """Decode attention with the cache sequence-sharded over
    ``model_axis`` and the batch over ``data_axis``.

    q: (B, H, Dh) one new token; cache_k/v: (B, T, KV, Dh);
    cur_len: scalar count of valid positions (global).  Returns the
    normalized (B, H, Dh) context, bitwise-equivalent (up to fp
    reassociation) to the single-shard path on the unsharded cache.
    With ``k_scale``/``v_scale`` ((B, KV) fp32, replicated over the
    model axis — one scale covers the whole sequence) the caches are
    int8 and decode through the q8 op.  ``kernel_impl`` is a
    deprecated alias for ``backend``.
    """
    if kernel_impl is not None:
        D.warn_kernel_impl_kwarg("dist.decode.sharded_flash_decode")
        backend = kernel_impl
    q8 = k_scale is not None
    op = "decode_partial_q8" if q8 else "decode_partial"
    # 'auto' resolves HERE, outside shard_map, by cache lookup only
    # (replaying a winner the local decode path measured for these
    # shapes, if any): the measuring dispatch tuner — like the block
    # tuner, hence tune=False below — must not run timed kernels
    # inside shard_map tracing
    sig = ((q, cache_k, cache_v, k_scale, v_scale, cur_len) if q8
           else (q, cache_k, cache_v, cur_len))
    backend = D.cached_backend(op, backend, sig)
    B, H, Dh = q.shape
    T = cache_k.shape[1]
    msize = mesh.shape.get(model_axis, 1) if model_axis else 1
    if model_axis not in mesh.axis_names or T % msize:
        # no model axis / ragged split: single-shard reference
        return local_decode_attend(q, cache_k, cache_v, cur_len,
                                   k_scale=k_scale, v_scale=v_scale,
                                   backend=backend)
    n_local = T // msize
    dsize = mesh.shape.get(data_axis, 1)
    dp = (data_axis if data_axis in mesh.axis_names
          and B % max(dsize, 1) == 0 else None)

    def shard_fn(q, k, v, *rest):
        cur = rest[-1]
        pos0 = jax.lax.axis_index(model_axis) * n_local
        o_t, m, l = D.dispatch(op, backend, q, k, v, *rest[:-1], cur,
                               pos0, tune=False)
        return _combine_partials(o_t, m, l, model_axis, q.dtype)

    scale_specs = (PS(dp, None), PS(dp, None)) if q8 else ()
    scale_args = (k_scale, v_scale) if q8 else ()
    fn = jax.shard_map(
        shard_fn, mesh=mesh,
        in_specs=(PS(dp, None, None),
                  PS(dp, model_axis, None, None),
                  PS(dp, model_axis, None, None))
                 + scale_specs + (PS(),),
        out_specs=PS(dp, None, None),
        # the psum/pmax combine replicates the output over the model
        # axis by construction, but the varying-axes check has no rule
        # for pallas_call — disable the static check, not the path
        check_vma=False)
    return fn(q, cache_k, cache_v, *scale_args,
              jnp.asarray(cur_len, jnp.int32).reshape(()))


def sharded_mla_flash_decode(mesh, q_abs, q_rope, cache_ckv,
                             cache_krope, cur_len, *, scale: float,
                             ckv_scale=None, krope_scale=None,
                             backend: str = "xla",
                             data_axis: str = "data",
                             model_axis: str = "model"):
    """Split-operand absorbed-MLA decode with BOTH latent caches
    sequence-sharded over ``model_axis`` and the batch over
    ``data_axis``.

    q_abs: (B, H, r) fp32 (pre-folded through wk_b); q_rope: (B, H,
    rope); cache_ckv: (B, T, r); cache_krope: (B, T, rope); cur_len:
    scalar global valid count.  Each shard computes the unnormalized
    partial against its slab through the ``decode_partial_mla``
    registry op — latent and rope operands stay separate all the way
    into the kernel, so no shard ever materializes k_cat/v_cat copies
    — and the same pmax/psum statistics combine as
    ``sharded_flash_decode`` stitches the softmax.  With
    ``ckv_scale``/``krope_scale`` ((B,) fp32, replicated over the
    model axis) the caches are int8 q8.  Returns the normalized
    (B, H, r) latent context."""
    q8 = ckv_scale is not None
    op = "decode_partial_mla_q8" if q8 else "decode_partial_mla"
    sig = ((q_abs, q_rope, cache_ckv, cache_krope, ckv_scale,
            krope_scale, cur_len) if q8
           else (q_abs, q_rope, cache_ckv, cache_krope, cur_len))
    backend = D.cached_backend(op, backend, sig, {"scale": scale})
    B, H, r = q_abs.shape
    T = cache_ckv.shape[1]
    msize = mesh.shape.get(model_axis, 1) if model_axis else 1
    if model_axis not in mesh.axis_names or T % msize:
        return local_mla_decode_attend(q_abs, q_rope, cache_ckv,
                                       cache_krope, cur_len,
                                       scale=scale,
                                       ckv_scale=ckv_scale,
                                       krope_scale=krope_scale,
                                       backend=backend)
    n_local = T // msize
    dsize = mesh.shape.get(data_axis, 1)
    dp = (data_axis if data_axis in mesh.axis_names
          and B % max(dsize, 1) == 0 else None)

    def shard_fn(qa, qr, ckv, kr, *rest):
        cur = rest[-1]
        pos0 = jax.lax.axis_index(model_axis) * n_local
        o_t, m, l = D.dispatch(op, backend, qa, qr, ckv, kr,
                               *rest[:-1], cur, pos0, scale=scale,
                               tune=False)
        return _combine_partials(o_t, m, l, model_axis, qa.dtype)

    scale_specs = (PS(dp), PS(dp)) if q8 else ()
    scale_args = (ckv_scale, krope_scale) if q8 else ()
    fn = jax.shard_map(
        shard_fn, mesh=mesh,
        in_specs=(PS(dp, None, None),
                  PS(dp, None, None),
                  PS(dp, model_axis, None),
                  PS(dp, model_axis, None))
                 + scale_specs + (PS(),),
        out_specs=PS(dp, None, None),
        check_vma=False)
    return fn(q_abs, q_rope, cache_ckv, cache_krope, *scale_args,
              jnp.asarray(cur_len, jnp.int32).reshape(()))


def local_mla_decode_attend(q_abs, q_rope, cache_ckv, cache_krope,
                            cur_len, *, scale: float,
                            ckv_scale=None, krope_scale=None,
                            backend="xla") -> jax.Array:
    """Single-shard split-operand MLA decode attention (normalized
    (B, H, r) latent context) through the dispatch registry.

    ``ckv_scale``/``krope_scale`` ((B,) fp32) select the q8 op over
    int8 latent caches."""
    if ckv_scale is not None:
        o_t, m, l = D.dispatch("decode_partial_mla_q8", backend, q_abs,
                               q_rope, cache_ckv, cache_krope,
                               ckv_scale, krope_scale, cur_len,
                               scale=scale)
    else:
        o_t, m, l = D.dispatch("decode_partial_mla", backend, q_abs,
                               q_rope, cache_ckv, cache_krope, cur_len,
                               scale=scale)
    return _normalize(o_t, l, q_abs.dtype)


def mla_decode_attend(q_abs, q_rope, cache_ckv, cache_krope, cur_len, *,
                      scale: float, ckv_scale=None, krope_scale=None,
                      backend: str = "xla", mesh=None,
                      seq_shard: bool = True) -> jax.Array:
    """Mesh-aware split-operand MLA decode attention used by
    ``models.lm``.

    The MLA sibling of ``decode_attend``: routes to
    ``sharded_mla_flash_decode`` when ``seq_shard`` and a mesh with a
    'model' axis divides the cache evenly, else the local registry op.
    The latent and rope caches ride as separate operands end to end —
    the copy-free replacement for the concatenated
    ``mla_absorbed_mqa`` + ``decode_attend`` route.
    """
    if seq_shard:
        mesh = resolve_mesh(mesh, "dist.decode.mla_decode_attend")
        T = cache_ckv.shape[1]
        if (mesh is not None and "model" in mesh.axis_names
                and T % mesh.shape["model"] == 0):
            return sharded_mla_flash_decode(mesh, q_abs, q_rope,
                                            cache_ckv, cache_krope,
                                            cur_len, scale=scale,
                                            ckv_scale=ckv_scale,
                                            krope_scale=krope_scale,
                                            backend=backend)
    return local_mla_decode_attend(q_abs, q_rope, cache_ckv,
                                   cache_krope, cur_len, scale=scale,
                                   ckv_scale=ckv_scale,
                                   krope_scale=krope_scale,
                                   backend=backend)


def _page_counts(lens, J, page_size):
    """(B,) valid-position counts -> (B, J) per-logical-page counts."""
    return jnp.clip(lens[:, None]
                    - jnp.arange(J, dtype=jnp.int32)[None, :] * page_size,
                    0, page_size).astype(jnp.int32)


def local_paged_decode_attend(q, k_pool, v_pool, table, lens, *,
                              k_scale=None, v_scale=None,
                              backend="xla") -> jax.Array:
    """Single-shard paged decode attention (normalized).

    q: (B, H, Dh); k_pool/v_pool: head-major (n_pages, KV, page_size,
    Dh); table: (B, max_pages) int32; lens: (B,) int32 valid positions
    per slot (0 = inactive slot -> zero output).  ``k_scale``/
    ``v_scale`` ((n_pages, KV) fp32 per-page per-head sidecars) select
    the q8 op over int8 pools."""
    ps = k_pool.shape[2]
    J = table.shape[1]
    counts = _page_counts(lens, J, ps)
    # page_size/max_pages ride as static kwargs so the page geometry
    # is an EXPLICIT part of the dispatch cache key (see the note at
    # the registered impls in models/attention.py)
    if k_scale is not None:
        o_t, m, l = D.dispatch("decode_partial_paged_q8", backend, q,
                               k_pool, v_pool, k_scale, v_scale, table,
                               counts, page_size=ps, max_pages=J)
    else:
        o_t, m, l = D.dispatch("decode_partial_paged", backend, q,
                               k_pool, v_pool, table, counts,
                               page_size=ps, max_pages=J)
    return _normalize(o_t, l, q.dtype)


def sharded_paged_flash_decode(mesh, q, k_pool, v_pool, table, lens, *,
                               k_scale=None, v_scale=None,
                               backend: str = "xla",
                               data_axis: str = "data",
                               model_axis: str = "model"):
    """Paged decode attention with the page POOL sharded over
    ``model_axis`` (shard s owns the contiguous slab of pages
    [s*pp, (s+1)*pp)) and the slot batch over ``data_axis``.

    Block tables are replicated and may point at any shard's pages:
    each shard zeroes the counts of pages outside its slab, computes
    the unnormalized partial over the pages it owns, and the same
    pmax/psum statistics combine as ``sharded_flash_decode`` stitches
    the slots back together — so page->shard placement is free (the
    allocator never needs to know the mesh).  Per-token collective
    bytes stay O(B * H * (Dh + 2)), independent of pool size.

    ``k_scale``/``v_scale`` ((n_pages, KV) fp32) select the q8 op over
    int8 pools; the sidecars shard on their leading page dim exactly
    like the pools, so each shard dequantizes its own pages locally.
    """
    n_pages, ps = k_pool.shape[0], k_pool.shape[2]
    J = table.shape[1]
    q8 = k_scale is not None
    op = "decode_partial_paged_q8" if q8 else "decode_partial_paged"
    # cache lookup under the same signature the LOCAL measuring path
    # writes — (B, J) counts, not (B,) lens — plus the page geometry
    # statics, so a winner measured locally replays here and a winner
    # from another (page_size, max_pages) does not
    counts_sig = _page_counts(lens, J, ps)
    sig = ((q, k_pool, v_pool, k_scale, v_scale, table, counts_sig)
           if q8 else (q, k_pool, v_pool, table, counts_sig))
    backend = D.cached_backend(op, backend,
                               sig, {"page_size": ps, "max_pages": J})
    msize = mesh.shape.get(model_axis, 1) if model_axis else 1
    if model_axis not in mesh.axis_names or n_pages % msize:
        return local_paged_decode_attend(q, k_pool, v_pool, table, lens,
                                         k_scale=k_scale,
                                         v_scale=v_scale,
                                         backend=backend)
    pp = n_pages // msize
    B = q.shape[0]
    dsize = mesh.shape.get(data_axis, 1)
    dp = (data_axis if data_axis in mesh.axis_names
          and B % max(dsize, 1) == 0 else None)

    def shard_fn(q, kp, vp, *rest):
        tbl, lens = rest[-2], rest[-1]
        p0 = jax.lax.axis_index(model_axis) * pp
        owned = (tbl >= p0) & (tbl < p0 + pp)
        tloc = jnp.clip(tbl - p0, 0, pp - 1)
        counts = jnp.where(owned, _page_counts(lens, J, ps), 0)
        o_t, m, l = D.dispatch(op, backend, q, kp, vp, *rest[:-2],
                               tloc, counts, page_size=ps,
                               max_pages=J, tune=False)
        return _combine_partials(o_t, m, l, model_axis, q.dtype)

    scale_specs = ((PS(model_axis, None), PS(model_axis, None))
                   if q8 else ())
    scale_args = (k_scale, v_scale) if q8 else ()
    # every shard attends with every head: the head-sharded query is
    # gathered here, under a scope of its own
    with jax.named_scope("attn.q_gather"):
        q = jax.lax.with_sharding_constraint(
            q, NamedSharding(mesh, PS(dp, None, None)))
    fn = jax.shard_map(
        shard_fn, mesh=mesh,
        in_specs=(PS(dp, None, None),
                  PS(model_axis, None, None, None),
                  PS(model_axis, None, None, None))
                 + scale_specs + (PS(dp, None), PS(dp)),
        out_specs=PS(dp, None, None),
        check_vma=False)
    return fn(q, k_pool, v_pool, *scale_args,
              table.astype(jnp.int32), jnp.asarray(lens, jnp.int32))


def paged_decode_attend(q, k_pool, v_pool, table, lens, *,
                        k_scale=None, v_scale=None,
                        backend: str = "xla", mesh=None,
                        seq_shard: bool = True) -> jax.Array:
    """Mesh-aware paged decode attention used by ``models.lm``.

    The paged sibling of ``decode_attend``: routes to
    ``sharded_paged_flash_decode`` when ``seq_shard`` and a mesh with a
    'model' axis divides the pool evenly, else the local registry op.
    ``k_scale``/``v_scale`` select the q8 (int8 pools) route.
    """
    if seq_shard:
        mesh = resolve_mesh(mesh, "dist.decode.paged_decode_attend")
        n_pages = k_pool.shape[0]
        if (mesh is not None and "model" in mesh.axis_names
                and n_pages % mesh.shape["model"] == 0):
            return sharded_paged_flash_decode(mesh, q, k_pool, v_pool,
                                              table, lens,
                                              k_scale=k_scale,
                                              v_scale=v_scale,
                                              backend=backend)
    return local_paged_decode_attend(q, k_pool, v_pool, table, lens,
                                     k_scale=k_scale, v_scale=v_scale,
                                     backend=backend)


def local_mla_paged_decode_attend(q_abs, q_rope, ckv_pool, krope_pool,
                                  table, lens, *, scale: float,
                                  ckv_scale=None, krope_scale=None,
                                  backend="xla") -> jax.Array:
    """Single-shard split-operand paged MLA decode attention
    (normalized (B, H, r) latent context).

    q_abs: (B, H, r) fp32; q_rope: (B, H, rope); ckv_pool: (n_pages,
    page_size, r); krope_pool: (n_pages, page_size, rope); table:
    (B, max_pages) int32; lens: (B,) int32 valid positions per slot.
    ``ckv_scale``/``krope_scale`` ((n_pages,) fp32 per-page sidecars)
    select the q8 op over int8 pools."""
    ps = ckv_pool.shape[1]
    J = table.shape[1]
    counts = _page_counts(lens, J, ps)
    if ckv_scale is not None:
        o_t, m, l = D.dispatch("decode_partial_mla_paged_q8", backend,
                               q_abs, q_rope, ckv_pool, krope_pool,
                               ckv_scale, krope_scale, table, counts,
                               scale=scale, page_size=ps, max_pages=J)
    else:
        o_t, m, l = D.dispatch("decode_partial_mla_paged", backend,
                               q_abs, q_rope, ckv_pool, krope_pool,
                               table, counts, scale=scale,
                               page_size=ps, max_pages=J)
    return _normalize(o_t, l, q_abs.dtype)


def sharded_mla_paged_flash_decode(mesh, q_abs, q_rope, ckv_pool,
                                   krope_pool, table, lens, *,
                                   scale: float, ckv_scale=None,
                                   krope_scale=None,
                                   backend: str = "xla",
                                   data_axis: str = "data",
                                   model_axis: str = "model"):
    """Split-operand paged MLA decode with BOTH latent pools sharded
    over ``model_axis`` (shard s owns pages [s*pp, (s+1)*pp)) and the
    slot batch over ``data_axis``.

    Same ownership-masked-counts construction as
    ``sharded_paged_flash_decode`` — block tables are replicated, each
    shard zeroes the counts of foreign pages and the pmax/psum
    statistics combine stitches the slots — so page->shard placement
    stays free, and no shard ever builds a pool-wide k_cat/v_cat copy.

    ``ckv_scale``/``krope_scale`` ((n_pages,) fp32) select the q8 op
    over int8 pools; the sidecars shard on the page dim exactly like
    the pools.
    """
    n_pages, ps = ckv_pool.shape[0], ckv_pool.shape[1]
    J = table.shape[1]
    q8 = ckv_scale is not None
    op = ("decode_partial_mla_paged_q8" if q8
          else "decode_partial_mla_paged")
    counts_sig = _page_counts(lens, J, ps)
    sig = ((q_abs, q_rope, ckv_pool, krope_pool, ckv_scale,
            krope_scale, table, counts_sig) if q8
           else (q_abs, q_rope, ckv_pool, krope_pool, table,
                 counts_sig))
    backend = D.cached_backend(
        op, backend, sig,
        {"scale": scale, "page_size": ps, "max_pages": J})
    msize = mesh.shape.get(model_axis, 1) if model_axis else 1
    if model_axis not in mesh.axis_names or n_pages % msize:
        return local_mla_paged_decode_attend(q_abs, q_rope, ckv_pool,
                                             krope_pool, table, lens,
                                             scale=scale,
                                             ckv_scale=ckv_scale,
                                             krope_scale=krope_scale,
                                             backend=backend)
    pp = n_pages // msize
    B = q_abs.shape[0]
    dsize = mesh.shape.get(data_axis, 1)
    dp = (data_axis if data_axis in mesh.axis_names
          and B % max(dsize, 1) == 0 else None)

    def shard_fn(qa, qr, ckv, kr, *rest):
        tbl, lens = rest[-2], rest[-1]
        p0 = jax.lax.axis_index(model_axis) * pp
        owned = (tbl >= p0) & (tbl < p0 + pp)
        tloc = jnp.clip(tbl - p0, 0, pp - 1)
        counts = jnp.where(owned, _page_counts(lens, J, ps), 0)
        o_t, m, l = D.dispatch(op, backend, qa, qr, ckv, kr,
                               *rest[:-2], tloc, counts, scale=scale,
                               page_size=ps, max_pages=J, tune=False)
        return _combine_partials(o_t, m, l, model_axis, qa.dtype)

    scale_specs = (PS(model_axis), PS(model_axis)) if q8 else ()
    scale_args = (ckv_scale, krope_scale) if q8 else ()
    fn = jax.shard_map(
        shard_fn, mesh=mesh,
        in_specs=(PS(dp, None, None),
                  PS(dp, None, None),
                  PS(model_axis, None, None),
                  PS(model_axis, None, None))
                 + scale_specs + (PS(dp, None), PS(dp)),
        out_specs=PS(dp, None, None),
        check_vma=False)
    return fn(q_abs, q_rope, ckv_pool, krope_pool, *scale_args,
              table.astype(jnp.int32), jnp.asarray(lens, jnp.int32))


def mla_paged_decode_attend(q_abs, q_rope, ckv_pool, krope_pool, table,
                            lens, *, scale: float, ckv_scale=None,
                            krope_scale=None, backend: str = "xla",
                            mesh=None, seq_shard: bool = True
                            ) -> jax.Array:
    """Mesh-aware split-operand paged MLA decode attention used by
    ``models.lm``.

    Routes to ``sharded_mla_paged_flash_decode`` when ``seq_shard`` and
    a mesh with a 'model' axis divides the pool evenly, else the local
    registry op — the copy-free replacement for concatenating the two
    pools into a KV=1 view of ``paged_decode_attend``.
    ``ckv_scale``/``krope_scale`` select the q8 (int8 pools) route.
    """
    if seq_shard:
        mesh = resolve_mesh(mesh, "dist.decode.mla_paged_decode_attend")
        n_pages = ckv_pool.shape[0]
        if (mesh is not None and "model" in mesh.axis_names
                and n_pages % mesh.shape["model"] == 0):
            return sharded_mla_paged_flash_decode(
                mesh, q_abs, q_rope, ckv_pool, krope_pool, table, lens,
                scale=scale, ckv_scale=ckv_scale,
                krope_scale=krope_scale, backend=backend)
    return local_mla_paged_decode_attend(q_abs, q_rope, ckv_pool,
                                         krope_pool, table, lens,
                                         scale=scale,
                                         ckv_scale=ckv_scale,
                                         krope_scale=krope_scale,
                                         backend=backend)


def decode_attend(q, cache_k, cache_v, cur_len, *,
                  k_scale=None, v_scale=None,
                  backend: str = "xla",
                  mesh=None, seq_shard: bool = True,
                  kernel_impl: Optional[str] = None) -> jax.Array:
    """Mesh-aware decode attention used by ``models.lm``.

    Routes to ``sharded_flash_decode`` when ``seq_shard`` and a mesh
    with a 'model' axis is available and the cache splits evenly; falls
    back to the local registry path otherwise, so the same model code
    serves one chip and a pod.  Pass the mesh explicitly (the engine
    does); omitting it hits the deprecated ambient-mesh fallback in
    ``hints.resolve_mesh``.  ``kernel_impl`` is a deprecated alias for
    ``backend``.
    """
    if kernel_impl is not None:
        D.warn_kernel_impl_kwarg("dist.decode.decode_attend")
        backend = kernel_impl
    if seq_shard:
        mesh = resolve_mesh(mesh, "dist.decode.decode_attend")
        T = cache_k.shape[1]
        if (mesh is not None and "model" in mesh.axis_names
                and T % mesh.shape["model"] == 0):
            return sharded_flash_decode(mesh, q, cache_k, cache_v,
                                        cur_len, k_scale=k_scale,
                                        v_scale=v_scale,
                                        backend=backend)
    return local_decode_attend(q, cache_k, cache_v, cur_len,
                               k_scale=k_scale, v_scale=v_scale,
                               backend=backend)


# ======================================================================
# chunked prefill: sequence-sharded chunk-prefix attention
# ======================================================================

def local_chunk_prefix_attend(q, k_pool, v_pool, table, counts, *,
                              k_scale=None, v_scale=None,
                              backend="xla"):
    """Single-shard chunk->prior-pages attention partial through the
    dispatch registry.

    q: (C, H, Dh) — one prompt chunk's queries; table: (J,) int32 —
    the chunk's PRIOR whole pages (earlier chunks + prefix-cache
    aliases); counts: (J,) int32 valid slots per page.  Returns the
    UNNORMALIZED fp32 partial (o_t (C, H, Dh), m (C, H), l (C, H)) —
    the caller merges it with the chunk's causal self-attention
    partial (``models.attention.merge_partials``) and normalizes,
    exactly like the local ``chunk_prefill_attend``.
    ``k_scale``/``v_scale`` ((n_pages, KV) fp32) select the q8 op over
    int8 pools."""
    ps, J = k_pool.shape[2], table.shape[0]
    if k_scale is not None:
        return D.dispatch("chunk_prefix_paged_q8", backend, q, k_pool,
                          v_pool, k_scale, v_scale, table, counts,
                          page_size=ps, max_pages=J)
    return D.dispatch("chunk_prefix_paged", backend, q, k_pool, v_pool,
                      table, counts, page_size=ps, max_pages=J)


def sharded_chunk_prefix_attend(mesh, q, k_pool, v_pool, table, counts,
                                *, k_scale=None, v_scale=None,
                                backend: str = "xla",
                                model_axis: str = "model"):
    """Chunk->prior-pages attention with the page pool sharded over
    ``model_axis`` — the chunked-prefill sibling of
    ``sharded_paged_flash_decode``.

    Shard s owns pages [s*pp, (s+1)*pp); the (J,) table is replicated
    and may point anywhere, so each shard zeroes the counts of foreign
    pages, computes its unnormalized partial over the pages it owns,
    and the pmax/psum statistics combine stitches the shards — run
    UNNORMALIZED here (m* = pmax m; o = psum o~*exp(m-m*); l = psum
    l*exp(m-m*)) so the caller can still merge the chunk's replicated
    causal self-attention partial before normalizing.  Collective
    bytes per chunk are O(C * H * (Dh + 2)), independent of prefix
    length — the same wire contract as sharded decode.  A chunk with
    no prior pages (J = 0, or every count zeroed) combines to the
    fully-masked partial (o = 0, m = NEG_INF, l = 0), which the merge
    treats as exact identity."""
    n_pages, ps = k_pool.shape[0], k_pool.shape[2]
    J = table.shape[0]
    q8 = k_scale is not None
    op = "chunk_prefix_paged_q8" if q8 else "chunk_prefix_paged"
    sig = ((q, k_pool, v_pool, k_scale, v_scale, table, counts)
           if q8 else (q, k_pool, v_pool, table, counts))
    backend = D.cached_backend(op, backend, sig,
                               {"page_size": ps, "max_pages": J})
    msize = mesh.shape.get(model_axis, 1) if model_axis else 1
    if (model_axis not in mesh.axis_names or msize == 1
            or n_pages % msize or J == 0):
        return local_chunk_prefix_attend(q, k_pool, v_pool, table,
                                         counts, k_scale=k_scale,
                                         v_scale=v_scale,
                                         backend=backend)
    pp = n_pages // msize

    def shard_fn(q, kp, vp, *rest):
        tbl, cnt = rest[-2], rest[-1]
        p0 = jax.lax.axis_index(model_axis) * pp
        owned = (tbl >= p0) & (tbl < p0 + pp)
        tloc = jnp.clip(tbl - p0, 0, pp - 1)
        cnt = jnp.where(owned, cnt, 0)
        o_t, m, l = D.dispatch(op, backend, q, kp, vp, *rest[:-2],
                               tloc, cnt, page_size=ps, max_pages=J,
                               tune=False)
        # unnormalized cross-shard combine: keep (o~, m, l) so the
        # caller's self-partial merge stays exact
        m_star = jax.lax.pmax(m, model_axis)
        s = jnp.exp(m - m_star)
        o = jax.lax.psum(o_t * s[..., None], model_axis)
        l = jax.lax.psum(l * s, model_axis)
        return o, m_star, l

    scale_specs = ((PS(model_axis, None), PS(model_axis, None))
                   if q8 else ())
    scale_args = (k_scale, v_scale) if q8 else ()
    fn = jax.shard_map(
        shard_fn, mesh=mesh,
        in_specs=(PS(None, None, None),
                  PS(model_axis, None, None, None),
                  PS(model_axis, None, None, None))
                 + scale_specs + (PS(None), PS(None)),
        out_specs=(PS(None, None, None), PS(None, None),
                   PS(None, None)),
        check_vma=False)
    return fn(q, k_pool, v_pool, *scale_args,
              table.astype(jnp.int32), counts.astype(jnp.int32))


def chunk_prefix_attend(q, k_pool, v_pool, table, counts, *,
                        k_scale=None, v_scale=None,
                        backend: str = "xla", mesh=None,
                        seq_shard: bool = True):
    """Mesh-aware chunk-prefix attention partial.

    Routes to ``sharded_chunk_prefix_attend`` when ``seq_shard`` and a
    mesh with a 'model' axis divides the pool evenly, else the local
    registry op.  Either way returns the unnormalized (o_t, m, l)
    partial for the caller's self-attention merge."""
    if seq_shard:
        mesh = resolve_mesh(mesh, "dist.decode.chunk_prefix_attend")
        n_pages = k_pool.shape[0]
        if (mesh is not None and "model" in mesh.axis_names
                and n_pages % mesh.shape["model"] == 0):
            return sharded_chunk_prefix_attend(mesh, q, k_pool, v_pool,
                                               table, counts,
                                               k_scale=k_scale,
                                               v_scale=v_scale,
                                               backend=backend)
    return local_chunk_prefix_attend(q, k_pool, v_pool, table, counts,
                                     k_scale=k_scale, v_scale=v_scale,
                                     backend=backend)
