"""Core layers: norms, rotary embeddings, MLPs, embeddings.

Logical axis vocabulary (resolved to mesh axes by dist.sharding.rules):
  embed    d_model dims                (replicated by default)
  vocab    vocabulary dim              -> 'model'
  heads    query-head dim              -> 'model' when divisible
  kv       kv-head dim                 -> 'model' when divisible
  ffn      feed-forward hidden dim     -> 'model'
  experts  routed-expert dim           -> ('data','model') or 'model'
  layers   scan-stacked layer dim      (never sharded)
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as PS

from repro.common.module import ParamDef, embed_init, ones_init, zeros_init
from repro.kernels import dispatch as D


def dt(cfg):
    return jnp.dtype(cfg.dtype)


# ---------------- norms ----------------

def rmsnorm_spec(d, dtype):
    return {"scale": ParamDef((d,), dtype, ("embed",), ones_init)}


def rmsnorm(p, x, eps=1e-5):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps)
    return (out * p["scale"].astype(jnp.float32)).astype(x.dtype)


def layernorm_spec(d, dtype):
    return {
        "scale": ParamDef((d,), dtype, ("embed",), ones_init),
        "bias": ParamDef((d,), dtype, ("embed",), zeros_init),
    }


def layernorm(p, x, eps=1e-5):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
    out = (xf - mu) * jax.lax.rsqrt(var + eps)
    out = out * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
    return out.astype(x.dtype)


# ---------------- rotary ----------------

def rope_freqs(d_head: int, theta: float):
    return 1.0 / (theta ** (jnp.arange(0, d_head, 2, dtype=jnp.float32) / d_head))


def apply_rope(x, positions, theta: float):
    """x: (..., S, H, Dh); positions: broadcastable to (..., S)."""
    d_head = x.shape[-1]
    freqs = rope_freqs(d_head, theta)                       # (Dh/2,)
    ang = positions[..., None].astype(jnp.float32) * freqs  # (..., S, Dh/2)
    cos = jnp.cos(ang)[..., None, :]                        # (..., S, 1, Dh/2)
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------- embedding / unembedding ----------------

def embedding_spec(vocab, d, dtype):
    return {"table": ParamDef((vocab, d), dtype, ("vocab", "embed"), embed_init)}


def embed(p, tokens):
    return jnp.take(p["table"], tokens, axis=0)


def unembed_spec(vocab, d, dtype):
    return {"w": ParamDef((d, vocab), dtype, ("embed", "vocab"))}


def unembed(p, x):
    return jnp.einsum("...d,dv->...v", x, p["w"])


# ---------------- MLP ----------------

def mlp_spec(d, d_ff, act, dtype):
    if act == "swiglu":
        return {
            "wi": ParamDef((d, d_ff), dtype, ("embed", "ffn")),
            "wg": ParamDef((d, d_ff), dtype, ("embed", "ffn")),
            "wo": ParamDef((d_ff, d), dtype, ("ffn", "embed")),
        }
    return {
        "wi": ParamDef((d, d_ff), dtype, ("embed", "ffn")),
        "wo": ParamDef((d_ff, d), dtype, ("ffn", "embed")),
    }


@D.register("swiglu", "xla")
def _swiglu_xla(x2, wg, wi):
    h = jnp.einsum("md,df->mf", x2, wi)
    g = jnp.einsum("md,df->mf", x2, wg)
    return jax.nn.silu(g.astype(jnp.float32)).astype(x2.dtype) * h


@D.register("swiglu", "pallas")
def _swiglu_pallas(x2, wg, wi):
    from repro.kernels import ops
    return ops.vwr_swiglu(x2, wg, wi)


@D.register("mlp", "xla")
def _mlp_xla(p, x, act, residual=None, mesh=None, scale=1.0):
    if act == "swiglu":
        lead, d = x.shape[:-1], x.shape[-1]
        h = D.dispatch("swiglu", "xla", x.reshape(-1, d),
                       p["wg"], p["wi"]).reshape(*lead, -1)
    else:
        h = jnp.einsum("...d,df->...f", x, p["wi"])
        fn = jax.nn.gelu if act == "gelu" else jax.nn.relu
        h = fn(h.astype(jnp.float32)).astype(x.dtype)
    if scale != 1.0:
        h = h * scale
    out = jnp.einsum("...f,fd->...d", h, p["wo"])
    return out if residual is None else residual + out


def _mlp_kernels(p, x2, act, r2=None, scale=1.0):
    from repro.kernels import ops
    if act == "swiglu":
        # dual-matmul fused swiglu: one staged x block feeds both
        # projections and silu(g) * h happens on the fp32 accumulators
        # in the final-K store — no separate elementwise pass
        h = D.dispatch("swiglu", "pallas", x2,
                       p["wg"], p["wi"]).astype(x2.dtype)
    else:
        h = ops.vwr_matmul(x2, p["wi"],
                           activation="gelu" if act == "gelu" else "relu")
    if scale != 1.0:
        h = h * scale
    return ops.vwr_matmul(h, p["wo"], residual=r2)


@D.register("mlp", "pallas")
def _mlp_pallas(p, x, act, residual=None, mesh=None, scale=1.0):
    from repro.dist import sharding as SH
    lead, d = x.shape[:-1], x.shape[-1]
    x2 = x.reshape(-1, d)
    if not SH.multi_device(mesh):
        r2 = None if residual is None else residual.reshape(-1, d)
        return _mlp_kernels(p, x2, act, r2, scale).reshape(*lead, d)
    # tensor parallel over 'model': column-split up-projections, row-
    # split down-projection, one psum
    ff = SH.tp_entry(mesh, p["wo"].shape[0])
    dp = SH.batch_entry(mesh, x2.shape[0])

    def local(p, x2):
        out = _mlp_kernels(p, x2, act, scale=scale)
        return out if ff is None else jax.lax.psum(out, ff)

    specs = {k: PS(ff, None) if k == "wo" else PS(None, ff) for k in p}
    out = SH.per_device(local, mesh, (specs, PS(dp, None)),
                        PS(dp, None))(p, x2).reshape(*lead, d)
    return out if residual is None else residual + out


def mlp(p, x, act: str, *, backend="xla", residual=None,
        kernel_impl=None, mesh=None, scale=1.0):
    """FFN block via the dispatch registry.  With ``residual`` the
    residual add is part of the block (``residual + mlp(x)``); on the
    pallas path it is fused into the down-projection's final-K store
    (one HBM round-trip), the non-gated activation into the
    up-projection, and swiglu runs the dual-matmul fused kernel.  On a
    multi-device ``mesh`` the pallas path runs tensor-parallel per
    device.  ``scale`` multiplies the down projection's input (a
    published residual multiplier; at 1.0 nothing is added).
    ``backend`` is a backend string or a ModelConfig; the
    legacy ``kernel_impl=`` kwarg still works but is deprecated."""
    if kernel_impl is not None:
        D.warn_kernel_impl_kwarg("layers.mlp")
        backend = kernel_impl
    kw = {} if scale == 1.0 else {"scale": scale}
    return D.dispatch("mlp", backend, p, x, act, residual=residual,
                      mesh=mesh, **kw)


# ---------------- frontends (stubs per brief) ----------------

def frontend_proj_spec(raw_dim, d, dtype):
    """Projects precomputed frame/patch embeddings into d_model."""
    return {"w": ParamDef((raw_dim, d), dtype, ("frontend_in", "embed"))}


def frontend_proj(p, emb):
    return jnp.einsum("...r,rd->...d", emb, p["w"])
