"""Granite (3.x dense): its weights, its float32 reference and its counts.

The architecture is the dense decoder of ``dense.py`` (RMSNorm, RoPE by
halves, grouped-query attention, SwiGLU, a tied head) with the four
scaling multipliers that Granite publishes (HF ``GraniteForCausalLM``):

    x0     = embedding_multiplier * embed(tokens)
    x      = x + residual_multiplier * attn(rmsnorm(x))
    x      = x + residual_multiplier * mlp(rmsnorm(x))
    logits = (rmsnorm(x) . E^T) / logits_scaling

and attention scores ``attention_multiplier * q.k`` in place of
``q.k / sqrt(d_head)``.  ``m`` is a configuration file's ``model``
section, which states the four under the published names.

Weights and counts are ``dense.py``'s, imported: the tree is the same
(no biases, the embedding is the head), and the multipliers add
nothing a count would see.  The reference is written here from the
equations above, in ``jax.numpy`` at ``Precision.HIGHEST``, blocked as
``dense.py``'s is (one sequence at a time, layer by layer under
``lax.scan``, attention in blocks of query rows); it imports nothing
from the program.  ``fp8=True`` is the control: every matrix product's
operands rounded to float8 e4m3, the precision below the served bf16.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.architectures.dense import (  # noqa: F401  (interface)
    Q_BLOCK, decode_bytes, decode_flops, kv_bytes_per_position, make,
    paged_decode_bytes, paged_decode_flops, pad_length, pad_rows,
    prefill_flops, shapes, weight_bytes)
from chipbench.architectures.dense import _mm, _rms, _rope, _round_up

F32 = jnp.float32


def _attention(q, k, v, scale, fp8):
    """Causal attention with scores ``scale * q.k``; q (T, H, Dh), k/v
    (T, KV, Dh), in blocks of Q_BLOCK query rows (T a multiple of it)."""
    T, H, Dh = q.shape
    KV = k.shape[1]
    k = jnp.repeat(k, H // KV, axis=1)
    v = jnp.repeat(v, H // KV, axis=1)
    qb = q.reshape(T // Q_BLOCK, Q_BLOCK, H, Dh)
    kpos = jnp.arange(T)

    def block(args):
        i, qi = args
        s = _mm("qhd,khd->hqk", qi, k, fp8) * scale
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        s = jnp.where(kpos[None, None, :] <= qpos[None, :, None], s,
                      -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return _mm("hqk,khd->qhd", p, v, fp8)

    out = jax.lax.map(block, (jnp.arange(T // Q_BLOCK), qb))
    return out.reshape(T, H, Dh)


@functools.partial(jax.jit, static_argnames=("m", "fp8"))
def _logits_at(weights, tokens, rows, m, fp8):
    """Float32 logits (R, vocab) at positions ``rows`` of ``tokens`` (T,).
    ``m`` is the configuration's ``model`` section as a sorted tuple."""
    m = dict(m)
    eps, theta = m["norm_eps"], m["rope_theta"]
    res = m["residual_multiplier"]
    scale = m["attention_multiplier"]
    x = weights["embed"]["table"][tokens].astype(F32) \
        * m["embedding_multiplier"]

    def layer(x, lp):
        a = lp["attn"]
        h = _rms(x, lp["attn_norm"]["scale"], eps)
        q, k, v = (_mm("td,dhk->thk", h, a["w" + n].astype(F32), fp8)
                   for n in "qkv")
        o = _attention(_rope(q, theta), _rope(k, theta), v, scale, fp8)
        x = x + res * _mm("thk,hkd->td", o, a["wo"].astype(F32), fp8)
        mp = lp["mlp"]
        h = _rms(x, lp["mlp_norm"]["scale"], eps)
        g = _mm("td,df->tf", h, mp["wg"].astype(F32), fp8)
        u = _mm("td,df->tf", h, mp["wi"].astype(F32), fp8)
        x = x + res * _mm("tf,fd->td", jax.nn.silu(g) * u,
                          mp["wo"].astype(F32), fp8)
        return x, None

    x, _ = jax.lax.scan(layer, x, weights["layers"])
    h = _rms(x[rows], weights["final_norm"]["scale"], eps)
    head = weights["embed"]["table"][:m["vocab"]].astype(F32).T
    return _mm("rd,dv->rv", h, head, fp8) / m["logits_scaling"]


def logits(weights, model: dict, seq: np.ndarray, rows: np.ndarray, *,
           t_pad: int = 0, r_pad: int = 0, fp8: bool = False) -> np.ndarray:
    """Float32 logits (len(rows), vocab) at positions ``rows`` of the
    token sequence ``seq``, padded as ``dense.logits`` pads."""
    T = max(_round_up(len(seq), Q_BLOCK), t_pad)
    tokens = np.zeros((T,), np.int32)
    tokens[:len(seq)] = seq
    R = len(rows)
    rows_p = np.full((max(_round_up(R, 64), r_pad),), rows[-1], np.int32)
    rows_p[:R] = rows
    return np.asarray(_logits_at(weights, jnp.asarray(tokens),
                                 jnp.asarray(rows_p),
                                 tuple(sorted(model.items())), fp8))[:R]


def served_gaps(weights, model: dict, prompt: np.ndarray,
                served: np.ndarray, *, t_pad: int = 0, r_pad: int = 0,
                control: bool = False):
    """For each served token, how far its reference logit lies below the
    reference's best at its position (``dense.served_gaps``, with this
    file's reference); with ``control`` also the fp8 control's gaps."""
    served = np.asarray(served, np.int32)
    seq = np.concatenate([np.asarray(prompt, np.int32), served[:-1]])
    R = len(served)
    rows = np.arange(len(prompt) - 1, len(prompt) - 1 + R, dtype=np.int32)
    lg = logits(weights, model, seq, rows, t_pad=t_pad, r_pad=r_pad)
    best = lg.max(-1)
    gaps = best - lg[np.arange(R), served]
    if not control:
        return gaps
    ctl = logits(weights, model, seq, rows, t_pad=t_pad, r_pad=r_pad,
                 fp8=True).argmax(-1)
    return gaps, best - lg[np.arange(R), ctl]
