"""Plain float32 reference of a dense decoder, and the served-token gap.

Written from the published description of the architecture (RMSNorm
before attention and MLP, rotary embeddings on query and key by halves,
grouped-query attention with optional q/k/v biases, SwiGLU, a final
RMSNorm and a tied or untied head), in ``jax.numpy`` at
``Precision.HIGHEST``, with no kernel, cache or batching of the serving
program.  It imports nothing from the program: it reads the weight tree
by key and the configuration file's ``model`` numbers.

``fp8=True`` is the control: the same pass with every matrix product's
operands rounded to float8 (e4m3, one scale per tensor), the precision
below the bf16 the configuration serves in.

Teacher-forced: one pass over a prompt and the tokens served for it
gives the logits at every position where a token was served.  The pass
runs one sequence at a time, layer by layer under ``lax.scan`` with each
layer's weights cast to float32 as it is used, and attention in blocks
of query rows, so it fits beside the weights.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
F32 = jnp.float32
Q_BLOCK = 512


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale.astype(F32)


def _fp8(x):
    """Round to float8 e4m3 with one scale per tensor, back in float32."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / s).astype(jnp.float8_e4m3fn).astype(F32) * s


def _mm(spec, a, b, fp8):
    if fp8:
        a, b = _fp8(a), _fp8(b)
    return jnp.einsum(spec, a, b, precision=HI)


def _rope(x, theta):
    """x (T, heads, Dh); rotation by halves at positions 0..T-1."""
    T, _, Dh = x.shape
    freqs = 1.0 / (theta ** (jnp.arange(0, Dh, 2, dtype=F32) / Dh))
    ang = jnp.arange(T, dtype=F32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attention(q, k, v, fp8):
    """Causal attention, q (T, H, Dh), k/v (T, KV, Dh), in blocks of
    Q_BLOCK query rows (T a multiple of it)."""
    T, H, Dh = q.shape
    KV = k.shape[1]
    k = jnp.repeat(k, H // KV, axis=1)
    v = jnp.repeat(v, H // KV, axis=1)
    qb = q.reshape(T // Q_BLOCK, Q_BLOCK, H, Dh)
    kpos = jnp.arange(T)

    def block(args):
        i, qi = args
        s = _mm("qhd,khd->hqk", qi, k, fp8) / np.sqrt(Dh)
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
    x = weights["embed"]["table"][tokens].astype(F32)

    def layer(x, lp):
        a = lp["attn"]
        h = _rms(x, lp["attn_norm"]["scale"], eps)
        proj = {n: _mm("td,dhk->thk", h, a["w" + n].astype(F32), fp8)
                for n in "qkv"}
        if "bq" in a:
            proj = {n: proj[n] + a["b" + n].astype(F32) for n in "qkv"}
        o = _attention(_rope(proj["q"], theta), _rope(proj["k"], theta),
                       proj["v"], fp8)
        x = x + _mm("thk,hkd->td", o, a["wo"].astype(F32), fp8)
        mp = lp["mlp"]
        h = _rms(x, lp["mlp_norm"]["scale"], eps)
        g = _mm("td,df->tf", h, mp["wg"].astype(F32), fp8)
        u = _mm("td,df->tf", h, mp["wi"].astype(F32), fp8)
        x = x + _mm("tf,fd->td", jax.nn.silu(g) * u, mp["wo"].astype(F32),
                    fp8)
        return x, None

    x, _ = jax.lax.scan(layer, x, weights["layers"])
    h = _rms(x[rows], weights["final_norm"]["scale"], eps)
    V = m["vocab"]
    if m.get("tie_embeddings"):
        head = weights["embed"]["table"][:V].astype(F32).T
    else:
        head = weights["unembed"]["w"][:, :V].astype(F32)
    return _mm("rd,dv->rv", h, head, fp8)


def _round_up(n: int, k: int) -> int:
    return -(-n // k) * k


def logits(weights, model: dict, seq: np.ndarray, rows: np.ndarray, *,
           t_pad: int = 0, r_pad: int = 0, fp8: bool = False) -> np.ndarray:
    """Float32 logits (len(rows), vocab) at positions ``rows`` of the
    token sequence ``seq``.  ``t_pad`` pads the sequence (a multiple of
    Q_BLOCK) and ``r_pad`` the rows, so that one compiled pass serves
    sequences of different lengths."""
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
    """For each served token: how far its reference logit lies below the
    reference's best logit at that position (0 where it is the best).

    Token i of ``served`` was produced at position len(prompt) - 1 + i,
    after the prompt and served[:i].  With ``control`` also the gaps of
    the tokens the fp8 control puts first at the same positions:
    returns (gaps, control gaps)."""
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


def pad_length(lengths) -> int:
    """The common padded length for sequences of ``lengths`` tokens."""
    return _round_up(max(lengths), Q_BLOCK)


def pad_rows(counts) -> int:
    """The common padded number of logit rows for ``counts`` tokens."""
    return _round_up(max(counts), 64)
