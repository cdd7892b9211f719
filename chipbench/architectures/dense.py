"""A dense decoder: its weights, its float32 reference and its counts.

The architecture: RMSNorm before attention and MLP, rotary embeddings
on query and key by halves, grouped-query attention with optional
q/k/v biases, SwiGLU, a final RMSNorm and a tied or untied head.  ``m``
is a configuration file's ``model`` section.  A configuration names
this file with ``"architecture": "dense"``; ``chipbench/spec.py`` loads
it by path, and the harness reaches it as ``cell.arch`` / ``rec.arch``.

Weights.  Seeded random weights, made on the device in one jitted call,
in the type they are served in.  The tree is the layout the serving
program takes (``embed``, ``final_norm``, the scan-stacked ``layers``,
``unembed`` when the head is not tied).  The reference reads the same
tree by the same keys, so both see one set of weights and neither makes
them.  Distributions (``weights`` in the configuration file): normal
with standard deviation 1/sqrt(fan-in) for every projection,
``embed_std`` for the embedding and an untied head, ``bias_std`` for
the query/key/value biases, and norm scales of 1.

Reference.  Written from the published description of the
architecture, in ``jax.numpy`` at ``Precision.HIGHEST``, with no kernel,
cache or batching of the serving program.  It imports nothing from the
program: it reads the weight tree by key and the ``model`` numbers.
``fp8=True`` is the control: the same pass with every matrix product's
operands rounded to float8 (e4m3, one scale per tensor), the precision
below the bf16 the configuration serves in.  Teacher-forced: one pass
over a prompt and the tokens served for it gives the logits at every
position where a token was served.  The pass runs one sequence at a
time, layer by layer under ``lax.scan`` with each layer's weights cast
to float32 as it is used, and attention in blocks of query rows, so it
fits beside the weights.

Counts.  Operations and bytes that the algorithm needs, computed from
shapes.  Decode counts only the slots that decode, and the positions
they attend (``ctx``: the sum over those slots of cached positions + 1).
Bytes are HBM traffic at the served types: bf16 weights and KV pages,
fp32 logits.  The peaks these are divided by come from ``peaks.json``.
"""
from __future__ import annotations

import functools
import math
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import costs

HI = jax.lax.Precision.HIGHEST
F32 = jnp.float32
Q_BLOCK = 512


def _vocab_padded(m: dict) -> int:
    return -(-m["vocab"] // 128) * 128


# -- weights ------------------------------------------------------------

def shapes(m: dict) -> Dict[str, Any]:
    """(shape, std) for every leaf; std None = ones, 'embed' = the
    configuration's embedding std, 'bias' = its bias std."""
    L, d, H, KV, Dh, F = (m["n_layers"], m["d_model"], m["n_heads"],
                          m["n_kv_heads"], m["d_head"], m["d_ff"])
    Vp = _vocab_padded(m)
    attn = {"wq": ((L, d, H, Dh), 1 / math.sqrt(d)),
            "wk": ((L, d, KV, Dh), 1 / math.sqrt(d)),
            "wv": ((L, d, KV, Dh), 1 / math.sqrt(d)),
            "wo": ((L, H, Dh, d), 1 / math.sqrt(H * Dh))}
    if m.get("qkv_bias"):
        attn.update(bq=((L, H, Dh), "bias"), bk=((L, KV, Dh), "bias"),
                    bv=((L, KV, Dh), "bias"))
    tree = {"embed": {"table": ((Vp, d), "embed")},
            "final_norm": {"scale": ((d,), None)},
            "layers": {"attn_norm": {"scale": ((L, d), None)},
                       "attn": attn,
                       "mlp_norm": {"scale": ((L, d), None)},
                       "mlp": {"wi": ((L, d, F), 1 / math.sqrt(d)),
                               "wg": ((L, d, F), 1 / math.sqrt(d)),
                               "wo": ((L, F, d), 1 / math.sqrt(F))}}}
    if not m.get("tie_embeddings"):
        tree["unembed"] = {"w": ((d, Vp), "embed")}
    return tree


def _is_leaf(x) -> bool:
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[0], tuple)


def make(config: dict, key, shardings=None):
    """The whole weight tree, drawn from ``key`` on the device in one
    program.  ``shardings`` (a matching tree, or None) places it."""
    m, w = config["model"], config["weights"]
    dtype = jnp.dtype(m["dtype"])
    spec = shapes(m)
    leaves, treedef = jax.tree.flatten(spec, is_leaf=_is_leaf)

    def draw(key):
        keys = jax.random.split(key, len(leaves))
        out = []
        for (shape, std), k in zip(leaves, keys):
            if std is None:
                out.append(jnp.ones(shape, dtype))
                continue
            s = {"embed": w["embed_std"], "bias": w["bias_std"]}.get(std, std)
            out.append((jax.random.normal(k, shape, jnp.float32) * s
                        ).astype(dtype))
        return jax.tree.unflatten(treedef, out)

    return jax.jit(draw, out_shardings=shardings)(key)


# -- the float32 reference and the served-token gap ---------------------

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


# -- counts -------------------------------------------------------------

def _layer_matmul_params(m: dict) -> int:
    d, H, KV, Dh, F = (m["d_model"], m["n_heads"], m["n_kv_heads"],
                       m["d_head"], m["d_ff"])
    return d * (H + 2 * KV) * Dh + H * Dh * d + 3 * d * F


def _head_params(m: dict) -> int:
    return m["d_model"] * m["vocab"]


def _attention_flops(m: dict, ctx: int) -> float:
    """Scores and weighted values over ``ctx`` attended positions."""
    return 4.0 * m["n_layers"] * m["n_heads"] * m["d_head"] * ctx


def weight_bytes(m: dict) -> int:
    """Weights one decode step reads: every layer, the norms and the
    head (the whole embedding table when it is the tied head)."""
    L, d = m["n_layers"], m["d_model"]
    per_layer = _layer_matmul_params(m) + 2 * d
    if m.get("qkv_bias"):
        per_layer += (m["n_heads"] + 2 * m["n_kv_heads"]) * m["d_head"]
    return costs.BF16 * (L * per_layer + d + _vocab_padded(m) * d)


def kv_bytes_per_position(m: dict) -> int:
    return costs.BF16 * 2 * m["n_layers"] * m["n_kv_heads"] * m["d_head"]


def decode_flops(m: dict, n: int, ctx: int) -> float:
    matmul = m["n_layers"] * _layer_matmul_params(m) + _head_params(m)
    return 2.0 * n * matmul + _attention_flops(m, ctx)


def decode_bytes(m: dict, n: int, ctx: int) -> float:
    kv = kv_bytes_per_position(m)
    return (weight_bytes(m) + kv * ctx + kv * n
            + costs.F32 * n * _vocab_padded(m))


def paged_decode_flops(m: dict, n: int, ctx: int) -> float:
    return _attention_flops(m, ctx)


def paged_decode_bytes(m: dict, n: int, ctx: int) -> float:
    """KV pages read, plus queries in and fp32 partials out."""
    L, H, Dh = m["n_layers"], m["n_heads"], m["d_head"]
    return (kv_bytes_per_position(m) * ctx
            + L * n * H * (costs.BF16 * Dh + costs.F32 * (Dh + 2)))


def prefill_flops(m: dict, P: int) -> float:
    """One prompt of ``P`` tokens: every projection, causal attention,
    and the head on the last token."""
    L, H, Dh = m["n_layers"], m["n_heads"], m["d_head"]
    return (2.0 * P * L * _layer_matmul_params(m)
            + 2.0 * L * H * Dh * P * (P + 1) + 2.0 * _head_params(m))
