"""Seeded random weights for a dense decoder, made on the device in one
jitted call, in the type they are served in.

The tree is the layout the serving program takes (``embed``,
``final_norm``, the scan-stacked ``layers``, ``unembed`` when the head
is not tied).  The reference reads the same tree by the same keys, so
both see one set of weights and neither makes them.  Distributions
(``weights`` in the configuration file): normal with standard deviation
1/sqrt(fan-in) for every projection, ``embed_std`` for the embedding and
an untied head, ``bias_std`` for the query/key/value biases, and norm
scales of 1.
"""
from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp


def vocab_padded(m: dict) -> int:
    return -(-m["vocab"] // 128) * 128


def shapes(m: dict) -> Dict[str, Any]:
    """(shape, std) for every leaf; std None = ones, 'embed' = the
    configuration's embedding std, 'bias' = its bias std."""
    L, d, H, KV, Dh, F = (m["n_layers"], m["d_model"], m["n_heads"],
                          m["n_kv_heads"], m["d_head"], m["d_ff"])
    Vp = vocab_padded(m)
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
