"""Mixture-of-Experts FFN: shared + routed top-k, capacity dispatch.

Two dispatch strategies (cfg.moe.dispatch):

``'gather'`` (baseline, pure GSPMD): position-in-expert is computed with a
one-hot cumsum, tokens are *gathered* into a static (G, E, C, D) buffer
(G = dispatch groups, C = per-expert capacity), experts run as one batched
einsum, results are gathered back per (token, k) slot.  No (G,S,E,C)
combine tensor is ever materialized (the classic GShard formulation would
need T*K*E*C elements — hopeless at our sizes); peak transient is the
dispatched activations themselves, T*K*cf*D.

``'sort'`` (beyond-paper perf iteration): position-in-expert via a stable
argsort over expert ids — O(T log T) instead of the O(T*K*E) cumsum
tensor; numerically identical (tested).

Token-dropping: assignments beyond capacity are dropped (keep=False) and
their gate weight contributes nothing; with cf=1.25 drops are rare.  The
aux load-balance loss keeps the router near-uniform.

Grouping policy: group = one batch row for train/prefill (so the group
axis shards over 'data' exactly like the batch), a single group of all B
tokens for decode (S=1) so capacity slots stay dense.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as PS

from repro.common.module import ParamDef, zeros_init
from repro.models.layers import mlp, mlp_spec


from repro.common.hints import shard_hint as _ep_constraint


def moe_spec(cfg):
    m = cfg.moe
    d = cfg.d_model
    dtype = jnp.dtype(cfg.dtype)
    spec: Dict = {
        "router": ParamDef((d, m.n_experts), jnp.float32, ("embed", "experts")),
        "wi": ParamDef((m.n_experts, d, m.d_expert), dtype,
                       ("experts", "embed", "expert_ff")),
        "wg": ParamDef((m.n_experts, d, m.d_expert), dtype,
                       ("experts", "embed", "expert_ff")),
        "wo": ParamDef((m.n_experts, m.d_expert, d), dtype,
                       ("experts", "expert_ff", "embed")),
    }
    if m.n_shared:
        spec["shared"] = mlp_spec(d, m.n_shared * m.d_expert, "swiglu", dtype)
    if m.score_fn == "sigmoid":
        # DeepSeek-V3 e-score correction bias: used for top-k *selection*
        # only, not in the gate weights. Updated out-of-band (bias update
        # rate is a training-schedule knob; see optim/router_bias.py).
        spec["e_bias"] = ParamDef((m.n_experts,), jnp.float32, ("experts",),
                                  zeros_init)
    return spec


# ---------------- routing ----------------

def router_scores(p, x, cfg):
    """x: (..., D) -> probs (..., E) fp32 and selection scores."""
    m = cfg.moe
    logits = jnp.einsum("...d,de->...e", x.astype(jnp.float32), p["router"])
    if m.score_fn == "sigmoid":
        probs = jax.nn.sigmoid(logits)
        sel = probs + p["e_bias"]          # bias influences selection only
    else:
        probs = jax.nn.softmax(logits, axis=-1)
        sel = probs
    return probs, sel, logits


def top_k_gates(probs, sel, cfg):
    """Returns (gates (...,K) fp32, idx (...,K) int32)."""
    m = cfg.moe
    _, idx = jax.lax.top_k(sel, m.top_k)
    gates = jnp.take_along_axis(probs, idx, axis=-1)
    if m.norm_topk:
        gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-20)
    return gates * m.routed_scale, idx


# ---------------- position-in-expert ----------------

def _positions_cumsum(idx_flat, n_experts):
    """idx_flat: (G, A) expert ids. Returns pos (G, A) int32.

    pos[a] = #{a' < a : idx[a'] == idx[a]} — via one-hot cumsum.
    """
    oh = jax.nn.one_hot(idx_flat, n_experts, dtype=jnp.int32)   # (G,A,E)
    pos = jnp.cumsum(oh, axis=1) - 1                            # inclusive -> -1
    return jnp.take_along_axis(pos, idx_flat[..., None], axis=-1)[..., 0]


def _positions_sort(idx_flat, n_experts):
    """Same contract as _positions_cumsum via stable argsort (O(A log A))."""
    G, A = idx_flat.shape

    def per_group(e):
        order = jnp.argsort(e, stable=True)              # assignments by expert
        sorted_e = e[order]
        # start offset of each expert's run = exclusive cumsum of counts
        counts = jnp.zeros(n_experts, jnp.int32).at[e].add(1)
        starts = jnp.concatenate([jnp.zeros(1, jnp.int32),
                                  jnp.cumsum(counts)[:-1]])
        pos_sorted = jnp.arange(A, dtype=jnp.int32) - starts[sorted_e]
        return jnp.zeros(A, jnp.int32).at[order].set(pos_sorted)

    return jax.vmap(per_group)(idx_flat)


# ---------------- dispatch / combine ----------------

def moe_ffn(p, x, cfg, mesh=None) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """x: (G, S, D) grouped tokens. Returns (y (G,S,D), aux dict).

    aux: 'lb_loss' (load balance), 'z_loss' (router logit magnitude),
    'drop_frac' (fraction of assignments dropped by capacity).
    ``mesh`` resolves the expert-parallel sharding constraints
    explicitly (callers without an ambient mesh context — the engine
    path — must pass it or full_ep constraints silently no-op).
    """
    m = cfg.moe
    G, S, D = x.shape
    E, K = m.n_experts, m.top_k
    A = S * K
    cap = int(max(1, -(-S * K * m.capacity_factor // E)))       # ceil

    probs, sel, logits = router_scores(p, x, cfg)               # (G,S,E)
    gates, idx = top_k_gates(probs, sel, cfg)                   # (G,S,K)

    idx_flat = idx.reshape(G, A)
    if m.dispatch == "sort":
        pos = _positions_sort(idx_flat, E)
    else:
        pos = _positions_cumsum(idx_flat, E)
    keep = pos < cap                                            # (G,A)

    # scatter token indices into (E*cap) slots; sentinel S = zero-pad row
    slot = jnp.where(keep, idx_flat * cap + pos, E * cap)       # (G,A)
    token_of_assign = jnp.broadcast_to(
        jnp.arange(S, dtype=jnp.int32)[:, None], (S, K)
    ).reshape(A)
    g_ix = jnp.arange(G, dtype=jnp.int32)[:, None]
    token_for_slot = jnp.full((G, E * cap + 1), S, jnp.int32)
    token_for_slot = token_for_slot.at[g_ix, slot].set(token_of_assign[None, :])
    token_for_slot = token_for_slot[:, : E * cap]               # (G, E*cap)

    x_pad = jnp.concatenate([x, jnp.zeros((G, 1, D), x.dtype)], axis=1)
    xd = jnp.take_along_axis(
        x_pad, token_for_slot[..., None], axis=1
    ).reshape(G, E, cap, D)                                     # dispatched

    ep_spec = PS(None, ("data", "model"), None, None)
    if m.ep == "full_ep":
        # tokens move to the expert owners (all-to-all-sized traffic);
        # expert weights, sharded E -> (data, model), never move.
        # (measured WORSE when combined with gather-based combine at
        # decode — §Perf H7a — so not applied by default)
        xd = _ep_constraint(xd, ep_spec, mesh=mesh)

    # expert FFN (swiglu) as batched einsum over the expert dim
    h = jnp.einsum("gecd,edf->gecf", xd, p["wi"])
    gte = jnp.einsum("gecd,edf->gecf", xd, p["wg"])
    h = jax.nn.silu(gte.astype(jnp.float32)).astype(h.dtype) * h
    y_e = jnp.einsum("gecf,efd->gecd", h, p["wo"])              # (G,E,cap,D)
    if m.ep == "full_ep":
        y_e = _ep_constraint(y_e, ep_spec, mesh=mesh)

    # combine: gather each assignment's slot output, weight by gate
    y_flat = y_e.reshape(G, E * cap, D)
    y_flat = jnp.concatenate([y_flat, jnp.zeros((G, 1, D), y_flat.dtype)],
                             axis=1)
    src = jnp.where(keep, idx_flat * cap + pos, E * cap)        # (G,A)
    y_a = jnp.take_along_axis(y_flat, src[..., None], axis=1)   # (G,A,D)
    w_a = (gates.reshape(G, A) * keep).astype(jnp.float32)
    y = (y_a.astype(jnp.float32) * w_a[..., None]).reshape(G, S, K, D).sum(2)
    y = y.astype(x.dtype)

    if m.n_shared:
        y = y + mlp(p["shared"], x, "swiglu", backend=cfg, mesh=mesh)

    # aux metrics / losses (fp32)
    me = probs.mean(axis=(0, 1))                                # (E,) mean prob
    ce = (jax.nn.one_hot(idx, E, dtype=jnp.float32).sum(2).mean(axis=(0, 1)))
    lb_loss = E * jnp.sum(me * ce) / K
    z_loss = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)
    drop_frac = 1.0 - keep.mean()
    aux = {"lb_loss": lb_loss, "z_loss": z_loss, "drop_frac": drop_frac}
    return y, aux
