"""xLSTM blocks: chunkwise-parallel mLSTM + sequential sLSTM.

mLSTM (matrix memory, per head of dim P):
    C_t = f_t C_{t-1} + i_t k_t v_t^T      n_t = f_t n_{t-1} + i_t k_t
    h_t = o_t * (C_t^T q_t) / max(|n_t . q_t|, exp(-m_t))
with exponential input gate i = exp(i~), sigmoid forget gate, and the
log-domain stabilizer m_t.  The chunkwise-parallel form below evaluates
within-chunk contributions as a masked attention-like matmul (the VWR
streaming case: one wide chunk staged, many MXU steps) and carries the
(C, n, m) state across chunks with a lax.scan — mirroring the Mamba2 SSD
structure in ssm.py.  A naive per-timestep scan in ``mlstm_ref`` is the
oracle; tests assert chunkwise == naive.

sLSTM (scalar memory, block-diagonal recurrence R per head) is truly
sequential — h_{t-1} feeds the gates — so it is a lax.scan over time by
construction (the paper's own CUDA kernels do the same; no parallel form
exists).  1-in-N layers are sLSTM per the xLSTM[m:s] notation.
"""
from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from repro.common.module import (ParamDef, const_init, head_proj_init,
                                 zeros_init)
from repro.models.layers import rmsnorm, rmsnorm_spec

# ======================================================================
# mLSTM
# ======================================================================


def mlstm_spec(cfg):
    xc = cfg.xlstm
    D, H = cfg.d_model, cfg.n_heads
    d_inner = int(xc.proj_factor * D)
    P = d_inner // H
    K = xc.conv1d_kernel
    dtype = jnp.dtype(cfg.dtype)
    return {
        "in_proj": ParamDef((D, 2 * d_inner), dtype, ("embed", "inner_all")),
        "conv_w": ParamDef((K, d_inner), dtype, ("conv_k", "inner")),
        "conv_b": ParamDef((d_inner,), dtype, ("inner",), zeros_init),
        "wq": ParamDef((d_inner, H, P), dtype, ("inner", "heads", "head_dim"),
                       head_proj_init),
        "wk": ParamDef((d_inner, H, P), dtype, ("inner", "heads", "head_dim"),
                       head_proj_init),
        "wv": ParamDef((d_inner, H, P), dtype, ("inner", "heads", "head_dim"),
                       head_proj_init),
        "w_i": ParamDef((d_inner, H), jnp.float32, ("inner", "heads"), zeros_init),
        "b_i": ParamDef((H,), jnp.float32, ("heads",), zeros_init),
        "w_f": ParamDef((d_inner, H), jnp.float32, ("inner", "heads"), zeros_init),
        "b_f": ParamDef((H,), jnp.float32, ("heads",), const_init(3.0)),
        "norm": rmsnorm_spec(d_inner, dtype),
        "out_proj": ParamDef((d_inner, D), dtype, ("inner", "embed")),
    }


class MLSTMState(NamedTuple):
    C: jax.Array      # (B, H, P, P) fp32 — stabilized matrix memory
    n: jax.Array      # (B, H, P) fp32
    m: jax.Array      # (B, H) fp32 — log stabilizer
    conv: jax.Array   # (B, K-1, d_inner)


def mlstm_init_state(cfg, batch: int) -> MLSTMState:
    xc = cfg.xlstm
    d_inner = int(xc.proj_factor * cfg.d_model)
    H = cfg.n_heads
    P = d_inner // H
    return MLSTMState(
        C=jnp.zeros((batch, H, P, P), jnp.float32),
        n=jnp.zeros((batch, H, P), jnp.float32),
        m=jnp.full((batch, H), -1e30, jnp.float32),
        conv=jnp.zeros((batch, xc.conv1d_kernel - 1, d_inner),
                       jnp.dtype(cfg.dtype)),
    )


def _mlstm_conv(p, x, K, left_ctx=None):
    if left_ctx is None:
        pad = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    else:
        pad = jnp.concatenate([left_ctx.astype(x.dtype), x], axis=1)
    out = sum(pad[:, i: i + x.shape[1], :] * p["conv_w"][i][None, None, :]
              for i in range(K)) + p["conv_b"]
    return jax.nn.silu(out.astype(jnp.float32)).astype(x.dtype)


def _mlstm_qkvif(p, xc_act):
    """xc_act: (B,S,d_inner) conv-activated branch -> q,k,v,(li,lf) fp32."""
    q = jnp.einsum("bse,ehp->bshp", xc_act, p["wq"])
    k = jnp.einsum("bse,ehp->bshp", xc_act, p["wk"])
    v = jnp.einsum("bse,ehp->bshp", xc_act, p["wv"])
    xf = xc_act.astype(jnp.float32)
    li = jnp.einsum("bse,eh->bsh", xf, p["w_i"]) + p["b_i"]      # log i-gate
    lf = jax.nn.log_sigmoid(
        jnp.einsum("bse,eh->bsh", xf, p["w_f"]) + p["b_f"]
    )                                                            # log f-gate
    return q, k, v, li, lf


def mlstm_chunkwise(q, k, v, li, lf, state: Tuple, chunk: int,
                    unroll: bool = False):
    """Chunkwise-parallel stabilized mLSTM sequence evaluation.

    q,k,v: (B,S,H,P); li,lf: (B,S,H) fp32.
    state: (C (B,H,P,P), n (B,H,P), m (B,H)) fp32.
    Returns (h (B,S,H,P) fp32, new_state).
    """
    B, S, H, P = q.shape
    Q = min(chunk, S)
    assert S % Q == 0, (S, Q)
    nc = S // Q
    scale = 1.0 / (P ** 0.5)

    qf = q.astype(jnp.float32).reshape(B, nc, Q, H, P)
    kf = (k.astype(jnp.float32) * scale).reshape(B, nc, Q, H, P)
    vf = v.astype(jnp.float32).reshape(B, nc, Q, H, P)
    lif = li.reshape(B, nc, Q, H)
    lff = lf.reshape(B, nc, Q, H)

    def body(carry, xs):
        C0, n0, m0 = carry                      # C0/n0 stabilized by exp(-m0)
        qq, kk, vv, ii, ff = xs                 # (B,Q,H,P)/(B,Q,H)
        b = jnp.cumsum(ff, axis=1)              # (B,Q,H) log-decay to chunk start
        a = ii - b                              # log i_s discounted to start
        g = jnp.maximum(m0[:, None, :], jax.lax.cummax(a, axis=1))  # (B,Q,H)
        m_t = b + g                             # per-position stabilizer

        # intra-chunk: Dmat[t,s] = exp(a_s - g_t) for s<=t.
        # Mask before exp: upper-triangle log-weights can be positive
        # and overflow, which would NaN the backward pass.
        ldm = a[:, None, :, :] - g[:, :, None, :]                # (B,t,s,H)
        tri = jnp.tril(jnp.ones((Q, Q), bool))
        dmat = jnp.exp(jnp.where(tri[None, :, :, None], ldm, -1e30))
        s_qk = jnp.einsum("bthp,bshp->btsh", qq, kk)             # (B,t,s,H)
        w = s_qk * dmat
        num = jnp.einsum("btsh,bshp->bthp", w, vv)
        den = w.sum(axis=2)                                      # (B,t,H)

        # inter-chunk: carry contribution with weight exp(m0 - g_t)
        wc = jnp.exp(m0[:, None, :] - g)                         # (B,t,H)
        num = num + wc[..., None] * jnp.einsum("bthp,bhpj->bthj", qq, C0)
        den = den + wc * jnp.einsum("bthp,bhp->bth", qq, n0)

        h = num / jnp.maximum(jnp.abs(den), jnp.exp(-m_t))[..., None]

        # end-of-chunk state
        bQ = b[:, -1, :]                                         # (B,H)
        gQ = g[:, -1, :]
        m1 = bQ + gQ
        wS = jnp.exp(a - gQ[:, None, :])                         # (B,s,H)
        kv = jnp.einsum("bshp,bsh,bshj->bhpj", kk, wS,
                        vv)                                      # (B,H,P,P)
        kn = jnp.einsum("bshp,bsh->bhp", kk, wS)
        decay = jnp.exp(m0 - gQ)                                 # (B,H)
        C1 = C0 * decay[..., None, None] + kv
        n1 = n0 * decay[..., None] + kn
        return (C1, n1, m1), h

    xs = tuple(t.swapaxes(0, 1) for t in (qf, kf, vf, lif, lff))
    if unroll:
        # accounting variant: python loop so XLA cost_analysis counts
        # every chunk (while bodies are counted once; DESIGN.md §8)
        carry, hs_l = state, []
        for c_ in range(nc):
            carry, h_ = body(carry, tuple(t[c_] for t in xs))
            hs_l.append(h_)
        (C, n, m), hs = carry, jnp.stack(hs_l)
    else:
        (C, n, m), hs = jax.lax.scan(body, state, xs)
    return hs.swapaxes(0, 1).reshape(B, S, H, P), (C, n, m)


def mlstm_ref(q, k, v, li, lf, state):
    """Naive per-timestep oracle (tests)."""
    B, S, H, P = q.shape
    scale = 1.0 / (P ** 0.5)
    qf, kf, vf = (t.astype(jnp.float32) for t in (q, k, v))
    kf = kf * scale

    def step(carry, xs):
        C, n, m = carry
        qt, kt, vt, it, ft = xs
        m1 = jnp.maximum(ft + m, it)
        fp = jnp.exp(ft + m - m1)
        ip = jnp.exp(it - m1)
        C = C * fp[..., None, None] + ip[..., None, None] * (
            kt[..., :, None] * vt[..., None, :])
        n = n * fp[..., None] + ip[..., None] * kt
        num = jnp.einsum("bhp,bhpj->bhj", qt, C)
        den = jnp.einsum("bhp,bhp->bh", qt, n)
        h = num / jnp.maximum(jnp.abs(den), jnp.exp(-m1))[..., None]
        return (C, n, m1), h

    xs = tuple(t.swapaxes(0, 1) for t in (qf, kf, vf, li, lf))
    (C, n, m), hs = jax.lax.scan(step, state, xs)
    return hs.swapaxes(0, 1), (C, n, m)


def mlstm_forward(p, x, cfg, state: MLSTMState | None = None):
    """Full mLSTM block. x: (B,S,D) -> (y, new_state)."""
    xc = cfg.xlstm
    B, S, D = x.shape
    d_inner = int(xc.proj_factor * D)
    H = cfg.n_heads
    K = xc.conv1d_kernel

    proj = jnp.einsum("bsd,de->bse", x, p["in_proj"])
    xa, z = proj[..., :d_inner], proj[..., d_inner:]
    tail = K - 1
    conv_tail = (xa[:, -tail:, :] if S >= tail
                 else jnp.pad(xa, ((0, 0), (tail - S, 0), (0, 0))))
    left = state.conv if state is not None else None
    xc_act = _mlstm_conv(p, xa, K, left_ctx=left)
    q, k, v, li, lf = _mlstm_qkvif(p, xc_act)

    if state is not None:
        st = (state.C, state.n, state.m)
    else:
        st = (jnp.zeros((B, H, d_inner // H, d_inner // H), jnp.float32),
              jnp.zeros((B, H, d_inner // H), jnp.float32),
              jnp.full((B, H), -1e30, jnp.float32))
    h, (C, n, m) = mlstm_chunkwise(q, k, v, li, lf, st, xc.chunk,
                                   unroll=cfg.accounting)

    h = h.reshape(B, S, d_inner)
    h = h * jax.nn.silu(z.astype(jnp.float32))
    h = rmsnorm(p["norm"], h.astype(x.dtype), cfg.norm_eps)
    y = jnp.einsum("bse,ed->bsd", h, p["out_proj"])
    return y, MLSTMState(C=C, n=n, m=m, conv=conv_tail)


def mlstm_step(p, x, state: MLSTMState, cfg):
    """Single-token decode. x: (B,D). O(P^2) per head, O(1) in seq."""
    xc = cfg.xlstm
    B, D = x.shape
    d_inner = int(xc.proj_factor * D)
    H = cfg.n_heads
    P = d_inner // H

    proj = jnp.einsum("bd,de->be", x, p["in_proj"])
    xa, z = proj[..., :d_inner], proj[..., d_inner:]
    conv_in = jnp.concatenate([state.conv, xa[:, None, :]], axis=1)
    xc_act = jnp.einsum("bke,ke->be", conv_in, p["conv_w"]) + p["conv_b"]
    xc_act = jax.nn.silu(xc_act.astype(jnp.float32)).astype(x.dtype)

    q, k, v, li, lf = _mlstm_qkvif(p, xc_act[:, None, :])
    h, (C, n, m) = mlstm_ref(q, k, v, li, lf, (state.C, state.n, state.m))

    h = h[:, 0].reshape(B, d_inner) * jax.nn.silu(z.astype(jnp.float32))
    h = rmsnorm(p["norm"], h.astype(x.dtype), cfg.norm_eps)
    y = jnp.einsum("be,ed->bd", h, p["out_proj"])
    return y, MLSTMState(C=C, n=n, m=m, conv=conv_in[:, 1:, :])


# ======================================================================
# sLSTM
# ======================================================================


def slstm_spec(cfg):
    D, H = cfg.d_model, cfg.n_heads
    P = D // H
    dtype = jnp.dtype(cfg.dtype)
    # proj factor 4/3 rounded up to a multiple of 64 (as the released
    # xLSTM does) — also keeps the dim TP-shardable
    ff = -(-int(D * 4 / 3) // 64) * 64
    return {
        # input weights for the 4 gates (z, i, f, o)
        "w_in": ParamDef((D, 4 * D), dtype, ("embed", "inner_all")),
        # block-diagonal recurrent weights, per head: (4, H, P, P)
        "r": ParamDef((4, H, P, P), dtype, ("gates", "heads", "head_dim",
                                            "head_dim2")),
        "b": ParamDef((4, D), jnp.float32, ("gates", "embed"), zeros_init),
        "norm": rmsnorm_spec(D, dtype),
        # post-block gated FFN (proj factor 4/3 per xLSTM paper)
        "ff_norm": rmsnorm_spec(D, dtype),
        "ff_wi": ParamDef((D, ff), dtype, ("embed", "ffn")),
        "ff_wg": ParamDef((D, ff), dtype, ("embed", "ffn")),
        "ff_wo": ParamDef((ff, D), dtype, ("ffn", "embed")),
    }


class SLSTMState(NamedTuple):
    c: jax.Array    # (B, D) fp32
    n: jax.Array    # (B, D) fp32
    h: jax.Array    # (B, D) fp32
    m: jax.Array    # (B, D) fp32


def slstm_init_state(cfg, batch: int) -> SLSTMState:
    D = cfg.d_model
    z = jnp.zeros((batch, D), jnp.float32)
    return SLSTMState(c=z, n=z, h=z, m=jnp.full((batch, D), -1e30, jnp.float32))


def _slstm_cell(p, wx, st: SLSTMState, H, P):
    """wx: (B, 4D) precomputed input contribution; one recurrent step."""
    B = wx.shape[0]
    D = H * P
    h_heads = st.h.reshape(B, H, P)
    rh = jnp.einsum("bhp,ghpj->gbhj", h_heads.astype(jnp.float32),
                    p["r"].astype(jnp.float32)).reshape(4, B, D)
    pre = wx.astype(jnp.float32).reshape(B, 4, D).transpose(1, 0, 2) \
        + rh + p["b"][:, None, :]
    zt = jnp.tanh(pre[0])
    it = pre[1]                                  # log-domain input gate
    ft = jax.nn.log_sigmoid(pre[2])              # log-domain forget gate
    ot = jax.nn.sigmoid(pre[3])
    m1 = jnp.maximum(ft + st.m, it)
    fp = jnp.exp(ft + st.m - m1)
    ip = jnp.exp(it - m1)
    c1 = fp * st.c + ip * zt
    n1 = fp * st.n + ip
    h1 = ot * c1 / jnp.maximum(n1, jnp.exp(-m1))
    return SLSTMState(c=c1, n=n1, h=h1, m=m1)


def slstm_forward(p, x, cfg, state: SLSTMState | None = None):
    """Full sLSTM block (recurrent scan over time). x: (B,S,D)."""
    B, S, D = x.shape
    H = cfg.n_heads
    P = D // H
    if state is None:
        state = slstm_init_state(cfg, B)

    xn = rmsnorm(p["norm"], x, cfg.norm_eps)
    wx = jnp.einsum("bsd,de->bse", xn, p["w_in"])     # (B,S,4D) hoisted

    if cfg.accounting:
        # ACCOUNTING ONLY (lowered, never executed): replace the true
        # recurrence with a flop-equivalent parallel program so XLA
        # cost_analysis counts the S recurrent R-matmuls exactly once
        # each (a scan body would be counted once total).
        xh = xn.reshape(B, S, H, P).astype(jnp.float32)
        rh = jnp.einsum("bshp,ghpj->bsghj", xh,
                        p["r"].astype(jnp.float32)).reshape(B, S, 4 * D)
        pre = wx.astype(jnp.float32) + rh
        y = jnp.tanh(pre[..., :D]).astype(x.dtype)
        state = slstm_init_state(cfg, B)
    else:
        def step(st, wxt):
            st1 = _slstm_cell(p, wxt, st, H, P)
            return st1, st1.h

        state, hs = jax.lax.scan(step, state, wx.swapaxes(0, 1))
        y = hs.swapaxes(0, 1).astype(x.dtype)         # (B,S,D)

    # post-block gated FFN
    yn = rmsnorm(p["ff_norm"], x + y, cfg.norm_eps)
    f = jnp.einsum("bsd,df->bsf", yn, p["ff_wi"])
    g = jnp.einsum("bsd,df->bsf", yn, p["ff_wg"])
    f = jax.nn.silu(g.astype(jnp.float32)).astype(f.dtype) * f
    out = y + jnp.einsum("bsf,fd->bsd", f, p["ff_wo"])
    return out, state


def slstm_step(p, x, state: SLSTMState, cfg):
    """Single-token decode. x: (B,D)."""
    H, P = cfg.n_heads, cfg.d_model // cfg.n_heads
    xn = rmsnorm(p["norm"], x, cfg.norm_eps)
    wx = jnp.einsum("bd,de->be", xn, p["w_in"])
    st = _slstm_cell(p, wx, state, H, P)
    y = st.h.astype(x.dtype)
    yn = rmsnorm(p["ff_norm"], x + y, cfg.norm_eps)
    f = jnp.einsum("bd,df->bf", yn, p["ff_wi"])
    g = jnp.einsum("bd,df->bf", yn, p["ff_wg"])
    f = jax.nn.silu(g.astype(jnp.float32)).astype(f.dtype) * f
    out = y + jnp.einsum("bf,fd->bd", f, p["ff_wo"])
    return out, st
