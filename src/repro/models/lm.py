"""Model assembly: spec + train/prefill/decode for every assigned family.

Families:
  dense   — pre-norm GQA attn + MLP                      (tinyllama, granite,
                                                          qwen, deepseek-coder)
  moe     — attn (GQA or MLA) + top-k MoE FFN            (olmoe, deepseek-v3)
  hybrid  — Mamba2 stack + one *shared* attn block every
            k layers (Zamba2)                            (zamba2)
  ssm     — mLSTM stack with 1-in-k sLSTM layers         (xlstm)
  vlm     — dense decoder + vision-frontend stub prefix  (internvl2)
  audio   — encoder-decoder, audio-frontend stub         (seamless-m4t)

Conventions:
  * attn/mlp sub-blocks take the residual stream as ``residual=`` and
    return the updated stream (the add is fused into the Pallas
    epilogue on the 'pallas' dispatch backend); SSM/MoE sub-blocks
    still return the residual *delta*.  Pre-norms are applied by the
    caller (exception: sLSTM blocks norm internally).
  * layer stacks are stored stacked (L, ...) and iterated with lax.scan
    (cfg.scan_layers=False unrolls — used by the roofline accounting pass,
    since XLA cost_analysis counts while bodies once; DESIGN.md §8).
  * decode caches ride through the layer scan as xs/ys so a step touches
    each layer's cache exactly once.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.common import module as M
from repro.common.hints import shard_batch  # noqa: F401  (re-export)
from repro.kernels import dispatch as D
from repro.models import attention as A
from repro.models import layers as L
from repro.models import mla as MLA
from repro.models import moe as MOE
from repro.models import ssm as SSM
from repro.models import xlstm as XL


# ======================================================================
# norms
# ======================================================================

def _norm_spec(cfg):
    if cfg.norm == "layernorm":
        return L.layernorm_spec(cfg.d_model, jnp.dtype(cfg.dtype))
    return L.rmsnorm_spec(cfg.d_model, jnp.dtype(cfg.dtype))


def _norm(cfg, p, x):
    if cfg.norm == "layernorm":
        return L.layernorm(p, x, cfg.norm_eps)
    return L.rmsnorm(p, x, cfg.norm_eps)


# ======================================================================
# published scaling multipliers (ModelConfig: embedding_multiplier,
# attention_multiplier, residual_multiplier, logits_scaling)
# ======================================================================
# Every forward path applies them through these helpers.  Each tests
# the static config in Python: at the defaults it returns its input
# untouched, so an unscaled model traces exactly as without them.

def _embed(params, tokens, cfg):
    x = L.embed(params["embed"], tokens).astype(jnp.dtype(cfg.dtype))
    if cfg.embedding_multiplier != 1.0:
        x = x * cfg.embedding_multiplier
    return x


def _scale_q(cfg, q):
    """Queries after RoPE.  Scores are ``attention_multiplier * q.k``
    in place of ``q.k / sqrt(d_head)``: the ratio is folded into q, so
    every attention kernel keeps its 1/sqrt(d_head)."""
    if cfg.attention_multiplier is None:
        return q
    s = cfg.attention_multiplier * q.shape[-1] ** 0.5
    return (q.astype(jnp.float32) * s).astype(q.dtype)


def _branch(cfg, y):
    """A residual branch times ``residual_multiplier``: applied to the
    input of its last (linear) projection where there is one, so a
    residual add fused into that projection stays fused."""
    if cfg.residual_multiplier == 1.0:
        return y
    return y * cfg.residual_multiplier


def _mlp(cfg, p, norm_p, x, *, residual=None, mesh=None):
    """Pre-norm MLP branch, ``residual + mlp(norm(x))`` with
    ``residual``; the residual multiplier scales the down projection's
    input."""
    return L.mlp(p, _norm(cfg, norm_p, x), cfg.act, backend=cfg,
                 residual=residual, mesh=mesh,
                 scale=cfg.residual_multiplier)


# ======================================================================
# per-family layer specs
# ======================================================================

def _attn_spec(cfg):
    return MLA.mla_spec(cfg) if cfg.mla is not None else A.gqa_spec(cfg)


def _dense_layer_spec(cfg, d_ff=None):
    return {
        "attn_norm": _norm_spec(cfg),
        "attn": _attn_spec(cfg),
        "mlp_norm": _norm_spec(cfg),
        "mlp": L.mlp_spec(cfg.d_model, d_ff or cfg.d_ff, cfg.act,
                          jnp.dtype(cfg.dtype)),
    }


def _moe_layer_spec(cfg):
    return {
        "attn_norm": _norm_spec(cfg),
        "attn": _attn_spec(cfg),
        "mlp_norm": _norm_spec(cfg),
        "moe": MOE.moe_spec(cfg),
    }


def _encoder_layer_spec(cfg):
    return {
        "attn_norm": _norm_spec(cfg),
        "attn": A.gqa_spec(cfg),
        "mlp_norm": _norm_spec(cfg),
        "mlp": L.mlp_spec(cfg.d_model, cfg.d_ff, cfg.act, jnp.dtype(cfg.dtype)),
    }


def _decoder_xattn_layer_spec(cfg):
    return {
        "self_norm": _norm_spec(cfg),
        "self": A.gqa_spec(cfg),
        "cross_norm": _norm_spec(cfg),
        "cross": A.gqa_spec(cfg),
        "mlp_norm": _norm_spec(cfg),
        "mlp": L.mlp_spec(cfg.d_model, cfg.d_ff, cfg.act, jnp.dtype(cfg.dtype)),
    }


def _shared_attn_block_spec(cfg):
    """Zamba2 shared block: attn + MLP, one set of weights for the stack."""
    return {
        "attn_norm": _norm_spec(cfg),
        "attn": A.gqa_spec(cfg),
        "mlp_norm": _norm_spec(cfg),
        "mlp": L.mlp_spec(cfg.d_model, cfg.d_ff, cfg.act, jnp.dtype(cfg.dtype)),
    }


# hybrid (zamba2) group structure: n_layers mamba blocks in groups of
# `attn_every`, a shared-attn invocation after each group.
def _hybrid_groups(cfg):
    k = cfg.mamba2.attn_every
    n_main_groups = cfg.n_layers // k
    tail = cfg.n_layers - n_main_groups * k
    n_invocations = n_main_groups + (1 if tail else 0)
    return k, n_main_groups, tail, n_invocations


# ssm (xlstm) group structure: groups of (slstm_every-1 mLSTM + 1 sLSTM)
def _ssm_groups(cfg):
    k = cfg.xlstm.slstm_every
    assert cfg.n_layers % k == 0, (cfg.n_layers, k)
    n_groups = cfg.n_layers // k
    return k - 1, n_groups           # mlstm per group, group count


# ======================================================================
# model spec
# ======================================================================

def model_spec(cfg) -> Dict[str, Any]:
    dtype = jnp.dtype(cfg.dtype)
    spec: Dict[str, Any] = {
        "embed": L.embedding_spec(cfg.vocab_padded, cfg.d_model, dtype),
        "final_norm": _norm_spec(cfg),
    }
    if not cfg.tie_embeddings:
        spec["unembed"] = L.unembed_spec(cfg.vocab_padded, cfg.d_model,
                                         dtype)

    if cfg.frontend:
        spec["frontend"] = L.frontend_proj_spec(cfg.frontend_dim, cfg.d_model,
                                                dtype)

    fam = cfg.family
    if fam in ("dense", "vlm"):
        spec["layers"] = M.stack_specs(_dense_layer_spec(cfg), cfg.n_layers)
    elif fam == "moe":
        m = cfg.moe
        if m.first_k_dense:
            spec["dense_layers"] = M.stack_specs(
                _dense_layer_spec(cfg, d_ff=m.d_ff_dense or cfg.d_ff),
                m.first_k_dense)
        spec["layers"] = M.stack_specs(_moe_layer_spec(cfg),
                                       cfg.n_layers - m.first_k_dense)
    elif fam == "hybrid":
        k, n_main, tail, _ = _hybrid_groups(cfg)
        spec["mamba_main"] = M.stack_specs(
            M.stack_specs(SSM.mamba2_spec(cfg), k), n_main)
        spec["mamba_norms"] = M.stack_specs(
            M.stack_specs(_norm_spec(cfg), k), n_main)
        if tail:
            spec["mamba_tail"] = M.stack_specs(SSM.mamba2_spec(cfg), tail)
            spec["tail_norms"] = M.stack_specs(_norm_spec(cfg), tail)
        spec["shared_attn"] = _shared_attn_block_spec(cfg)
    elif fam == "ssm":
        m_per, n_groups = _ssm_groups(cfg)
        spec["mlstm"] = M.stack_specs(
            M.stack_specs(XL.mlstm_spec(cfg), m_per), n_groups)
        spec["mlstm_norms"] = M.stack_specs(
            M.stack_specs(_norm_spec(cfg), m_per), n_groups)
        spec["slstm"] = M.stack_specs(XL.slstm_spec(cfg), n_groups)
    elif fam == "audio":
        spec["enc_layers"] = M.stack_specs(_encoder_layer_spec(cfg),
                                           cfg.enc_layers)
        spec["enc_norm"] = _norm_spec(cfg)
        spec["layers"] = M.stack_specs(_decoder_xattn_layer_spec(cfg),
                                       cfg.n_layers)
    else:
        raise ValueError(f"unknown family {fam}")
    return spec


def abstract_init(cfg):
    return M.abstract_params(model_spec(cfg))


def init(cfg, key):
    return M.init_params(model_spec(cfg), key)


# ======================================================================
# remat / scan plumbing
# ======================================================================

def _maybe_remat(cfg, fn):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "dots":
        policy = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        return jax.checkpoint(fn, policy=policy)
    return jax.checkpoint(fn)


def _scan_stack(cfg, body, x, stacked, extra_xs=None, length=None):
    """Run `body(x, layer_params, extra) -> (x, y)` over a stacked tree.

    (H8, measured: per-layer batch pins fix the backward batch-
    sharding loss but force 560 GB of re-gathers — refuted; the
    single entry pin in `backbone` is the kept variant.)"""
    body = _maybe_remat(cfg, body)
    if cfg.scan_layers:
        def f(c, xs):
            lp, ex = xs
            return body(c, lp, ex)
        xs = (stacked, extra_xs)
        return jax.lax.scan(f, x, xs, length=length)
    # unrolled (accounting / debugging)
    n = length
    if n is None:
        n = jax.tree.leaves(stacked)[0].shape[0]
    ys = []
    for i in range(n):
        lp = jax.tree.map(lambda a: a[i], stacked)
        ex = None if extra_xs is None else jax.tree.map(
            lambda a: a[i], extra_xs)
        x, y = body(x, lp, ex)
        ys.append(y)
    if ys and ys[0] is not None:
        ys = jax.tree.map(lambda *t: jnp.stack(t), *ys)
    else:
        ys = None
    return x, ys


# ======================================================================
# layer bodies (training / prefill)
# ======================================================================

def _attn_delta(cfg, ap, h, positions, *, causal=True, residual=None,
                mesh=None):
    """h already normed; ap = attention param subtree.

    Returns (residual + attn(h) if residual is given else attn(h),
    (k, v)) for cache building.  The residual add is fused into the
    output projection's final-K store on the pallas kernel path.  All
    implementation choice goes through the dispatch registry
    (cfg.kernel_impl selects the backend: 'xla' | 'pallas' | 'auto')."""
    if cfg.mla is not None:
        out, cache = MLA.mla_attention(ap, h, positions, cfg, causal=causal,
                                       dense=cfg.accounting,
                                       head_axis=_head_axis(cfg),
                                       mesh=mesh)
        out = _branch(cfg, out)
        return (out if residual is None else residual + out), cache
    with jax.named_scope("attn.qkv"):
        q, k, v = A.qkv_proj(ap, h, positions, cfg.rope_theta, cfg,
                             mesh=mesh)
        q = _scale_q(cfg, q)
    if cfg.accounting:
        o = A.full_attn_ref(q, k, v, causal=causal, q_positions=positions,
                            kv_positions=positions)
    else:
        o = D.dispatch("attention", cfg, q, k, v, causal=causal,
                       q_positions=positions, kv_positions=positions,
                       block_q=cfg.attn_block_q,
                       block_kv=cfg.attn_block_kv,
                       head_axis=_head_axis(cfg), mesh=mesh)
    with jax.named_scope("attn.out"):
        out = A.o_proj(ap, _branch(cfg, o), cfg, residual=residual,
                       mesh=mesh)
    return out, (k, v)


def _head_axis(cfg):
    """Mesh axis carrying kv heads in the activation layout (None when
    heads are replicated, e.g. the 'ddp' strategy)."""
    if cfg.sharding_strategy == "ddp":
        return None
    return "model"


def _dense_body(cfg, positions, x, lp, _ex, *, causal=True, collect=False,
                mesh=None):
    x, kv = _attn_delta(cfg, lp["attn"], _norm(cfg, lp["attn_norm"], x),
                        positions, causal=causal, residual=x, mesh=mesh)
    with jax.named_scope("mlp"):
        x = _mlp(cfg, lp["mlp"], lp["mlp_norm"], x, residual=x, mesh=mesh)
    return x, (kv if collect else None)


def _moe_body(cfg, positions, x, lp, _ex, *, collect=False, mesh=None):
    x, kv = _attn_delta(cfg, lp["attn"], _norm(cfg, lp["attn_norm"], x),
                        positions, residual=x, mesh=mesh)
    with jax.named_scope("mlp"):
        y, aux = MOE.moe_ffn(lp["moe"], _norm(cfg, lp["mlp_norm"], x), cfg,
                              mesh=mesh)
    return x + _branch(cfg, y), ((kv if collect else None), aux)


def _xattn_body(cfg, positions, enc_out, enc_valid, x, lp, _ex, *,
                collect=False, mesh=None):
    """Encoder-decoder decoder layer (training/prefill)."""
    x, kv = _attn_delta(cfg, lp["self"], _norm(cfg, lp["self_norm"], x),
                        positions, residual=x, mesh=mesh)
    h = _norm(cfg, lp["cross_norm"], x)
    q = _scale_q(cfg, jnp.einsum("bsd,dhk->bshk", h, lp["cross"]["wq"]))
    k = jnp.einsum("bsd,dhk->bshk", enc_out, lp["cross"]["wk"])
    v = jnp.einsum("bsd,dhk->bshk", enc_out, lp["cross"]["wv"])
    if cfg.accounting:
        o = A.full_attn_ref(q, k, v, causal=False, kv_valid=enc_valid)
    else:
        o = A.blockwise_attn(q, k, v, causal=False, kv_valid=enc_valid,
                             block_q=cfg.attn_block_q,
                             block_kv=cfg.attn_block_kv, mesh=mesh)
    x = A.o_proj(lp["cross"], _branch(cfg, o), cfg, residual=x, mesh=mesh)
    x = _mlp(cfg, lp["mlp"], lp["mlp_norm"], x, residual=x, mesh=mesh)
    return x, ((kv, (k, v)) if collect else None)


def _shared_attn_apply(cfg, sp, x, positions, *, collect=False, mesh=None):
    x, kv = _attn_delta(cfg, sp["attn"], _norm(cfg, sp["attn_norm"], x),
                        positions, residual=x, mesh=mesh)
    x = _mlp(cfg, sp["mlp"], sp["mlp_norm"], x, residual=x, mesh=mesh)
    return x, (kv if collect else None)


# ======================================================================
# backbone forward (training / prefill): tokens -> final hidden states
# ======================================================================

class ForwardOut(NamedTuple):
    h: jax.Array                      # (B, S, D) final hidden (post-norm)
    aux: Dict[str, jax.Array]         # scalar aux metrics (moe losses, ...)
    caches: Any                       # per-family cache material (prefill)


def backbone(params, tokens, cfg, *, frontend_emb=None,
             enc_tokens_valid=None, collect_cache=False,
             mesh=None) -> ForwardOut:
    """tokens: (B, S_text) int32. frontend_emb: (B, S_f, fe_dim) or None.

    For 'audio', frontend_emb is the ENCODER input sequence and tokens are
    decoder tokens.  For 'vlm', frontend embeddings are projected and
    prepended to the token embeddings (sequence = S_f + S_text).
    ``collect_cache=True`` (prefill) additionally returns the per-layer
    cache material (KV stacks / recurrent final states).  ``mesh`` (the
    engine passes it) resolves the internal sharding hints explicitly
    instead of through the deprecated ambient-mesh lookup.
    """
    fam = cfg.family
    cc = collect_cache
    aux: Dict[str, jax.Array] = {}
    caches: Any = None

    x = _embed(params, tokens, cfg)
    if fam == "vlm":
        pre = L.frontend_proj(params["frontend"], frontend_emb)
        x = jnp.concatenate([pre.astype(x.dtype), x], axis=1)
    # NOTE: batch-pinning x here (H8b) trades -183 GB all-reduce for
    # +495 GB all-gather on the fixed (16,16) mesh — net worse on the
    # ICI roofline, big HBM win (bytes_accessed -73%); kept OFF, see
    # EXPERIMENTS.md §Perf H8.
    S = x.shape[1]
    positions = jnp.arange(S)

    if fam in ("dense", "vlm"):
        body = functools.partial(_dense_body, cfg, positions, collect=cc,
                                 mesh=mesh)
        x, kvs = _scan_stack(cfg, body, x, params["layers"])
        caches = kvs

    elif fam == "moe":
        m = cfg.moe
        kv_d = None
        if m.first_k_dense:
            body = functools.partial(_dense_body, cfg, positions,
                                     collect=cc, mesh=mesh)
            x, kv_d = _scan_stack(cfg, body, x, params["dense_layers"])
        body = functools.partial(_moe_body, cfg, positions, collect=cc,
                                 mesh=mesh)
        x, (kv_m, moe_aux) = _scan_stack(cfg, body, x, params["layers"])
        aux["lb_loss"] = jnp.mean(moe_aux["lb_loss"])
        aux["z_loss_router"] = jnp.mean(moe_aux["z_loss"])
        aux["drop_frac"] = jnp.mean(moe_aux["drop_frac"])
        caches = (kv_d, kv_m)

    elif fam == "hybrid":
        k, n_main, tail, _ = _hybrid_groups(cfg)
        sp = params["shared_attn"]

        def mamba_body(x, lp, ex):
            d, st = SSM.mamba2_forward(lp, _norm(cfg, ex, x), cfg)
            return x + _branch(cfg, d), (st if cc else None)

        def group_body(x, gp, gn):
            x, sts = _scan_stack(cfg, mamba_body, x, gp, extra_xs=gn)
            x, kv = _shared_attn_apply(cfg, sp, x, positions, collect=cc,
                                       mesh=mesh)
            return x, (sts, kv)

        x, (st_main, kv_main) = _scan_stack(
            cfg, group_body, x, params["mamba_main"],
            extra_xs=params["mamba_norms"])
        st_tail = kv_tail = None
        if tail:
            x, st_tail = _scan_stack(cfg, mamba_body, x, params["mamba_tail"],
                                     extra_xs=params["tail_norms"])
            x, kv_tail = _shared_attn_apply(cfg, sp, x, positions,
                                            collect=cc, mesh=mesh)
        caches = ((st_main, kv_main), (st_tail, kv_tail))

    elif fam == "ssm":
        def ml_body(x, lp, ex):
            d, st = XL.mlstm_forward(lp, _norm(cfg, ex, x), cfg)
            return x + _branch(cfg, d), (st if cc else None)

        def group_body(x, gp, _ex):
            x, m_sts = _scan_stack(cfg, ml_body, x, gp["m"], extra_xs=gp["n"])
            d, s_st = XL.slstm_forward(gp["s"], x, cfg)
            return x + _branch(cfg, d), ((m_sts, s_st) if cc else None)

        stacked = {"m": params["mlstm"], "n": params["mlstm_norms"],
                   "s": params["slstm"]}
        x, caches = _scan_stack(cfg, group_body, x, stacked)

    elif fam == "audio":
        enc = L.frontend_proj(params["frontend"], frontend_emb)
        enc = enc.astype(jnp.dtype(cfg.dtype))
        enc_pos = jnp.arange(enc.shape[1])
        body = functools.partial(_dense_body, cfg, enc_pos, causal=False,
                                 mesh=mesh)
        enc, _ = _scan_stack(cfg, body, enc, params["enc_layers"])
        enc = _norm(cfg, params["enc_norm"], enc)

        body = functools.partial(_xattn_body, cfg, positions, enc,
                                 enc_tokens_valid, collect=cc, mesh=mesh)
        x, caches = _scan_stack(cfg, body, x, params["layers"])

    else:
        raise ValueError(fam)

    x = _norm(cfg, params["final_norm"], x)
    return ForwardOut(h=x, aux=aux, caches=caches)


# ======================================================================
# loss
# ======================================================================

def _logits(params, h, cfg):
    if cfg.tie_embeddings:
        logits = jnp.einsum("...d,vd->...v", h, params["embed"]["table"])
    else:
        logits = L.unembed(params["unembed"], h)
    if cfg.logits_scaling != 1.0:
        logits = logits / cfg.logits_scaling
    if cfg.vocab_padded != cfg.vocab:
        pad_mask = jnp.arange(cfg.vocab_padded) >= cfg.vocab
        logits = jnp.where(pad_mask, -1e30, logits.astype(jnp.float32)
                           ).astype(logits.dtype)
    return logits


def ce_loss(params, h, labels, mask, cfg) -> Tuple[jax.Array, Dict]:
    """Cross-entropy over (B,S,D) hiddens, optionally chunked along S.

    The unembedding is vocab-sharded ('model' axis); logsumexp and the
    label-logit gather over the sharded vocab dim lower to partial
    reductions + a small all-reduce under GSPMD (vocab-parallel CE).
    """
    B, S, D = h.shape
    C = cfg.logits_chunk or S
    C = min(C, S)
    pad = (-S) % C
    if pad:
        h = jnp.pad(h, ((0, 0), (0, pad), (0, 0)))
        labels = jnp.pad(labels, ((0, 0), (0, pad)))
        mask = jnp.pad(mask, ((0, 0), (0, pad)))
    nchunk = h.shape[1] // C

    def one_chunk(hc, lc, mc):
        logits = _logits(params, hc, cfg).astype(jnp.float32)
        lz = jax.nn.logsumexp(logits, axis=-1)                 # (B,C)
        # label pick via a masked sum over the (model-sharded) vocab
        # dim: GSPMD reduces fp32 (B,C) partials with a tiny psum.
        # (take_along_axis over a sharded dim lowers to an all-reduce
        # of the FULL fp32 logits — measured 8-40 GB/device/step;
        # EXPERIMENTS.md §Perf H1.)
        hit = jnp.arange(logits.shape[-1]) == lc[..., None]
        ll = jnp.sum(jnp.where(hit, logits, 0.0), axis=-1)
        loss = (lz - ll) * mc
        zl = (lz * lz) * mc
        return loss.sum(), zl.sum()

    if nchunk == 1:
        loss_sum, z_sum = one_chunk(h, labels, mask)
    else:
        hs = h.reshape(B, nchunk, C, D).swapaxes(0, 1)
        ls = labels.reshape(B, nchunk, C).swapaxes(0, 1)
        ms = mask.reshape(B, nchunk, C).swapaxes(0, 1)
        if cfg.scan_layers:
            def step(acc, xs):
                a, b = one_chunk(*xs)
                return (acc[0] + a, acc[1] + b), None
            (loss_sum, z_sum), _ = jax.lax.scan(
                step, (jnp.zeros(()), jnp.zeros(())), (hs, ls, ms))
        else:
            loss_sum = z_sum = jnp.zeros(())
            for i in range(nchunk):
                a, b = one_chunk(hs[i], ls[i], ms[i])
                loss_sum, z_sum = loss_sum + a, z_sum + b

    denom = jnp.maximum(mask.sum(), 1.0)
    return loss_sum / denom, {"z_loss": z_sum / denom}


def train_loss(params, batch, cfg) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """batch: tokens (B,S), labels (B,S), loss_mask (B,S) [+ frontend_emb]."""
    # the registry knows which backends differentiate: 'auto' narrows
    # to the differentiable set, a forward-only pin ('pallas') raises
    cfg = cfg.replace(kernel_impl=D.training_backend(cfg))
    out = backbone(params, batch["tokens"], cfg,
                   frontend_emb=batch.get("frontend_emb"))
    labels, mask = batch["labels"], batch["loss_mask"].astype(jnp.float32)
    if cfg.family == "vlm":
        # hidden seq = frontend prefix + text; loss only on text part
        nf = batch["frontend_emb"].shape[1]
        h = out.h[:, nf:, :]
    else:
        h = out.h
    loss, lmx = ce_loss(params, h, labels, mask, cfg)
    metrics = {"ce": loss, **lmx, **out.aux}
    total = loss + cfg.z_loss_coef * lmx["z_loss"]
    if "lb_loss" in out.aux:
        total = total + cfg.lb_coef * out.aux["lb_loss"] \
            + cfg.router_z_coef * out.aux["z_loss_router"]
    metrics["loss"] = total
    return total, metrics


# ======================================================================
# prefill / decode (serving)
# ======================================================================

def _gqa_cache_shape(cfg, B, T):
    return (B, T, cfg.n_kv_heads, cfg.d_head)


def cache_spec(cfg, batch: int, max_len: int, enc_len: int = 0):
    """ShapeDtypeStruct tree for the decode cache (dry-run / allocation)."""
    fam = cfg.family
    dt_ = jnp.dtype(cfg.dtype)
    f32 = jnp.float32

    def sds(shape, dtype=dt_):
        return jax.ShapeDtypeStruct(shape, dtype)

    if fam in ("dense", "vlm"):
        if cfg.mla is not None:
            m = cfg.mla
            return {"ckv": sds((cfg.n_layers, batch, max_len, m.kv_lora_rank)),
                    "krope": sds((cfg.n_layers, batch, max_len,
                                  m.rope_head_dim))}
        sh = _gqa_cache_shape(cfg, batch, max_len)
        return {"k": sds((cfg.n_layers, *sh)), "v": sds((cfg.n_layers, *sh))}

    if fam == "moe":
        m = cfg.moe
        n_moe = cfg.n_layers - m.first_k_dense
        if cfg.mla is not None:
            ml = cfg.mla

            def mla_c(L):
                return {"ckv": sds((L, batch, max_len, ml.kv_lora_rank)),
                        "krope": sds((L, batch, max_len, ml.rope_head_dim))}
            return {"dense": mla_c(m.first_k_dense) if m.first_k_dense else None,
                    "moe": mla_c(n_moe)}
        sh = _gqa_cache_shape(cfg, batch, max_len)

        def gqa_c(L):
            return {"k": sds((L, *sh)), "v": sds((L, *sh))}
        return {"dense": gqa_c(m.first_k_dense) if m.first_k_dense else None,
                "moe": gqa_c(n_moe)}

    if fam == "hybrid":
        mc = cfg.mamba2
        k, n_main, tail, n_inv = _hybrid_groups(cfg)
        d_inner = mc.expand * cfg.d_model
        H = d_inner // mc.head_dim
        d_xbc = d_inner + 2 * mc.n_groups * mc.d_state
        sh = _gqa_cache_shape(cfg, batch, max_len)

        def mstate(*lead):
            return SSM.Mamba2State(
                ssm=sds((*lead, batch, H, mc.d_state, mc.head_dim), f32),
                conv=sds((*lead, batch, mc.d_conv - 1, d_xbc)))
        return {
            "mamba_main": mstate(n_main, k),
            "mamba_tail": mstate(tail) if tail else None,
            "attn_k": sds((n_inv, *sh)), "attn_v": sds((n_inv, *sh)),
        }

    if fam == "ssm":
        xc = cfg.xlstm
        m_per, n_groups = _ssm_groups(cfg)
        d_inner = int(xc.proj_factor * cfg.d_model)
        H = cfg.n_heads
        P = d_inner // H
        return {
            "mlstm": XL.MLSTMState(
                C=sds((n_groups, m_per, batch, H, P, P), f32),
                n=sds((n_groups, m_per, batch, H, P), f32),
                m=sds((n_groups, m_per, batch, H), f32),
                conv=sds((n_groups, m_per, batch, xc.conv1d_kernel - 1,
                          d_inner))),
            "slstm": XL.SLSTMState(
                c=sds((n_groups, batch, cfg.d_model), f32),
                n=sds((n_groups, batch, cfg.d_model), f32),
                h=sds((n_groups, batch, cfg.d_model), f32),
                m=sds((n_groups, batch, cfg.d_model), f32)),
        }

    if fam == "audio":
        sh = _gqa_cache_shape(cfg, batch, max_len)
        xh = _gqa_cache_shape(cfg, batch, enc_len or max_len)
        return {"self_k": sds((cfg.n_layers, *sh)),
                "self_v": sds((cfg.n_layers, *sh)),
                "cross_k": sds((cfg.n_layers, *xh)),
                "cross_v": sds((cfg.n_layers, *xh))}

    raise ValueError(fam)


def init_cache(cfg, batch: int, max_len: int, enc_len: int = 0):
    spec = cache_spec(cfg, batch, max_len, enc_len)
    cache = jax.tree.map(
        lambda s: jnp.zeros(s.shape, s.dtype), spec,
        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))
    # mLSTM / sLSTM stabilizers start at -inf-ish
    if cfg.family == "ssm":
        cache["mlstm"] = cache["mlstm"]._replace(
            m=jnp.full_like(cache["mlstm"].m, -1e30))
        cache["slstm"] = cache["slstm"]._replace(
            m=jnp.full_like(cache["slstm"].m, -1e30))
    return cache


# ---------------- decode attention helpers ----------------

def _rope1(x, pos, theta):
    """x: (B,H,Dh) one token at scalar position pos."""
    return L.apply_rope(x[:, None], jnp.asarray(pos)[None], theta)[:, 0]


def _decode_attend(cfg, q, ck, cv, n_valid, mesh=None):
    """Decode attention: GQA, absorbed MLA and cross-attention all pass
    through here, and from here through ``dist.decode`` — distributed
    FlashDecoding when the cache is sequence-sharded
    (cfg.decode_shard == 'seq' and a mesh was passed), the shard-local
    ``decode_partial`` registry op (cfg.kernel_impl selects 'xla' |
    'pallas' | 'auto') otherwise."""
    from repro.dist import decode as DD
    return DD.decode_attend(q, ck, cv, n_valid, backend=cfg.kernel_impl,
                            mesh=mesh,
                            seq_shard=(cfg.decode_shard == "seq"))


def _decode_gqa(cfg, lp, h, ck, cv, cur_len, mesh=None):
    """h: (B,D) normed. ck/cv: (B,T,KV,Dh). Returns (delta, ck, cv)."""
    B = h.shape[0]
    q = jnp.einsum("bd,dhk->bhk", h, lp["wq"])
    k = jnp.einsum("bd,dhk->bhk", h, lp["wk"])
    v = jnp.einsum("bd,dhk->bhk", h, lp["wv"])
    if "bq" in lp:
        q, k, v = q + lp["bq"], k + lp["bk"], v + lp["bv"]
    q = _scale_q(cfg, _rope1(q, cur_len, cfg.rope_theta))
    k = _rope1(k, cur_len, cfg.rope_theta)
    ck = jax.lax.dynamic_update_slice(ck, k[:, None], (0, cur_len, 0, 0))
    cv = jax.lax.dynamic_update_slice(cv, v[:, None], (0, cur_len, 0, 0))
    o = _decode_attend(cfg, q, ck, cv, cur_len + 1, mesh)
    delta = jnp.einsum("bhk,hkd->bd", _branch(cfg, o), lp["wo"])
    return delta, ck, cv


def _decode_mla(cfg, lp, h, cckv, ckr, cur_len, mesh=None):
    """MLA absorbed decode. cckv: (B,T,r); ckr: (B,T,rope).

    Split-operand path: q_nope is folded through wk_b
    (``MLA.mla_absorbed_queries``) and the latent + rope caches ride
    as SEPARATE operands through ``dist.decode.mla_decode_attend`` —
    the ``decode_partial_mla`` registry op locally (VWR split-operand
    flash-decode kernel, 'auto' dispatch) and the same pmax/psum
    combine sequence-sharded.  No per-step k_cat/v_cat cache copies,
    no rope zero-pad in the value stream: staged cache bytes per token
    drop from 2*(r+rope) to r+rope features/position."""
    from repro.dist import decode as DD
    h3 = h[:, None, :]
    pos = jnp.asarray(cur_len)[None]
    q_nope, q_rope = MLA.mla_queries(lp, h3, pos, cfg)
    c_kv, k_rope = MLA.mla_latent(lp, h3, pos, cfg)
    cckv = jax.lax.dynamic_update_slice(cckv, c_kv, (0, cur_len, 0))
    ckr = jax.lax.dynamic_update_slice(ckr, k_rope, (0, cur_len, 0))
    q_abs, q_rope_f, scale = MLA.mla_absorbed_queries(
        lp, q_nope[:, 0], q_rope[:, 0], cfg)
    o = DD.mla_decode_attend(q_abs, q_rope_f, cckv, ckr, cur_len + 1,
                             scale=scale, backend=cfg.kernel_impl,
                             mesh=mesh,
                             seq_shard=(cfg.decode_shard == "seq"))
    delta = _branch(cfg, MLA.mla_decode_finish(lp, o.astype(jnp.float32),
                                               cfg))
    return delta.astype(h.dtype), cckv, ckr


def _decode_cross(cfg, lp, h, xk, xv, mesh=None):
    """Cross-attention against the (static) encoder KV cache."""
    q = _scale_q(cfg, jnp.einsum("bd,dhk->bhk", h, lp["wq"]))
    T = xk.shape[1]
    o = _decode_attend(cfg, q, xk, xv, jnp.int32(T), mesh)
    return jnp.einsum("bhk,hkd->bd", _branch(cfg, o), lp["wo"])


# ---------------- paged decode (block-table + per-slot lengths) -------

def _rope_slots(x, pos, theta):
    """x: (B,H,Dh) one token per slot at per-slot positions pos (B,)."""
    return L.apply_rope(x[:, None], pos[:, None], theta)[:, 0]


def _paged_attend(cfg, q, k_pool, v_pool, table, n_valid, mesh=None,
                  k_scale=None, v_scale=None):
    """Paged decode attention: GQA, absorbed MLA and (identity-paged)
    cross-attention all route through ``dist.decode.paged_decode_attend``
    — the pool-sharded FlashDecoding combine when
    cfg.decode_shard == 'seq', the shard-local ``decode_partial_paged``
    registry op otherwise.  ``n_valid`` (B,) counts valid positions per
    slot (0 = inactive slot); ``k_scale``/``v_scale`` ((n_pages, KV)
    fp32) select the q8 route over int8 pools."""
    from repro.dist import decode as DD
    return DD.paged_decode_attend(q, k_pool, v_pool, table, n_valid,
                                  k_scale=k_scale, v_scale=v_scale,
                                  backend=cfg.kernel_impl, mesh=mesh,
                                  seq_shard=(cfg.decode_shard == "seq"))


def _page_write_ids(table, lens, page_size, n_pages):
    """Physical (page, offset) each slot's new token writes to; inactive
    slots (lens == 0) get page id ``n_pages`` so mode='drop' scatters
    discard the write instead of corrupting page table[b, 0]."""
    active = lens > 0
    pages = jnp.take_along_axis(table, (lens // page_size)[:, None],
                                axis=1)[:, 0]
    pages = jnp.where(active, pages, n_pages)
    return pages, lens % page_size, lens + active.astype(lens.dtype)


def _decode_gqa_paged(cfg, lp, h, kp, vp, table, lens, mesh=None,
                      kscale=None, vscale=None):
    """h: (B,D) normed; kp/vp: (n_pages, KV, ps, Dh) pools; lens: (B,)
    per-slot valid positions (the new token writes at position lens).
    With ``kscale``/``vscale`` ((n_pages, KV) fp32 sidecars) the pools
    are int8: the token quantizes on write and attention dequantizes
    in-kernel.  Returns (delta, kp, vp, kscale, vscale)."""
    n_pages, ps = kp.shape[0], kp.shape[2]
    with jax.named_scope("attn.qkv"):
        q = jnp.einsum("bd,dhk->bhk", h, lp["wq"])
        k = jnp.einsum("bd,dhk->bhk", h, lp["wk"])
        v = jnp.einsum("bd,dhk->bhk", h, lp["wv"])
        if "bq" in lp:
            q, k, v = q + lp["bq"], k + lp["bk"], v + lp["bv"]
        q = _scale_q(cfg, _rope_slots(q, lens, cfg.rope_theta))
        k = _rope_slots(k, lens, cfg.rope_theta)
    with jax.named_scope("attn.kv_write"):
        pages, offs, n_valid = _page_write_ids(table, lens, ps, n_pages)
        if kscale is not None:
            from repro.engine import paged_cache as PC
            kp, kscale = PC.quantized_page_write(kp, kscale, pages, offs,
                                                 k)
            vp, vscale = PC.quantized_page_write(vp, vscale, pages, offs,
                                                 v)
        else:
            kp = kp.at[pages, :, offs].set(k.astype(kp.dtype),
                                           mode="drop")
            vp = vp.at[pages, :, offs].set(v.astype(vp.dtype),
                                           mode="drop")
    with jax.named_scope("attn.paged"):
        o = _paged_attend(cfg, q, kp, vp, table, n_valid, mesh,
                          k_scale=kscale, v_scale=vscale)
    with jax.named_scope("attn.out"):
        delta = jnp.einsum("bhk,hkd->bd", _branch(cfg, o), lp["wo"])
    return delta, kp, vp, kscale, vscale


def _decode_mla_paged(cfg, lp, h, ckv_pool, krope_pool, table, lens,
                      mesh=None, ckv_scale=None, krope_scale=None):
    """MLA absorbed decode against paged latent pools: ckv_pool
    (n_pages, ps, r); krope_pool (n_pages, ps, rope).

    Split-operand path: the two pools ride SEPARATELY through
    ``dist.decode.mla_paged_decode_attend`` — the
    ``decode_partial_mla_paged`` registry op stages only the block
    table's pages (scalar-prefetch on the pallas backend), where the
    concat view used to copy the whole POOL into k_cat/v_cat every
    step.  With ``ckv_scale``/``krope_scale`` ((n_pages,) fp32) the
    pools are int8, quantized on write and dequantized in-kernel."""
    from repro.dist import decode as DD
    n_pages, ps = ckv_pool.shape[0], ckv_pool.shape[1]
    h3 = h[:, None, :]
    pos = lens[:, None]
    q_nope, q_rope = MLA.mla_queries(lp, h3, pos, cfg)
    c_kv, k_rope = MLA.mla_latent(lp, h3, pos, cfg)
    pages, offs, n_valid = _page_write_ids(table, lens, ps, n_pages)
    if ckv_scale is not None:
        from repro.engine import paged_cache as PC
        ckv_pool, ckv_scale = PC.quantized_page_write(
            ckv_pool, ckv_scale, pages, offs, c_kv[:, 0])
        krope_pool, krope_scale = PC.quantized_page_write(
            krope_pool, krope_scale, pages, offs, k_rope[:, 0])
    else:
        ckv_pool = ckv_pool.at[pages, offs].set(
            c_kv[:, 0].astype(ckv_pool.dtype), mode="drop")
        krope_pool = krope_pool.at[pages, offs].set(
            k_rope[:, 0].astype(krope_pool.dtype), mode="drop")
    q_abs, q_rope_f, scale = MLA.mla_absorbed_queries(
        lp, q_nope[:, 0], q_rope[:, 0], cfg)
    o = DD.mla_paged_decode_attend(q_abs, q_rope_f, ckv_pool,
                                   krope_pool, table, n_valid,
                                   scale=scale, ckv_scale=ckv_scale,
                                   krope_scale=krope_scale,
                                   backend=cfg.kernel_impl,
                                   mesh=mesh,
                                   seq_shard=(cfg.decode_shard == "seq"))
    delta = _branch(cfg, MLA.mla_decode_finish(lp, o.astype(jnp.float32),
                                               cfg))
    return delta.astype(h.dtype), ckv_pool, krope_pool, ckv_scale, \
        krope_scale


def _decode_cross_paged(cfg, lp, h, xk, xv, enc_lens, page_size,
                        mesh=None):
    """Cross-attention against the slot-dense encoder cache, VIEWED as
    an identity-paged pool (slot b's pages are rows [b*Jx, (b+1)*Jx) of
    the reshaped cache, turned head-major like the self-attention
    pools), so per-slot encoder lengths ride the same paged masking as
    self-attention.  Cross KV is static per slot and attended
    shard-locally."""
    from repro.dist import decode as DD
    B, Tx = xk.shape[0], xk.shape[1]
    Jx = Tx // page_size

    def pool_view(x):                 # (B, Tx, KV, Dh) -> (B*Jx, KV, ps, Dh)
        return jnp.swapaxes(
            x.reshape(B * Jx, page_size, *x.shape[2:]), 1, 2)

    kp, vp = pool_view(xk), pool_view(xv)
    tbl = (jnp.arange(B, dtype=jnp.int32)[:, None] * Jx
           + jnp.arange(Jx, dtype=jnp.int32)[None, :])
    q = _scale_q(cfg, jnp.einsum("bd,dhk->bhk", h, lp["wq"]))
    o = DD.paged_decode_attend(q, kp, vp, tbl, enc_lens,
                               backend=cfg.kernel_impl, mesh=mesh,
                               seq_shard=False)
    return jnp.einsum("bhk,hkd->bd", _branch(cfg, o), lp["wo"])


def _paged_attn_delta(cfg, lens, table, h, lp, cache_slice, mesh):
    """Shared attention step of the paged layer bodies: routes MLA vs
    GQA, detects int8 pools by their scale sidecars in the cache
    slice, and returns (delta, updated cache slice)."""
    if cfg.mla is not None:
        d, ckv, ckr, cs, rs = _decode_mla_paged(
            cfg, lp["attn"], h, cache_slice["ckv"],
            cache_slice["krope"], table, lens, mesh,
            cache_slice.get("ckv_scale"), cache_slice.get("krope_scale"))
        new = {"ckv": ckv, "krope": ckr}
        if cs is not None:
            new["ckv_scale"], new["krope_scale"] = cs, rs
    else:
        d, kp, vp, ks, vs = _decode_gqa_paged(
            cfg, lp["attn"], h, cache_slice["k"], cache_slice["v"],
            table, lens, mesh, cache_slice.get("k_scale"),
            cache_slice.get("v_scale"))
        new = {"k": kp, "v": vp}
        if ks is not None:
            new["k_scale"], new["v_scale"] = ks, vs
    return d, new


def _dense_paged_body(cfg, lens, table, x, lp, cache_slice, mesh=None):
    h = _norm(cfg, lp["attn_norm"], x)
    d, new = _paged_attn_delta(cfg, lens, table, h, lp, cache_slice,
                               mesh)
    x = x + d
    with jax.named_scope("mlp"):
        x = x + _mlp(cfg, lp["mlp"], lp["mlp_norm"], x, mesh=mesh)
    return x, new


def _moe_paged_body(cfg, lens, table, x, lp, cache_slice, mesh=None):
    h = _norm(cfg, lp["attn_norm"], x)
    d, new = _paged_attn_delta(cfg, lens, table, h, lp, cache_slice,
                               mesh)
    x = x + d
    with jax.named_scope("mlp"):
        y, _aux = MOE.moe_ffn(lp["moe"],
                              _norm(cfg, lp["mlp_norm"], x)[None], cfg,
                              mesh=mesh)
    return x + _branch(cfg, y[0]), new


def _audio_paged_body(cfg, lens, table, enc_lens, x, lp, cs, mesh=None):
    h = _norm(cfg, lp["self_norm"], x)
    d, kp, vp, _, _ = _decode_gqa_paged(cfg, lp["self"], h,
                                        cs["self_k"], cs["self_v"],
                                        table, lens, mesh)
    x = x + d
    h = _norm(cfg, lp["cross_norm"], x)
    x = x + _decode_cross_paged(cfg, lp["cross"], h, cs["cross_k"],
                                cs["cross_v"], enc_lens,
                                cs["self_k"].shape[2], mesh)
    with jax.named_scope("mlp"):
        x = x + _mlp(cfg, lp["mlp"], lp["mlp_norm"], x, mesh=mesh)
    return x, {"self_k": kp, "self_v": vp}


def paged_decode_step(params, batch, cfg, mesh=None):
    """One-token serve step over a paged KV cache.

    batch: token (B,), cur_len (B,) per-slot valid positions,
    block_table (B, max_pages) int32, cache (page pools from
    ``engine.paged_cache``) [+ enc_lens (B,) for audio].  Slots with
    cur_len == 0 are inactive: their write is dropped, their attention
    masks to zero, and their logits are garbage the caller discards.
    Returns (logits (B, vocab) fp32, new_cache)."""
    fam = cfg.family
    if fam not in ("dense", "vlm", "moe", "audio"):
        raise ValueError(
            f"paged decode supports KV-cache families "
            f"('dense', 'vlm', 'moe', 'audio'); family {fam!r} carries "
            "O(1) recurrent state per slot — use the dense decode path")
    tok = batch["token"]
    lens = jnp.asarray(batch["cur_len"], jnp.int32)
    table = jnp.asarray(batch["block_table"], jnp.int32)
    cache = batch["cache"]
    x = _embed(params, tok, cfg)

    if fam in ("dense", "vlm"):
        body = functools.partial(_dense_paged_body, cfg, lens, table,
                                 mesh=mesh)
        x, new_cache = _scan_stack(cfg, body, x, params["layers"],
                                   extra_xs=cache)

    elif fam == "moe":
        m = cfg.moe
        new_cache = dict(cache)
        if m.first_k_dense:
            body = functools.partial(_dense_paged_body, cfg, lens, table,
                                     mesh=mesh)
            x, nd = _scan_stack(cfg, body, x, params["dense_layers"],
                                extra_xs=cache["dense"])
            new_cache["dense"] = nd
        body = functools.partial(_moe_paged_body, cfg, lens, table,
                                 mesh=mesh)
        x, nm = _scan_stack(cfg, body, x, params["layers"],
                            extra_xs=cache["moe"])
        new_cache["moe"] = nm

    else:                                   # audio
        enc_lens = jnp.asarray(batch["enc_lens"], jnp.int32)
        body = functools.partial(_audio_paged_body, cfg, lens, table,
                                 enc_lens, mesh=mesh)
        xs_cache = {"self_k": cache["self_k"], "self_v": cache["self_v"],
                    "cross_k": cache["cross_k"],
                    "cross_v": cache["cross_v"]}
        x, upd = _scan_stack(cfg, body, x, params["layers"],
                             extra_xs=xs_cache)
        new_cache = dict(cache)
        new_cache.update(upd)

    with jax.named_scope("lm_head"):
        h = _norm(cfg, params["final_norm"], x)
        logits = _logits(params, h[:, None, :], cfg)[:, 0].astype(
            jnp.float32)
    return logits, new_cache


def _dense_decode_body(cfg, cur_len, x, lp, cache_slice, mesh=None):
    if cfg.mla is not None:
        h = _norm(cfg, lp["attn_norm"], x)
        d, cckv, ckr = _decode_mla(cfg, lp["attn"], h, cache_slice["ckv"],
                                   cache_slice["krope"], cur_len, mesh)
        new = {"ckv": cckv, "krope": ckr}
    else:
        h = _norm(cfg, lp["attn_norm"], x)
        d, ck, cv = _decode_gqa(cfg, lp["attn"], h, cache_slice["k"],
                                cache_slice["v"], cur_len, mesh)
        new = {"k": ck, "v": cv}
    x = x + d
    x = x + _mlp(cfg, lp["mlp"], lp["mlp_norm"], x, mesh=mesh)
    return x, new


def _moe_decode_body(cfg, cur_len, x, lp, cache_slice, mesh=None):
    if cfg.mla is not None:
        h = _norm(cfg, lp["attn_norm"], x)
        d, cckv, ckr = _decode_mla(cfg, lp["attn"], h, cache_slice["ckv"],
                                   cache_slice["krope"], cur_len, mesh)
        new = {"ckv": cckv, "krope": ckr}
    else:
        h = _norm(cfg, lp["attn_norm"], x)
        d, ck, cv = _decode_gqa(cfg, lp["attn"], h, cache_slice["k"],
                                cache_slice["v"], cur_len, mesh)
        new = {"k": ck, "v": cv}
    x = x + d
    # decode grouping: one group of all B tokens (see moe.py docstring)
    y, _aux = MOE.moe_ffn(lp["moe"], _norm(cfg, lp["mlp_norm"], x)[None],
                          cfg, mesh=mesh)
    return x + _branch(cfg, y[0]), new


def decode_step(params, batch, cfg, mesh=None):
    """One-token serve step. batch: token (B,), cur_len (), cache pytree.

    Returns (logits (B, vocab) fp32, new_cache).  ``mesh`` is the
    explicit device mesh for the sequence-sharded decode path
    (cfg.decode_shard == 'seq'); without it that path falls back to the
    deprecated ambient-mesh lookup.  ``engine.DecodeEngine`` (or
    ``steps.build_decode(cfg, mesh)``) threads it for you.

    With a ``block_table`` operand in the batch (and per-slot (B,)
    ``cur_len``), the step runs over a paged KV cache instead —
    ``paged_decode_step`` — which is how continuous batching serves
    slots at different lengths from one shared page pool.
    """
    if "block_table" in batch:
        return paged_decode_step(params, batch, cfg, mesh=mesh)
    fam = cfg.family
    tok = batch["token"]
    cur = batch["cur_len"]
    cache = batch["cache"]
    x = _embed(params, tok, cfg)                                   # (B,D)

    if fam in ("dense", "vlm"):
        body = functools.partial(_dense_decode_body, cfg, cur, mesh=mesh)
        x, new_cache = _scan_stack(cfg, body, x, params["layers"],
                                   extra_xs=cache)

    elif fam == "moe":
        m = cfg.moe
        new_cache = dict(cache)
        if m.first_k_dense:
            body = functools.partial(_dense_decode_body, cfg, cur,
                                     mesh=mesh)
            x, nd = _scan_stack(cfg, body, x, params["dense_layers"],
                                extra_xs=cache["dense"])
            new_cache["dense"] = nd
        body = functools.partial(_moe_decode_body, cfg, cur, mesh=mesh)
        x, nm = _scan_stack(cfg, body, x, params["layers"],
                            extra_xs=cache["moe"])
        new_cache["moe"] = nm

    elif fam == "hybrid":
        k, n_main, tail, n_inv = _hybrid_groups(cfg)
        sp = params["shared_attn"]

        def mamba_dec(x, lp, ex):
            nrm, st = ex
            d, st1 = SSM.mamba2_step(lp, _norm(cfg, nrm, x), st, cfg)
            return x + _branch(cfg, d), st1

        def shared_dec(x, ck, cv):
            h = _norm(cfg, sp["attn_norm"], x)
            d, ck, cv = _decode_gqa(cfg, sp["attn"], h, ck, cv, cur, mesh)
            x = x + d
            x = x + _mlp(cfg, sp["mlp"], sp["mlp_norm"], x, mesh=mesh)
            return x, ck, cv

        def group_dec(x, gp, ex):
            gn, gst, ck, cv = ex
            x, st1 = _scan_stack(cfg, mamba_dec, x, gp, extra_xs=(gn, gst))
            x, ck, cv = shared_dec(x, ck, cv)
            return x, (st1, ck, cv)

        x, (st_main, ak, av) = _scan_stack(
            cfg, group_dec, x, params["mamba_main"],
            extra_xs=(params["mamba_norms"], cache["mamba_main"],
                      cache["attn_k"][:n_main], cache["attn_v"][:n_main]))
        new_cache = {"mamba_main": st_main, "mamba_tail": None}
        if tail:
            x, st_tail = _scan_stack(
                cfg, mamba_dec, x, params["mamba_tail"],
                extra_xs=(params["tail_norms"], cache["mamba_tail"]))
            x, tk, tv = shared_dec(x, cache["attn_k"][n_main],
                                   cache["attn_v"][n_main])
            new_cache["mamba_tail"] = st_tail
            ak = jnp.concatenate([ak, tk[None]], 0)
            av = jnp.concatenate([av, tv[None]], 0)
        new_cache["attn_k"], new_cache["attn_v"] = ak, av

    elif fam == "ssm":
        def ml_dec(x, lp, ex):
            nrm, st = ex
            d, st1 = XL.mlstm_step(lp, _norm(cfg, nrm, x), st, cfg)
            return x + _branch(cfg, d), st1

        def group_dec(x, gp, ex):
            gst = ex
            x, mst = _scan_stack(cfg, ml_dec, x, gp["m"],
                                 extra_xs=(gp["n"], gst["mlstm"]))
            d, sst = XL.slstm_step(gp["s"], x, gst["slstm"], cfg)
            return x + _branch(cfg, d), {"mlstm": mst, "slstm": sst}

        stacked = {"m": params["mlstm"], "n": params["mlstm_norms"],
                   "s": params["slstm"]}
        x, new_cache = _scan_stack(
            cfg, group_dec, x, stacked,
            extra_xs={"mlstm": cache["mlstm"], "slstm": cache["slstm"]})

    elif fam == "audio":
        def dec_body(x, lp, cs):
            h = _norm(cfg, lp["self_norm"], x)
            d, ck, cv = _decode_gqa(cfg, lp["self"], h, cs["self_k"],
                                    cs["self_v"], cur, mesh)
            x = x + d
            h = _norm(cfg, lp["cross_norm"], x)
            x = x + _decode_cross(cfg, lp["cross"], h, cs["cross_k"],
                                  cs["cross_v"], mesh)
            x = x + _mlp(cfg, lp["mlp"], lp["mlp_norm"], x, mesh=mesh)
            return x, {"self_k": ck, "self_v": cv}

        xs_cache = {"self_k": cache["self_k"], "self_v": cache["self_v"],
                    "cross_k": cache["cross_k"], "cross_v": cache["cross_v"]}
        x, upd = _scan_stack(cfg, dec_body, x, params["layers"],
                             extra_xs=xs_cache)
        new_cache = dict(cache)
        new_cache.update(upd)

    else:
        raise ValueError(fam)

    h = _norm(cfg, params["final_norm"], x)
    logits = _logits(params, h[:, None, :], cfg)[:, 0].astype(jnp.float32)
    return logits, new_cache


def prefill(params, batch, cfg, mesh=None):
    """Full-sequence prefill: returns (last-token logits, cache material).

    The cache material is the backbone's per-layer KV stacks / final
    recurrent states at the prefill length;
    ``engine.pad_cache_from_prefill`` pads them into a fixed-size
    decode cache (``engine.DecodeEngine`` does both in one call).
    ``mesh`` is threaded to the backbone's sharding hints.
    """
    out = backbone(params, batch["tokens"], cfg,
                   frontend_emb=batch.get("frontend_emb"),
                   collect_cache=True, mesh=mesh)
    with jax.named_scope("lm_head"):
        logits = _logits(params, out.h[:, -1:, :], cfg)[:, 0]
    return logits.astype(jnp.float32), out.caches


# ---------------- suffix-only prefill (prefix cache) ----------------

def _gather_prefix_kv(sub, keys, pages):
    """Gather the matched prefix pages back out of one family's pools
    into dense prefill-cache-shaped ``(L, 1, M, ...)`` arrays
    (M = n_pages * page_size), dequantizing int8 pools through their
    per-page scale sidecars.  For model-dtype pools the gathered rows
    are bit-identical to the KV the original prefill wrote."""
    out = []
    for kk in keys:
        g = sub[kk][:, pages]       # GQA (L, J, KV, ps, Dh), MLA (L, J, ps, r)
        if kk + "_scale" in sub:
            s = sub[kk + "_scale"][:, pages]  # (L, J[, KV])
            g = g.astype(jnp.float32) * s.reshape(
                s.shape + (1,) * (g.ndim - s.ndim))
        if g.ndim == 5:                       # GQA: back to (ps, KV, Dh)
            g = jnp.swapaxes(g, 2, 3)
        L, J, ps = g.shape[:3]
        out.append(g.reshape(L, 1, J * ps, *g.shape[3:]))
    return tuple(out)


def _suffix_attn_delta(cfg, ap, h, q_pos, kv_pos, prefix, *,
                       residual=None, mesh=None):
    """Attention step of the suffix bodies: queries at global positions
    ``q_pos`` over concat(prefix KV from the pools, suffix KV computed
    here).  Runs the blockwise (xla) path directly — the streaming
    kv scan sees the same kv length and block boundaries as the
    whole-prompt prefill, so every suffix row is bit-identical to the
    corresponding row of a full prefill (the pallas prefill kernel has
    no positional-offset support; admission is batch-1 and off the
    decode hot path, so kernel parity is deliberately future work)."""
    if cfg.mla is not None:
        pckv, pkrope = prefix
        out, cache = MLA.mla_attention_suffix(
            ap, h, q_pos, kv_pos, cfg, pckv, pkrope,
            head_axis=_head_axis(cfg), mesh=mesh)
        out = _branch(cfg, out)
        return (out if residual is None else residual + out), cache
    pk, pv = prefix
    q, k, v = A.qkv_proj(ap, h, q_pos, cfg.rope_theta, cfg,
                         mesh=mesh)
    q = _scale_q(cfg, q)
    k_all = jnp.concatenate([pk.astype(k.dtype), k], axis=1)
    v_all = jnp.concatenate([pv.astype(v.dtype), v], axis=1)
    if cfg.accounting:
        o = A.full_attn_ref(q, k_all, v_all, causal=True,
                            q_positions=q_pos, kv_positions=kv_pos)
    else:
        o = A.blockwise_attn(q, k_all, v_all, causal=True,
                             q_positions=q_pos, kv_positions=kv_pos,
                             block_q=cfg.attn_block_q,
                             block_kv=cfg.attn_block_kv,
                             head_axis=_head_axis(cfg), mesh=mesh)
    return A.o_proj(ap, _branch(cfg, o), cfg, residual=residual,
                    mesh=mesh), (k, v)


def _dense_suffix_body(cfg, q_pos, kv_pos, x, lp, prefix, *, mesh=None):
    x, kv = _suffix_attn_delta(cfg, lp["attn"],
                               _norm(cfg, lp["attn_norm"], x),
                               q_pos, kv_pos, prefix, residual=x,
                               mesh=mesh)
    x = _mlp(cfg, lp["mlp"], lp["mlp_norm"], x, residual=x, mesh=mesh)
    return x, kv


def _moe_suffix_body(cfg, q_pos, kv_pos, x, lp, prefix, *, mesh=None):
    x, kv = _suffix_attn_delta(cfg, lp["attn"],
                               _norm(cfg, lp["attn_norm"], x),
                               q_pos, kv_pos, prefix, residual=x,
                               mesh=mesh)
    y, aux = MOE.moe_ffn(lp["moe"], _norm(cfg, lp["mlp_norm"], x), cfg,
                         mesh=mesh)
    return x + _branch(cfg, y), (kv, aux)


def prefill_suffix(params, batch, cfg, mesh=None):
    """Prefill only the SUFFIX of a prompt whose prefix is already
    resident in the page pools (prefix cache hit).

    batch: ``tokens`` (1, S) int32 suffix tokens, ``pages`` (J_m,)
    int32 matched physical page ids (whole pages, prefix order), and
    ``cache`` — the live page pools the prefix is read from.  The
    matched length M = J_m * page_size rides the ``pages`` operand's
    SHAPE, so under jit this compiles once per (S, M) pair — the same
    per-shape compile discipline as whole-prompt prefill.

    Returns (last-token logits (1, vocab_padded) fp32, suffix cache
    material) exactly like ``prefill`` restricted to positions
    [M, M+S): the caches scatter into the slot's pages from page index
    J_m on (the suffix starts page-aligned by construction).

    Families: dense and moe (GQA or MLA).  The frontend families
    (vlm/audio) prepend non-token positions, so a token-only prefix
    index cannot alias their pages — the scheduler gates them off.
    """
    fam = cfg.family
    if fam not in ("dense", "moe"):
        raise ValueError(
            f"prefill_suffix supports the token-only families "
            f"('dense', 'moe'); family {fam!r} prepends frontend "
            "positions that a token-keyed prefix index cannot match")
    tokens = batch["tokens"]
    pages = jnp.asarray(batch["pages"], jnp.int32)
    cache = batch["cache"]
    keys = ("ckv", "krope") if cfg.mla is not None else ("k", "v")
    sub = cache["moe"] if fam == "moe" else cache
    ps = sub[keys[0]].shape[-2]
    M = pages.shape[0] * ps
    S = tokens.shape[1]
    q_pos = jnp.arange(S) + M
    kv_pos = jnp.arange(M + S)

    x = _embed(params, tokens, cfg)

    if fam == "dense":
        prefix = _gather_prefix_kv(cache, keys, pages)
        body = functools.partial(_dense_suffix_body, cfg, q_pos, kv_pos,
                                 mesh=mesh)
        x, kvs = _scan_stack(cfg, body, x, params["layers"],
                             extra_xs=prefix)
        caches = kvs
    else:                                   # moe
        m = cfg.moe
        kv_d = None
        if m.first_k_dense:
            prefix_d = _gather_prefix_kv(cache["dense"], keys, pages)
            body = functools.partial(_dense_suffix_body, cfg, q_pos,
                                     kv_pos, mesh=mesh)
            x, kv_d = _scan_stack(cfg, body, x, params["dense_layers"],
                                  extra_xs=prefix_d)
        prefix_m = _gather_prefix_kv(cache["moe"], keys, pages)
        body = functools.partial(_moe_suffix_body, cfg, q_pos, kv_pos,
                                 mesh=mesh)
        x, (kv_m, _aux) = _scan_stack(cfg, body, x, params["layers"],
                                      extra_xs=prefix_m)
        caches = (kv_d, kv_m)

    h = _norm(cfg, params["final_norm"], x)
    logits = _logits(params, h[:, -1:, :], cfg)[:, 0]
    return logits.astype(jnp.float32), caches


# ---------------- chunked prefill (one chunk of an in-flight prompt) -----

def prefill_chunk(params, batch, cfg, mesh=None):
    """One chunk of a chunked prefill: compute the chunk's KV against
    everything already resident and scatter it into its granted pages.

    batch: ``tokens`` (1, C) int32 chunk tokens at global positions
    [M, M+C) where M = len(pages) * page_size; ``pages`` (J_p,) int32 —
    ALL pages holding positions [0, M) in prefix order (prefix-cache
    matched pages followed by earlier chunks' pages — the scheduler
    keeps every non-final chunk page-aligned, so the resident prefix is
    always whole pages); ``write_pages`` (J_w,) int32 — the pages
    positions [M, M+C) land in; ``cache`` — the live page pools.

    Composes with ``prefill_suffix``: the attention math IS the
    suffix-prefill math (a chunk is a suffix whose prefix grows chunk
    by chunk), so every chunk row — and in particular the final chunk's
    last-token logits — is bit-identical to the corresponding row of a
    whole-prompt prefill when the pools store the model dtype.  On top
    of that this writes the chunk's KV into ``write_pages`` (the
    quantize-on-write scatter for int8 pools), so the NEXT chunk can
    read it back through the block table.

    Returns (last-chunk-token logits (1, vocab_padded) fp32, updated
    cache).  Intermediate chunks' logits are discarded by the caller;
    the final chunk's seed the first generated token.
    """
    from repro.engine import paged_cache as PC
    logits, caches = prefill_suffix(
        params, {"tokens": batch["tokens"], "pages": batch["pages"],
                 "cache": batch["cache"]}, cfg, mesh=mesh)
    table = jnp.asarray(batch["write_pages"], jnp.int32)[None]  # (1, J_w)
    cache = PC.write_prefill(cfg, batch["cache"], caches, table)
    return logits, cache


# ---------------- xlstm decode uses ml/sl steps with scalar inputs -------

def ssm_decode_supported(cfg) -> bool:
    return cfg.family in ("hybrid", "ssm")
