"""Model-numerics tests: each fused/chunked formulation against its
naive oracle, plus MoE dispatch correctness."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.common.config import (MLAConfig, Mamba2Config, ModelConfig,
                                 MoEConfig, XLSTMConfig)
from repro.models import attention as A
from repro.models import lm, mla, moe, ssm, xlstm

KEY = jax.random.PRNGKey(0)


def _cfg(**kw):
    base = dict(name="t", family="dense", n_layers=2, d_model=64,
                n_heads=4, n_kv_heads=2, d_head=16, d_ff=128, vocab=256,
                dtype="float32", remat="none", attn_block_q=32,
                attn_block_kv=32)
    base.update(kw)
    return ModelConfig(**base)


# ---------------------------------------------------------------- init

@pytest.mark.parametrize("family", ["gqa", "mla", "xlstm"])
def test_per_head_projections_init_with_their_true_fan_in(family):
    """A per-head projection (d, H, Dh) draws with std 1/sqrt(d) and
    its output (H, Dh, d) with 1/sqrt(H*Dh); the second-to-last axis
    alone (H, or Dh) would give random models attention scores of
    std ~sqrt(d) and a one-hot softmax."""
    if family == "gqa":
        cfg = _cfg(d_model=512, n_heads=8, n_kv_heads=2, d_head=64)
        p = A.gqa_spec(cfg)
        ins, outs = ("wq", "wk", "wv"), ("wo",)
    elif family == "mla":
        cfg = _cfg(mla=MLAConfig(q_lora_rank=256, kv_lora_rank=128,
                                 rope_head_dim=16, nope_head_dim=32,
                                 v_head_dim=32))
        p = mla.mla_spec(cfg)
        ins, outs = ("wq_b", "wk_b", "wv_b"), ("wo",)
    else:
        cfg = _cfg(family="ssm", d_model=128, xlstm=XLSTMConfig())
        p = xlstm.mlstm_spec(cfg)
        ins, outs = ("wq", "wk", "wv"), ()
    for name in ins + outs:
        d = p[name]
        fan_in = d.shape[0] if name in ins else d.shape[0] * d.shape[1]
        w = np.asarray(d.init(KEY, d.shape, jnp.float32))
        np.testing.assert_allclose(w.std(), fan_in ** -0.5, rtol=0.05,
                                   err_msg=name)


# ---------------------------------------------------------------- attn

def test_blockwise_attention_matches_dense():
    k1, k2, k3 = jax.random.split(KEY, 3)
    q = jax.random.normal(k1, (2, 100, 8, 16))
    k = jax.random.normal(k2, (2, 100, 2, 16))
    v = jax.random.normal(k3, (2, 100, 2, 16))
    out = A.blockwise_attn(q, k, v, causal=True, block_q=32, block_kv=48)
    want = A.full_attn_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_flash_decode_partial_combine():
    """Sharded partial softmax combined == monolithic decode."""
    k1, k2, k3 = jax.random.split(KEY, 3)
    B, T, KV, Dh, H = 2, 64, 2, 16, 4
    q = jax.random.normal(k1, (B, H, Dh))
    ck = jax.random.normal(k2, (B, T, KV, Dh))
    cv = jax.random.normal(k3, (B, T, KV, Dh))
    cur = jnp.int32(50)
    want = A.decode_attend_local(q, ck, cv, jnp.arange(T), cur)

    # two shards, manual combine
    o1, m1, l1 = A.flash_decode_partial(q, ck[:, :32], cv[:, :32],
                                        jnp.arange(0, 32), cur)
    o2, m2, l2 = A.flash_decode_partial(q, ck[:, 32:], cv[:, 32:],
                                        jnp.arange(32, 64), cur)
    m = jnp.maximum(m1, m2)
    num = o1 * jnp.exp(m1 - m)[..., None] + o2 * jnp.exp(m2 - m)[..., None]
    den = l1 * jnp.exp(m1 - m) + l2 * jnp.exp(m2 - m)
    got = (num / den[..., None]).astype(want.dtype)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------- mamba2

def test_mamba2_closed_form_matches_scan():
    cfg = _cfg(family="hybrid",
               mamba2=Mamba2Config(d_state=8, head_dim=16, chunk=8,
                                   attn_every=2))
    p = jax.tree.map(
        lambda d: d.init(KEY, d.shape, d.dtype),
        ssm.mamba2_spec(cfg),
        is_leaf=lambda x: hasattr(x, "init"))
    x = jax.random.normal(KEY, (2, 32, 64))
    y1, s1 = ssm.mamba2_forward(p, x, cfg)
    y2, s2 = ssm.mamba2_forward(p, x, cfg.replace(accounting=True))
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), rtol=2e-4,
                               atol=2e-4)
    np.testing.assert_allclose(np.asarray(s1.ssm), np.asarray(s2.ssm),
                               rtol=2e-4, atol=2e-4)


def test_mamba2_chunk_invariance():
    cfg8 = _cfg(family="hybrid",
                mamba2=Mamba2Config(d_state=8, head_dim=16, chunk=8,
                                    attn_every=2))
    cfg16 = cfg8.replace(mamba2=Mamba2Config(d_state=8, head_dim=16,
                                             chunk=16, attn_every=2))
    p = jax.tree.map(lambda d: d.init(KEY, d.shape, d.dtype),
                     ssm.mamba2_spec(cfg8),
                     is_leaf=lambda x: hasattr(x, "init"))
    x = jax.random.normal(KEY, (2, 32, 64))
    y1, _ = ssm.mamba2_forward(p, x, cfg8)
    y2, _ = ssm.mamba2_forward(p, x, cfg16)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), rtol=2e-4,
                               atol=2e-4)


def test_mamba2_decode_matches_forward():
    """Prefill then stepwise decode == one long forward."""
    cfg = _cfg(family="hybrid",
               mamba2=Mamba2Config(d_state=8, head_dim=16, chunk=8,
                                   attn_every=2))
    p = jax.tree.map(lambda d: d.init(KEY, d.shape, d.dtype),
                     ssm.mamba2_spec(cfg),
                     is_leaf=lambda x: hasattr(x, "init"))
    x = jax.random.normal(KEY, (1, 24, 64))
    y_full, _ = ssm.mamba2_forward(p, x, cfg)
    y_pre, st = ssm.mamba2_forward(p, x[:, :16], cfg)
    ys = [y_pre]
    for t in range(16, 24):
        y_t, st = ssm.mamba2_step(p, x[:, t], st, cfg)
        ys.append(y_t[:, None])
    y_inc = jnp.concatenate(ys, axis=1)
    np.testing.assert_allclose(np.asarray(y_inc), np.asarray(y_full),
                               rtol=1e-3, atol=1e-3)


# ---------------------------------------------------------------- xlstm

def test_mlstm_chunkwise_matches_naive():
    B, S, H, P = 2, 32, 2, 8
    ks = jax.random.split(KEY, 5)
    q, k, v = (jax.random.normal(ks[i], (B, S, H, P)) for i in range(3))
    li = jax.random.normal(ks[3], (B, S, H))
    lf = jax.nn.log_sigmoid(jax.random.normal(ks[4], (B, S, H)) + 2.0)
    st0 = (jnp.zeros((B, H, P, P)), jnp.zeros((B, H, P)),
           jnp.full((B, H), -1e30))
    h1, s1 = xlstm.mlstm_chunkwise(q, k, v, li, lf, st0, chunk=8)
    h2, s2 = xlstm.mlstm_ref(q, k, v, li, lf, st0)
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h2), rtol=2e-4,
                               atol=2e-4)
    # states agree up to the shared stabilizer convention
    c1 = s1[0] * jnp.exp(s1[2])[..., None, None]
    c2 = s2[0] * jnp.exp(s2[2])[..., None, None]
    np.testing.assert_allclose(np.asarray(c1), np.asarray(c2), rtol=2e-3,
                               atol=2e-3)


def test_mlstm_chunkwise_unroll_equal():
    B, S, H, P = 1, 32, 2, 8
    ks = jax.random.split(KEY, 5)
    q, k, v = (jax.random.normal(ks[i], (B, S, H, P)) for i in range(3))
    li = jax.random.normal(ks[3], (B, S, H))
    lf = jax.nn.log_sigmoid(jax.random.normal(ks[4], (B, S, H)))
    st0 = (jnp.zeros((B, H, P, P)), jnp.zeros((B, H, P)),
           jnp.full((B, H), -1e30))
    h1, _ = xlstm.mlstm_chunkwise(q, k, v, li, lf, st0, 8, unroll=False)
    h2, _ = xlstm.mlstm_chunkwise(q, k, v, li, lf, st0, 8, unroll=True)
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h2), rtol=1e-6,
                               atol=1e-6)


def test_slstm_step_matches_forward():
    cfg = _cfg(family="ssm", n_kv_heads=4,
               xlstm=XLSTMConfig(slstm_every=2, chunk=8))
    p = jax.tree.map(lambda d: d.init(KEY, d.shape, d.dtype),
                     xlstm.slstm_spec(cfg),
                     is_leaf=lambda x: hasattr(x, "init"))
    x = jax.random.normal(KEY, (2, 12, 64))
    y_full, st_full = xlstm.slstm_forward(p, x, cfg)
    st = xlstm.slstm_init_state(cfg, 2)
    ys = []
    for t in range(12):
        y_t, st = xlstm.slstm_step(p, x[:, t], st, cfg)
        ys.append(y_t[:, None])
    np.testing.assert_allclose(np.asarray(jnp.concatenate(ys, 1)),
                               np.asarray(y_full), rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------- moe

def test_moe_positions_sort_equals_cumsum():
    idx = jax.random.randint(KEY, (3, 64), 0, 8)
    p1 = moe._positions_cumsum(idx, 8)
    p2 = moe._positions_sort(idx, 8)
    np.testing.assert_array_equal(np.asarray(p1), np.asarray(p2))


def test_moe_matches_dense_reference():
    """With capacity large enough to never drop, capacity dispatch ==
    dense per-expert evaluation."""
    cfg = _cfg(family="moe",
               moe=MoEConfig(n_experts=4, top_k=2, d_expert=32,
                             capacity_factor=4.0, norm_topk=True))
    p = jax.tree.map(lambda d: d.init(KEY, d.shape, d.dtype),
                     moe.moe_spec(cfg),
                     is_leaf=lambda x: hasattr(x, "init"))
    x = jax.random.normal(KEY, (2, 16, 64))
    y, aux = moe.moe_ffn(p, x, cfg)
    assert float(aux["drop_frac"]) == 0.0

    probs, sel, _ = moe.router_scores(p, x, cfg)
    gates, idx = moe.top_k_gates(probs, sel, cfg)

    def expert(e, xx):
        h = xx @ p["wi"][e]
        g = xx @ p["wg"][e]
        return (jax.nn.silu(g) * h) @ p["wo"][e]

    want = jnp.zeros_like(x)
    for e in range(4):
        ye = expert(e, x)
        w_e = jnp.where(idx == e, gates, 0.0).sum(-1)
        want = want + ye * w_e[..., None]
    np.testing.assert_allclose(np.asarray(y), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_moe_capacity_drops_counted():
    cfg = _cfg(family="moe",
               moe=MoEConfig(n_experts=4, top_k=2, d_expert=32,
                             capacity_factor=0.25))
    p = jax.tree.map(lambda d: d.init(KEY, d.shape, d.dtype),
                     moe.moe_spec(cfg),
                     is_leaf=lambda x: hasattr(x, "init"))
    x = jax.random.normal(KEY, (1, 32, 64))
    _, aux = moe.moe_ffn(p, x, cfg)
    assert 0.0 < float(aux["drop_frac"]) < 1.0


# ---------------------------------------------------------------- mla

def test_mla_decode_absorbed_matches_expanded():
    """Absorbed decode scores/values == expanded-form attention on the
    same (prefix + new token) sequence."""
    cfg = _cfg(mla=MLAConfig(q_lora_rank=32, kv_lora_rank=16,
                             rope_head_dim=8, nope_head_dim=16,
                             v_head_dim=16))
    p = jax.tree.map(lambda d: d.init(KEY, d.shape, d.dtype),
                     mla.mla_spec(cfg),
                     is_leaf=lambda x: hasattr(x, "init"))
    B, T = 2, 12
    x = jax.random.normal(KEY, (B, T, 64))
    positions = jnp.arange(T)

    # expanded full-sequence attention, last token's output
    out_full, (ckv, krope) = mla.mla_attention(p, x, positions, cfg,
                                               causal=True, dense=True)
    want = out_full[:, -1]

    # absorbed decode of the last token against the cached latents
    q_nope, q_rope = mla.mla_queries(p, x[:, -1:], positions[-1:], cfg)
    o_t, m, l = mla.mla_decode_partial(
        p, q_nope[:, 0], q_rope[:, 0], ckv, krope, jnp.arange(T),
        jnp.int32(T), cfg)
    o = o_t / jnp.maximum(l, 1e-30)[..., None]
    got = mla.mla_decode_finish(p, o.astype(jnp.float32), cfg)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-3, atol=2e-3)


# ---------------------------------------------------------------- lm e2e

def test_dense_decode_matches_prefill_logits():
    """Teacher-forced decode reproduces full-forward logits."""
    cfg = _cfg(n_layers=2)
    params = lm.init(cfg, KEY)
    B, S = 2, 12
    tokens = jax.random.randint(KEY, (B, S), 0, cfg.vocab)

    out = lm.backbone(params, tokens, cfg)
    logits_all = lm._logits(params, out.h, cfg)

    cache = lm.init_cache(cfg, B, S)
    logits_inc = []
    for t in range(S):
        lg, cache = lm.decode_step(
            params, {"token": tokens[:, t], "cur_len": jnp.int32(t),
                     "cache": cache}, cfg)
        logits_inc.append(lg[:, None])
    got = jnp.concatenate(logits_inc, 1)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(logits_all, np.float32),
                               rtol=1e-3, atol=1e-3)


def test_ce_loss_chunked_equals_whole():
    cfg = _cfg()
    params = lm.init(cfg, KEY)
    h = jax.random.normal(KEY, (2, 16, 64))
    labels = jax.random.randint(KEY, (2, 16), 0, cfg.vocab)
    mask = jnp.ones((2, 16))
    l1, _ = lm.ce_loss(params, h, labels, mask, cfg)
    l2, _ = lm.ce_loss(params, h, labels, mask,
                       cfg.replace(logits_chunk=5))
    np.testing.assert_allclose(float(l1), float(l2), rtol=1e-5)
