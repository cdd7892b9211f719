"""Quantized KV page pool tests: the shared symmetric-int8 helper, the
q8 decode-partial ops (dense + paged, GQA + split-operand MLA, xla +
pallas), quantize-on-write (prefill scatter and the per-step decode
page write), and the engine/scheduler plumbing — greedy q8 token
streams pinned to the bf16 engine, with bounded logit drift."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.common.config import MLAConfig, ModelConfig, MoEConfig
from repro.engine import DecodeEngine, EngineConfig, Request, Scheduler
from repro.engine import paged_cache as PC
from repro.kernels import dispatch as D
from repro.kernels.quant import (QEPS, dequantize_int8, int8_scale,
                                 quantize_int8)

KEY = jax.random.PRNGKey(0)


def _cfg(**kw):
    base = dict(name="t", family="dense", n_layers=2, d_model=64,
                n_heads=4, n_kv_heads=2, d_head=16, d_ff=128, vocab=256,
                dtype="float32", remat="none", attn_block_q=32,
                attn_block_kv=32)
    base.update(kw)
    return ModelConfig(**base)


def _mla_cfg():
    return _cfg(mla=MLAConfig(q_lora_rank=32, kv_lora_rank=16,
                              rope_head_dim=8, nope_head_dim=16,
                              v_head_dim=16))


def _moe_mla_cfg():
    return _cfg(family="moe",
                moe=MoEConfig(n_experts=4, top_k=2, d_expert=32,
                              first_k_dense=1, d_ff_dense=128,
                              capacity_factor=4.0),
                mla=MLAConfig(q_lora_rank=32, kv_lora_rank=16,
                              rope_head_dim=8, nope_head_dim=16,
                              v_head_dim=16))


# ------------------------------------------------- quant helper


def test_quantize_int8_roundtrip_and_symmetry():
    x = jax.random.normal(KEY, (4, 32, 2, 16)) * 3.0
    q, s = quantize_int8(x, axis=(1, 3))
    assert q.dtype == jnp.int8 and s.dtype == jnp.float32
    assert s.shape == (4, 1, 2, 1)          # keepdims: broadcasts back
    # roundtrip error within half a quantization step per group
    err = jnp.abs(dequantize_int8(q, s) - x)
    assert float(err.max()) <= float(s.max()) / 2 + 1e-7
    # symmetric grid: q(x) == -q(-x) exactly
    qn, sn = quantize_int8(-x, axis=(1, 3))
    np.testing.assert_array_equal(np.asarray(qn), -np.asarray(q))
    np.testing.assert_array_equal(np.asarray(sn), np.asarray(s))


def test_quantize_int8_all_zero_group_is_safe():
    """The eps floor keeps all-zero groups finite and exact."""
    np.testing.assert_allclose(float(int8_scale(jnp.float32(0.0))),
                               QEPS / 127.0, rtol=1e-6)
    q, s = quantize_int8(jnp.zeros((2, 8)), axis=1)
    assert np.isfinite(np.asarray(s)).all()
    np.testing.assert_array_equal(np.asarray(q), 0)
    np.testing.assert_array_equal(np.asarray(dequantize_int8(q, s)), 0.0)


def test_compression_shares_quant_helper():
    """dist.compression consumes the same int8 recipe (one idiom for
    wire payloads and KV pages)."""
    from repro.dist import compression
    assert compression.quantize_int8 is quantize_int8


# ------------------------------------------------- q8 op contracts


def _quant_cache(k, v):
    """(B,T,KV,Dh) caches -> int8 + per-(B,KV) fp32 scales."""
    B, _, KV, _ = k.shape
    kq, ks = quantize_int8(k, axis=(1, 3))
    vq, vs = quantize_int8(v, axis=(1, 3))
    return kq, vq, ks.reshape(B, KV), vs.reshape(B, KV)


def _quant_pools(kp, vp):
    """(n_pages,KV,ps,Dh) pools -> int8 + per-(page,KV) fp32 scales."""
    n_pages, KV, _, _ = kp.shape
    kq, ks = quantize_int8(kp, axis=(2, 3))
    vq, vs = quantize_int8(vp, axis=(2, 3))
    return kq, vq, ks.reshape(n_pages, KV), vs.reshape(n_pages, KV)


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_q8_dense_op_matches_dequantized_reference(backend):
    """decode_partial_q8 == decode_partial run on the dequantized
    cache: the in-kernel scale hoist is exact, not approximate."""
    B, T, KV, Dh, H = 2, 64, 2, 16, 4
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, H, Dh))
    k = jax.random.normal(ks[1], (B, T, KV, Dh))
    v = jax.random.normal(ks[2], (B, T, KV, Dh))
    kq, vq, ksc, vsc = _quant_cache(k, v)
    kf = kq.astype(jnp.float32) * ksc[:, None, :, None]
    vf = vq.astype(jnp.float32) * vsc[:, None, :, None]
    cur = jnp.int32(50)
    want = D.dispatch("decode_partial", "xla", q, kf, vf, cur)
    got = D.dispatch("decode_partial_q8", backend, q, kq, vq, ksc, vsc,
                     cur)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_q8_paged_op_matches_dequantized_reference(backend):
    B, KV, Dh, H, ps, J, n_pages = 2, 2, 16, 4, 4, 6, 16
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, H, Dh))
    kp = jax.random.normal(ks[1], (n_pages, KV, ps, Dh))
    vp = jax.random.normal(ks[2], (n_pages, KV, ps, Dh))
    kq, vq, ksc, vsc = _quant_pools(kp, vp)
    table = jnp.asarray(np.random.default_rng(0).permutation(n_pages)
                        [:B * J].reshape(B, J), jnp.int32)
    lens = jnp.array([13, 21], jnp.int32)
    counts = jnp.clip(lens[:, None] - jnp.arange(J)[None, :] * ps,
                      0, ps).astype(jnp.int32)
    kf = kq.astype(jnp.float32) * ksc[:, :, None, None]
    vf = vq.astype(jnp.float32) * vsc[:, :, None, None]
    want = D.dispatch("decode_partial_paged", "xla", q, kf, vf, table,
                      counts)
    got = D.dispatch("decode_partial_paged_q8", backend, q, kq, vq,
                     ksc, vsc, table, counts)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_q8_mla_ops_match_dequantized_reference(backend):
    """Split-operand MLA q8 (latent + rope quantized independently,
    per-page/per-row scales) against the dequantized split ops — dense
    cache and paged pool forms."""
    B, H, r, rope, T = 2, 4, 16, 8, 64
    scale = 1.0 / (24 ** 0.5)
    ks = jax.random.split(KEY, 4)
    q_abs = jax.random.normal(ks[0], (B, H, r))
    q_rope = jax.random.normal(ks[1], (B, H, rope))
    ckv = jax.random.normal(ks[2], (B, T, r))
    krope = jax.random.normal(ks[3], (B, T, rope))
    cq, cs = quantize_int8(ckv, axis=(1, 2))
    rq, rs = quantize_int8(krope, axis=(1, 2))
    cs, rs = cs.reshape(B), rs.reshape(B)
    cur = jnp.int32(50)
    want = D.dispatch("decode_partial_mla", "xla", q_abs, q_rope,
                      cq.astype(jnp.float32) * cs[:, None, None],
                      rq.astype(jnp.float32) * rs[:, None, None],
                      cur, scale=scale)
    got = D.dispatch("decode_partial_mla_q8", backend, q_abs, q_rope,
                     cq, rq, cs, rs, cur, scale=scale)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-5, atol=1e-5)

    # paged: per-page scales over the pooled latents
    ps, J, n_pages = 4, 6, 16
    ckv_pool = jax.random.normal(ks[2], (n_pages, ps, r))
    krope_pool = jax.random.normal(ks[3], (n_pages, ps, rope))
    cq, cs = quantize_int8(ckv_pool, axis=(1, 2))
    rq, rs = quantize_int8(krope_pool, axis=(1, 2))
    cs, rs = cs.reshape(n_pages), rs.reshape(n_pages)
    table = jnp.asarray(np.random.default_rng(0).permutation(n_pages)
                        [:B * J].reshape(B, J), jnp.int32)
    counts = jnp.clip(jnp.array([13, 21])[:, None]
                      - jnp.arange(J)[None, :] * ps, 0, ps)
    counts = counts.astype(jnp.int32)
    want = D.dispatch("decode_partial_mla_paged", "xla", q_abs, q_rope,
                      cq.astype(jnp.float32) * cs[:, None, None],
                      rq.astype(jnp.float32) * rs[:, None, None],
                      table, counts, scale=scale)
    got = D.dispatch("decode_partial_mla_paged_q8", backend, q_abs,
                     q_rope, cq, rq, cs, rs, table, counts, scale=scale)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-5, atol=1e-5)


def test_q8_attend_drift_vs_unquantized_is_bounded():
    """Against the UNquantized cache the q8 attend output drifts by the
    quantization error only — small and bounded, and nonzero (the q8
    path really is engaged)."""
    from repro.dist.decode import local_decode_attend
    B, T, KV, Dh, H = 2, 64, 2, 16, 4
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, H, Dh))
    k = jax.random.normal(ks[1], (B, T, KV, Dh))
    v = jax.random.normal(ks[2], (B, T, KV, Dh))
    kq, vq, ksc, vsc = _quant_cache(k, v)
    cur = jnp.int32(50)
    want = local_decode_attend(q, k, v, cur)
    got = local_decode_attend(q, kq, vq, cur, k_scale=ksc, v_scale=vsc)
    drift = float(jnp.abs(got - want).max())
    assert 0.0 < drift < 0.05, drift


# ------------------------------------------------- quantize-on-write


def test_prefill_scatter_q8_roundtrip_error_bounds():
    """_scatter_pages_q8 quantizes per page (per head for GQA): the
    dequantized pages reproduce the prefill KV within half a step of
    each page's own scale, and the partial last page's padding lands as
    exact zeros (the scrub)."""
    L, B, S, KV, Dh, ps, n_pages = 2, 2, 10, 2, 8, 4, 12
    kv = jax.random.normal(KEY, (L, B, S, KV, Dh)) * 2.0
    J = -(-S // ps)
    table = jnp.asarray([[0, 1, 9], [4, 3, 7]], jnp.int32)
    pool = jnp.zeros((L, n_pages, KV, ps, Dh), jnp.int8)
    scales = jnp.zeros((L, n_pages, KV), jnp.float32)
    pool, scales = PC._scatter_pages_q8(pool, scales, kv, table)

    got = (pool[:, table[:, :J]].astype(jnp.float32)
           * scales[:, table[:, :J]][..., None, None])
    got = jnp.swapaxes(got, 3, 4).reshape(L, B, J * ps, KV, Dh)
    err = jnp.abs(got[:, :, :S] - kv)
    step = scales[:, table[:, :J]].max()
    assert float(err.max()) <= float(step) / 2 + 1e-7
    # pad rows of the partial page are exact zeros
    np.testing.assert_array_equal(np.asarray(got[:, :, S:]), 0.0)


def test_quantized_page_write_fresh_reset_and_growth():
    """The decode-step page write: offset 0 resets the scale and scrubs
    the reused page; later writes grow the scale monotonically and
    requantize resident rows onto the new grid; inactive slots (page id
    == n_pages) are dropped."""
    n_pages, ps, KV, Dh = 4, 4, 2, 8
    pool = jnp.full((n_pages, KV, ps, Dh), 55, jnp.int8)  # stale bytes
    scales = jnp.full((n_pages, KV), 9.9, jnp.float32)    # stale scales
    x0 = jax.random.normal(KEY, (1, KV, Dh))
    pages = jnp.array([2], jnp.int32)

    # fresh page: scale reset to the token's amax, rest of page zeroed
    pool, scales = PC.quantized_page_write(
        pool, scales, pages, jnp.array([0], jnp.int32), x0)
    s0 = np.asarray(int8_scale(jnp.max(jnp.abs(x0), axis=-1))[0])
    np.testing.assert_allclose(np.asarray(scales[2]), s0, rtol=1e-6)
    row0 = np.asarray(pool[2, :, 0].astype(jnp.float32)
                      * scales[2][:, None])
    np.testing.assert_allclose(row0, np.asarray(x0[0]),
                               atol=float(s0.max()) / 2 + 1e-7)
    np.testing.assert_array_equal(np.asarray(pool[2, :, 1:]), 0)

    # growth: a larger token raises the scale; the resident row is
    # requantized onto the new grid and stays within its half-step
    x1 = 4.0 * jax.random.normal(jax.random.PRNGKey(1), (1, KV, Dh))
    pool, scales = PC.quantized_page_write(
        pool, scales, pages, jnp.array([1], jnp.int32), x1)
    s1 = np.asarray(scales[2])
    assert (s1 >= s0 - 1e-9).all()
    row0 = np.asarray(pool[2, :, 0].astype(jnp.float32)
                      * scales[2][:, None])
    np.testing.assert_allclose(row0, np.asarray(x0[0]),
                               atol=float(s1.max()) + 1e-7)

    # a smaller token never shrinks the scale (monotone while filling)
    pool, scales = PC.quantized_page_write(
        pool, scales, pages, jnp.array([2], jnp.int32), 0.01 * x0)
    np.testing.assert_allclose(np.asarray(scales[2]), s1, rtol=1e-6)

    # inactive slot: page id n_pages drops the write entirely
    before = np.asarray(pool), np.asarray(scales)
    pool, scales = PC.quantized_page_write(
        pool, scales, jnp.array([n_pages], jnp.int32),
        jnp.array([0], jnp.int32), x0)
    np.testing.assert_array_equal(np.asarray(pool), before[0])
    np.testing.assert_array_equal(np.asarray(scales), before[1])


def test_paged_cache_spec_q8_layout():
    """int8 pools + fp32 sidecars with the layer axis leading (the
    _scan_stack per-layer slicing contract); bf16 spec is unchanged."""
    cfg = _cfg()
    spec = PC.paged_cache_spec(cfg, 8, 4, 2, kv_dtype="int8")
    assert spec["k"].dtype == jnp.int8
    assert spec["k_scale"].shape == (cfg.n_layers, 8, cfg.n_kv_heads)
    assert spec["k_scale"].dtype == jnp.float32
    mspec = PC.paged_cache_spec(_mla_cfg(), 8, 4, 2, kv_dtype="int8")
    assert mspec["ckv"].dtype == jnp.int8
    assert mspec["ckv_scale"].shape == (cfg.n_layers, 8)
    assert mspec["krope_scale"].shape == (cfg.n_layers, 8)
    base = PC.paged_cache_spec(cfg, 8, 4, 2)
    assert "k_scale" not in base
    assert base["k"].dtype == jnp.dtype(cfg.dtype)
    with pytest.raises(ValueError, match="kv_dtype"):
        PC.paged_cache_spec(cfg, 8, 4, 2, kv_dtype="fp8")
    with pytest.raises(ValueError, match="audio"):
        PC.paged_cache_spec(_cfg(family="audio", enc_layers=2,
                                 frontend="audio", frontend_dim=24),
                            8, 4, 2, enc_len=8, kv_dtype="int8")


# ------------------------------------------------- engine + scheduler


def _engines(cfg, B=2, P=8, G=6, page_size=4):
    """(bf16 paged engine, int8 paged engine) sharing one param tree."""
    bf16 = DecodeEngine(cfg, EngineConfig(batch=B, max_len=P + G,
                                          paged=True,
                                          page_size=page_size))
    q8 = DecodeEngine(cfg, EngineConfig(batch=B, max_len=P + G,
                                        paged=True, page_size=page_size,
                                        kv_dtype="int8"),
                      params=bf16.params)
    return bf16, q8


@pytest.mark.parametrize("make_cfg", [_cfg, _mla_cfg, _moe_mla_cfg],
                         ids=["gqa", "mla", "moe-mla"])
def test_engine_greedy_q8_matches_bf16(make_cfg, rng):
    """Greedy decode with int8 page pools is token-for-token identical
    to the bf16 paged engine on short prompts, and the prefill logits
    drift only within the quantization error bound."""
    cfg = make_cfg()
    B, P, G = 2, 8, 6
    bf16, q8 = _engines(cfg, B=B, P=P, G=G)
    batch = {"tokens": jnp.asarray(rng.integers(2, cfg.vocab, (B, P)),
                                   jnp.int32)}
    want, _ = bf16.generate(batch, gen=G)
    got, _ = q8.generate(batch, gen=G)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    lw, cache_w = bf16.prefill(batch)
    lg, cache_g = q8.prefill(batch)
    drift = float(jnp.abs(lg - lw).max())
    assert drift < 0.1, drift
    # decode-step logits (through the quantized page write) drift too,
    # but stay bounded
    tok = jnp.argmax(lw, -1).astype(jnp.int32)
    lens = jnp.full((B,), P, jnp.int32)
    tbl = bf16.default_block_table()
    lw2, _ = bf16.decode_step(tok, lens, cache_w, tbl)
    lg2, _ = q8.decode_step(tok, lens, cache_g, tbl)
    assert float(jnp.abs(lg2 - lw2).max()) < 0.25


def test_engine_q8_validation():
    cfg = _cfg()
    with pytest.raises(ValueError, match="requires paged"):
        DecodeEngine(cfg, EngineConfig(batch=1, max_len=8,
                                       kv_dtype="int8"))
    with pytest.raises(ValueError, match="kv_dtype"):
        DecodeEngine(cfg, EngineConfig(batch=1, max_len=8, paged=True,
                                       page_size=4, kv_dtype="fp8"))
    audio = _cfg(family="audio", enc_layers=2, frontend="audio",
                 frontend_dim=24)
    with pytest.raises(ValueError, match="audio"):
        DecodeEngine(audio, EngineConfig(batch=1, max_len=8, paged=True,
                                         page_size=4, kv_dtype="int8"))


def test_scheduler_q8_stream_slot_reuse(rng):
    """Continuous batching over int8 pools: 3 requests over 2 slots —
    page/slot reuse goes through the offset-0 scale reset, and every
    stream matches a solo q8 engine run."""
    cfg = _cfg()
    P = 8
    eng = DecodeEngine(cfg, EngineConfig(batch=2, max_len=P + 8,
                                         paged=True, page_size=4,
                                         n_pages=10, kv_dtype="int8"))
    reqs = [Request(rid=i, tokens=rng.integers(
                0, cfg.vocab, (P,)).astype(np.int32), gen=g)
            for i, g in enumerate((3, 7, 5))]
    sched = Scheduler(eng)
    for r in reqs:
        sched.submit(r)
    out = sched.run()
    assert set(out) == {0, 1, 2}
    assert sched.stats["prefills"] == 3
    assert sched.allocator.free_pages == eng.n_pages

    solo = DecodeEngine(cfg, EngineConfig(batch=1, max_len=P + 8,
                                          paged=True, page_size=4,
                                          kv_dtype="int8"),
                        params=eng.params)
    for r in reqs:
        want, _ = solo.generate(
            {"tokens": jnp.asarray(r.tokens)[None]}, gen=r.gen)
        np.testing.assert_array_equal(out[r.rid], np.asarray(want[0]),
                                      err_msg=f"request {r.rid}")
