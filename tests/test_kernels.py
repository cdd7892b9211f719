"""Pallas kernel sweeps: shapes x dtypes vs the pure-jnp oracles
(interpret mode on CPU; same calls compile to Mosaic on TPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

KEY = jax.random.PRNGKey(0)


def _rand(key, shape, dtype):
    x = jax.random.normal(key, shape, jnp.float32)
    return x.astype(dtype)


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("m,k,n,bm,bk,bn", [
    (64, 64, 64, 64, 64, 64),
    (128, 256, 96, 64, 128, 32),
    (100, 130, 50, 32, 64, 32),      # ragged -> padding path
    (256, 512, 256, 128, 256, 128),
])
def test_vwr_matmul(dtype, m, k, n, bm, bk, bn):
    k1, k2 = jax.random.split(KEY)
    x = _rand(k1, (m, k), dtype)
    w = _rand(k2, (k, n), dtype)
    out = ops.vwr_matmul(x, w, bm=bm, bk=bk, bn=bn)
    want = ref.matmul_ref(x, w)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("n,h,w,c,f,kh,kw,bh,bf", [
    (1, 9, 9, 8, 8, 3, 3, 2, 8),
    (2, 13, 11, 7, 5, 3, 3, 4, 4),
    (1, 8, 8, 4, 16, 1, 1, 4, 16),   # 1x1 conv
    (2, 12, 10, 3, 9, 5, 5, 4, 4),
])
def test_vwr_conv2d(dtype, n, h, w, c, f, kh, kw, bh, bf):
    k1, k2 = jax.random.split(KEY)
    x = _rand(k1, (n, h, w, c), dtype)
    wts = _rand(k2, (kh, kw, c, f), dtype)
    out = ops.vwr_conv2d(x, wts, bh=bh, bf=bf)
    want = ref.conv2d_ref(x, wts)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("n,h,w,c,k,bh", [
    (1, 10, 10, 8, 3, 4),
    (2, 12, 9, 16, 3, 2),
    (1, 9, 9, 4, 5, 5),
])
def test_vwr_depthwise(dtype, n, h, w, c, k, bh):
    k1, k2 = jax.random.split(KEY)
    x = _rand(k1, (n, h, w, c), dtype)
    wts = _rand(k2, (k, k, c), dtype)
    out = ops.vwr_depthwise(x, wts, bh=bh)
    want = ref.depthwise_ref(x, wts)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("m,k,n,act,bias,res", [
    (64, 64, 64, "relu", True, False),
    (100, 130, 50, "gelu", True, True),      # ragged + full epilogue
    (128, 64, 96, "silu", False, True),
    (64, 128, 64, None, True, True),         # bias+residual only
])
def test_vwr_matmul_fused_epilogue(dtype, m, k, n, act, bias, res):
    """Fused bias/activation/residual == the unfused two-pass
    composition (the final-K store applies the epilogue in fp32)."""
    k1, k2, k3, k4 = jax.random.split(KEY, 4)
    x = _rand(k1, (m, k), dtype)
    w = _rand(k2, (k, n), dtype)
    b = _rand(k3, (n,), dtype) if bias else None
    r = _rand(k4, (m, n), dtype) if res else None
    out = ops.vwr_matmul(x, w, b, r, activation=act, bm=32, bk=64, bn=32)
    want = ref.matmul_ref(x, w).astype(jnp.float32)
    if b is not None:
        want = want + b.astype(jnp.float32)
    if act is not None:
        fn = {"relu": jax.nn.relu, "gelu": jax.nn.gelu,
              "silu": jax.nn.silu}[act]
        want = fn(want)
    if r is not None:
        want = want + r.astype(jnp.float32)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want.astype(dtype), np.float32),
                               **_tol(dtype))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("m,k,n,bm,bk,bn", [
    (64, 64, 64, 64, 64, 64),
    (100, 130, 50, 32, 64, 32),      # ragged -> padding path
    (128, 256, 96, 64, 128, 32),
])
def test_vwr_swiglu_fused_dual_matmul(dtype, m, k, n, bm, bk, bn):
    """The dual-matmul fused swiglu == silu(x@wg) * (x@wi) composed
    from two plain matmuls (one staged x block, the gate product on
    the fp32 accumulators in the final-K store)."""
    k1, k2, k3 = jax.random.split(KEY, 3)
    x = _rand(k1, (m, k), dtype)
    wg = _rand(k2, (k, n), dtype)
    wi = _rand(k3, (k, n), dtype)
    out = ops.vwr_swiglu(x, wg, wi, bm=bm, bk=bk, bn=bn)
    g = ref.matmul_ref(x, wg).astype(jnp.float32)
    h = ref.matmul_ref(x, wi).astype(jnp.float32)
    want = (jax.nn.silu(g) * h).astype(dtype)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,s,h,kv,d,bq,bkv,causal", [
    (2, 64, 4, 4, 16, 32, 32, True),
    (2, 100, 8, 2, 16, 32, 64, True),    # GQA + ragged seq
    (1, 128, 4, 4, 32, 64, 64, False),
    (1, 96, 4, 1, 32, 32, 32, True),     # MQA
    (2, 64, 12, 4, 16, 32, 32, True),    # GQA with non-pow2 group G=3
])
def test_vwr_attention(dtype, b, s, h, kv, d, bq, bkv, causal):
    k1, k2, k3 = jax.random.split(KEY, 3)
    q = _rand(k1, (b, s, h, d), dtype)
    k = _rand(k2, (b, s, kv, d), dtype)
    v = _rand(k3, (b, s, kv, d), dtype)
    out = ops.vwr_attention(q, k, v, causal=causal, bq=bq, bkv=bkv)
    g = h // kv
    kr = jnp.repeat(k, g, 2).transpose(0, 2, 1, 3).reshape(b * h, s, d)
    vr = jnp.repeat(v, g, 2).transpose(0, 2, 1, 3).reshape(b * h, s, d)
    qf = q.transpose(0, 2, 1, 3).reshape(b * h, s, d)
    want = ref.attention_ref(qf, kr, vr, causal=causal)
    want = want.reshape(b, h, s, d).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               **_tol(dtype))


def test_vwr_attention_gqa_zero_copy_vs_oracle():
    """H=8 query heads over KV=2 heads: the zero-copy BlockSpec
    routing (kv block = b // G) must match the dense GQA oracle that
    logically broadcasts each KV head over its group."""
    from repro.models.attention import full_attn_ref
    k1, k2, k3 = jax.random.split(KEY, 3)
    q = _rand(k1, (2, 96, 8, 32), jnp.float32)
    k = _rand(k2, (2, 96, 2, 32), jnp.float32)
    v = _rand(k3, (2, 96, 2, 32), jnp.float32)
    out = ops.vwr_attention(q, k, v, causal=True, bq=32, bkv=32)
    want = full_attn_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-4, atol=2e-4)
    # and it must equal the old head-expanded (materialized) layout
    g = 4
    expanded = ops.vwr_attention(q, jnp.repeat(k, g, 2),
                                 jnp.repeat(v, g, 2),
                                 causal=True, bq=32, bkv=32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expanded),
                               rtol=1e-6, atol=1e-6)


def test_attention_matches_model_blockwise():
    """Pallas kernel == the model's pure-JAX blockwise path (the one
    the dry-run lowers) — kernel_impl swap is semantics-preserving."""
    from repro.models.attention import blockwise_attn
    k1, k2, k3 = jax.random.split(KEY, 3)
    q = _rand(k1, (2, 64, 4, 16), jnp.float32)
    k = _rand(k2, (2, 64, 2, 16), jnp.float32)
    v = _rand(k3, (2, 64, 2, 16), jnp.float32)
    a = ops.vwr_attention(q, k, v, causal=True, bq=32, bkv=32)
    b = blockwise_attn(q, k, v, causal=True, block_q=32, block_kv=32)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4,
                               atol=2e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("n,h,w,c,f,kh,act,bias", [
    (1, 9, 9, 8, 8, 3, "relu", True),
    (2, 13, 11, 7, 5, 3, "gelu", True),     # ragged + padding path
    (1, 8, 8, 4, 16, 1, None, True),        # bias only
    (2, 12, 10, 3, 9, 5, "relu", False),    # activation only
])
def test_vwr_conv2d_fused_epilogue(dtype, n, h, w, c, f, kh, act, bias):
    """Fused bias+activation == the unfused two-pass composition (the
    single store applies the epilogue on the fp32 accumulator)."""
    k1, k2, k3 = jax.random.split(KEY, 3)
    x = _rand(k1, (n, h, w, c), dtype)
    wts = _rand(k2, (kh, kh, c, f), dtype)
    b = _rand(k3, (f,), dtype) if bias else None
    out = ops.vwr_conv2d(x, wts, b, activation=act, bh=4, bf=4)
    want = ref.conv2d_ref(x, wts).astype(jnp.float32)
    if b is not None:
        want = want + b.astype(jnp.float32)
    if act is not None:
        want = {"relu": jax.nn.relu, "gelu": jax.nn.gelu}[act](want)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want.astype(dtype), np.float32),
                               **_tol(dtype))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,t,h,kv,d,bkv,cur", [
    (2, 64, 4, 4, 16, 32, 50),
    (2, 100, 8, 2, 16, 32, 100),     # GQA + ragged cache -> padding
    (1, 96, 4, 1, 32, 64, 1),        # MQA, single valid position
])
def test_vwr_flash_decode_partials(dtype, b, t, h, kv, d, bkv, cur):
    """Normalized kernel partials == decode_attend_local; the (m, l)
    stats obey the distributed-FlashDecoding combine contract."""
    from repro.models.attention import decode_attend_local, \
        flash_decode_partial
    k1, k2, k3 = jax.random.split(KEY, 3)
    q = _rand(k1, (b, h, d), dtype)
    ck = _rand(k2, (b, t, kv, d), dtype)
    cv = _rand(k3, (b, t, kv, d), dtype)
    o_t, m, l = ops.vwr_flash_decode(q, ck, cv, jnp.int32(cur), bkv=bkv)
    got = (o_t / jnp.maximum(l, 1e-30)[..., None]).astype(dtype)
    want = decode_attend_local(q, ck, cv, jnp.arange(t), jnp.int32(cur))
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))
    # stats match the XLA partial formulation (same combine contract)
    o_ref, m_ref, l_ref = flash_decode_partial(q, ck, cv, jnp.arange(t),
                                               jnp.int32(cur))
    tol = _tol(dtype)
    np.testing.assert_allclose(np.asarray(m), np.asarray(m_ref), **tol)
    np.testing.assert_allclose(np.asarray(l), np.asarray(l_ref),
                               rtol=5 * tol["rtol"], atol=5 * tol["atol"])


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,h,kv,d,ps,j,rows", [
    pytest.param(2, 4, 2, 16, 8, 4, "ragged", id="2-4-2-16-8-4"),  # GQA
    # MQA (the absorbed-MLA view)
    pytest.param(3, 4, 1, 32, 4, 6, "ragged", id="3-4-1-32-4-6"),
    # qwen1.5-0.5b's MHA geometry; J = 11 is no multiple of the pages
    # staged per block (4 fp32, 8 bf16)
    pytest.param(3, 16, 16, 64, 16, 11, "serving", id="mha-serving"),
    pytest.param(3, 16, 16, 64, 16, 11, "sharded", id="mha-sharded"),
    # tinyllama-1.1b's GQA, G = 8
    pytest.param(2, 32, 4, 64, 16, 5, "serving", id="gqa8-serving"),
    pytest.param(2, 32, 4, 64, 16, 5, "sharded", id="gqa8-sharded"),
    pytest.param(2, 4, 2, 16, 8, 1, "serving", id="j1-serving"),
    pytest.param(3, 4, 2, 16, 8, 9, "sharded", id="gqa-sharded-blocks"),
])
def test_vwr_paged_flash_decode_matches_gather_ref(dtype, b, h, kv, d,
                                                   ps, j, rows):
    """The block-table-indexed paged kernel == the XLA gather reference
    == the dense kernel on the gathered cache, including zero-count
    (masked) pages and per-slot ragged lengths.

    ``rows`` is how the table rows look: "ragged" lengths; "serving",
    the scheduler's table — long ragged rows, slot 1 inactive (every
    count 0) and unallocated entries pointing at page 0; "sharded",
    long ragged rows whose pages another shard owns (odd page ids)
    carry count 0 in the middle of the row, as
    ``dist.decode.sharded_paged_flash_decode`` makes them."""
    from repro.kernels.vwr_decode import NEG_INF
    from repro.models.attention import paged_flash_decode_partial
    n_pages = b * j + 3
    k1, k2, k3, k4 = jax.random.split(KEY, 4)
    q = _rand(k1, (b, h, d), dtype)
    kp = _rand(k2, (n_pages, kv, ps, d), dtype)
    vp = _rand(k3, (n_pages, kv, ps, d), dtype)
    # shuffled disjoint page assignment + ragged per-slot lengths
    perm = jax.random.permutation(k4, n_pages)[:b * j]
    table = perm.reshape(b, j).astype(jnp.int32)
    slots = jnp.arange(b, dtype=jnp.int32)
    if rows == "ragged":
        lens = (slots * (ps + 1) + 3) % (j * ps)
    else:
        lens = j * ps * (b - slots) // b - slots
    if rows == "serving":
        lens = lens.at[1].set(0)
    counts = jnp.clip(lens[:, None] - jnp.arange(j)[None, :] * ps,
                      0, ps).astype(jnp.int32)
    if rows == "serving":
        table = jnp.where(counts > 0, table, 0)
    if rows == "sharded":
        counts = jnp.where(table % 2 == 1, 0, counts)
    got = ops.vwr_paged_flash_decode(q, kp, vp, table, counts)
    want = paged_flash_decode_partial(q, kp, vp, table, counts)
    tol = _tol(dtype)
    for g, w, name in zip(got, want, ("o_tilde", "m", "l")):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=5 * tol["rtol"],
                                   atol=5 * tol["atol"], err_msg=name)
    # an inactive slot keeps the all-masked contract exactly
    for slot in range(b):
        if int(counts[slot].sum()) == 0:
            assert float(jnp.abs(got[0][slot]).max()) == 0.0
            assert float(got[1][slot].max()) == np.float32(NEG_INF)
            assert float(jnp.abs(got[2][slot]).max()) == 0.0
    if rows == "sharded":
        return                   # live positions are no prefix of a row
    # and the gathered-dense oracle agrees slot by slot
    from repro.models.attention import decode_attend_local
    dense_k = jnp.swapaxes(kp[table], 2, 3).reshape(b, j * ps, kv, d)
    dense_v = jnp.swapaxes(vp[table], 2, 3).reshape(b, j * ps, kv, d)
    norm = (got[0] / jnp.maximum(got[2], 1e-30)[..., None])
    for slot in range(b):
        if int(lens[slot]) == 0:
            assert float(jnp.abs(norm[slot]).max()) == 0.0
            continue
        want_o = decode_attend_local(
            q[slot:slot + 1], dense_k[slot:slot + 1],
            dense_v[slot:slot + 1], jnp.arange(j * ps), lens[slot])
        np.testing.assert_allclose(
            np.asarray(norm[slot], np.float32),
            np.asarray(want_o[0], np.float32),
            rtol=5 * tol["rtol"], atol=5 * tol["atol"])


def test_vwr_flash_decode_sharded_offset():
    """pos0 slab offsets partition the softmax: combining two half-
    cache partials reproduces the full-cache result."""
    from repro.models.attention import decode_attend_local
    k1, k2, k3 = jax.random.split(KEY, 3)
    B, T, KV, D, H = 2, 64, 2, 16, 4
    q = _rand(k1, (B, H, D), jnp.float32)
    ck = _rand(k2, (B, T, KV, D), jnp.float32)
    cv = _rand(k3, (B, T, KV, D), jnp.float32)
    cur = jnp.int32(50)
    halves = [ops.vwr_flash_decode(q, ck[:, s], cv[:, s], cur,
                                   pos0=s.start)
              for s in (slice(0, 32), slice(32, 64))]
    m_star = jnp.maximum(halves[0][1], halves[1][1])
    o = sum(o_t * jnp.exp(m - m_star)[..., None] for o_t, m, _ in halves)
    l = sum(l * jnp.exp(m - m_star) for _, m, l in halves)
    got = o / jnp.maximum(l, 1e-30)[..., None]
    want = decode_attend_local(q, ck, cv, jnp.arange(T), cur)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_mla_absorbed_mqa_view_matches_partial_oracle():
    """MLA decode recast as MQA flash-decode (concat latent+rope cache,
    KV=1) must reproduce the absorbed-form einsum partial's normalized
    output — the contract that lets MLA ride the GQA decode path."""
    from repro.common.config import MLAConfig, ModelConfig
    from repro.models import mla
    from repro.models.attention import flash_decode_partial

    cfg = ModelConfig(name="t", family="dense", n_layers=1, d_model=64,
                      n_heads=4, n_kv_heads=4, d_head=16, d_ff=128,
                      vocab=64, dtype="float32", remat="none",
                      mla=MLAConfig(q_lora_rank=32, kv_lora_rank=16,
                                    rope_head_dim=8, nope_head_dim=16,
                                    v_head_dim=16))
    p = jax.tree.map(lambda d: d.init(KEY, d.shape, d.dtype),
                     mla.mla_spec(cfg),
                     is_leaf=lambda x: hasattr(x, "init"))
    B, T = 2, 12
    x = _rand(KEY, (B, T, 64), jnp.float32)
    _, (ckv, krope) = mla.mla_attention(p, x, jnp.arange(T), cfg,
                                        causal=True, dense=True)
    q_nope, q_rope = mla.mla_queries(p, x[:, -1:], jnp.arange(T)[-1:],
                                     cfg)
    o_ref, m_ref, l_ref = mla.mla_decode_partial(
        p, q_nope[:, 0], q_rope[:, 0], ckv, krope, jnp.arange(T),
        jnp.int32(T), cfg)
    want = o_ref / np.maximum(np.asarray(l_ref), 1e-30)[..., None]

    q_cat, k_cat, v_cat, r = mla.mla_absorbed_mqa(
        p, q_nope[:, 0], q_rope[:, 0], ckv, krope, cfg)
    # xla registry impl
    o_t, m, l = flash_decode_partial(q_cat, k_cat, v_cat, jnp.arange(T),
                                     jnp.int32(T))
    got = (o_t / np.maximum(np.asarray(l), 1e-30)[..., None])[..., :r]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    # pallas registry impl (the VWR flash-decode kernel)
    o_t2, m2, l2 = ops.vwr_flash_decode(q_cat, k_cat, v_cat,
                                        jnp.int32(T), bkv=32)
    got2 = (o_t2 / np.maximum(np.asarray(l2), 1e-30)[..., None])[..., :r]
    np.testing.assert_allclose(np.asarray(got2), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_decode_step_pallas_matches_xla():
    """cfg.kernel_impl='pallas' decode (the VWR flash-decode kernel
    inside lm._decode_gqa) is semantics-preserving vs the einsum/XLA
    decode path, across several steps of cache growth."""
    from repro.common.config import ModelConfig
    from repro.models import lm

    cfg = ModelConfig(name="t", family="dense", n_layers=2, d_model=64,
                      n_heads=4, n_kv_heads=2, d_head=16, d_ff=128,
                      vocab=256, dtype="float32", remat="none",
                      qkv_bias=True)
    params = lm.init(cfg, jax.random.PRNGKey(0))
    B, T = 2, 16
    cache_x = lm.init_cache(cfg, B, T)
    cache_p = lm.init_cache(cfg, B, T)
    tok = jax.random.randint(jax.random.PRNGKey(1), (B,), 0, 256)
    pcfg = cfg.replace(kernel_impl="pallas")
    for step in range(3):
        bx = {"token": tok, "cur_len": jnp.int32(step), "cache": cache_x}
        bp = {"token": tok, "cur_len": jnp.int32(step), "cache": cache_p}
        want, cache_x = lm.decode_step(params, bx, cfg)
        got, cache_p = lm.decode_step(params, bp, pcfg)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-4)
        tok = jnp.argmax(want, -1).astype(jnp.int32)
