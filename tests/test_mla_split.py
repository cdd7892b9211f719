"""Split-operand MLA decode tests: the copy-free
``decode_partial_mla`` / ``decode_partial_mla_paged`` ops must be
equivalent to the concatenated absorbed-MQA view (k_cat/v_cat +
``decode_partial``) — numerically at the op level and token-for-token
through the engine — plus the block-table width bucketing pins
(bucketed streams identical to fixed-width, dispatch cache keyed by
page geometry)."""
import contextlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.common.config import MLAConfig, ModelConfig
from repro.engine import (DecodeEngine, EngineConfig, Request,
                          RequestStatus, Scheduler)
from repro.engine.paged_cache import bucket_table_width
from repro.kernels import dispatch as D
from repro.models import mla as MLA

KEY = jax.random.PRNGKey(0)


def _cfg(**kw):
    base = dict(name="t", family="dense", n_layers=2, d_model=64,
                n_heads=4, n_kv_heads=2, d_head=16, d_ff=128, vocab=256,
                dtype="float32", remat="none", attn_block_q=32,
                attn_block_kv=32,
                mla=MLAConfig(q_lora_rank=32, kv_lora_rank=16,
                              rope_head_dim=8, nope_head_dim=16,
                              v_head_dim=16))
    base.update(kw)
    return ModelConfig(**base)


# ----------------------------------------------------------------------
# concatenated-view reference impls (the pre-split production path):
# ``MLA.mla_concat_view``'s q/k/v concats feeding the plain
# ``decode_partial`` ops, output sliced back to the latent dims.
# Registered over the split ops to drive the whole engine through the
# concat path for the bit-exactness pins.
# ----------------------------------------------------------------------

def _concat_mla_partial(q_abs, q_rope, c_kv, k_rope, cur_len, pos0=0, *,
                        scale, tune=True):
    q_cat, k_cat, v_cat, r = MLA.mla_concat_view(q_abs, q_rope, c_kv,
                                                 k_rope, scale)
    o_t, m, l = D.dispatch("decode_partial", "xla", q_cat, k_cat, v_cat,
                           cur_len, pos0)
    return o_t[..., :r], m, l


def _concat_mla_paged_partial(q_abs, q_rope, ckv_pool, krope_pool,
                              table, counts, *, scale, page_size=None,
                              max_pages=None, tune=True):
    q_cat, k_cat, v_cat, r = MLA.mla_concat_view(q_abs, q_rope,
                                                 ckv_pool, krope_pool,
                                                 scale, paged=True)
    o_t, m, l = D.dispatch("decode_partial_paged", "xla", q_cat, k_cat,
                           v_cat, table, counts)
    return o_t[..., :r], m, l


@contextlib.contextmanager
def _concat_registered():
    """Temporarily make the concat view the 'xla' backend of the split
    ops (re-registration is the supported test seam in the dispatch
    registry)."""
    saved = {op: dict(D._REGISTRY[op])
             for op in ("decode_partial_mla", "decode_partial_mla_paged")}
    try:
        D.register("decode_partial_mla", "xla")(_concat_mla_partial)
        D.register("decode_partial_mla_paged", "xla")(
            _concat_mla_paged_partial)
        yield
    finally:
        for op, table in saved.items():
            D._REGISTRY[op] = table


def _rand_split_inputs(B=2, H=4, r=16, rope=8, T=20):
    ks = jax.random.split(KEY, 4)
    return (jax.random.normal(ks[0], (B, H, r)),
            jax.random.normal(ks[1], (B, H, rope)),
            jax.random.normal(ks[2], (B, T, r)),
            jax.random.normal(ks[3], (B, T, rope)))


# ------------------------------------------------- op-level equivalence


def test_split_partial_matches_concat_view():
    """Split-operand XLA reference == concatenated k_cat/v_cat view
    (same softmax statistics, latent-sliced output), and the pallas
    split kernel matches its own XLA reference."""
    q_abs, q_rope, ckv, krope = _rand_split_inputs()
    scale = 1.0 / (24 ** 0.5)
    cur = jnp.int32(13)
    o_s, m_s, l_s = D.dispatch("decode_partial_mla", "xla", q_abs,
                               q_rope, ckv, krope, cur, scale=scale)
    o_c, m_c, l_c = _concat_mla_partial(q_abs, q_rope, ckv, krope, cur,
                                        scale=scale)
    np.testing.assert_allclose(np.asarray(o_s), np.asarray(o_c),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(m_s), np.asarray(m_c),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(l_s), np.asarray(l_c),
                               rtol=1e-5, atol=1e-5)
    o_p, m_p, l_p = D.dispatch("decode_partial_mla", "pallas", q_abs,
                               q_rope, ckv, krope, cur, scale=scale)
    np.testing.assert_allclose(np.asarray(o_p), np.asarray(o_s),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(l_p), np.asarray(l_s),
                               rtol=1e-5, atol=1e-5)


def test_split_paged_partial_matches_concat_view():
    """Paged split-operand op (xla gather ref AND pallas scalar-
    prefetch kernel) == concatenated pool view, with count-0 pages
    (unallocated / foreign) masked identically."""
    B, H, r, rope, ps, J, n_pages = 2, 4, 16, 8, 4, 5, 12
    ks = jax.random.split(KEY, 4)
    q_abs = jax.random.normal(ks[0], (B, H, r))
    q_rope = jax.random.normal(ks[1], (B, H, rope))
    ckv_pool = jax.random.normal(ks[2], (n_pages, ps, r))
    krope_pool = jax.random.normal(ks[3], (n_pages, ps, rope))
    table = jnp.asarray([[0, 2, 4, 0, 0], [1, 3, 5, 7, 0]], jnp.int32)
    lens = jnp.asarray([9, 18], jnp.int32)
    counts = jnp.clip(lens[:, None] - jnp.arange(J)[None, :] * ps,
                      0, ps).astype(jnp.int32)
    scale = 1.0 / (24 ** 0.5)
    want = _concat_mla_paged_partial(q_abs, q_rope, ckv_pool,
                                     krope_pool, table, counts,
                                     scale=scale)
    for backend in ("xla", "pallas"):
        got = D.dispatch("decode_partial_mla_paged", backend, q_abs,
                         q_rope, ckv_pool, krope_pool, table, counts,
                         scale=scale, page_size=ps, max_pages=J)
        for g, w in zip(got, want):
            np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                       rtol=1e-5, atol=1e-5,
                                       err_msg=backend)


# ------------------------------------------------- engine token pins


@pytest.mark.parametrize("paged", [False, True], ids=["dense", "paged"])
def test_engine_split_vs_concat_token_streams(paged, rng):
    """Greedy MLA generation through the split-operand path is token-
    for-token identical to the concatenated k_cat/v_cat path, dense
    cache and paged pools alike."""
    cfg = _cfg()
    B, P, G = 2, 8, 6
    kw = dict(paged=True, page_size=4) if paged else {}
    eng = DecodeEngine(cfg, EngineConfig(batch=B, max_len=P + G, **kw))
    batch = {"tokens": jnp.asarray(rng.integers(2, cfg.vocab, (B, P)),
                                   jnp.int32)}
    got, _ = eng.generate(batch, gen=G)
    with _concat_registered():
        eng_c = DecodeEngine(cfg, EngineConfig(batch=B, max_len=P + G,
                                               **kw), params=eng.params)
        want, _ = eng_c.generate(batch, gen=G)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ------------------------------------------------- table-width buckets


def test_bucket_table_width():
    assert bucket_table_width(0, 8) == 1
    assert bucket_table_width(1, 8) == 1
    assert bucket_table_width(2, 8) == 2
    assert bucket_table_width(3, 8) == 4
    assert bucket_table_width(5, 8) == 8
    assert bucket_table_width(8, 8) == 8
    assert bucket_table_width(9, 8) == 8          # clamped
    assert bucket_table_width(3, 6) == 4          # non-pow2 max_pages
    assert bucket_table_width(5, 6) == 6


@pytest.mark.parametrize("mla", [False, True], ids=["gqa", "mla"])
def test_scheduler_bucketed_tables_match_fixed_width(mla, rng):
    """Bucketed decode steps produce token streams identical to
    fixed-width max_pages runs, including a slot that crosses a bucket
    boundary mid-generation (2 live pages -> 3, bucket 2 -> 4), with
    admission/retire semantics untouched."""
    cfg = _cfg() if mla else _cfg(mla=None)
    P, G = 7, 10                      # 7+1 fills page 2 mid-stream
    ecfg = EngineConfig(batch=2, max_len=32, paged=True, page_size=4)
    eng = DecodeEngine(cfg, ecfg)
    reqs = [Request(rid=i, tokens=rng.integers(
                0, cfg.vocab, (P,)).astype(np.int32), gen=G)
            for i in range(3)]

    def run(bucket):
        sched = Scheduler(eng, bucket_tables=bucket)
        for r in reqs:
            sched.submit(r)
        return sched.run(), sched.stats

    got, stats_b = run(True)
    want, stats_f = run(False)
    assert set(got) == set(want) == {0, 1, 2}
    # a prefill that raised marks its request FAILED with no decode
    # step at all, which would leave table_widths empty
    assert all(v.status is RequestStatus.FINISHED
               for v in (*got.values(), *want.values()))
    for rid in want:
        np.testing.assert_array_equal(got[rid], want[rid],
                                      err_msg=f"request {rid}")
    # fixed-width stages max_pages columns every step...
    assert set(stats_f["table_widths"]) == {eng.max_pages}
    # ...bucketing stages only live pages and crosses 2 -> 4 mid-run
    assert set(stats_b["table_widths"]) == {2, 4}
    assert max(stats_b["table_widths"]) < eng.max_pages
    # same scheduling either way: identical admission/retire counts
    for k in ("prefills", "admitted", "retired", "steps", "preempted"):
        assert stats_b[k] == stats_f[k], k


def test_scheduler_counts_live_and_staged_table_entries(rng):
    """``decode_pages_live`` adds up, step by step, the pages of the
    slots that decode — exactly the leading entries of their table
    rows — and ``decode_pages_table`` the B x W entries staged; live
    never exceeds staged, and idle slots and bucket rounding leave a
    gap."""
    cfg = _cfg(mla=None)
    ecfg = EngineConfig(batch=3, max_len=32, paged=True, page_size=4)
    eng = DecodeEngine(cfg, ecfg)
    sched = Scheduler(eng, bucket_tables=True)
    for i, (P, G) in enumerate([(7, 10), (3, 4), (12, 6), (5, 9)]):
        sched.submit(Request(rid=i, tokens=rng.integers(
            0, cfg.vocab, (P,)).astype(np.int32), gen=G))
    want = {"decode_pages_live": 0, "decode_pages_table": 0}
    prepare = sched._prepare_step

    def counted():
        plan = prepare()
        if plan is not None:
            was_running, _, _, batch = plan
            table = np.asarray(batch["block_table"])
            live = 0
            for sid in was_running:
                pages = sched.slots[sid].pages
                np.testing.assert_array_equal(table[sid, :len(pages)],
                                              pages)
                live += len(pages)
            assert live <= table.size
            want["decode_pages_live"] += live
            want["decode_pages_table"] += table.size
        return plan

    sched._prepare_step = counted
    res = sched.run()
    assert set(res) == {0, 1, 2, 3}
    for k, v in want.items():
        assert sched.stats[k] == v, k
    assert 0 < want["decode_pages_live"] < want["decode_pages_table"]


# ------------------------------------------------- dispatch geometry


def test_paged_dispatch_cache_keyed_by_page_geometry(tmp_path,
                                                     monkeypatch):
    """A measured 'auto' winner for one (page_size, max_pages) must not
    replay for another: the geometry statics are folded into the
    dispatch cache key alongside the operand shapes."""
    from repro.kernels import autotune
    from repro.kernels import ops as kops

    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE",
                       str(tmp_path / "autotune.json"))
    # another test in this process may have turned the tuner off
    monkeypatch.delenv("REPRO_AUTOTUNE", raising=False)
    autotune.reset()
    B, H, KV, Dh, ps, J, n_pages = 2, 4, 2, 16, 4, 6, 12
    q = jnp.zeros((B, H, Dh))
    kp = jnp.zeros((n_pages, KV, ps, Dh))
    tbl = jnp.zeros((B, J), jnp.int32)
    cnt = jnp.zeros((B, J), jnp.int32)
    args = (q, kp, kp, tbl, cnt)
    geom = {"page_size": ps, "max_pages": J}
    other = {"page_size": 2 * ps, "max_pages": J}

    # distinct static kwargs -> distinct signatures on the same arrays
    assert (D._arg_signature(args, geom)
            != D._arg_signature(args, other))

    # persist an 'xla' winner under geometry A; replay honors it for A
    # and falls back to the prior (pallas-first) for geometry B
    shape, dtype = D._arg_signature(args, geom)
    tag = kops._backend_tag(kops._auto_interpret(None))
    key = autotune.cache_key("dispatch:decode_partial_paged", shape,
                             dtype, tag)
    autotune._persist(autotune.cache_path(), {key: {"blocks": ["xla"]}})
    assert D.cached_backend("decode_partial_paged", "auto", args,
                            geom) == "xla"
    assert D.cached_backend("decode_partial_paged", "auto", args,
                            other) == "pallas"


def test_paged_dispatch_cache_keyed_by_pool_dtype(tmp_path,
                                                  monkeypatch):
    """A measured 'auto' winner for bf16 pools must not replay for the
    int8+scales call at the same shapes: the query leads both operand
    lists in fp32, so keying only the FIRST array dtype collided them.
    Every distinct operand dtype joins the signature."""
    from repro.kernels import autotune
    from repro.kernels import ops as kops

    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE",
                       str(tmp_path / "autotune.json"))
    # another test in this process may have turned the tuner off
    monkeypatch.delenv("REPRO_AUTOTUNE", raising=False)
    autotune.reset()
    B, H, KV, Dh, ps, J, n_pages = 2, 4, 2, 16, 4, 6, 12
    q = jnp.zeros((B, H, Dh))                       # fp32 leads both
    tbl = jnp.zeros((B, J), jnp.int32)
    cnt = jnp.zeros((B, J), jnp.int32)
    geom = {"page_size": ps, "max_pages": J}
    kp16 = jnp.zeros((n_pages, KV, ps, Dh), jnp.bfloat16)
    bf16_args = (q, kp16, kp16, tbl, cnt)
    kp8 = jnp.zeros((n_pages, KV, ps, Dh), jnp.int8)
    sc = jnp.zeros((n_pages, KV), jnp.float32)
    q8_args = (q, kp8, kp8, sc, sc, tbl, cnt)

    sig16 = D._arg_signature(bf16_args, geom)
    sig8 = D._arg_signature(q8_args, geom)
    assert sig16 != sig8
    assert "int8" in sig8[1] and "int8" not in sig16[1]

    # persist an 'xla' winner for the bf16 pools; the q8 twin still
    # resolves through the prior (pallas-first), not the bf16 entry
    tag = kops._backend_tag(kops._auto_interpret(None))
    key = autotune.cache_key("dispatch:decode_partial_paged", sig16[0],
                             sig16[1], tag)
    autotune._persist(autotune.cache_path(), {key: {"blocks": ["xla"]}})
    assert D.cached_backend("decode_partial_paged", "auto", bf16_args,
                            geom) == "xla"
    assert D.cached_backend("decode_partial_paged_q8", "auto", q8_args,
                            geom) == "pallas"
    assert D.cached_backend("decode_partial_paged", "auto", q8_args,
                            geom) == "pallas"
