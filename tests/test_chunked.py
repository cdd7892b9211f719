"""Chunked prefill + unified mixed-step tests: the ``pack_chunk``
token-budget rule's boundary cases, the chunk-prefix kernel partials
(pallas interpret vs the XLA gather reference, incl. int8 pools and
the fully-masked-prefix identity), greedy bit-identity of the chunked
scheduler against the non-chunked one for every paged family x kv
dtype x prefix-cache setting, the page-boundary / 1-token-final-chunk
/ prefix-hit-all-but-one-token admission edges, mid-prefill preemption
keeping exactly the completed whole pages, and the mixed-step
transient-fault retry redoing only the in-flight chunk (the hypothesis
mirror of the packer invariants lives in tests/test_resilience_prop.py)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.common.config import MLAConfig, ModelConfig, MoEConfig
from repro.engine import (DecodeEngine, EngineConfig, Request,
                          RequestStatus, Scheduler, faults)
from repro.engine.scheduler import pack_chunk
from repro.models import attention as A

PS = 4          # page_size used throughout
CT = 8          # chunk_tokens (2 pages) used throughout


def _cfg(**kw):
    base = dict(name="t", family="dense", n_layers=2, d_model=64,
                n_heads=4, n_kv_heads=2, d_head=16, d_ff=128, vocab=256,
                dtype="float32", remat="none", attn_block_q=32,
                attn_block_kv=32)
    base.update(kw)
    return ModelConfig(**base)


_MLA = MLAConfig(q_lora_rank=32, kv_lora_rank=16, rope_head_dim=8,
                 nope_head_dim=16, v_head_dim=16)


def _mla_cfg():
    return _cfg(mla=_MLA)


def _moe_mla_cfg():
    return _cfg(family="moe",
                moe=MoEConfig(n_experts=4, top_k=2, d_expert=32,
                              first_k_dense=1, d_ff_dense=128,
                              capacity_factor=4.0),
                mla=_MLA)


def _engine(cfg, B=2, max_len=32, n_pages=24, **kw):
    return DecodeEngine(cfg, EngineConfig(
        batch=B, max_len=max_len, paged=True, page_size=PS,
        n_pages=n_pages, chunked_prefill=True, chunk_tokens=CT, **kw))


def _run(eng, reqs, **sched_kw):
    sched = Scheduler(eng, **sched_kw)
    for r in reqs:
        sched.submit(r)
    return sched, sched.run()


def _reqs(rng, vocab, specs):
    return [Request(rid=i, tokens=rng.integers(2, vocab, (p,))
                    .astype(np.int32), gen=g, seed=i)
            for i, (p, g) in enumerate(specs)]


# ------------------------------------------------- pack_chunk boundaries


@pytest.mark.parametrize("remaining,n_decode,budget,want", [
    (40, 2, 2 + CT, CT),       # full chunk fits beside the decodes
    (40, 10, 10, 0),           # decode fills the budget: no chunk
    (40, 7, 10, 0),            # room 3 < page: floored away
    (40, 6, 10, PS),           # room 4: one whole page
    (40, 2, 2 + CT - 1, PS),   # room 7 floors to one page, not two
    (5, 2, 2 + CT, 5),         # final chunk: exact, unaligned
    (1, 2, 2 + CT, 1),         # 1-token final chunk
    (CT, 2, 2 + CT, CT),       # final chunk landing ON the boundary
    (40, 0, 1, 0),             # budget 1, room < page
    (3, 0, 1, 0),              # would be final but room 1 < remaining 3
    (1, 0, 1, 1),              # empty batch still prefills
], ids=["full", "starved", "floored-0", "floored-1page", "floored-7",
        "final-unaligned", "final-1tok", "final-aligned", "tiny-budget",
        "tiny-budget-nonfinal", "empty-batch"])
def test_pack_chunk_boundaries(remaining, n_decode, budget, want):
    got = pack_chunk(remaining, n_decode, budget, CT, PS)
    assert got == want
    # the invariants the hypothesis property pins, spot-checked here:
    assert got <= remaining and got <= CT
    if got:
        assert n_decode + got <= budget
    if 0 < got < remaining:
        assert got % PS == 0   # non-final chunks end page-aligned


# ------------------------------------------------- kernel partials


def _pool(rng, n_pages=6, KV=2, Dh=16):
    k = rng.standard_normal((n_pages, KV, PS, Dh)).astype(np.float32)
    v = rng.standard_normal((n_pages, KV, PS, Dh)).astype(np.float32)
    return jnp.asarray(k), jnp.asarray(v)


@pytest.mark.parametrize("counts", [(PS, PS, PS), (PS, 3, 0), (PS, 1, 1)],
                         ids=["full-pages", "partial-tail", "sparse"])
def test_chunk_prefix_pallas_matches_xla(counts, rng):
    """The pallas chunk-prefix kernel (interpret mode on CPU) returns
    the same (o_tilde, m, l) partial as the XLA gather reference."""
    from repro.models.attention import D
    C, H, Dh = 8, 4, 16
    kp, vp = _pool(rng)
    q = jnp.asarray(rng.standard_normal((C, H, Dh)), jnp.float32)
    table = jnp.asarray([4, 1, 3], jnp.int32)
    cnt = jnp.asarray(counts, jnp.int32)
    want = D.dispatch("chunk_prefix_paged", "xla", q, kp, vp, table,
                      cnt, page_size=PS, max_pages=3)
    got = D.dispatch("chunk_prefix_paged", "pallas", q, kp, vp, table,
                     cnt, page_size=PS, max_pages=3)
    for w, g, name in zip(want, got, ("o_tilde", "m", "l")):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=2e-5, atol=2e-5, err_msg=name)


def test_chunk_prefix_q8_pallas_matches_xla(rng):
    from repro.models.attention import D
    C, H, KV, Dh = 8, 4, 2, 16
    kq = jnp.asarray(rng.integers(-127, 128, (6, KV, PS, Dh)), jnp.int8)
    vq = jnp.asarray(rng.integers(-127, 128, (6, KV, PS, Dh)), jnp.int8)
    ks = jnp.asarray(rng.uniform(0.01, 0.05, (6, KV)), jnp.float32)
    vs = jnp.asarray(rng.uniform(0.01, 0.05, (6, KV)), jnp.float32)
    q = jnp.asarray(rng.standard_normal((C, H, Dh)), jnp.float32)
    table = jnp.asarray([0, 5, 2], jnp.int32)
    cnt = jnp.asarray([PS, PS, 2], jnp.int32)
    want = D.dispatch("chunk_prefix_paged_q8", "xla", q, kq, vq, ks, vs,
                      table, cnt, page_size=PS, max_pages=3)
    got = D.dispatch("chunk_prefix_paged_q8", "pallas", q, kq, vq, ks,
                     vs, table, cnt, page_size=PS, max_pages=3)
    for w, g, name in zip(want, got, ("o_tilde", "m", "l")):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=2e-5, atol=2e-5, err_msg=name)


def test_masked_prefix_partial_is_identity(rng):
    """A fully masked prefix partial (counts all zero — the FIRST chunk
    of a prompt) merges into the self partial as an exact no-op: the
    chunk's output equals plain causal self-attention."""
    C, H, KV, Dh = 8, 4, 2, 16
    kp, vp = _pool(rng)
    q = jnp.asarray(rng.standard_normal((C, H, Dh)), jnp.float32)
    ck = jnp.asarray(rng.standard_normal((C, KV, Dh)), jnp.float32)
    cv = jnp.asarray(rng.standard_normal((C, KV, Dh)), jnp.float32)
    table = jnp.zeros((3,), jnp.int32)
    cnt = jnp.zeros((3,), jnp.int32)
    got = A.chunk_prefill_attend(q, ck, cv, kp, vp, table, cnt)
    o_t, _, l = A.chunk_self_attn_partial(q, ck, cv)
    want = A.normalize_partial(o_t, l, q.dtype)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_chunk_rows_match_whole_prefill_rows(rng):
    """Blockwise exactness, one chunk at a time: prefix partial over
    the earlier chunks' pooled KV merged with the chunk's self partial
    reproduces the corresponding rows of one dense causal pass over
    the whole prompt."""
    S, C, H, KV, Dh = 16, CT, 4, 2, 16
    k = rng.standard_normal((S, KV, Dh)).astype(np.float32)
    v = rng.standard_normal((S, KV, Dh)).astype(np.float32)
    q = rng.standard_normal((S, H, Dh)).astype(np.float32)
    o_t, _, l = A.chunk_self_attn_partial(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v))
    whole = np.asarray(A.normalize_partial(o_t, l, jnp.float32))
    n_pages = S // PS
    kp = jnp.asarray(k.reshape(n_pages, PS, KV, Dh).transpose(0, 2, 1, 3))
    vp = jnp.asarray(v.reshape(n_pages, PS, KV, Dh).transpose(0, 2, 1, 3))
    # chunks after the first (chunk 0 has no prior pages; its identity
    # with plain causal self-attention is pinned above)
    for c0 in range(C, S, C):
        jp = c0 // PS
        table = jnp.arange(jp, dtype=jnp.int32)
        cnt = jnp.full((jp,), PS, jnp.int32)
        got = A.chunk_prefill_attend(
            jnp.asarray(q[c0:c0 + C]), jnp.asarray(k[c0:c0 + C]),
            jnp.asarray(v[c0:c0 + C]), kp, vp, table, cnt)
        np.testing.assert_allclose(np.asarray(got), whole[c0:c0 + C],
                                   rtol=2e-5, atol=2e-5,
                                   err_msg=f"chunk at {c0}")


# ------------------------------------------------- scheduler bit-identity


# this seed pins greedy identity for the int8 cells too: chunks after
# the first read the earlier chunks' KV through the quantized pages
# where the whole prefill saw full precision, so a near-tie argmax
# could flip — identity is pinned empirically at this scale/seed,
# exactly like the prefix-cache int8 tests
_SEED = 0
_SPECS = [(19, 6), (5, 4), (11, 5)]


@pytest.mark.parametrize("make_cfg", [_cfg, _mla_cfg, _moe_mla_cfg],
                         ids=["gqa", "mla", "moe-mla"])
@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
@pytest.mark.parametrize("prefix", [False, True],
                         ids=["no-prefix", "prefix"])
def test_chunked_scheduler_matches_non_chunked(make_cfg, kv_dtype,
                                               prefix):
    cfg = make_cfg()
    eng = _engine(cfg, kv_dtype=kv_dtype, prefix_cache=prefix)
    rng = np.random.default_rng(_SEED)
    prompts = [rng.integers(2, cfg.vocab, (p,)).astype(np.int32)
               for p, _ in _SPECS]

    def reqs():
        return [Request(rid=i, tokens=prompts[i], gen=g, seed=i)
                for i, (_, g) in enumerate(_SPECS)]

    off, want = _run(eng, reqs(), chunked_prefill=False)
    on, got = _run(eng, reqs())
    for i in range(len(_SPECS)):
        np.testing.assert_array_equal(np.asarray(got[i]),
                                      np.asarray(want[i]),
                                      err_msg=f"req {i}")
    assert on.stats["chunks"] >= 3 and on.stats["mixed_steps"] >= 3
    assert on.stats["chunked_tokens"] == sum(p for p, _ in _SPECS)
    assert off.stats["chunks"] == 0 and off.stats["mixed_steps"] == 0
    assert on.allocator.free_pages == eng.n_pages - (
        on.prefix.cached_pages if on.prefix is not None else 0)
    on.allocator.check()


# ------------------------------------------------- admission edges


def _identity_case(prompt_len, gen, want_chunks, rng):
    cfg = _cfg()
    eng = _engine(cfg, max_len=40, n_pages=32)
    toks = rng.integers(2, cfg.vocab, (prompt_len,)).astype(np.int32)
    _, want = _run(eng, [Request(rid=0, tokens=toks, gen=gen, seed=0)],
                   chunked_prefill=False)
    on, got = _run(eng, [Request(rid=0, tokens=toks, gen=gen, seed=0)])
    np.testing.assert_array_equal(np.asarray(got[0]),
                                  np.asarray(want[0]))
    assert on.stats["chunks"] == want_chunks
    assert on.allocator.free_pages == eng.n_pages


def test_chunk_ends_exactly_on_page_boundary(rng):
    """Prompt = 2 full chunks: the final chunk lands exactly on a page
    boundary (remaining == room, aligned)."""
    _identity_case(2 * CT, 5, 2, rng)


def test_one_token_final_chunk(rng):
    """Prompt = 2 chunks + 1: the final chunk carries a single token
    (the promotion logits come from a C=1 chunk)."""
    _identity_case(2 * CT + 1, 5, 3, rng)


def test_prefix_hit_consuming_all_but_one_token(rng):
    """A cached prefix covering every whole page of the prompt leaves a
    1-token suffix: chunked admission must enqueue exactly one 1-token
    final chunk over the aliased resident pages."""
    cfg = _cfg()
    eng = _engine(cfg, max_len=32, n_pages=24, prefix_cache=True)
    toks = rng.integers(2, cfg.vocab, (2 * PS + 1,)).astype(np.int32)

    _, want = _run(eng, [Request(rid=0, tokens=toks, gen=5, seed=0)],
                   chunked_prefill=False, prefix_cache=False)
    sched = Scheduler(eng)
    sched.submit(Request(rid=0, tokens=toks, gen=5, seed=0))
    out0 = sched.run()                      # cold: inserts 2 pages
    np.testing.assert_array_equal(np.asarray(out0[0]),
                                  np.asarray(want[0]))
    chunks_cold = sched.stats["chunks"]
    sched.submit(Request(rid=1, tokens=toks, gen=5, seed=1))
    out = sched.run()                       # hit: 8 of 9 tokens cached
    assert sched.stats["prefix_hits"] == 1
    assert sched.stats["prefix_hit_tokens"] == 2 * PS
    assert sched.stats["chunks"] == chunks_cold + 1   # one 1-token chunk
    np.testing.assert_array_equal(np.asarray(out[1]),
                                  np.asarray(want[0]))
    sched.prefix.check()
    sched.allocator.check()


# ------------------------------------------------- preemption mid-prefill


def test_preempt_mid_prefill_keeps_completed_pages(rng):
    """Preempting a PREFILLING slot drops only the in-flight chunk: the
    whole pages its completed chunks wrote travel WITH the queued slot,
    re-admission grants just the missing tail, chunking resumes where
    it left off, and the stream is bit-identical to the non-chunked
    scheduler."""
    cfg = _cfg()
    eng = _engine(cfg, B=1, max_len=32, n_pages=16)
    toks = rng.integers(2, cfg.vocab, (19,)).astype(np.int32)
    _, want = _run(eng, [Request(rid=0, tokens=toks, gen=6, seed=0)],
                   chunked_prefill=False)

    sched = Scheduler(eng)
    sched.submit(Request(rid=0, tokens=toks, gen=6, seed=0))
    assert sched.admit() == 1
    slot = sched.slots[0]
    assert slot.req.status is RequestStatus.PREFILLING
    granted = len(slot.pages)
    sched.step()                            # chunk 1: prefilled 8
    sched.step()                            # chunk 2: prefilled 16
    assert slot.prefilled == 2 * CT
    sched._preempt(0)
    # exactly the completed whole pages stayed with the queued slot;
    # the unwritten tail pages went back to the pool
    item = sched.pending[0]
    assert len(item.pages) == 2 * CT // PS
    assert item.prefilled == 2 * CT
    assert sched.allocator.free_pages == eng.n_pages - len(item.pages)
    assert granted > len(item.pages)
    sched.allocator.check()

    out = sched.run()                       # re-admit: 1 chunk remains
    np.testing.assert_array_equal(np.asarray(out[0]),
                                  np.asarray(want[0]))
    assert sched.stats["preempted"] == 1
    # 2 chunks before the preemption + the resumed 3-token final chunk
    assert sched.stats["chunks"] == 3
    assert sched.stats["chunked_tokens"] == 19
    assert sched.allocator.free_pages == eng.n_pages
    sched.allocator.check()


# ------------------------------------------------- mixed-step faults


def test_transient_fault_mid_chunk_retries_that_chunk_only(rng):
    """A transient fault landing on a mixed step redoes the in-flight
    chunk and nothing else: one step retry, the successful-chunk count
    matches the clean run, and the stream is bit-identical."""
    cfg = _cfg()
    eng = _engine(cfg, B=1, max_len=32, n_pages=16)
    toks = rng.integers(2, cfg.vocab, (19,)).astype(np.int32)

    def run(with_fault):
        sched = Scheduler(eng)
        proxy = None
        if with_fault:
            proxy = faults.inject(sched, decode_faults=[
                faults.TransientError(step=1)])   # the 2nd chunk
        sched.submit(Request(rid=0, tokens=toks, gen=6, seed=0))
        return sched, proxy, sched.run()

    _, _, clean = run(False)
    sched, proxy, out = run(True)
    assert sched.stats["step_retries"] == 1
    assert proxy.mixed_fn.injected == 1     # it hit a MIXED step
    assert sched.stats["chunks"] == 3       # no completed chunk redone
    assert out[0].ok
    np.testing.assert_array_equal(np.asarray(out[0]),
                                  np.asarray(clean[0]))
    assert sched.allocator.free_pages == eng.n_pages


def test_nonfinite_final_chunk_quarantines_alone(rng):
    """NaN chunk logits surfacing at promotion fail that request alone
    (the isfinite guard in ``_promote``); the slot and its pages free,
    and a later request on the same scheduler runs clean."""
    cfg = _cfg()
    eng = _engine(cfg, B=1, max_len=32, n_pages=16)
    toks = rng.integers(2, cfg.vocab, (19,)).astype(np.int32)
    sched = Scheduler(eng)
    sched.submit(Request(rid=0, tokens=toks, gen=6, seed=0))
    assert sched.admit() == 1
    assert sched.slots[0].req.status is RequestStatus.PREFILLING
    # promotion with poisoned final-chunk logits (the injectors can
    # only corrupt the decode logits, which a PREFILLING slot
    # discards — drive the guard directly)
    sched._prefilling.popleft()
    sched.slots[0].prefilled = len(toks)
    sched._promote(0, jnp.full((1, cfg.vocab), jnp.nan, jnp.float32))
    out0 = sched.finished[0]
    assert out0.status is RequestStatus.FAILED
    assert "chunked prefill" in out0.error
    assert sched.slots[0] is None
    assert sched.allocator.free_pages == eng.n_pages
    sched.allocator.check()
    sched.submit(Request(rid=1, tokens=toks[:9], gen=4, seed=1))
    out = sched.run()
    assert out[1].ok and len(out[1]) == 4
    assert sched.allocator.free_pages == eng.n_pages
    sched.allocator.check()


# ------------------------------------------------- config validation


def test_chunk_tokens_must_be_page_multiple():
    eng = _engine(_cfg())
    with pytest.raises(ValueError, match="multiple of"):
        Scheduler(eng, chunk_tokens=PS + 1)


def test_itl_percentiles_populated(rng):
    cfg = _cfg()
    eng = _engine(cfg)
    sched, out = _run(eng, _reqs(rng, cfg.vocab, _SPECS))
    assert all(v.ok for v in out.values())
    itl = sched.itl_percentiles()
    assert set(itl) == {"p50", "p90", "p99"}
    assert all(v >= 0 for v in itl.values())
    for i, (_, g) in enumerate(_SPECS):
        assert out[i].token_times is not None
        assert len(out[i].token_times) == g
