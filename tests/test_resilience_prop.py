"""Property tests (hypothesis) for the fault-tolerant serving layer:
random alloc/free interleavings against the PageAllocator invariant,
random admit/step/cancel sequences driving the Scheduler's
bookkeeping (growth, preemption, parking, rejection, retirement) on a
model-free fake engine — with and without chunked prefill, where the
``pack_chunk`` token-budget rule must never exceed the budget, never
starve a decoding slot, and keep non-final chunks page-aligned — and
random insert/match/evict/decref interleavings against the
prefix-cache refcount partition (the trie plus outstanding holds
account for every ref, eviction never drops a held page).  Token-level
correctness under faults is pinned by tests/test_resilience.py;
prefix-cache token identity by tests/test_prefix_cache.py (which also
carries a deterministic mirror of the partition property for
hypothesis-less environments); chunked-prefill token identity by
tests/test_chunked.py.  The durable-serving property rides the same
fake engine: a crash injected at a RANDOM step of a RANDOM
submit/cancel stream, recovered via snapshot + journal replay
(``serve_with_recovery``), yields the same result map as the
crash-free run with the page pool fully drained (real-model
bit-identity lives in tests/test_snapshot.py)."""
import tempfile
import types

import jax.numpy as jnp
import numpy as np
import pytest

hypothesis = pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.engine import (EngineConfig, PrefixCache, Request,  # noqa: E402
                          RequestStatus, Scheduler, faults)
from repro.engine import paged_cache as PC  # noqa: E402
from repro.engine.paged_cache import (PageAllocator,  # noqa: E402
                                      PagePoolExhausted)
from repro.engine.scheduler import pack_chunk  # noqa: E402
from repro.runtime.resilience import (RestartPolicy,  # noqa: E402
                                      serve_with_recovery)


@settings(max_examples=200, deadline=None)
@given(remaining=st.integers(1, 512), n_decode=st.integers(0, 64),
       budget=st.integers(1, 600), ct_pages=st.integers(1, 16),
       ps=st.sampled_from([1, 2, 4, 8]))
def test_pack_chunk_never_over_budget_never_starves(
        remaining, n_decode, budget, ct_pages, ps):
    """The token-budget packing rule, over its whole domain: the chunk
    never pushes the step past ``budget`` tokens, decoding slots are
    never starved (decodes alone filling the budget yields a zero
    chunk — never the other way around), non-final chunks end
    page-aligned, a chunk never overshoots the remaining prompt or
    ``chunk_tokens``, and whenever a whole page (or the whole
    remainder) fits beside the decodes the prefill makes progress."""
    ct = ct_pages * ps
    c = pack_chunk(remaining, n_decode, budget, ct, ps)
    assert 0 <= c <= min(remaining, ct)
    if c:
        assert n_decode + c <= budget   # never exceeds the budget
    if budget <= n_decode:
        assert c == 0                   # decode always wins the budget
    if 0 < c < remaining:
        assert c % ps == 0              # non-final chunks page-aligned
    room = min(budget - n_decode, ct)
    if room >= min(remaining, ps):
        assert c > 0                    # liveness: chunking advances


@settings(max_examples=60, deadline=None)
@given(st.integers(1, 12),
       st.lists(st.tuples(st.booleans(), st.integers(0, 6)),
                max_size=40))
def test_allocator_invariants_under_random_ops(n_pages, ops):
    """Random alloc/free interleavings: the owned/free partition of
    the pool holds after every op, over-allocation always raises, and
    the pool drains back to fully free."""
    al = PageAllocator(n_pages)
    owned = []
    for is_alloc, k in ops:
        if is_alloc:
            if k > al.free_pages:
                with pytest.raises(PagePoolExhausted):
                    al.alloc(k)
            else:
                owned.extend(al.alloc(k))
        elif owned:
            take = owned[:min(k, len(owned))]
            owned = owned[len(take):]
            if take:
                al.free(take)
        al.check()
        assert al.used_pages == len(owned)
        assert len(set(owned)) == len(owned)
    if owned:
        al.free(owned)
    al.check()
    assert al.free_pages == n_pages


_PREFIX_OPS = st.lists(
    st.one_of(
        # (insert, token-seed, length)
        st.tuples(st.just("insert"), st.integers(0, 3),
                  st.integers(1, 14)),
        # (match, token-seed, length) — a hit takes a hold (incref)
        st.tuples(st.just("match"), st.integers(0, 3),
                  st.integers(1, 14)),
        st.tuples(st.just("release"), st.integers(0, 7), st.just(0)),
        st.tuples(st.just("evict"), st.integers(1, 4), st.just(0))),
    max_size=50)


def _toks(seed: int, length: int) -> np.ndarray:
    """Deterministic token stream per seed: overlapping prefixes across
    seeds (all start from the same base) so matches actually hit."""
    base = np.arange(length, dtype=np.int32)
    return base + (seed // 2)   # seeds 0/1, 2/3 share streams pairwise


@settings(max_examples=40, deadline=None)
@given(st.integers(4, 16), _PREFIX_OPS)
def test_prefix_refcount_partition_under_random_ops(n_pages, ops):
    """Random insert / match+hold / release / evict interleavings: the
    refcount of every owned page equals (trie nodes owning it) +
    (outstanding match holds on it), eviction never frees a page a hold
    still pins, and clear() drains the pool completely."""
    ps = 4
    al = PageAllocator(n_pages)
    pc = PrefixCache(ps, al)
    holds = []

    def partition():
        counts = {}
        stack = list(pc._root.children.values())
        while stack:
            nd = stack.pop()
            stack.extend(nd.children.values())
            counts[nd.page] = counts.get(nd.page, 0) + 1
        for pages in holds:
            for p in pages:
                counts[p] = counts.get(p, 0) + 1
        assert set(counts) == {
            p for p in range(n_pages) if al.refcount(p) > 0}
        for p, want in counts.items():
            assert al.refcount(p) == want, f"page {p}"
        al.check()
        pc.check()

    for op, a, b in ops:
        if op == "insert":
            # retiring-slot idiom: alloc whole pages, insert, drop the
            # slot refs (trie keeps what it indexed, dupes free)
            n_whole = b // ps
            if n_whole <= al.free_pages:
                pages = al.alloc(n_whole)
                pc.insert(_toks(a, b), pages)
                if pages:
                    al.decref(pages)
        elif op == "match":
            pages = pc.match(_toks(a, b))
            if pages:
                al.incref(pages)
                holds.append(pages)
        elif op == "release" and holds:
            al.decref(holds.pop(a % len(holds)))
        elif op == "evict":
            held = {p for hold in holds for p in hold}
            pc.evict(a)
            for p in held:
                assert al.refcount(p) >= 1, "evicted a held page"
        partition()
    for pages in holds:
        al.decref(pages)
    pc.clear()
    al.check()
    assert al.free_pages == n_pages


class _FakeEngine:
    """No-jax-model engine: real EngineConfig/paged-cache layout, but
    prefill/decode return zeros — fast enough to drive the *scheduler's
    bookkeeping* through hypothesis."""

    def __init__(self, batch=2, max_len=16, page_size=4, n_pages=6):
        self.cfg = types.SimpleNamespace(family="dense", mla=None,
                                         frontend_tokens=0)
        self.ecfg = EngineConfig(batch=batch, max_len=max_len,
                                 paged=True, page_size=page_size,
                                 n_pages=n_pages)
        self.page_size = page_size
        self.max_pages = PC.max_pages(max_len, page_size)
        self.n_pages = n_pages
        self.params = None
        L, KV, Dh, V = 1, 1, 1, 8
        self._pool = (L, n_pages, KV, page_size, Dh)   # head-major
        self._V = V

    def init_paged_cache(self, enc_len=None):
        return {"k": jnp.zeros(self._pool), "v": jnp.zeros(self._pool)}

    def prefill_fn(self, params, batch):
        S = batch["tokens"].shape[1]
        kv = jnp.zeros((1, 1, S, 1, 1))
        return jnp.zeros((1, self._V)), (kv, kv)

    def decode_fn(self, params, dbatch):
        B = dbatch["token"].shape[0]
        return jnp.zeros((B, self._V)), dbatch["cache"]

    def suffix_prefill_fn(self, params, batch):
        # suffix-only prefill: same zeros contract as prefill_fn, the
        # matched prefix rides along only as already-resident pages
        S = batch["tokens"].shape[1]
        kv = jnp.zeros((1, 1, S, 1, 1))
        return jnp.zeros((1, self._V)), (kv, kv)

    def mixed_fn(self, params, batch):
        # unified mixed step: (decode logits, chunk logits, cache) —
        # zeros keep the scheduler's chunk bookkeeping fully exercised
        B = batch["token"].shape[0]
        return (jnp.zeros((B, self._V)), jnp.zeros((1, self._V)),
                batch["cache"])


_OPS = st.lists(
    st.one_of(
        st.tuples(st.just("submit"), st.integers(1, 12),
                  st.integers(1, 6)),
        st.tuples(st.just("step"), st.just(0), st.just(0)),
        st.tuples(st.just("admit"), st.just(0), st.just(0)),
        st.tuples(st.just("cancel"), st.integers(0, 5), st.just(0))),
    min_size=1, max_size=14)


@settings(max_examples=10, deadline=None)
@given(_OPS, st.integers(0, 2))
def test_scheduler_invariants_under_random_sequences(ops, max_preempt):
    """Drive random submit/admit/step/cancel interleavings (growth,
    preemption, parking, rejection and retirement all fire from these)
    and assert after every transition: the allocator partition holds,
    active slots' pages are exactly the owned pages with no aliasing,
    and the drained stream leaves a full pool with every request
    terminal exactly once."""
    eng = _FakeEngine()
    sched = Scheduler(eng, max_preemptions=max_preempt)
    rng = np.random.default_rng(0)
    submitted = []

    def invariants():
        sched.allocator.check()
        pages = [p for s in sched.slots if s is not None
                 for p in s.pages]
        assert len(set(pages)) == len(pages), "page aliased across slots"
        assert len(pages) == sched.allocator.used_pages
        for s in sched.slots:
            if s is not None:
                assert s.req.status is RequestStatus.RUNNING

    for op, a, b in ops:
        if op == "submit":
            rid = len(submitted)
            submitted.append(rid)
            sched.submit(Request(
                rid=rid,
                tokens=rng.integers(0, 8, (a,)).astype(np.int32),
                gen=b))
        elif op == "step":
            sched.step()
        elif op == "admit":
            sched.admit()
        elif op == "cancel" and a < len(submitted):
            sched.cancel(a)
        invariants()
    out = sched.run()
    invariants()
    assert sched.allocator.free_pages == eng.n_pages
    assert set(out) == set(submitted)
    for rid in submitted:
        assert out[rid].status in {
            RequestStatus.FINISHED, RequestStatus.REJECTED,
            RequestStatus.CANCELLED, RequestStatus.TIMED_OUT,
            RequestStatus.FAILED}


@settings(max_examples=10, deadline=None)
@given(_OPS, st.integers(0, 2))
def test_scheduler_chunked_invariants_under_random_sequences(
        ops, max_preempt):
    """The scheduler property with chunked prefill ON: active slots
    are RUNNING or PREFILLING, a PREFILLING slot's completed prefix is
    always whole pages (``prefilled`` page-aligned) and tracked in the
    chunking queue, pages are never aliased across slots OR the queued
    preempted slots that kept their completed pages, and — the packer's
    no-starvation guarantee surfaced at the scheduler level — every
    slot that enters a step RUNNING and leaves it RUNNING emits exactly
    one token, no matter what chunks rode along."""
    eng = _FakeEngine()
    sched = Scheduler(eng, max_preemptions=max_preempt,
                      chunked_prefill=True, chunk_tokens=4)
    rng = np.random.default_rng(0)
    submitted = []

    def invariants():
        sched.allocator.check()
        pages = [p for s in sched.slots if s is not None
                 for p in s.pages]
        for q in (sched.pending, sched.parked):
            for item in q:
                pages.extend(getattr(item, "pages", []))
        assert len(set(pages)) == len(pages), "page aliased"
        assert len(pages) == sched.allocator.used_pages
        for sid, s in enumerate(sched.slots):
            if s is None:
                assert sid not in sched._prefilling
                continue
            assert s.req.status in (RequestStatus.RUNNING,
                                    RequestStatus.PREFILLING)
            if s.req.status is RequestStatus.PREFILLING:
                assert sid in sched._prefilling
                assert s.prefilled % eng.page_size == 0
                assert s.prefilled < len(s.req.tokens)
            else:
                assert sid not in sched._prefilling

    for op, a, b in ops:
        if op == "submit":
            rid = len(submitted)
            submitted.append(rid)
            sched.submit(Request(
                rid=rid,
                tokens=rng.integers(0, 8, (a,)).astype(np.int32),
                gen=b))
        elif op == "step":
            running = {sid: (s.req.rid, len(s.out))
                       for sid, s in enumerate(sched.slots)
                       if s is not None
                       and s.req.status is RequestStatus.RUNNING}
            sched.step()
            for sid, (rid, n0) in running.items():
                s = sched.slots[sid]
                if (s is not None and s.req.rid == rid
                        and s.req.status is RequestStatus.RUNNING):
                    assert len(s.out) == n0 + 1, \
                        f"slot {sid} starved by the chunk"
        elif op == "admit":
            sched.admit()
        elif op == "cancel" and a < len(submitted):
            sched.cancel(a)
        invariants()
    out = sched.run()
    invariants()
    assert sched.allocator.free_pages == eng.n_pages
    assert set(out) == set(submitted)
    for rid in submitted:
        assert out[rid].status in {
            RequestStatus.FINISHED, RequestStatus.REJECTED,
            RequestStatus.CANCELLED, RequestStatus.TIMED_OUT,
            RequestStatus.FAILED}


@settings(max_examples=10, deadline=None)
@given(_OPS, st.integers(0, 2))
def test_scheduler_prefix_cache_invariants_under_random_sequences(
        ops, max_preempt):
    """The scheduler property with the prefix cache ON: the strict
    'no page aliased across slots' invariant is deliberately relaxed to
    the refcount partition — every owned page's refcount equals the
    slot rows holding it plus the trie nodes owning it — while
    eviction, preemption, growth and retirement interleave at random
    (prompts are drawn from a 2-token alphabet so cross-request prefix
    hits actually occur).  The drained pool holds exactly the trie's
    pages; clear() returns the rest."""
    eng = _FakeEngine()
    sched = Scheduler(eng, max_preemptions=max_preempt,
                      prefix_cache=True)
    rng = np.random.default_rng(0)
    submitted = []

    def invariants():
        sched.allocator.check()
        sched.prefix.check()
        counts = {}
        for s in sched.slots:
            if s is not None:
                assert s.req.status is RequestStatus.RUNNING
                for p in s.pages:
                    counts[p] = counts.get(p, 0) + 1
        stack = list(sched.prefix._root.children.values())
        while stack:
            nd = stack.pop()
            stack.extend(nd.children.values())
            counts[nd.page] = counts.get(nd.page, 0) + 1
        assert len(counts) == sched.allocator.used_pages
        for p, want in counts.items():
            assert sched.allocator.refcount(p) == want, f"page {p}"

    for op, a, b in ops:
        if op == "submit":
            rid = len(submitted)
            submitted.append(rid)
            sched.submit(Request(
                rid=rid,
                tokens=rng.integers(0, 2, (a,)).astype(np.int32),
                gen=b))
        elif op == "step":
            sched.step()
        elif op == "admit":
            sched.admit()
        elif op == "cancel" and a < len(submitted):
            sched.cancel(a)
        invariants()
    out = sched.run()
    invariants()
    assert sched.allocator.free_pages == \
        eng.n_pages - sched.prefix.cached_pages
    sched.prefix.clear()
    assert sched.allocator.free_pages == eng.n_pages
    assert set(out) == set(submitted)


_WORKLOAD = st.lists(
    st.one_of(
        # (submit, prompt_len, gen)
        st.tuples(st.just("submit"), st.integers(1, 10),
                  st.integers(1, 5)),
        # (cancel, submitted-index, _)
        st.tuples(st.just("cancel"), st.integers(0, 5), st.just(0))),
    min_size=1, max_size=8)


@settings(max_examples=10, deadline=None)
@given(_WORKLOAD, st.integers(1, 8), st.sampled_from([0, 2]))
def test_crash_recovery_result_map_identical(ops, crash_step, every):
    """Crash at a RANDOM step of a RANDOM submit/cancel stream,
    recover from the latest snapshot (cadence 0 = journal-only) plus
    the journal, and the final result map — every rid's tokens and
    terminal status — is identical to the crash-free run's, with the
    allocator partition intact and the pool fully drained.  (When the
    stream drains before ``crash_step`` decode calls the crash never
    fires and recovery is vacuous — hypothesis varies both sides.)"""

    def apply_ops(sched):
        rng = np.random.default_rng(0)
        submitted = []
        for op, a, b in ops:
            if op == "submit":
                rid = len(submitted)
                submitted.append(rid)
                sched.submit(Request(
                    rid=rid,
                    tokens=rng.integers(0, 8, (a,)).astype(np.int32),
                    gen=b))
            elif a < len(submitted):
                sched.cancel(a)

    ref = Scheduler(_FakeEngine())
    apply_ops(ref)
    want = ref.run()

    def on_start(sched, fresh):
        if fresh:       # the crash hits only the pre-recovery process
            faults.inject(sched, decode_faults=[
                faults.CrashFault(step=crash_step)])

    eng = _FakeEngine()
    with tempfile.TemporaryDirectory() as d:
        sched = serve_with_recovery(
            eng, d, apply_ops, snapshot_every=every,
            policy=RestartPolicy(max_restarts=3, backoff_s=0.0),
            on_start=on_start)
    assert set(sched.finished) == set(want)
    for rid, res in want.items():
        got = sched.finished[rid]
        assert got.status is res.status, f"req {rid}"
        np.testing.assert_array_equal(np.asarray(got),
                                      np.asarray(res),
                                      err_msg=f"req {rid}")
    sched.allocator.check()
    assert sched.allocator.free_pages == eng.n_pages
