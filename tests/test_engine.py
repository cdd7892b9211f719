"""DecodeEngine + kernel-dispatch registry tests: CLI -> EngineConfig
mapping, engine decode vs the raw lm loop, the moe+mla cache-padding
branch, registry routing/'auto', and the kernel_impl deprecation shim."""
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.common.config import MLAConfig, ModelConfig, MoEConfig
from repro.engine import DecodeEngine, EngineConfig, pad_cache_from_prefill
from repro.kernels import dispatch as D
from repro.models import lm

KEY = jax.random.PRNGKey(0)


def _cfg(**kw):
    base = dict(name="t", family="dense", n_layers=2, d_model=64,
                n_heads=4, n_kv_heads=2, d_head=16, d_ff=128, vocab=256,
                dtype="float32", remat="none", attn_block_q=32,
                attn_block_kv=32)
    base.update(kw)
    return ModelConfig(**base)


def _mla_moe_cfg():
    # capacity_factor 4.0: prefill groups B*S tokens, decode groups B —
    # a tight capacity drops different tokens in the two groupings, so
    # the consistency check needs the no-drop regime (same choice as
    # test_models.test_moe_matches_dense_reference)
    return _cfg(family="moe",
                moe=MoEConfig(n_experts=4, top_k=2, d_expert=32,
                              first_k_dense=1, d_ff_dense=128,
                              capacity_factor=4.0),
                mla=MLAConfig(q_lora_rank=32, kv_lora_rank=16,
                              rope_head_dim=8, nope_head_dim=16,
                              v_head_dim=16))


# ---------------------------------------------------------------- CLI


def test_serve_cli_maps_to_engine_config():
    from repro.launch import serve

    args = serve.build_parser().parse_args(
        ["--arch", "qwen1.5-0.5b", "--batch", "3", "--prompt-len", "16",
         "--gen", "8", "--data-model", "2", "4", "--shard", "seq",
         "--kernel-impl", "pallas"])
    ecfg = serve.engine_config_from_args(args)
    assert ecfg == EngineConfig(batch=3, max_len=24, mesh_shape=(2, 4),
                                decode_shard="seq", kernel_impl="pallas")


def test_serve_cli_defaults_and_vlm_budget():
    from repro.launch import serve

    args = serve.build_parser().parse_args(
        ["--arch", "internvl2-2b", "--prompt-len", "16", "--gen", "8"])
    ecfg = serve.engine_config_from_args(args)
    assert ecfg.mesh_shape == (jax.device_count(), 1)
    assert ecfg.decode_shard == "none" and ecfg.kernel_impl == "xla"
    assert ecfg.max_len == 24
    # the vlm frontend prefix counts against the cache budget
    vlm = _cfg(family="vlm", frontend="vision", frontend_tokens=8,
               frontend_dim=32)
    assert serve.engine_config_from_args(args, vlm).max_len == 32


def test_serve_stream_exits_nonzero_on_lost_requests():
    """`--reduce none` parses; a stream with a FAILED or REJECTED request
    ends the process non-zero, a fully FINISHED one does not."""
    import types
    from repro.engine import RequestStatus
    from repro.launch import serve

    args = serve.build_parser().parse_args(
        ["--arch", "tinyllama-1.1b", "--reduce", "none"])
    assert args.reduce == "none"

    def res(status):
        return types.SimpleNamespace(status=status, error="boom")

    serve._exit_if_lost({0: res(RequestStatus.FINISHED)})
    for lost in (RequestStatus.FAILED, RequestStatus.REJECTED):
        with pytest.raises(SystemExit, match="1 request"):
            serve._exit_if_lost({0: res(RequestStatus.FINISHED),
                                 1: res(lost)})


# ---------------------------------------------------------------- engine


def test_engine_generate_matches_raw_decode_loop():
    """Engine prefill + decode == lm.prefill + pad + lm.decode_step."""
    cfg = _cfg()
    B, P, G = 2, 8, 5
    eng = DecodeEngine(cfg, EngineConfig(batch=B, max_len=P + G))
    toks = jax.random.randint(KEY, (B, P), 0, cfg.vocab)
    got, stats = eng.generate({"tokens": toks}, gen=G)
    assert got.shape == (B, G)
    assert stats["t_decode_s"] >= 0

    logits, caches = lm.prefill(eng.params, {"tokens": toks}, cfg)
    cache = pad_cache_from_prefill(cfg, caches, B, P + G, enc_len=P)
    tok = jnp.argmax(logits, -1).astype(jnp.int32)
    want = [tok]
    for i in range(G - 1):
        lg, cache = lm.decode_step(
            eng.params, {"token": tok, "cur_len": jnp.int32(P + i),
                         "cache": cache}, cfg)
        tok = jnp.argmax(lg, -1).astype(jnp.int32)
        want.append(tok)
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(jnp.stack(want, 1)))


def test_engine_rejects_overlong_generation_and_bad_batch():
    cfg = _cfg()
    eng = DecodeEngine(cfg, EngineConfig(batch=2, max_len=12))
    toks = jnp.zeros((2, 8), jnp.int32)
    with pytest.raises(ValueError, match="max_len"):
        eng.generate({"tokens": toks}, gen=6)
    with pytest.raises(ValueError, match="batch"):
        eng.prefill({"tokens": jnp.zeros((4, 8), jnp.int32)})


def test_engine_inherits_cfg_pinned_knobs():
    """EngineConfig defaults (None) inherit a cfg pinned to
    pallas/seq instead of silently resetting it; an explicit
    EngineConfig value still wins."""
    cfg = _cfg(kernel_impl="pallas")
    eng = DecodeEngine(cfg, EngineConfig(batch=1, max_len=8))
    assert eng.cfg.kernel_impl == "pallas"
    assert eng.ecfg.kernel_impl == "pallas"
    assert eng.cfg.decode_shard == "none"
    eng2 = DecodeEngine(cfg, EngineConfig(batch=1, max_len=8,
                                          kernel_impl="xla"))
    assert eng2.cfg.kernel_impl == "xla"


def test_engine_seq_shard_divisibility_checked():
    """(A stub mesh stands in for a 2-chip model axis: the check fires
    before the engine touches devices, and make_local_mesh refuses
    (1, 2) on the single CPU device.)"""
    class _Mesh:
        shape = {"data": 1, "model": 2}

    with pytest.raises(ValueError, match="divisible"):
        DecodeEngine(_cfg(), EngineConfig(batch=2, max_len=13,
                                          decode_shard="seq"),
                     mesh=_Mesh())


def test_make_local_mesh_refuses_missing_devices():
    """A mesh needing more devices than exist is an error, never a
    silently smaller mesh; the one constructor states Auto axes."""
    from jax.sharding import AxisType
    from repro.launch.mesh import make_local_mesh
    n = jax.device_count()
    with pytest.raises(ValueError, match="devices"):
        make_local_mesh(1, n + 1)
    mesh = make_local_mesh(1, 1)
    assert mesh.shape == {"data": 1, "model": 1}
    assert set(mesh.axis_types) == {AxisType.Auto}
    with pytest.raises(ValueError, match="devices"):
        DecodeEngine(_cfg(), EngineConfig(batch=1, max_len=8,
                                          mesh_shape=(n + 1, 1)))


# ---------------------------------------------------------------- cache


def test_pad_cache_from_prefill_mla_moe_branch():
    """The moe+mla branch places BOTH the dense-layer and moe-layer
    latent stacks (regression: the pre-PR-2 code sliced layer 0 and
    lacked the mla+moe case entirely)."""
    cfg = _mla_moe_cfg()
    B, P, T = 2, 8, 12
    params = lm.init(cfg, KEY)
    toks = jax.random.randint(KEY, (B, P), 0, cfg.vocab)
    _, caches = lm.prefill(params, {"tokens": toks}, cfg)
    kv_d, kv_m = caches

    cache = pad_cache_from_prefill(cfg, caches, B, T)
    n_moe = cfg.n_layers - cfg.moe.first_k_dense
    r, rope = cfg.mla.kv_lora_rank, cfg.mla.rope_head_dim
    assert cache["dense"]["ckv"].shape == (1, B, T, r)
    assert cache["moe"]["ckv"].shape == (n_moe, B, T, r)
    assert cache["moe"]["krope"].shape == (n_moe, B, T, rope)
    # prefill latents land in the first P positions of every layer...
    np.testing.assert_allclose(np.asarray(cache["dense"]["ckv"][:, :, :P]),
                               np.asarray(kv_d[0]), rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(cache["moe"]["ckv"][:, :, :P]),
                               np.asarray(kv_m[0]), rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(cache["moe"]["krope"][:, :, :P]),
                               np.asarray(kv_m[1]), rtol=1e-6, atol=1e-6)
    # ...and the tail stays zero
    assert float(jnp.abs(cache["moe"]["ckv"][:, :, P:]).max()) == 0.0


def test_mla_moe_prefill_decode_consistency():
    """Teacher-forced decode from a padded mla+moe cache continues the
    prefill: decode logits == full-forward logits at those positions."""
    cfg = _mla_moe_cfg()
    B, S, P = 2, 12, 8
    params = lm.init(cfg, KEY)
    tokens = jax.random.randint(KEY, (B, S), 0, cfg.vocab)

    out = lm.backbone(params, tokens, cfg)
    want = lm._logits(params, out.h, cfg).astype(jnp.float32)

    _, caches = lm.prefill(params, {"tokens": tokens[:, :P]}, cfg)
    cache = pad_cache_from_prefill(cfg, caches, B, S)
    for t in range(P, S):
        lg, cache = lm.decode_step(
            params, {"token": tokens[:, t], "cur_len": jnp.int32(t),
                     "cache": cache}, cfg)
        np.testing.assert_allclose(np.asarray(lg), np.asarray(want[:, t]),
                                   rtol=1e-3, atol=1e-3)


# ---------------------------------------------------------------- registry


def test_dispatch_registry_routes_and_errors():
    assert "mlp" in D.ops() and "decode_partial" in D.ops()
    assert set(D.backends("qkv_proj")) == {"xla", "pallas"}
    with pytest.raises(KeyError, match="no implementations"):
        D.dispatch("nonexistent_op", "xla")
    with pytest.raises(KeyError, match="no 'mosaic' backend"):
        D.dispatch("mlp", "mosaic", {}, None, "relu")
    # a ModelConfig selects via kernel_impl
    assert D.resolve("mlp", _cfg()) is D.resolve("mlp", "xla")


def test_dispatch_auto_measures_and_persists(tmp_path, monkeypatch):
    """backend='auto' measures both impls once, persists the winner
    under dispatch:<op>, and hits the cache on the next call."""
    import json
    import os
    from repro.kernels import autotune

    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE",
                       str(tmp_path / "tune.json"))
    autotune.reset()
    x = jax.random.normal(KEY, (2, 16, 64))
    p = {"wi": jax.random.normal(KEY, (64, 128)),
         "wg": jax.random.normal(KEY, (64, 128)),
         "wo": jax.random.normal(KEY, (128, 64))}
    from repro.models.layers import mlp
    out = mlp(p, x, "swiglu", backend="auto")
    want = mlp(p, x, "swiglu", backend="xla")
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-4, atol=2e-4)
    table = json.load(open(os.environ["REPRO_AUTOTUNE_CACHE"]))
    assert any(k.startswith("dispatch:mlp|") for k in table)
    hits0 = autotune.stats["hits"]
    mlp(p, x, "swiglu", backend="auto")
    assert autotune.stats["hits"] > hits0
    autotune.reset()


def test_dispatch_auto_disabled_trusts_prior(tmp_path, monkeypatch):
    """REPRO_AUTOTUNE=0: 'auto' resolves from the preference order
    (pallas first) without measuring or touching the cache."""
    from repro.kernels import autotune

    monkeypatch.setenv("REPRO_AUTOTUNE", "0")
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE",
                       str(tmp_path / "tune.json"))
    autotune.reset()
    table = D._REGISTRY["mlp"]
    assert D._resolve_auto("mlp", table,
                           ({}, jnp.zeros((4, 8)), "relu"), {}) == "pallas"
    assert autotune.stats["measured"] == 0
    autotune.reset()


def test_cached_backend_replays_measured_winner(tmp_path, monkeypatch):
    """The lookup-only resolver (used when building shard_map programs,
    where measuring is unsafe) replays a persisted dispatch winner and
    falls back to the prior order on a miss."""
    import json
    from repro.kernels import autotune
    from repro.kernels import ops as kops

    path = str(tmp_path / "tune.json")
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", path)
    autotune.reset()
    q = jnp.zeros((2, 4, 16))
    ck = jnp.zeros((2, 64, 2, 16))
    args = (q, ck, ck, jnp.int32(64))
    # miss -> prior order (pallas first)
    assert D.cached_backend("decode_partial", "auto", args) == "pallas"
    # persist a winner pointing at index 1 (= 'xla') and replay it
    shape, dtype = D._arg_signature(args, {})
    tag = kops._backend_tag(kops._auto_interpret(None))
    key = autotune.cache_key("dispatch:decode_partial", shape, dtype, tag)
    with open(path, "w") as f:
        json.dump({key: {"blocks": [1], "us": 1.0}}, f)
    autotune.reset()
    assert D.cached_backend("decode_partial", "auto", args) == "xla"
    # a concrete backend passes through untouched
    assert D.cached_backend("decode_partial", "pallas", args) == "pallas"
    autotune.reset()


def test_cached_backend_replay_survives_registry_growth(tmp_path,
                                                        monkeypatch):
    """Regression: persisted dispatch winners used to be positional
    indices into the CURRENT candidate list, so registering one more
    backend (e.g. this PR's paged variant) silently shifted every
    replay.  Winners are now stored by NAME; legacy integer entries
    are tolerated while still in range."""
    import json
    from repro.kernels import autotune
    from repro.kernels import ops as kops

    path = str(tmp_path / "tune.json")
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", path)
    autotune.reset()
    q = jnp.zeros((2, 4, 16))
    ck = jnp.zeros((2, 64, 2, 16))
    args = (q, ck, ck, jnp.int32(64))
    shape, dtype = D._arg_signature(args, {})
    tag = kops._backend_tag(kops._auto_interpret(None))
    key = autotune.cache_key("dispatch:decode_partial", shape, dtype, tag)
    with open(path, "w") as f:
        json.dump({key: {"blocks": ["xla"], "us": 1.0}}, f)
    assert D.cached_backend("decode_partial", "auto", args) == "xla"

    # registering an extra backend reorders/extends the candidate list;
    # a name entry must replay unchanged
    try:
        D.register("decode_partial", "aaa_stub")(lambda *a, **k: None)
        autotune.reset()
        assert D.cached_backend("decode_partial", "auto", args) == "xla"
        # legacy int entry: decoded positionally while in range (the
        # old format), against the candidate list including the stub
        with open(path, "w") as f:
            json.dump({key: {"blocks": [1], "us": 1.0}}, f)
        autotune.reset()
        cands = ["pallas", "xla", "aaa_stub"]
        assert D.cached_backend("decode_partial", "auto", args) == \
            cands[1]
        # out-of-range legacy index: prior order, not a crash
        with open(path, "w") as f:
            json.dump({key: {"blocks": [7], "us": 1.0}}, f)
        autotune.reset()
        assert D.cached_backend("decode_partial", "auto", args) == \
            "pallas"
    finally:
        D._REGISTRY["decode_partial"].pop("aaa_stub", None)
        autotune.reset()


def test_resolve_auto_migrates_legacy_index_entries(tmp_path,
                                                    monkeypatch):
    """The measuring resolver rewrites a legacy positional entry to the
    backend name in place, so old cache files heal on first use."""
    import json
    from repro.kernels import autotune
    from repro.kernels import ops as kops

    path = str(tmp_path / "tune.json")
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", path)
    autotune.reset()
    x = jax.random.normal(KEY, (2, 8, 64))
    p = {"wi": jax.random.normal(KEY, (64, 128)),
         "wo": jax.random.normal(KEY, (128, 64))}
    args = (p, x, "relu")
    shape, dtype = D._arg_signature(args, {})
    tag = kops._backend_tag(kops._auto_interpret(None))
    key = autotune.cache_key("dispatch:mlp", shape, dtype, tag)
    with open(path, "w") as f:
        json.dump({key: {"blocks": [1], "us": 1.0}}, f)
    assert D._resolve_auto("mlp", D._REGISTRY["mlp"], args, {}) == "xla"
    table = json.load(open(path))
    assert table[key]["blocks"] == ["xla"]
    autotune.reset()


def test_train_loss_pins_auto_to_xla():
    """kernel_impl='auto' must not break the backward pass: train_loss
    runs it on the xla backend (pallas stays rejected)."""
    cfg = _cfg(kernel_impl="auto")
    params = lm.init(cfg, KEY)
    toks = jax.random.randint(KEY, (2, 8), 0, cfg.vocab)
    batch = {"tokens": toks, "labels": toks,
             "loss_mask": jnp.ones((2, 8), jnp.float32)}
    g = jax.grad(lambda p: lm.train_loss(p, batch, cfg)[0])(params)
    assert all(np.isfinite(np.asarray(x)).all()
               for x in jax.tree.leaves(g))
    with pytest.raises(ValueError, match="forward-only"):
        lm.train_loss(params, batch, cfg.replace(kernel_impl="pallas"))


# ---------------------------------------------------------------- shim


def test_kernel_impl_kwarg_warns_once(monkeypatch):
    from repro.models import attention as A
    from repro.models.layers import mlp

    monkeypatch.setattr(D, "_KERNEL_IMPL_WARNED", False)
    p = {"wi": jax.random.normal(KEY, (64, 128)),
         "wo": jax.random.normal(KEY, (128, 64))}
    x = jax.random.normal(KEY, (2, 4, 64))
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        out = mlp(p, x, "relu", kernel_impl="xla")
        # the second legacy call (different site!) stays silent
        ap = {"wq": jax.random.normal(KEY, (64, 4, 16)),
              "wk": jax.random.normal(KEY, (64, 2, 16)),
              "wv": jax.random.normal(KEY, (64, 2, 16)),
              "wo": jax.random.normal(KEY, (4, 16, 64))}
        q, k, v = A.qkv_proj(ap, x, jnp.arange(4), 1e4, kernel_impl="xla")
        o = A.o_proj(ap, q, kernel_impl="xla")
    dep = [x for x in w if issubclass(x.category, DeprecationWarning)
           and "kernel_impl" in str(x.message)]
    assert len(dep) == 1, [str(x.message) for x in w]
    assert "dispatch" in str(dep[0].message)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(mlp(p, x, "relu")),
                               rtol=1e-6, atol=1e-6)
    assert o.shape == x.shape
