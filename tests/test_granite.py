"""Granite's published scaling multipliers, served against the float32
reference of ``chipbench/architectures/granite.py``.

A tiny granite-shaped model (grouped queries G = 4 at d_head 128, a
tied head, every multiplier far from 1) on seeded weights: each served
path is compared with the reference by logits — prefill then paged
decode through the cache, dense decode, suffix prefill against resident
pages, one chunk of a chunked prefill, and the sequence-sharded paged
decode on a (1, 4) mesh of virtual devices.  Dropping any one
multiplier from the program fails the comparison; at the default
multipliers the helpers add nothing to a program.  On the (1, 4) mesh
every pool leaf is made already sharded, and the scheduler's per-shard
count of live table entries adds up to its live count.
"""
import dataclasses
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))

from chipbench import serve, spec  # noqa: E402

granite = spec.load_architecture(REPO, "granite")

CONFIG = {
    "weights": {"embed_std": 0.5, "bias_std": 0.1},
    "model": {
        "name": "tiny-granite", "family": "dense",
        "n_layers": 2, "d_model": 256, "n_heads": 8, "n_kv_heads": 2,
        "d_head": 128, "d_ff": 256, "vocab": 500, "qkv_bias": False,
        "tie_embeddings": True, "act": "swiglu", "rope_theta": 1e7,
        "norm_eps": 1e-5, "embedding_multiplier": 12.0,
        "attention_multiplier": 0.0078125, "residual_multiplier": 0.22,
        "logits_scaling": 16.0, "dtype": "float32"},
}
MULTIPLIERS = ("embedding_multiplier", "attention_multiplier",
               "residual_multiplier", "logits_scaling")
P, GEN = 21, 7
# float32 on both sides; the program accumulates in another order
# (flash attention's online softmax, page-wise decode, the sharded
# combine), so they agree to float32 rounding compounded over two
# layers, not bit for bit: 1e-4 of the largest logit (the paths read
# 1e-7 to 5e-7).  A dropped multiplier moves them by 2e-3 (attention)
# to 15 (logits) of it.
RTOL = 1e-4


def _weights():
    return granite.make(CONFIG, jax.random.PRNGKey(3))


def _prompt():
    return np.random.default_rng(0).integers(2, 500, (P,)).astype(np.int32)


def _forced():
    return np.random.default_rng(1).integers(2, 500, (GEN,)).astype(
        np.int32)


def _reference(w, seq, rows):
    return granite.logits(w, CONFIG["model"], np.asarray(seq, np.int32),
                          np.asarray(rows))


def _err(got, want):
    return float(np.abs(got - want).max() / np.abs(want).max())


def _cfg(impl="pallas", **drop):
    return serve.model_config(CONFIG, impl).replace(**drop)


def _engine(cfg, w, **kw):
    from repro.engine import DecodeEngine, EngineConfig
    return DecodeEngine(cfg, EngineConfig(
        batch=1, max_len=64, paged=True, page_size=16, n_pages=4, **kw),
        params=w)


def _paged_logits(cfg, w):
    """Prefill, then paged decode teacher-forced; logits from P-1 on."""
    eng = _engine(cfg, w)
    logits, cache = eng.prefill({"tokens": jnp.asarray(_prompt())[None]})
    out = [np.asarray(logits[0])]
    table = eng.default_block_table()
    for i, tok in enumerate(_forced()[:-1]):
        logits, cache = eng.decode_step(jnp.asarray([tok]), P + i, cache,
                                        block_table=table)
        out.append(np.asarray(logits[0]))
    return np.stack(out)[:, :CONFIG["model"]["vocab"]]


def _dense_logits(cfg, w):
    """Prefill, then the dense (unpaged) decode step."""
    from repro.engine import DecodeEngine, EngineConfig
    eng = DecodeEngine(cfg, EngineConfig(batch=1, max_len=64), params=w)
    logits, cache = eng.prefill({"tokens": jnp.asarray(_prompt())[None]})
    out = [np.asarray(logits[0])]
    for i, tok in enumerate(_forced()[:-1]):
        logits, cache = eng.decode_step(jnp.asarray([tok]), P + i, cache)
        out.append(np.asarray(logits[0]))
    return np.stack(out)[:, :CONFIG["model"]["vocab"]]


def _suffix_logits(cfg, w):
    """The first page prefilled, the rest of the prompt as a suffix
    over it (a prefix-cache hit): the last prompt token's logits."""
    eng = _engine(cfg, w)
    prompt = _prompt()
    _, cache = eng.prefill({"tokens": jnp.asarray(prompt[:16])[None]})
    logits, _ = eng.suffix_prefill_fn(eng.params, {
        "tokens": jnp.asarray(prompt[16:])[None],
        "pages": jnp.asarray([0], jnp.int32), "cache": cache})
    return np.asarray(logits)[:, :CONFIG["model"]["vocab"]]


def _chunk_logits(cfg, w):
    """The rest of the prompt as one chunk of a chunked prefill, written
    into its page; then one decode step reads it back."""
    from repro.models import lm
    eng = _engine(cfg, w)
    prompt = _prompt()
    _, cache = eng.prefill({"tokens": jnp.asarray(prompt[:16])[None]})
    logits, cache = lm.prefill_chunk(eng.params, {
        "tokens": jnp.asarray(prompt[16:])[None],
        "pages": jnp.asarray([0], jnp.int32),
        "write_pages": jnp.asarray([1], jnp.int32), "cache": cache},
        eng.cfg)
    step, _ = eng.decode_step(jnp.asarray(_forced()[:1]), P, cache,
                              block_table=eng.default_block_table())
    V = CONFIG["model"]["vocab"]
    return np.stack([np.asarray(logits[0])[:V], np.asarray(step[0])[:V]])


PATHS = {
    # name: (served logits, reference rows (positions))
    "paged": (_paged_logits, np.arange(P - 1, P - 1 + GEN)),
    "dense": (_dense_logits, np.arange(P - 1, P - 1 + GEN)),
    "suffix": (_suffix_logits, np.asarray([P - 1])),
    "chunk": (_chunk_logits, np.asarray([P - 1, P])),
}


def _want(w, rows):
    seq = np.concatenate([_prompt(), _forced()[:-1]])
    return _reference(w, seq, rows)


@pytest.mark.parametrize("path", sorted(PATHS))
def test_served_path_matches_reference(path):
    fn, rows = PATHS[path]
    w = _weights()
    assert _err(fn(_cfg(), w), _want(w, rows)) < RTOL


@pytest.mark.parametrize("field", MULTIPLIERS)
def test_each_multiplier_is_needed(field):
    """The program with one multiplier at its default (no scaling)
    against the reference with all four: the comparison fails."""
    default = {f.name: f.default for f in
               dataclasses.fields(_cfg())}[field]
    w = _weights()
    fn, rows = PATHS["paged"]
    assert _err(fn(_cfg(**{field: default}), w), _want(w, rows)) > RTOL


def test_default_multipliers_add_nothing(monkeypatch):
    """At the defaults a model's lowered paged decode step is the same
    text with the multiplier helpers as with pass-throughs."""
    from repro.configs import get_config, reduced
    from repro.models import layers as L
    from repro.models import lm
    cfg = reduced(get_config("qwen1.5-0.5b")).replace(kernel_impl="pallas")
    eng = _engine(cfg, None)
    batch = {"token": jnp.zeros((1,), jnp.int32),
             "cur_len": jnp.ones((1,), jnp.int32),
             "block_table": jnp.zeros((1, 4), jnp.int32),
             "cache": eng.init_paged_cache()}

    def lowered():
        step = jax.jit(lambda p, b: lm.paged_decode_step(p, b, cfg))
        return step.lower(eng.params, batch).as_text()

    with_helpers = lowered()
    monkeypatch.setattr(lm, "_scale_q", lambda cfg, q: q)
    monkeypatch.setattr(lm, "_branch", lambda cfg, y: y)
    monkeypatch.setattr(lm, "_embed", lambda params, tokens, cfg: L.embed(
        params["embed"], tokens).astype(jnp.dtype(cfg.dtype)))
    monkeypatch.setattr(lm, "_mlp", lambda cfg, p, norm_p, x, residual=None,
                        mesh=None: L.mlp(p, lm._norm(cfg, norm_p, x),
                                         cfg.act, backend=cfg,
                                         residual=residual, mesh=mesh))
    assert lowered() == with_helpers


def test_repo_preset_is_the_benchmark_configuration():
    """``configs/granite_3_8b.py`` states the model the benchmark runs:
    every field of the configuration file's ``model`` section but its
    name."""
    from repro.configs import get_config
    bench = json.loads((REPO / "chipbench" / "configs"
                        / "granite-3.1-8b-tp4.json").read_text())
    preset = get_config("granite-3-8b")
    for key, value in bench["model"].items():
        if key != "name":
            assert getattr(preset, key) == value, key


def _run_on_four(body: str) -> str:
    """``body`` after ``PRELUDE``, in a child process that sees four CPU
    devices."""
    code = ("import os\n"
            "os.environ['XLA_FLAGS']="
            "'--xla_force_host_platform_device_count=4'\n"
            + PRELUDE + textwrap.dedent(body))
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=900, cwd=REPO,
                       env={**os.environ, "PYTHONPATH": f"src:{REPO}"})
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    return r.stdout


PRELUDE = """
import jax, jax.numpy as jnp, numpy as np
from tests import test_granite as T
from repro.engine import DecodeEngine, EngineConfig, Request, Scheduler

def tp4_engine(w, **kw):
    return DecodeEngine(T._cfg(), EngineConfig(
        batch=kw.pop("batch", 1), max_len=64, mesh_shape=(1, 4),
        paged=True, page_size=16, decode_shard="seq",
        kernel_impl="pallas", **kw), params=w)
"""


def test_tp4_seq_sharded_paged_decode_matches_reference():
    """Tensor-parallel weights and the page pool split by slab over a
    (1, 4) mesh: prefill, then the sequence-sharded paged decode."""
    out = _run_on_four("""
    w = T._weights()
    eng = tp4_engine(w, n_pages=8)
    assert eng.mesh.shape["model"] == 4
    logits, cache = eng.prefill({"tokens": jnp.asarray(T._prompt())[None]})
    got = [np.asarray(logits[0])]
    table = eng.default_block_table()
    for i, tok in enumerate(T._forced()[:-1]):
        logits, cache = eng.decode_step(jnp.asarray([tok]), T.P + i,
                                        cache, block_table=table)
        got.append(np.asarray(logits[0]))
    got = np.stack(got)[:, :T.CONFIG["model"]["vocab"]]
    want = T._want(w, np.arange(T.P - 1, T.P - 1 + T.GEN))
    print("REL_ERR", T._err(got, want))
    """)
    err = float(out.split("REL_ERR")[-1].split()[0])
    assert err < RTOL


def test_tp4_pool_leaves_are_made_sharded():
    """Every pool leaf carries ``paged_cache_pspecs``' sharding and no
    device holds more than a quarter of it."""
    _run_on_four("""
    from repro.dist import sharding as SH
    eng = tp4_engine(T._weights(), n_pages=8)
    cache = eng.init_paged_cache()
    want = SH.paged_cache_pspecs(eng.cfg, eng.mesh, 1, seq_shard=True,
                                 n_pages=8)
    for name, leaf in cache.items():
        assert leaf.sharding.spec == want[name], (name, leaf.sharding)
        assert len(leaf.addressable_shards) == 4
        for shard in leaf.addressable_shards:
            assert shard.data.size * 4 == leaf.size, (name, shard.data.shape)
    """)


def test_tp4_live_pages_counted_by_shard():
    """Served on the (1, 4) mesh, the per-shard counts of live table
    entries add up to the live count; on one device there are none."""
    _run_on_four("""
    eng = tp4_engine(T._weights(), batch=2, n_pages=16)
    s = Scheduler(eng)
    rng = np.random.default_rng(5)
    for i, (n, gen) in enumerate(((40, 12), (21, 30), (17, 6))):
        s.submit(Request(rid=i, tokens=rng.integers(2, 500, (n,)),
                         gen=gen))
    done = s.run()
    assert all(r.ok for r in done.values())
    by_shard = s.stats["decode_pages_live_by_shard"]
    assert len(by_shard) == 4 and sum(by_shard) > 0
    assert sum(by_shard) == s.stats["decode_pages_live"]
    assert by_shard[0] > 0
    one = DecodeEngine(T._cfg(), EngineConfig(
        batch=2, max_len=64, paged=True, page_size=16, n_pages=16),
        params=T._weights())
    assert "decode_pages_live_by_shard" not in Scheduler(one).stats
    """)


@pytest.mark.parametrize("n_pages,slabs", [(16, 4), (18, 1)])
def test_page_slabs_follow_the_pool_sharding(n_pages, slabs):
    """The slabs the scheduler counts by are the pool's page sharding:
    four contiguous slabs when the pages split evenly over the model
    axis, else one, and then the pool's page dim is not sharded."""
    from jax.sharding import AbstractMesh

    from repro.dist import sharding as SH
    mesh = AbstractMesh((1, 4), ("data", "model"))
    assert SH.page_slabs(mesh, n_pages) == slabs
    pool = SH.paged_cache_pspecs(_cfg(), mesh, 2, seq_shard=True,
                                 n_pages=n_pages)
    assert (pool["k"][1] == "model") == (slabs == 4)
    slab = SH.page_slab_of(np.arange(n_pages), n_pages, slabs)
    assert np.array_equal(np.bincount(slab), [n_pages // slabs] * slabs)
    assert np.all(np.diff(slab) >= 0)
