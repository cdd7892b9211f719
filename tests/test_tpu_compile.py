"""The main-path Pallas kernels compile for a TPU v5e chip at real widths.

Nothing runs: each case lowers and compiles one kernel for a *described*
v5e chip (``jax.experimental.topologies``) with ``interpret=False`` and
checks that the compiled HLO holds the Mosaic kernel
(``tpu_custom_call``).  This is what interpret-mode tests cannot show:
Mosaic refuses blocks whose last two dims are neither (8, 128)-aligned
nor the array's own.

Widths: tinyllama-1.1b (32 heads, 4 KV heads, Dh 64, d_ff 5632) on
16-token pages for the GQA kernels, and qwen1.5-0.5b (16 heads, 16 KV
heads) at the benchmark cells' pools for the bf16 paged decode;
DeepSeek-V3's latent (r 512, rope 64, 128 heads) for MLA.  The topology is described inside a fixture,
never at import time, so every pytest-xdist worker collects the same
tests and only the worker that runs this file loads the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

B, H, KV, D, PS = 8, 32, 4, 64, 16          # tinyllama decode, 8 slots
J = 68                                       # 1088 positions per slot
N_PAGES = B * J
C = 32                                       # chunked-prefill chunk
R, ROPE, H_MLA = 512, 64, 128                # DeepSeek-V3 latent
BF16, F32, I32, I8 = jnp.bfloat16, jnp.float32, jnp.int32, jnp.int8


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                          # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def _no_cache_no_tuning(monkeypatch):
    """A compile for a described chip cannot be read back from the
    persistent cache (it warns on the next lookup), and block sizes
    come from the cost-model prior: nothing here can be measured."""
    from jax.experimental.compilation_cache import compilation_cache
    monkeypatch.setenv("REPRO_AUTOTUNE", "0")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("q8", [False, True], ids=["bf16", "int8"])
def test_paged_gqa_decode(one_chip, q8):
    pool = ((N_PAGES, KV, PS, D), I8 if q8 else BF16)
    scales = ((N_PAGES, KV), F32)
    tables = [((B, J), I32), ((B, J), I32)]
    if q8:
        _compile(lambda q, k, v, ks, vs, t, c: ops.vwr_paged_flash_decode_q8(
            q, k, v, ks, vs, t, c, interpret=False), one_chip,
            ((B, H, D), BF16), pool, pool, scales, scales, *tables)
    else:
        _compile(lambda q, k, v, t, c: ops.vwr_paged_flash_decode(
            q, k, v, t, c, interpret=False), one_chip,
            ((B, H, D), BF16), pool, pool, *tables)


@pytest.mark.parametrize("b,j,n_pages", [(24, 320, 3800), (64, 24, 1536)],
                         ids=["longctx", "chat"])
def test_paged_mha_decode_benchmark_cells(one_chip, b, j, n_pages):
    """qwen1.5-0.5b (16 heads over 16 KV heads, Dh 64) at the benchmark
    cells' slots, widest table and pool: 8 whole pages staged a step."""
    pool = ((n_pages, 16, PS, D), BF16)
    _compile(lambda q, k, v, t, c: ops.vwr_paged_flash_decode(
        q, k, v, t, c, interpret=False), one_chip,
        ((b, 16, D), BF16), pool, pool, ((b, j), I32), ((b, j), I32))


@pytest.mark.parametrize("q8", [False, True], ids=["bf16", "int8"])
def test_chunk_prefix(one_chip, q8):
    pool = ((N_PAGES, KV, PS, D), I8 if q8 else BF16)
    scales = ((N_PAGES, KV), F32)
    tables = [((J,), I32), ((J,), I32)]
    if q8:
        _compile(lambda q, k, v, ks, vs, t, c:
                 ops.vwr_chunk_prefix_attend_q8(q, k, v, ks, vs, t, c,
                                                interpret=False),
                 one_chip, ((C, H, D), BF16), pool, pool, scales, scales,
                 *tables)
    else:
        _compile(lambda q, k, v, t, c: ops.vwr_chunk_prefix_attend(
            q, k, v, t, c, interpret=False), one_chip,
            ((C, H, D), BF16), pool, pool, *tables)


@pytest.mark.parametrize("q8", [False, True], ids=["bf16", "int8"])
def test_dense_flash_decode(one_chip, q8):
    T = J * PS
    cache = ((B, T, KV, D), I8 if q8 else BF16)
    if q8:
        _compile(lambda q, k, v, ks, vs: ops.vwr_flash_decode_q8(
            q, k, v, ks, vs, T - 3, interpret=False), one_chip,
            ((B, H, D), BF16), cache, cache, ((B, KV), F32),
            ((B, KV), F32))
    else:
        _compile(lambda q, k, v: ops.vwr_flash_decode(
            q, k, v, T - 3, interpret=False), one_chip,
            ((B, H, D), BF16), cache, cache)


@pytest.mark.parametrize("q8", [False, True], ids=["bf16", "int8"])
def test_mla_paged_decode(one_chip, q8):
    n_pages = B * 64
    lat, rp = ((n_pages, PS, R), I8 if q8 else BF16), \
        ((n_pages, PS, ROPE), I8 if q8 else BF16)
    q = [((B, H_MLA, R), F32), ((B, H_MLA, ROPE), F32)]
    tables = [((B, 64), I32), ((B, 64), I32)]
    scale = 1.0 / (192 ** 0.5)
    if q8:
        _compile(lambda qa, qr, ck, kr, cs, rs, t, c:
                 ops.vwr_mla_paged_flash_decode_q8(
                     qa, qr, ck, kr, cs, rs, t, c, scale=scale,
                     interpret=False),
                 one_chip, *q, lat, rp, ((n_pages,), F32),
                 ((n_pages,), F32), *tables)
    else:
        _compile(lambda qa, qr, ck, kr, t, c:
                 ops.vwr_mla_paged_flash_decode(
                     qa, qr, ck, kr, t, c, scale=scale, interpret=False),
                 one_chip, *q, lat, rp, *tables)


@pytest.mark.parametrize("q8", [False, True], ids=["bf16", "int8"])
def test_mla_dense_and_chunk_prefix(one_chip, q8):
    scale = 1.0 / (192 ** 0.5)
    T = 1024
    dt = I8 if q8 else BF16
    q = [((B, H_MLA, R), F32), ((B, H_MLA, ROPE), F32)]
    cache = [((B, T, R), dt), ((B, T, ROPE), dt)]
    if q8:
        _compile(lambda qa, qr, ck, kr, cs, rs: ops.vwr_mla_flash_decode_q8(
            qa, qr, ck, kr, cs, rs, T - 3, scale=scale, interpret=False),
            one_chip, *q, *cache, ((B,), F32), ((B,), F32))
    else:
        _compile(lambda qa, qr, ck, kr: ops.vwr_mla_flash_decode(
            qa, qr, ck, kr, T - 3, scale=scale, interpret=False),
            one_chip, *q, *cache)
    n_pages = 64
    qc = [((4, H_MLA, R), F32), ((4, H_MLA, ROPE), F32)]
    pools = [((n_pages, PS, R), dt), ((n_pages, PS, ROPE), dt)]
    tables = [((16,), I32), ((16,), I32)]
    if q8:
        _compile(lambda qa, qr, ck, kr, cs, rs, t, c:
                 ops.vwr_mla_chunk_prefix_attend_q8(
                     qa, qr, ck, kr, cs, rs, t, c, scale=scale,
                     interpret=False),
                 one_chip, *qc, *pools, ((n_pages,), F32),
                 ((n_pages,), F32), *tables)
    else:
        _compile(lambda qa, qr, ck, kr, t, c:
                 ops.vwr_mla_chunk_prefix_attend(
                     qa, qr, ck, kr, t, c, scale=scale, interpret=False),
                 one_chip, *qc, *pools, *tables)


@pytest.mark.parametrize("M", [8, 1024], ids=["decode", "prefill"])
def test_fused_matmul_and_swiglu(one_chip, M):
    x, w = ((M, 2048), BF16), ((2048, 5632), BF16)
    _compile(lambda x, wg, wi: ops.vwr_swiglu(x, wg, wi, interpret=False),
             one_chip, x, w, w)
    _compile(lambda h, wo, r: ops.vwr_matmul(h, wo, residual=r,
                                             interpret=False),
             one_chip, ((M, 5632), BF16), ((5632, 2048), BF16),
             ((M, 2048), BF16))


def test_prefill_attention(one_chip):
    S = 1024
    _compile(lambda q, k, v: ops.vwr_attention(q, k, v, causal=True,
                                               interpret=False),
             one_chip, ((1, S, H, D), BF16), ((1, S, KV, D), BF16),
             ((1, S, KV, D), BF16))
