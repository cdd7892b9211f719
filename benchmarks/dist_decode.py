"""Distributed-decode benchmark: sharded vs local decode attention.

Runs in a subprocess pinned to ``JAX_PLATFORMS=cpu`` with
--xla_force_host_platform_device_count=8 (the parent process has already
locked jax to the visible device count, and may hold a chip), and
merges its rows — each stamped ``"platform": "cpu"`` — into
``BENCH_kernels.json`` next to the kernel micro-bench rows.

Per (B, T) cell:
  * local decode latency (``decode_attend_local`` on the full cache),
  * sharded decode latency (``dist.decode.sharded_flash_decode`` on a
    (1, 8) mesh — sequence-sharded cache, psum combine),
  * modeled per-token collective bytes from the compiled HLO
    (``hlo_analysis.collective_bytes``) — the headline number: the
    combine moves O(B*H*(Dh+2)) stat bytes instead of the O(B*T*KV*Dh)
    cache, independent of context length.

Plus one ``engine_decode`` row: a full one-token ``DecodeEngine`` step
(reduced arch, (1, 8) mesh, sequence-sharded cache, explicit mesh —
the production serve path) with its per-token collective bytes from
the engine's compiled decode step.

The ``mla_decode`` / ``mla_decode_paged`` rows pin the split-operand
MLA win in the staged_bytes column: ``mla_split`` stages r+rope
features/position (latent read once for scores AND values),
``mla_concat`` 2*(r+rope) (k_cat + zero-padded v_cat copies — on the
paged path, copies of the whole pool).  ``paged_decode_bucketed``
pins the block-table width bucketing: the table sliced to the
power-of-two bucket of the live page count stages the live-table
row's bytes instead of the fixed max_pages budget.

On a host-device CPU mesh the sharded latency is pure overhead
(interpret-mode kernels, emulated collectives); the latency columns
track the *trajectory*, the collective-bytes column is the modeled
production quantity.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

_CHILD = r"""
import os
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=8 "
                           + os.environ.get("XLA_FLAGS", ""))
import json
import time

import jax
import jax.numpy as jnp

from repro.dist.decode import sharded_flash_decode
from repro.launch import hlo_analysis
from repro.launch.mesh import make_mesh
from repro.models.attention import decode_attend_local


def timed(fn, *args, reps=5):
    jax.block_until_ready(fn(*args))
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    ts.sort()
    return ts[len(ts) // 2] * 1e6


rows = []
mesh = make_mesh((1, 8), ("data", "model"))
key = jax.random.PRNGKey(0)
H, KV, Dh = 8, 2, 64
for B, T in ((4, 2048), (4, 8192)):
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, H, Dh))
    ck = jax.random.normal(ks[1], (B, T, KV, Dh))
    cv = jax.random.normal(ks[2], (B, T, KV, Dh))
    cur = jnp.int32(T)

    local = jax.jit(lambda q, k, v, c: decode_attend_local(
        q, k, v, jnp.arange(T), c))
    shard = jax.jit(lambda q, k, v, c: sharded_flash_decode(
        mesh, q, k, v, c))
    t_local = timed(local, q, ck, cv, cur)
    t_shard = timed(shard, q, ck, cv, cur)
    coll, kinds = hlo_analysis.collective_bytes(
        shard.lower(q, ck, cv, cur).compile().as_text())
    cache_bytes = 2 * B * T * KV * Dh * 4
    rows.append({
        "op": "dist_decode", "shape": f"{B}x{T}x{H}x{KV}x{Dh}",
        "us": round(t_shard, 1), "us_ref": round(t_local, 1),
        "flops": B * H * 2 * T * Dh * 2, "staged_bytes": cache_bytes,
        "arith_intensity": None,
        "note": (f"mesh (1,8) seq-sharded; collective {coll:.0f} B/token"
                 f" vs cache {cache_bytes} B ({kinds})"),
        "collective_bytes": coll,
    })

# ---- paged vs dense decode attention ---------------------------------
# same (B, T) cells at 50% occupancy — the continuous-batching regime.
# The dense path streams its full (B, T) budget every step (dead bytes
# included: masking skips math, not DMA); the paged path's block table
# names only the ceil(len/page_size) pages that hold live data, so the
# step stages half the bytes.  That table-width economy is exactly
# what the scheduler's per-request page allocation buys.
from repro.dist.decode import local_paged_decode_attend

PS_PAGE = 64
for B, T in ((4, 2048), (4, 8192)):
    T_live = T // 2
    J = T_live // PS_PAGE                       # live pages per slot
    n_pages = B * J
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, H, Dh))
    kp = jax.random.normal(ks[1], (n_pages, KV, PS_PAGE, Dh))
    vp = jax.random.normal(ks[2], (n_pages, KV, PS_PAGE, Dh))
    table = (jnp.arange(B, dtype=jnp.int32)[:, None] * J
             + jnp.arange(J, dtype=jnp.int32)[None, :])
    lens = jnp.full((B,), T_live, jnp.int32)
    # dense comparison cache: same live contents, (B, T) budget
    def dense_of(pool):
        return jnp.swapaxes(pool.reshape(B, J, KV, PS_PAGE, Dh), 2,
                            3).reshape(B, T_live, KV, Dh)
    ck = jnp.zeros((B, T, KV, Dh)).at[:, :T_live].set(dense_of(kp))
    cv = jnp.zeros((B, T, KV, Dh)).at[:, :T_live].set(dense_of(vp))

    local = jax.jit(lambda q, k, v, c: decode_attend_local(
        q, k, v, jnp.arange(T), c))
    paged = jax.jit(lambda q, kp, vp, tb, ln: local_paged_decode_attend(
        q, kp, vp, tb, ln))
    t_dense = timed(local, q, ck, cv, jnp.int32(T_live))
    t_paged = timed(paged, q, kp, vp, table, lens)
    live_bytes = 2 * B * T_live * KV * Dh * 4
    budget_bytes = 2 * B * T * KV * Dh * 4
    rows.append({
        "op": "paged_decode", "shape": f"{B}x{T}x{H}x{KV}x{Dh}",
        "us": round(t_paged, 1), "us_ref": round(t_dense, 1),
        "flops": B * H * 2 * T_live * Dh * 2,
        "staged_bytes": live_bytes, "arith_intensity": None,
        "note": (f"page_size {PS_PAGE}, 50% occupancy: paged stages "
                 f"{live_bytes} live B/token vs the dense budget's "
                 f"{budget_bytes} B/token (us_ref = dense)"),
        "collective_bytes": None,
    })

# ---- MLA decode: split-operand vs concatenated cache -----------------
# The concat view (mla_absorbed_mqa) rebuilds k_cat + zero-padded v_cat
# copies of the latent+rope cache every step, so it STAGES
# 2*(r+rope) features/position; the split-operand decode_partial_mla
# path reads the latent cache once (scores AND values) plus the rope
# cache — r+rope features/position, a 2x staged-cache-bytes win the
# staged_bytes columns pin (dense and paged).
from repro.dist.decode import (local_mla_decode_attend,
                               local_mla_paged_decode_attend,
                               local_paged_decode_attend)
from repro.models.mla import mla_concat_view

R_LAT, ROPE = 256, 32                   # deepseek-shaped ratio r:rope
scale_mla = 1.0 / ((R_LAT + ROPE) ** 0.5)
for B, T in ((4, 2048), (4, 8192)):
    ks = jax.random.split(key, 4)
    q_abs = jax.random.normal(ks[0], (B, H, R_LAT))
    q_rope = jax.random.normal(ks[1], (B, H, ROPE))
    ckv = jax.random.normal(ks[2], (B, T, R_LAT))
    krope = jax.random.normal(ks[3], (B, T, ROPE))
    cur = jnp.int32(T)

    split = jax.jit(lambda qa, qr, ck, kr, c: local_mla_decode_attend(
        qa, qr, ck, kr, c, scale=scale_mla))

    def concat_attend(qa, qr, ck, kr, c):
        q_cat, k_cat, v_cat, r = mla_concat_view(qa, qr, ck, kr,
                                                 scale_mla)
        return decode_attend_local(q_cat, k_cat, v_cat, jnp.arange(T),
                                   c)[..., :r]

    concat = jax.jit(concat_attend)
    t_split = timed(split, q_abs, q_rope, ckv, krope, cur)
    t_concat = timed(concat, q_abs, q_rope, ckv, krope, cur)
    split_bytes = B * T * (R_LAT + ROPE) * 4
    concat_bytes = 2 * B * T * (R_LAT + ROPE) * 4
    shape = f"{B}x{T}x{H}x{R_LAT}+{ROPE}"
    flops = B * H * 2 * T * (R_LAT + ROPE + R_LAT)
    rows.append({
        "op": "mla_decode", "shape": shape, "us": round(t_split, 1),
        "us_ref": round(t_concat, 1), "flops": flops,
        "staged_bytes": split_bytes, "arith_intensity": None,
        "note": (f"mla_split: latent+rope as separate operands, "
                 f"{split_bytes} staged cache B/token "
                 f"({concat_bytes / split_bytes:.1f}x fewer than "
                 "mla_concat; us_ref = concat)"),
        "collective_bytes": None,
    })
    rows.append({
        "op": "mla_decode", "shape": shape, "us": round(t_concat, 1),
        "us_ref": None, "flops": flops,
        "staged_bytes": concat_bytes, "arith_intensity": None,
        "note": (f"mla_concat: k_cat + zero-padded v_cat cache copies "
                 f"rebuilt per step, {concat_bytes} staged cache "
                 "B/token"),
        "collective_bytes": None,
    })

# paged MLA: the concat view copies the whole POOL per step
for B, T in ((4, 2048),):
    T_live = T // 2
    J = T_live // PS_PAGE
    n_pages = B * J
    ks = jax.random.split(key, 4)
    q_abs = jax.random.normal(ks[0], (B, H, R_LAT))
    q_rope = jax.random.normal(ks[1], (B, H, ROPE))
    ckv_pool = jax.random.normal(ks[2], (n_pages, PS_PAGE, R_LAT))
    krope_pool = jax.random.normal(ks[3], (n_pages, PS_PAGE, ROPE))
    table = (jnp.arange(B, dtype=jnp.int32)[:, None] * J
             + jnp.arange(J, dtype=jnp.int32)[None, :])
    lens = jnp.full((B,), T_live, jnp.int32)

    psplit = jax.jit(lambda qa, qr, ck, kr, tb, ln:
                     local_mla_paged_decode_attend(
                         qa, qr, ck, kr, tb, ln, scale=scale_mla))

    def concat_paged_attend(qa, qr, ck, kr, tb, ln):
        # mla_concat_view materializes whole-POOL k_cat/v_cat copies —
        # exactly the cost the split row avoids
        q_cat, k_cat, v_cat, r = mla_concat_view(qa, qr, ck, kr,
                                                 scale_mla, paged=True)
        return local_paged_decode_attend(q_cat, k_cat, v_cat, tb,
                                         ln)[..., :r]

    pconcat = jax.jit(concat_paged_attend)
    t_psplit = timed(psplit, q_abs, q_rope, ckv_pool, krope_pool,
                     table, lens)
    t_pconcat = timed(pconcat, q_abs, q_rope, ckv_pool, krope_pool,
                      table, lens)
    split_bytes = B * T_live * (R_LAT + ROPE) * 4
    # concat copies the whole pool (k_cat + v_cat) before attending
    concat_bytes = 2 * n_pages * PS_PAGE * (R_LAT + ROPE) * 4 \
        + split_bytes
    shape = f"{B}x{T}x{H}x{R_LAT}+{ROPE}"
    rows.append({
        "op": "mla_decode_paged", "shape": shape,
        "us": round(t_psplit, 1), "us_ref": round(t_pconcat, 1),
        "flops": B * H * 2 * T_live * (R_LAT + ROPE + R_LAT),
        "staged_bytes": split_bytes, "arith_intensity": None,
        "note": (f"mla_split paged: pools stay separate, {split_bytes} "
                 f"staged cache B/token "
                 f"({concat_bytes / split_bytes:.1f}x fewer than "
                 "mla_concat's pool-wide copies; us_ref = concat)"),
        "collective_bytes": None,
    })
    rows.append({
        "op": "mla_decode_paged", "shape": shape,
        "us": round(t_pconcat, 1), "us_ref": None,
        "flops": B * H * 2 * T_live * (R_LAT + ROPE + R_LAT),
        "staged_bytes": concat_bytes, "arith_intensity": None,
        "note": (f"mla_concat paged: whole-pool k_cat/v_cat copies per "
                 f"step, {concat_bytes} staged cache B/token"),
        "collective_bytes": None,
    })

# ---- bucketed block tables: stage only live table columns ------------
# Fixed-width tables hold max_pages columns per slot (the jit-stable
# engine budget) even when every live slot owns a handful — the
# dead-column analogue of the dense cache's dead bytes.  Bucketing
# slices the table to the power-of-two width covering the longest
# slot (engine.paged_cache.bucket_table_width), converging on the
# live-table paged_decode row above.
from repro.engine.paged_cache import bucket_table_width

for B, T in ((4, 2048),):
    T_live = T // 2
    J_live = T_live // PS_PAGE                  # live pages per slot
    J_max = T // PS_PAGE                        # engine-wide budget
    n_pages = B * J_max
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, H, Dh))
    kp = jax.random.normal(ks[1], (n_pages, KV, PS_PAGE, Dh))
    vp = jax.random.normal(ks[2], (n_pages, KV, PS_PAGE, Dh))
    table = jnp.zeros((B, J_max), jnp.int32).at[:, :J_live].set(
        jnp.arange(B, dtype=jnp.int32)[:, None] * J_live
        + jnp.arange(J_live, dtype=jnp.int32)[None, :])
    lens = jnp.full((B,), T_live, jnp.int32)
    W = bucket_table_width(J_live, J_max)

    paged = jax.jit(lambda q, kp, vp, tb, ln: local_paged_decode_attend(
        q, kp, vp, tb, ln))
    t_fixed = timed(paged, q, kp, vp, table, lens)
    t_bucket = timed(paged, q, kp, vp, table[:, :W], lens)
    live_bytes = 2 * B * T_live * KV * Dh * 4
    bucket_bytes = 2 * B * W * PS_PAGE * KV * Dh * 4
    fixed_bytes = 2 * B * J_max * PS_PAGE * KV * Dh * 4
    rows.append({
        "op": "paged_decode_bucketed", "shape": f"{B}x{T}x{H}x{KV}x{Dh}",
        "us": round(t_bucket, 1), "us_ref": round(t_fixed, 1),
        "flops": B * H * 2 * T_live * Dh * 2,
        "staged_bytes": bucket_bytes, "arith_intensity": None,
        "note": (f"table bucketed {J_max}->{W} cols at 50% occupancy: "
                 f"{bucket_bytes} staged B/token vs fixed-width "
                 f"{fixed_bytes} (live-table floor {live_bytes}; "
                 "us_ref = fixed-width)"),
        "collective_bytes": None,
    })

# ---- quantized KV pages: int8 pools + fp32 per-page scale sidecars ---
# Same live contents and page geometry as the paged rows above, stored
# int8 with one fp32 scale per page (per KV head for GQA, per page for
# the MLA latents).  The decode step stages ~half the bf16 pools'
# bytes per token — the sidecar adds 4 B per (page, head) against a
# page's page_size*Dh int8 payload — and the q8 ops dequantize inside
# the staged block (scale hoisted out of the int8 dot).
from repro.kernels.quant import quantize_int8

for B, T in ((4, 2048), (4, 8192)):
    T_live = T // 2
    J = T_live // PS_PAGE
    n_pages = B * J
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, H, Dh))
    kp = jax.random.normal(ks[1], (n_pages, KV, PS_PAGE, Dh))
    vp = jax.random.normal(ks[2], (n_pages, KV, PS_PAGE, Dh))
    table = (jnp.arange(B, dtype=jnp.int32)[:, None] * J
             + jnp.arange(J, dtype=jnp.int32)[None, :])
    lens = jnp.full((B,), T_live, jnp.int32)
    kq, ksc = quantize_int8(kp, axis=(1, 3))
    vq, vsc = quantize_int8(vp, axis=(1, 3))
    ksc, vsc = ksc.reshape(n_pages, KV), vsc.reshape(n_pages, KV)

    bf16 = jax.jit(lambda q, kp, vp, tb, ln: local_paged_decode_attend(
        q, kp, vp, tb, ln))
    q8 = jax.jit(lambda q, kp, vp, ks_, vs_, tb, ln:
                 local_paged_decode_attend(q, kp, vp, tb, ln,
                                           k_scale=ks_, v_scale=vs_))
    t_bf16 = timed(bf16, q, kp.astype(jnp.bfloat16),
                   vp.astype(jnp.bfloat16), table, lens)
    t_q8 = timed(q8, q, kq, vq, ksc, vsc, table, lens)
    bf16_bytes = 2 * B * T_live * KV * Dh * 2
    q8_bytes = 2 * B * T_live * KV * Dh + 2 * B * J * KV * 4
    rows.append({
        "op": "paged_decode_q8", "shape": f"{B}x{T}x{H}x{KV}x{Dh}",
        "us": round(t_q8, 1), "us_ref": round(t_bf16, 1),
        "flops": B * H * 2 * T_live * Dh * 2,
        "staged_bytes": q8_bytes, "arith_intensity": None,
        "note": (f"int8 pages + per-page-per-head fp32 scales: "
                 f"{q8_bytes} staged cache B/token, "
                 f"{bf16_bytes / q8_bytes:.2f}x fewer than bf16 "
                 f"pools' {bf16_bytes} (us_ref = bf16 pools)"),
        "collective_bytes": None,
    })

for B, T in ((4, 2048), (4, 8192)):
    T_live = T // 2
    J = T_live // PS_PAGE
    n_pages = B * J
    ks = jax.random.split(key, 4)
    q_abs = jax.random.normal(ks[0], (B, H, R_LAT))
    q_rope = jax.random.normal(ks[1], (B, H, ROPE))
    ckv_pool = jax.random.normal(ks[2], (n_pages, PS_PAGE, R_LAT))
    krope_pool = jax.random.normal(ks[3], (n_pages, PS_PAGE, ROPE))
    table = (jnp.arange(B, dtype=jnp.int32)[:, None] * J
             + jnp.arange(J, dtype=jnp.int32)[None, :])
    lens = jnp.full((B,), T_live, jnp.int32)
    cq, csc = quantize_int8(ckv_pool, axis=(1, 2))
    rq, rsc = quantize_int8(krope_pool, axis=(1, 2))
    csc, rsc = csc.reshape(n_pages), rsc.reshape(n_pages)

    mbf16 = jax.jit(lambda qa, qr, ck, kr, tb, ln:
                    local_mla_paged_decode_attend(
                        qa, qr, ck, kr, tb, ln, scale=scale_mla))
    mq8 = jax.jit(lambda qa, qr, ck, kr, cs, rs, tb, ln:
                  local_mla_paged_decode_attend(
                      qa, qr, ck, kr, tb, ln, scale=scale_mla,
                      ckv_scale=cs, krope_scale=rs))
    t_mbf16 = timed(mbf16, q_abs, q_rope,
                    ckv_pool.astype(jnp.bfloat16),
                    krope_pool.astype(jnp.bfloat16), table, lens)
    t_mq8 = timed(mq8, q_abs, q_rope, cq, rq, csc, rsc, table, lens)
    bf16_bytes = B * T_live * (R_LAT + ROPE) * 2
    q8_bytes = B * T_live * (R_LAT + ROPE) + 2 * B * J * 4
    shape = f"{B}x{T}x{H}x{R_LAT}+{ROPE}"
    rows.append({
        "op": "mla_decode_paged_q8", "shape": shape,
        "us": round(t_mq8, 1), "us_ref": round(t_mbf16, 1),
        "flops": B * H * 2 * T_live * (R_LAT + ROPE + R_LAT),
        "staged_bytes": q8_bytes, "arith_intensity": None,
        "note": (f"int8 latent pages + per-page fp32 scales "
                 f"(split-operand): {q8_bytes} staged cache B/token, "
                 f"{bf16_bytes / q8_bytes:.2f}x fewer than bf16 "
                 f"pools' {bf16_bytes} (us_ref = bf16 pools)"),
        "collective_bytes": None,
    })

# ---- full engine step: the production serve path ---------------------
from repro.configs import get_config, reduced
from repro.engine import DecodeEngine, EngineConfig

B, P, G = 2, 32, 32
cfg = reduced(get_config("qwen1.5-0.5b"))
eng = DecodeEngine(cfg, EngineConfig(batch=B, max_len=P + G,
                                     mesh_shape=(1, 8),
                                     decode_shard="seq"))
toks = jax.random.randint(key, (B, P), 2, cfg.vocab)
logits, cache = eng.prefill({"tokens": toks})
tok = jnp.argmax(logits, -1).astype(jnp.int32)
dbatch = {"token": tok, "cur_len": jnp.int32(P), "cache": cache}
t_eng = timed(eng.decode_fn, eng.params, dbatch)
coll, kinds = hlo_analysis.collective_bytes(
    eng.decode_fn.lower(eng.params, dbatch).compile().as_text())
rows.append({
    "op": "engine_decode", "shape": f"{cfg.name}:{B}x{P + G}",
    "us": round(t_eng, 1), "us_ref": None, "flops": None,
    "staged_bytes": None, "arith_intensity": None,
    "note": (f"DecodeEngine one-token step, mesh (1,8) seq-sharded, "
             f"explicit mesh; collective {coll:.0f} B/token ({kinds})"),
    "collective_bytes": coll,
})

# ---- paged engine step: pool seq-sharded over 8 devices --------------
peng = DecodeEngine(cfg, EngineConfig(batch=B, max_len=P + G,
                                      mesh_shape=(1, 8),
                                      decode_shard="seq", paged=True,
                                      page_size=8),
                    params=eng.params)
logits_p, pcache = peng.prefill({"tokens": toks})
ptable = peng.default_block_table()
lens = jnp.full((B,), P, jnp.int32)
pbatch = {"token": tok, "cur_len": lens, "block_table": ptable,
          "cache": pcache}
t_peng = timed(peng.decode_fn, peng.params, pbatch)
coll_p, kinds_p = hlo_analysis.collective_bytes(
    peng.decode_fn.lower(peng.params, pbatch).compile().as_text())
rows.append({
    "op": "engine_decode_paged", "shape": f"{cfg.name}:{B}x{P + G}",
    "us": round(t_peng, 1), "us_ref": round(t_eng, 1), "flops": None,
    "staged_bytes": None, "arith_intensity": None,
    "note": (f"paged DecodeEngine one-token step (page_size 8, pool "
             f"seq-sharded over 8 shards, block-table combine); "
             f"collective {coll_p:.0f} B/token ({kinds_p}); "
             "us_ref = dense engine step"),
    "collective_bytes": coll_p,
})
# ---- scheduler pick: one fused (3,B) transfer vs per-slot syncs ------
# The fault-tolerant scheduler computes every slot's token choice
# (greedy argmax, seeded categorical, isfinite guard) in one jitted
# call and crosses the device boundary as a single (3, B) int32 stack;
# the naive loop pays 3 separate device->host round-trips per slot.
import numpy as np
from repro.engine.scheduler import Scheduler

B_, V = 8, 4096
lg = jax.random.normal(key, (B_, V))
seeds = jnp.arange(B_, dtype=jnp.int32)
steps = jnp.full((B_,), 3, jnp.int32)
temps = jnp.full((B_,), 0.7, jnp.float32)
pick = jax.jit(Scheduler._pick)


def batched():
    return np.asarray(pick(lg, seeds, steps, temps))


def per_slot():
    out = []
    for b in range(B_):
        k = jax.random.fold_in(jax.random.PRNGKey(b), 3)
        out.append(int(jnp.argmax(lg[b])))
        out.append(int(jax.random.categorical(k, lg[b] / 0.7)))
        out.append(bool(jnp.all(jnp.isfinite(lg[b]))))
    return out


fused = batched()
loop = per_slot()
assert [int(x) for x in fused[0]] == loop[0::3]      # greedy agrees
assert [int(x) for x in fused[1]] == loop[1::3]      # sampled agrees
t_fused = timed(batched)
t_loop = timed(per_slot)
rows.append({
    "op": "sched_pick", "shape": f"{B_}x{V}",
    "us": round(t_fused, 1), "us_ref": round(t_loop, 1),
    "flops": None, "staged_bytes": 3 * B_ * 4,
    "arith_intensity": None,
    "note": (f"batched pick: 1 fused (3,{B_}) int32 transfer/step vs "
             f"{3 * B_} per-slot device syncs (us_ref = per-slot "
             "loop); sampled/greedy streams bit-identical"),
    "collective_bytes": None,
})

# ---- prefix cache: warm vs cold TTFT on a shared system prompt -------
# Production traffic reuses a handful of system prompts; the radix
# cache turns that reuse into resident pages, so admission prefills
# only the per-request suffix.  TTFT here = submit -> first token
# (admission prefill + scatter + argmax), measured on a 100%-shared
# system prompt: cold pays the full P-token prefill, warm only the
# suffix — prefill FLOPs drop with the positions (attention is
# super-linear, so the wall-clock win grows with the prompt).
import time as _time

from repro.engine import Request, Scheduler

PS_PC, SYS_PAGES, SUF = 8, 12, 8
SYS = SYS_PAGES * PS_PC                         # 96 shared tokens
P_PC = SYS + SUF                                # 104-token prompts
pceng = DecodeEngine(cfg, EngineConfig(batch=2, max_len=P_PC + 4,
                                       paged=True, page_size=PS_PC,
                                       n_pages=64, prefix_cache=True))
rng_pc = np.random.default_rng(0)


def _pc_prompt(sys_toks):
    suf = rng_pc.integers(2, cfg.vocab, (SUF,)).astype(np.int32)
    return np.concatenate([sys_toks, suf])


def _ttft(sched, rid, prompt):
    sched.submit(Request(rid=rid, tokens=prompt, gen=1))
    t0 = _time.perf_counter()
    assert sched.admit() == 1
    jax.block_until_ready(sched.cache)
    return (_time.perf_counter() - t0) * 1e6


sched_pc = Scheduler(pceng)
# warm-up: compile both the full-prompt and the suffix prefill paths
warm_sys = rng_pc.integers(2, cfg.vocab, (SYS,)).astype(np.int32)
_ttft(sched_pc, "w0", _pc_prompt(warm_sys))
_ttft(sched_pc, "w1", _pc_prompt(warm_sys))
sched_pc.prefix.clear()

sys_toks = rng_pc.integers(2, cfg.vocab, (SYS,)).astype(np.int32)
t_cold = _ttft(sched_pc, "cold", _pc_prompt(sys_toks))
warm = sorted(_ttft(sched_pc, f"warm{i}", _pc_prompt(sys_toks))
              for i in range(5))
t_warm = warm[len(warm) // 2]
hit_rate = sched_pc.stats["prefix_hits"] / max(
    1, sched_pc.stats["prefix_hits"] + sched_pc.stats["prefix_misses"])
# per-position prefill cost: attention O(S*T) + MLP O(S) — report the
# dominant linear term as the FLOPs column (positions actually run)
D = cfg.d_model
flops_cold = P_PC * (12 * D * D + 2 * P_PC * D)
flops_warm = SUF * (12 * D * D + 2 * P_PC * D)
rows.append({
    "op": "prefix_cache_decode",
    "shape": f"{cfg.name}:{P_PC}p/{SYS}shared",
    "us": round(t_warm, 1), "us_ref": round(t_cold, 1),
    "flops": flops_warm, "staged_bytes": None, "arith_intensity": None,
    "note": (f"TTFT warm {t_warm:.0f}us vs cold {t_cold:.0f}us "
             f"({t_cold / t_warm:.1f}x) on a 100%-shared {SYS}-token "
             f"system prompt: suffix-only prefill runs {SUF} of "
             f"{P_PC} positions ({flops_cold / flops_warm:.1f}x fewer "
             f"prefill FLOPs); hit rate {hit_rate:.2f}, "
             f"{sched_pc.stats['prefix_hit_tokens']} tokens from cache "
             "(us_ref = cold full prefill)"),
    "collective_bytes": None,
})

# ---- mixed stream: chunked prefill pins decode ITL p99 ---------------
# A long prompt entering a busy batch is the classic ITL-tail killer:
# non-chunked admission runs the WHOLE prefill while every decoding
# slot waits, so the waiting slots' inter-token latency spikes by the
# full prefill wall time.  The token-budget mixed step slices the
# prompt into chunk_tokens-sized pieces that ride inside ordinary
# decode steps, collapsing the tail from "one full prefill" to "one
# chunk".  us = chunked ITL p99 during the prefill window, us_ref =
# the same window under whole-prompt admission — bench_diff's
# speedup-shrink guard watches the us_ref/us ratio.
LONG_P, CT, PS_MX = 128, 16, 8
mxeng = DecodeEngine(cfg, EngineConfig(
    batch=4, max_len=LONG_P + 64, paged=True, page_size=PS_MX,
    n_pages=48, chunked_prefill=True, chunk_tokens=CT))
rng_mx = np.random.default_rng(0)
shorts_mx = [rng_mx.integers(2, cfg.vocab, (16,)).astype(np.int32)
             for _ in range(3)]
long_mx = rng_mx.integers(2, cfg.vocab, (LONG_P,)).astype(np.int32)


# _stream_gaps: wall gaps between the 3 decoding slots' token
# emissions while the long prompt is admitted + prefilled.  Every
# executed step emits one token per decoding slot, so the step-to-step
# gaps ARE those slots' inter-token latencies — and the non-chunked
# run folds the whole admission prefill into the first gap.
def _stream_gaps(chunked):
    sched = Scheduler(mxeng, chunked_prefill=chunked)
    for i, toks in enumerate(shorts_mx):
        sched.submit(Request(rid=f"s{i}", tokens=toks, gen=56))
    sched.admit()
    for _ in range(4):                      # shorts into steady decode
        sched.step()
    sched.submit(Request(rid="long", tokens=long_mx, gen=4))
    marks = [_time.perf_counter()]
    sched.admit()                  # non-chunked: full prefill in here
    for _ in range(LONG_P // CT):  # chunked: the long's 8 chunk steps
        sched.step()
        jax.block_until_ready(sched.cache)
        marks.append(_time.perf_counter())
    sched.run()                             # drain; frees every page
    return np.diff(marks) * 1e6


_stream_gaps(True)                          # compile the mixed step
_stream_gaps(False)                         # compile the long prefill
itl_mix = float(np.percentile(_stream_gaps(True), 99))
itl_base = float(np.percentile(_stream_gaps(False), 99))
rows.append({
    "op": "mixed_stream",
    "shape": f"{cfg.name}:{LONG_P}p/ct{CT}",
    "us": round(itl_mix, 1), "us_ref": round(itl_base, 1),
    "flops": None, "staged_bytes": None, "arith_intensity": None,
    "note": (f"decode ITL p99 while a {LONG_P}-token prompt prefills: "
             f"chunked {itl_mix:.0f}us vs whole-prompt admission "
             f"{itl_base:.0f}us ({itl_base / itl_mix:.1f}x lower "
             f"tail; chunk_tokens={CT}, 3 slots decoding; us_ref = "
             "non-chunked batch-1 admission)"),
    "collective_bytes": None,
})

# ---- snapshot_restore: durability cost of crash-safe serving ---------
# Snapshot a mid-stream scheduler (paged KV + live slots + queues) to
# disk, then restore it into a fresh Scheduler on the same engine.
# us = restore wall µs, us_ref = synchronous save wall µs,
# staged_bytes = on-disk snapshot size.  The snapshot captures the
# scheduler's own step counter, so recovery resumes from that step
# with zero recomputation — the note carries save/restore ms and the
# steps-to-resume figure (remaining decode steps replayed: 0).
import shutil as _shutil
import tempfile as _tempfile

from repro.engine.snapshot import restore as _sn_restore
from repro.engine.snapshot import snapshot as _sn_snapshot

sched_sn = Scheduler(mxeng, chunked_prefill=True)
rng_sn = np.random.default_rng(1)
for i in range(3):
    sched_sn.submit(Request(
        rid=f"sn{i}",
        tokens=rng_sn.integers(2, cfg.vocab, (24,)).astype(np.int32),
        gen=16))
sched_sn.admit()
for _ in range(6):                       # mid-stream: slots decoding
    sched_sn.step()
jax.block_until_ready(sched_sn.cache)
step_at_snap = int(sched_sn.stats["steps"])

d_sn = _tempfile.mkdtemp()
try:
    t0 = _time.perf_counter()
    snap_step = _sn_snapshot(sched_sn, d_sn)
    save_us = (_time.perf_counter() - t0) * 1e6
    snap_dir = os.path.join(d_sn, f"step_{snap_step}")
    snap_bytes = sum(
        os.path.getsize(os.path.join(root_, f_))
        for root_, _, files_ in os.walk(snap_dir) for f_ in files_)
    t0 = _time.perf_counter()
    sched_rs = _sn_restore(d_sn, mxeng)
    restore_us = (_time.perf_counter() - t0) * 1e6
finally:
    _shutil.rmtree(d_sn, ignore_errors=True)
assert int(sched_rs.stats["steps"]) == step_at_snap
sched_sn.run()                           # drain both; free every page
sched_rs.run()
rows.append({
    "op": "snapshot_restore",
    "shape": f"{cfg.name}:b4/p{PS_MX}x48",
    "us": round(restore_us, 1), "us_ref": round(save_us, 1),
    "flops": None, "staged_bytes": int(snap_bytes),
    "arith_intensity": None,
    "note": (f"engine snapshot {snap_bytes / 1e6:.1f} MB on disk: "
             f"save {save_us / 1e3:.1f}ms / restore "
             f"{restore_us / 1e3:.1f}ms at step {step_at_snap}, "
             "steps-to-resume 0 (restored scheduler continues from "
             "the captured step; us_ref = synchronous save)"),
    "collective_bytes": None,
})

# every row names the platform it ran on: the host CPU's virtual
# devices, never a chip
for row in rows:
    row["platform"] = jax.devices()[0].platform
print("JSON:" + json.dumps(rows))
"""


def dist_decode_bench(json_path="BENCH_kernels.json"):
    """Appends dist_decode rows to the kernel-bench JSON artifact."""
    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    pp = os.environ.get("PYTHONPATH")
    # the child emulates an 8-device mesh on the host CPU: pinned to the
    # CPU so it never contends with the parent for a chip
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": src + (os.pathsep + pp if pp else "")}
    r = subprocess.run([sys.executable, "-c", _CHILD],
                       capture_output=True, text=True, timeout=900,
                       env=env)
    if r.returncode != 0:
        raise RuntimeError(f"dist_decode child failed:\n{r.stderr[-2000:]}")
    payload = [ln for ln in r.stdout.splitlines()
               if ln.startswith("JSON:")][-1]
    rows = json.loads(payload[len("JSON:"):])
    print("\n# dist_decode: op,shape,us,us_ref,"
          "collective_bytes_per_token")
    for row in rows:
        coll = row["collective_bytes"]
        print(f"{row['op']},{row['shape']},{row['us']},{row['us_ref']},"
              f"{'-' if coll is None else format(coll, '.0f')}")
    if json_path:
        existing = []
        if os.path.exists(json_path):
            try:
                with open(json_path) as f:
                    existing = json.load(f)
            except ValueError:
                existing = []
        existing = [r for r in existing
                    if r.get("op") not in ("dist_decode", "engine_decode",
                                           "paged_decode",
                                           "engine_decode_paged",
                                           "mla_decode",
                                           "mla_decode_paged",
                                           "paged_decode_bucketed",
                                           "paged_decode_q8",
                                           "mla_decode_paged_q8",
                                           "sched_pick",
                                           "prefix_cache_decode",
                                           "mixed_stream",
                                           "snapshot_restore")]
        existing.extend(rows)
        with open(json_path, "w") as f:
            json.dump(existing, f, indent=1)
        print(f"# merged {len(rows)} dist_decode rows -> {json_path}")
    return rows


if __name__ == "__main__":
    dist_decode_bench()
