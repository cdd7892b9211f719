"""Flash-attention kernel with VWR-style wide KV staging + zero-copy GQA.

Attention at long context is the LM-era version of the paper's
streaming workload: the KV cache is read once per query block with
near-zero reuse, so the HBM<->VMEM transaction width decides
throughput.  Each grid step stages one wide (bkv x D) K/V block (the
ultra-wide transaction), against which the resident query block runs
two MXU matmuls and a running-softmax update whose fp32 accumulators
(acc, m, l) live in VMEM scratch — the R1-R4 local registers of §4.3.5.

GQA is zero-copy: K/V stay at their native (B*KV, S, D) shape in HBM
and the K/V BlockSpec index map routes each of the G query heads in a
group to the one shared KV head (block index ``b // g``).  No
``jnp.repeat`` materialization — the HBM footprint and the staged
bytes per distinct KV element drop by the group factor G, which is
exactly the paper's access-ratio argument: one wide KV line serves G
narrow consumers.

q: (B*H, S, D); k, v: (B*KV, S, D) flattened heads; causal optional.
Grid: (B*H, q-blocks, kv-blocks), kv innermost (sequential).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                 scale, causal, bq, bkv, n_kv):
    i = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    def body():
        q = q_ref[0].astype(jnp.float32) * scale          # (bq, D)
        k = k_ref[0].astype(jnp.float32)                  # (bkv, D)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)
        if causal:
            qpos = i * bq + jax.lax.broadcasted_iota(
                jnp.int32, (bq, bkv), 0)
            kpos = j * bkv + jax.lax.broadcasted_iota(
                jnp.int32, (bq, bkv), 1)
            s = jnp.where(kpos <= qpos, s, NEG_INF)
        m_prev = m_ref[:, 0]
        m_new = jnp.maximum(m_prev, s.max(axis=-1))       # (bq,)
        p = jnp.exp(s - m_new[:, None])
        p = jnp.where((m_new > NEG_INF / 2)[:, None], p, 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_ref[:, 0] = l_ref[:, 0] * corr + p.sum(axis=-1)
        pv = jnp.dot(p, v_ref[0].astype(jnp.float32),
                     preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * corr[:, None] + pv
        m_ref[:, 0] = m_new

    if causal:
        # skip fully-masked kv blocks (above the causal diagonal)
        pl.when(j * bkv <= i * bq + bq - 1)(body)
    else:
        body()

    @pl.when(j == n_kv - 1)
    def _store():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[:, 0], 1e-30)[:, None]
                    ).astype(o_ref.dtype)


def vwr_attention_p(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, bq: int = 256, bkv: int = 512,
                    g: int = 1, interpret: bool = False) -> jax.Array:
    """q: (BH, S, D); k, v: (BH // g, S, D) — g query heads share each
    KV head (zero-copy GQA; g=1 is plain MHA).  S % bq == 0 and
    S % bkv == 0 (ops pads)."""
    BH, S, D = q.shape
    BKV = k.shape[0]
    assert BH == BKV * g and v.shape == k.shape
    assert S % bq == 0 and S % bkv == 0
    n_kv = S // bkv
    scale = 1.0 / (D ** 0.5)
    kernel = functools.partial(_attn_kernel, scale=scale, causal=causal,
                               bq=bq, bkv=bkv, n_kv=n_kv)
    return pl.pallas_call(
        kernel,
        grid=(BH, S // bq, n_kv),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
            # b // g: query head b reads its group's shared KV head —
            # since g divides the per-batch head count, the flattened
            # (batch*H + h) // g == batch*KV + h // g identity holds.
            pl.BlockSpec((1, bkv, D), lambda b, i, j: (b // g, j, 0)),
            pl.BlockSpec((1, bkv, D), lambda b, i, j: (b // g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, S, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, D), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(dimension_semantics=(
            "parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)
