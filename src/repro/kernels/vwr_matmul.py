"""VWR-streamed matmul kernel (Pallas TPU) with fused epilogues.

The TPU realization of the paper's asymmetric-port VWR (§4.1/§4.3.4):
one HBM->VMEM DMA stages an ultra-wide (bm x bk) LHS block and a
(bk x bn) RHS block; the MXU then consumes that staged data in many
128x128 substeps before the next wide transaction.  The width ratio
N = (bm*bk + bk*bn) staged bytes per (bm*bk*bn) MACs is the tunable
analogue of the paper's SRAM/VFU width ratio — raising the block sizes
raises arithmetic intensity exactly the way widening the VWR raises
the paper's access ratio.

fp32 accumulation in a VMEM scratch across the K grid dimension
(sequential innermost), bf16/fp32 inputs.

Fused epilogue: ``bias`` add, ``activation`` (relu/gelu/silu), and a
``residual`` add are applied to the fp32 accumulator inside the
final-K store, so ``act(x @ w + bias) + residual`` costs exactly one
HBM round-trip for the output — the won access-ratio is not thrown
away on a second elementwise pass (the paper's §4.1 argument applied
to the epilogue instead of the GEMM body).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

ACTIVATIONS = {
    "relu": lambda x: jnp.maximum(x, 0.0),
    "gelu": jax.nn.gelu,
    "silu": jax.nn.silu,
}


def _matmul_kernel(x_ref, w_ref, *rest, n_k: int, has_bias: bool,
                   has_res: bool, activation):
    o_ref, acc_ref = rest[-2], rest[-1]
    b_ref = rest[0] if has_bias else None
    r_ref = rest[1 if has_bias else 0] if has_res else None

    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(x_ref[...], w_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == n_k - 1)
    def _store():
        out = acc_ref[...]
        if has_bias:
            out = out + b_ref[...].astype(jnp.float32)       # (1,bn) bcast
        if activation is not None:
            out = ACTIVATIONS[activation](out)
        if has_res:
            out = out + r_ref[...].astype(jnp.float32)
        o_ref[...] = out.astype(o_ref.dtype)


def _dual_matmul_kernel(x_ref, wg_ref, wi_ref, o_ref, accg_ref, acci_ref,
                        *, n_k: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        accg_ref[...] = jnp.zeros_like(accg_ref)
        acci_ref[...] = jnp.zeros_like(acci_ref)

    x = x_ref[...]
    accg_ref[...] += jnp.dot(x, wg_ref[...],
                             preferred_element_type=jnp.float32)
    acci_ref[...] += jnp.dot(x, wi_ref[...],
                             preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == n_k - 1)
    def _store():
        out = jax.nn.silu(accg_ref[...]) * acci_ref[...]
        o_ref[...] = out.astype(o_ref.dtype)


def vwr_swiglu_p(x: jax.Array, wg: jax.Array, wi: jax.Array, *,
                 bm: int = 256, bk: int = 512, bn: int = 256,
                 interpret: bool = False) -> jax.Array:
    """``silu(x @ wg) * (x @ wi)`` in one kernel pass (dual-matmul
    fused-swiglu epilogue).

    x: (M, K); wg, wi: (K, N); dims must divide the block sizes
    (``ops.vwr_swiglu`` pads).  One staged (bm x bk) x block feeds BOTH
    matmuls' MXU substeps — the gate's and the up-projection's — so the
    LHS wide transaction is paid once, and the ``silu(g) * h`` product
    happens on the two fp32 accumulators inside the final-K store: the
    gate and up activations never round-trip HBM and the elementwise
    pass that used to follow the two separate matmuls disappears."""
    M, K = x.shape
    K2, N = wg.shape
    assert K == K2 and wi.shape == (K, N)
    assert M % bm == 0 and K % bk == 0 and N % bn == 0
    n_k = K // bk
    return pl.pallas_call(
        functools.partial(_dual_matmul_kernel, n_k=n_k),
        grid=(M // bm, N // bn, n_k),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32),
                        pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=(
            "parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, wg, wi)


def vwr_matmul_p(x: jax.Array, w: jax.Array, bias=None, residual=None, *,
                 bm: int = 256, bk: int = 512, bn: int = 256,
                 activation: str = None,
                 interpret: bool = False) -> jax.Array:
    """x: (M, K), w: (K, N) — M, K, N must divide the block sizes
    (ops.vwr_matmul pads).  Optional fused epilogue on the final-K
    store: bias (1, N), activation name, residual (M, N).  Returns
    ``act(x @ w + bias) + residual`` as (M, N) in x.dtype."""
    M, K = x.shape
    K2, N = w.shape
    assert K == K2 and M % bm == 0 and K % bk == 0 and N % bn == 0
    assert activation is None or activation in ACTIVATIONS, activation
    n_k = K // bk
    kernel = functools.partial(
        _matmul_kernel, n_k=n_k, has_bias=bias is not None,
        has_res=residual is not None, activation=activation)
    in_specs = [
        pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
        pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
    ]
    operands = [x, w]
    if bias is not None:
        assert bias.shape == (1, N), bias.shape
        in_specs.append(pl.BlockSpec((1, bn), lambda i, j, k: (0, j)))
        operands.append(bias)
    if residual is not None:
        assert residual.shape == (M, N), residual.shape
        in_specs.append(pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)))
        operands.append(residual)
    return pl.pallas_call(
        kernel,
        grid=(M // bm, N // bn, n_k),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=(
            "parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(*operands)
