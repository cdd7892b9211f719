"""Depthwise conv kernel — the paper's headline low-reuse case (§3.4).

MobileNet-style depthwise convolutions have K^2 reuse per activation
and no cross-channel reduction: systolic arrays idle (no GEMM K-dim to
fold), GPUs stall on bandwidth.  The VWR discipline keeps the VPU fed:
one wide HBM->VMEM stage per halo'd row block, K^2 shifted elementwise
multiply-accumulates per staged block (VPU, not MXU — there is no
matmul here, exactly why SAs collapse).

x: (N, H, W, C), w: (KH, KW, C), stride 1, VALID.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _dw_kernel(x_ref, w_ref, o_ref, *, KH, KW, bh, W_out):
    x = x_ref[0]                                   # (bh+KH-1, W, C)
    C = x.shape[-1]
    acc = jnp.zeros((bh, W_out, C), jnp.float32)
    for kj in range(KH):
        for ki in range(KW):
            xs = x[kj: kj + bh, ki: ki + W_out, :]
            acc += xs.astype(jnp.float32) * w_ref[kj, ki][None, None, :]
    o_ref[0] = acc.astype(o_ref.dtype)


def vwr_depthwise_p(x: jax.Array, w: jax.Array, *, bh: int = 8,
                    interpret: bool = False) -> jax.Array:
    """x: (N, H, W, C) with (H-KH+1) % bh == 0; w: (KH, KW, C)."""
    N, H, W, C = x.shape
    KH, KW, C2 = w.shape
    assert C == C2
    H_out, W_out = H - KH + 1, W - KW + 1
    assert H_out % bh == 0
    kernel = functools.partial(_dw_kernel, KH=KH, KW=KW, bh=bh,
                               W_out=W_out)
    params = pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel"))
    return pl.pallas_call(
        kernel,
        grid=(N, H_out // bh),
        in_specs=[
            # overlapping (halo'd) row windows: the row index map
            # returns ELEMENT offsets
            pl.BlockSpec((1, pl.Element(bh + KH - 1), W, C),
                         lambda n, r: (n, r * bh, 0, 0)),
            pl.BlockSpec((KH, KW, C), lambda n, r: (0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, bh, W_out, C), lambda n, r: (n, r, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((N, H_out, W_out, C), x.dtype),
        compiler_params=params,
        interpret=interpret,
    )(x, w)
