"""Direct (im2col-free) conv2d kernel — the paper's §6.1 dataflow on TPU.

The paper's complaint (§3.3): mapping conv onto GEMM hardware needs
im2col, inflating a 7x7/256^2 conv by x46.  Its fix: a fine-grained
shuffler slides the data instead.  On TPU the same idea is a Pallas
kernel that stages one *halo'd* input row-block in VMEM (the ultra-wide
transaction; `pl.Element` indexing gives the K-1-row halo of §6.2.1's
duplication argument) and accumulates over kernel taps with *static
shifted slices* of that staged block — the VREG-level analogue of the
VFU shuffler's one-lane shifts.  Zero data inflation in HBM: each
input element is read exactly once per row-block.

Fused epilogue: ``bias`` add and ``activation`` (relu/gelu/silu) are
applied to the fp32 accumulator before the single store — a CNN's
conv -> bias -> relu chain costs exactly one HBM round-trip for the
output instead of write + re-read + re-write (the extra elementwise
pass the ProVet CNN demo used to pay).

x: (N, H, W, C), w: (KH, KW, C, F), stride 1, VALID.
Grid: (batch, row-blocks, F-blocks); taps unrolled inside the kernel
(KH*KW MXU calls per staged block — the N-reads-per-wide-transaction
ratio of §4.3.4).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.vwr_matmul import ACTIVATIONS


def _conv_kernel(x_ref, w_ref, *rest, KH, KW, bh, W_out, has_bias,
                 activation):
    o_ref = rest[-1]
    b_ref = rest[0] if has_bias else None
    x = x_ref[0]                                   # (bh+KH-1, W, C)
    C = x.shape[-1]
    bf = w_ref.shape[-1]
    acc = jnp.zeros((bh * W_out, bf), jnp.float32)
    for kj in range(KH):
        for ki in range(KW):
            xs = x[kj: kj + bh, ki: ki + W_out, :]          # lane shift
            acc += jnp.dot(xs.reshape(bh * W_out, C), w_ref[kj, ki],
                           preferred_element_type=jnp.float32)
    if has_bias:
        acc = acc + b_ref[...].astype(jnp.float32)          # (1,bf) bcast
    if activation is not None:
        acc = ACTIVATIONS[activation](acc)
    o_ref[0] = acc.reshape(bh, W_out, bf).astype(o_ref.dtype)


def vwr_conv2d_p(x: jax.Array, w: jax.Array, bias=None, *, bh: int = 8,
                 bf: int = 128, activation: str = None,
                 interpret: bool = False) -> jax.Array:
    """x: (N, H, W, C) with (H-KH+1) % bh == 0; w: (KH, KW, C, F) with
    F % bf == 0 (ops.vwr_conv2d pads).  Optional fused epilogue: bias
    (1, F) and activation name applied on the fp32 accumulator before
    the store.  Returns (N, H', W', F)."""
    N, H, W, C = x.shape
    KH, KW, C2, F = w.shape
    assert C == C2
    H_out, W_out = H - KH + 1, W - KW + 1
    assert H_out % bh == 0 and F % bf == 0, (H_out, bh, F, bf)
    assert activation is None or activation in ACTIVATIONS, activation
    kernel = functools.partial(_conv_kernel, KH=KH, KW=KW, bh=bh,
                               W_out=W_out, has_bias=bias is not None,
                               activation=activation)
    in_specs = [
        # overlapping (halo'd) row windows: the row index map returns
        # ELEMENT offsets
        pl.BlockSpec((1, pl.Element(bh + KH - 1), W, C),
                     lambda n, r, f: (n, r * bh, 0, 0)),
        pl.BlockSpec((KH, KW, C, bf), lambda n, r, f: (0, 0, 0, f)),
    ]
    operands = [x, w]
    if bias is not None:
        assert bias.shape == (1, F), bias.shape
        in_specs.append(pl.BlockSpec((1, bf), lambda n, r, f: (0, f)))
        operands.append(bias)
    params = pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "parallel"))
    return pl.pallas_call(
        kernel,
        grid=(N, H_out // bh, F // bf),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, bh, W_out, bf),
                               lambda n, r, f: (n, r, 0, f)),
        out_shape=jax.ShapeDtypeStruct((N, H_out, W_out, F), x.dtype),
        compiler_params=params,
        interpret=interpret,
    )(*operands)
