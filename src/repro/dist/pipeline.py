"""Pipeline parallelism: microbatch schedule over a 'pipe' mesh axis.

Stage weights stay resident on their owning device for the whole pass —
only the (mb, D) activation edge crosses the interconnect, via
``ppermute`` ring handoffs (the multi-device version of the paper's
"move the data once, consume it N times" discipline: a stage's weights
are the wide resident operand, the microbatch stream the narrow one).

GPipe schedule: microbatch t enters stage 0 at tick t and exits stage
S-1 at tick t + S - 1; the pipeline drains after n_micro + S - 1 ticks
with S - 1 bubble ticks — the standard fill/drain cost.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as PS


def pipeline_apply(mesh, stage_fn, stage_params, x, *, n_micro: int,
                   axis_name: str = "pipe"):
    """Apply ``stage_fn`` S times in sequence, one stage per device.

    stage_fn: (params_s, (mb, ...)) -> (mb, ...) — one pipeline stage;
    stage_params: pytree whose leaves are stacked (S, ...) per-stage
    weights, sequence-sharded over ``axis_name``;
    x: (n_micro * mb, ...) global input batch.

    Returns stage_fn(w[S-1], ... stage_fn(w[0], x)) — numerically the
    sequential composition, computed with the GPipe microbatch schedule.
    """
    S = mesh.shape[axis_name]
    n_stages = jax.tree.leaves(stage_params)[0].shape[0]
    assert n_stages == S, (n_stages, S)
    n_tokens = x.shape[0]
    assert n_tokens % n_micro == 0, (n_tokens, n_micro)
    mb = n_tokens // n_micro
    perm = [(i, (i + 1) % S) for i in range(S)]

    def run(wp, xg):
        s = jax.lax.axis_index(axis_name)
        w = jax.tree.map(lambda a: a[0], wp)        # this device's stage
        xm = xg.reshape(n_micro, mb, *xg.shape[1:])
        recv = jnp.zeros_like(xm[0])
        outs = []
        for t in range(n_micro + S - 1):
            fed = xm[t] if t < n_micro else jnp.zeros_like(recv)
            inp = jnp.where(s == 0, fed, recv)
            y = stage_fn(w, inp)
            if t >= S - 1:
                # last stage emits microbatch t - (S - 1) this tick
                outs.append(jnp.where(s == S - 1, y, jnp.zeros_like(y)))
            recv = jax.lax.ppermute(y, axis_name, perm)
        out = jax.lax.psum(jnp.stack(outs), axis_name)
        return out.reshape(n_tokens, *xg.shape[1:])

    fn = jax.shard_map(run, mesh=mesh,
                       in_specs=(PS(axis_name), PS()), out_specs=PS())
    return fn(stage_params, x)
