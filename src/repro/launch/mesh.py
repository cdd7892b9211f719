"""Mesh construction (never touches jax device state at import time).

``make_mesh`` is the one constructor every mesh in the repo goes
through.  It states its axis types: every axis is ``AxisType.Auto``, so
sharding is propagated by the compiler (GSPMD) and ``shard_map`` bodies
run in manual mode — the semantics the sharding rules in
``repro.dist`` are written for.  (``jax.make_mesh`` on its own defaults
to ``Explicit`` axes, under which gathers over a sharded operand raise
``ShardingTypeError``.)
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, *, devices=None):
    """Mesh of ``shape`` over ``axes`` with Auto axis types.  ``devices``
    defaults to the first ``prod(shape)`` local devices."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_local_mesh(data: int = 1, model: int = 1, *, devices=None):
    """(data, model) mesh over local devices (or over ``devices``).
    Raises when the mesh needs more devices than exist."""
    n = len(devices) if devices is not None else jax.device_count()
    if data * model > n:
        raise ValueError(
            f"mesh (data={data}, model={model}) needs {data * model} "
            f"devices, {n} available")
    return make_mesh((data, model), ("data", "model"), devices=devices)
