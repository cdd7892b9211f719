"""Minimal parameter-definition system (no flax available / needed).

A module is a function pair:
  ``spec(cfg, ...) -> dict[name -> ParamDef | nested dict]``
  ``apply(params, inputs, ...) -> outputs``

``ParamDef`` carries shape, dtype, *logical axes* and an init function.
Logical axes are resolved to mesh ``PartitionSpec`` via a rules table, the
same idea as flax.linen.partitioning but ~100 lines.  This keeps the
multi-pod dry-run allocation-free: ``abstract_params`` gives
ShapeDtypeStructs, ``param_pspecs`` gives in_shardings, and only real
training materializes arrays.
"""
from __future__ import annotations

import functools
import math
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as PS


class ParamDef:
    __slots__ = ("shape", "dtype", "axes", "init")

    def __init__(self, shape, dtype, axes, init: Optional[Callable] = None):
        assert len(axes) == len(shape), (shape, axes)
        self.shape = tuple(int(s) for s in shape)
        self.dtype = dtype
        self.axes = tuple(axes)
        self.init = init if init is not None else fan_in_init

    def __repr__(self):
        return f"ParamDef({self.shape}, {self.dtype}, {self.axes})"


# ---------------- initializers ----------------

def fan_in_init(key, shape, dtype, n_in: int = 1, n_out: int = 1):
    """Normal(0, 1/fan_in).  The fan-in is the product of the ``n_in``
    input axes just before the ``n_out`` trailing output axes; leading
    axes (a stacked layer axis) are batch.  A per-head projection
    ``(d, H, Dh)`` takes ``n_out=2``, its output ``(H, Dh, d)``
    ``n_in=2``."""
    end = len(shape) - n_out
    fan_in = (math.prod(shape[max(end - n_in, 0):end]) if len(shape) >= 2
              else shape[-1])
    scale = 1.0 / math.sqrt(max(fan_in, 1))
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


# per-head projections: (d, H, Dh) in, (H, Dh, d) out
head_proj_init = functools.partial(fan_in_init, n_out=2)
head_out_init = functools.partial(fan_in_init, n_in=2)


def embed_init(key, shape, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * 0.02).astype(dtype)


def zeros_init(key, shape, dtype):
    return jnp.zeros(shape, dtype)


def ones_init(key, shape, dtype):
    return jnp.ones(shape, dtype)


def const_init(v):
    def f(key, shape, dtype):
        return jnp.full(shape, v, dtype)
    return f


# ---------------- tree utilities ----------------

def _is_def(x) -> bool:
    return isinstance(x, ParamDef)


def init_params(spec: Dict[str, Any], key) -> Dict[str, Any]:
    """Materialize a spec tree into real arrays (deterministic in key)."""
    leaves, treedef = jax.tree.flatten(spec, is_leaf=_is_def)
    keys = jax.random.split(key, len(leaves))
    vals = [d.init(k, d.shape, d.dtype) for d, k in zip(leaves, keys)]
    return jax.tree.unflatten(treedef, vals)


def abstract_params(spec: Dict[str, Any]):
    """ShapeDtypeStructs standing in for params — no allocation."""
    return jax.tree.map(
        lambda d: jax.ShapeDtypeStruct(d.shape, d.dtype), spec, is_leaf=_is_def
    )


def param_pspecs(spec: Dict[str, Any], rules: Dict[str, Any]):
    """Resolve logical axes -> PartitionSpec using a rules dict.

    rules maps logical axis name -> mesh axis (str | tuple | None).
    Unknown axes default to None (replicated).
    """
    def resolve(d: ParamDef):
        out = []
        used = set()
        for ax, size in zip(d.axes, d.shape):
            mesh_ax = rules.get(ax)
            flat = (mesh_ax if isinstance(mesh_ax, tuple)
                    else ((mesh_ax,) if mesh_ax is not None else ()))
            # each mesh axis may appear at most once per spec
            if mesh_ax is None or any(a in used for a in flat):
                out.append(None)
                continue
            used.update(flat)
            out.append(mesh_ax)
        return PS(*out)

    return jax.tree.map(resolve, spec, is_leaf=_is_def)


def stack_specs(spec: Dict[str, Any], n: int, axis_name: str = "layers"):
    """Stack a per-layer spec n times along a leading axis (for scan)."""
    def stack(d: ParamDef):
        return ParamDef((n, *d.shape), d.dtype, (axis_name, *d.axes), d.init)

    return jax.tree.map(stack, spec, is_leaf=_is_def)


def count_params(spec: Dict[str, Any]) -> int:
    leaves = jax.tree.leaves(spec, is_leaf=_is_def)
    return sum(int(jnp.prod(jnp.array(d.shape))) for d in leaves)
