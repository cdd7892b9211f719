"""Sharding hint helpers.

Every helper takes the mesh as an **explicit** argument; the
ambient-mesh lookup (the ``with mesh:`` context) survives only as a
deprecated fallback for callers that predate the explicit-mesh API
(``DecodeEngine`` / ``lm.decode_step(..., mesh=...)`` thread the mesh
through instead).  The ``with_sharding_constraint`` wrappers no-op only
when the resolved mesh lacks the referenced axes (single-device
tests); any other error propagates.
"""
from __future__ import annotations

import math
import warnings

import jax
from jax.sharding import NamedSharding, PartitionSpec as PS


def ambient_mesh():
    """The physical mesh of the enclosing ``with mesh:`` context, or
    None outside one.  The single place that touches the private
    jax._src thread-resources API.

    DEPRECATED as an implicit dependency: new code should thread the
    mesh explicitly (see ``resolve_mesh``); this lookup remains only so
    pre-engine call sites keep working."""
    from jax._src import mesh as mesh_lib
    cur = mesh_lib.thread_resources.env.physical_mesh
    return None if cur.empty else cur


_AMBIENT_WARNED = False


def resolve_mesh(mesh, context: str = ""):
    """Explicit mesh when given; else the deprecated ambient fallback
    (one DeprecationWarning per process when it actually resolves)."""
    if mesh is not None:
        return mesh
    cur = ambient_mesh()
    if cur is not None:
        global _AMBIENT_WARNED
        if not _AMBIENT_WARNED:
            _AMBIENT_WARNED = True
            warnings.warn(
                f"{context or 'repro.common.hints'}: falling back to the "
                "ambient `with mesh:` context is deprecated — pass the "
                "mesh explicitly (lm.decode_step/lm.prefill/dist.decode "
                "take mesh=; engine.DecodeEngine owns one).",
                DeprecationWarning, stacklevel=3)
    return cur


def _constrain(x, spec, cur):
    """A bare PartitionSpec only resolves inside a ``with mesh:``
    context; binding the resolved mesh into a NamedSharding works on
    the explicit-mesh path too."""
    return jax.lax.with_sharding_constraint(x, NamedSharding(cur, spec))


def shard_hint(x, spec, mesh=None):
    """with_sharding_constraint iff the resolved mesh has every axis the
    spec references."""
    cur = mesh if mesh is not None else ambient_mesh()
    names = set(cur.axis_names) if cur is not None else set()
    need = {a for e in spec for a in
            ((e,) if isinstance(e, str) else (e or ()))}
    if need and need.issubset(names):
        return _constrain(x, spec, cur)
    return x


def shard_batch(x, ndim=None, extra=None, mesh=None):
    """Constrain dim 0 to the data axes present in the resolved mesh
    (('pod','data') on the multi-pod mesh, ('data',) single-pod) and
    leave other dims free.  No-op without a mesh, without data axes, or
    when dim 0 does not divide over them."""
    cur = mesh if mesh is not None else ambient_mesh()
    if cur is None:
        return x
    dp = tuple(a for a in ("pod", "data") if a in cur.axis_names)
    if not dp or x.shape[0] % math.prod(cur.shape[a] for a in dp):
        return x
    n = ndim or x.ndim
    spec = PS(dp if len(dp) > 1 else dp[0], *([None] * (n - 1)))
    return _constrain(x, spec, cur)
