"""The one sharding surface: logical axes -> mesh PartitionSpecs.

Every layer that needs a sharding — ``launch.train`` (init + step),
``launch.dryrun`` (in_shardings for every (arch x shape x mesh) cell),
``launch.serve`` (sharded decode), and the optimizer's ZeRO-1 pass —
goes through this module, so the logical-axis vocabulary declared by
``ParamDef`` specs (see models/layers.py docstring) resolves to mesh
axes in exactly one place.

Strategies:
  'fsdp_tp'  TP over 'model' (vocab / heads / kv / ffn / expert-ffn /
             ssm-inner dims) + the largest remaining param dim
             ('embed') sharded over the data axes (FSDP).  Default.
  'ddp'      params replicated; optimizer state ZeRO-1-shards them
             (``optim.adamw.zero1_pspecs``).  Right for sub-1B archs.
  'serve'    TP only — decode batches are small, so params stay
             gather-free on the data axes and the batch dim carries
             'data'.

A dim is only assigned a mesh axis when its size divides the axis
(product) size; each mesh axis appears at most once per spec.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional

import jax
from jax.sharding import NamedSharding, PartitionSpec as PS

from repro.common.module import ParamDef

# mesh axes that carry the batch / FSDP dim, in nesting order
DATA_AXES = ("pod", "data")

# logical axes that tensor-parallelize over 'model'
_TP_AXES = ("vocab", "heads", "kv", "ffn", "expert_ff", "inner",
            "inner_all", "q_lora", "kv_lora")


def data_axes(mesh) -> tuple:
    return tuple(a for a in DATA_AXES if a in mesh.axis_names)


def data_size(mesh) -> int:
    return math.prod(mesh.shape[a] for a in data_axes(mesh)) or 1


def model_axis(mesh) -> Optional[str]:
    return "model" if "model" in mesh.axis_names else None


def _dp_entry(mesh):
    """The PartitionSpec entry for the data dims: a single axis name or
    a tuple when the mesh also has a 'pod' axis."""
    dp = data_axes(mesh)
    if not dp:
        return None
    return dp if len(dp) > 1 else dp[0]


def rules(cfg, mesh, strategy: Optional[str] = None) -> Dict[str, Any]:
    """logical axis name -> mesh axis (str | tuple | None)."""
    strategy = strategy or cfg.sharding_strategy
    if strategy == "ddp":
        return {}
    mp = model_axis(mesh)
    table: Dict[str, Any] = {ax: mp for ax in _TP_AXES}
    dp = _dp_entry(mesh)
    # experts spread over EVERY axis, or not at all: full EP gives each
    # device whole experts (weights never move — the layout both train
    # and serve want), and a strict-subset expert sharding buys no
    # memory over full EP while adding resharding noise that top-k
    # routing amplifies discontinuously (a ~1e-6 reassociation flips
    # an expert choice into an O(1) logit change — measured on the
    # (2,4) mesh).  The divisibility check in _resolve falls back to
    # replicated when E doesn't cover the full product.
    flat_dp = dp if isinstance(dp, tuple) else ((dp,) if dp else ())
    full = flat_dp + ((mp,) if mp else ())
    table["experts"] = (full if len(full) > 1
                        else (full[0] if full else None))
    if strategy == "serve":
        return table
    if strategy != "fsdp_tp":
        raise ValueError(f"unknown sharding strategy {strategy!r}")
    table["embed"] = dp
    return table


def _axis_sizes(mesh) -> Dict[str, int]:
    return dict(mesh.shape)


def _resolve(d: ParamDef, table: Dict[str, Any], sizes: Dict[str, int]) -> PS:
    used = set()
    out = []
    for ax, size in zip(d.axes, d.shape):
        mesh_ax = table.get(ax)
        flat = (mesh_ax if isinstance(mesh_ax, tuple)
                else ((mesh_ax,) if mesh_ax is not None else ()))
        n = math.prod(sizes[a] for a in flat) if flat else 1
        if not flat or any(a in used for a in flat) or size % n:
            out.append(None)
            continue
        used.update(flat)
        out.append(mesh_ax)
    return PS(*out)


def param_pspecs(cfg, mesh, strategy: Optional[str] = None):
    """PartitionSpec tree matching ``lm.abstract_init(cfg)``."""
    from repro.models import lm  # local import: dist must not cycle

    table = rules(cfg, mesh, strategy)
    sizes = _axis_sizes(mesh)
    return jax.tree.map(lambda d: _resolve(d, table, sizes),
                        lm.model_spec(cfg),
                        is_leaf=lambda x: isinstance(x, ParamDef))


# ----------------------------------------------------------------------
# batch pspecs
# ----------------------------------------------------------------------

def _batched(mesh, aval_or_ndim, batch: Optional[int] = None) -> PS:
    """dim 0 over the data axes (when divisible), rest replicated."""
    if hasattr(aval_or_ndim, "shape"):
        ndim = len(aval_or_ndim.shape)
        batch = aval_or_ndim.shape[0] if aval_or_ndim.shape else None
    else:
        ndim = aval_or_ndim
    dp = _dp_entry(mesh)
    if ndim == 0 or dp is None or batch is None \
            or batch % data_size(mesh):
        return PS(*([None] * ndim))
    return PS(dp, *([None] * (ndim - 1)))


def train_batch_pspecs(cfg, mesh, batch_specs):
    """PartitionSpec tree for a train/prefill batch dict (abstract
    values from ``launch.steps.batch_specs``): the global batch dim
    shards over the data axes, everything else is replicated."""
    return jax.tree.map(lambda a: _batched(mesh, a), batch_specs)


def cache_pspecs(cfg, mesh, batch: int, *, seq_shard: bool = False):
    """PartitionSpec tree matching ``lm.cache_spec(cfg, batch, T)``,
    branch for branch.

    Default (GSPMD decode): batch over the data axes, the kv-head dim
    of attention caches over 'model' when divisible.  With
    ``seq_shard=True`` the cache *sequence* dim takes 'model' instead —
    the layout ``dist.decode.sharded_flash_decode`` consumes (each
    model shard owns a contiguous slab of the context and never sees
    the rest).  Recurrent states (hybrid/ssm) shard their head dim over
    'model': per-head state never crosses shards during decode.
    """
    from repro.models import lm, ssm as SSM, xlstm as XL  # local import

    mp = model_axis(mesh)
    dp = _dp_entry(mesh)
    bax = (dp if dp is not None and batch % data_size(mesh) == 0
           else None)
    sizes = _axis_sizes(mesh)
    seqax = mp if (seq_shard and mp is not None) else None
    kvax = (mp if (not seq_shard and mp is not None
                   and cfg.n_kv_heads % sizes[mp] == 0) else None)

    def heads_ax(n_heads):
        if mp is None or n_heads % sizes[mp]:
            return None
        return mp

    def kv_cache(lead: int) -> PS:
        # (*lead, B, T, KV, Dh)
        return PS(*([None] * lead), bax, seqax, kvax, None)

    fam = cfg.family
    if fam in ("dense", "vlm"):
        if cfg.mla is not None:
            latent = PS(None, bax, seqax, None)
            return {"ckv": latent, "krope": latent}
        return {"k": kv_cache(1), "v": kv_cache(1)}

    if fam == "moe":
        if cfg.mla is not None:
            latent = PS(None, bax, seqax, None)

            def mla_c():
                return {"ckv": latent, "krope": latent}
            return {"dense": mla_c() if cfg.moe.first_k_dense else None,
                    "moe": mla_c()}

        def gqa_c():
            return {"k": kv_cache(1), "v": kv_cache(1)}
        return {"dense": gqa_c() if cfg.moe.first_k_dense else None,
                "moe": gqa_c()}

    if fam == "hybrid":
        mc = cfg.mamba2
        _, _, tail, _ = lm._hybrid_groups(cfg)
        hax = heads_ax((mc.expand * cfg.d_model) // mc.head_dim)

        def mstate(lead: int):
            # ssm: (*lead, B, H, d_state, head_dim); conv: (*lead, B,
            # d_conv-1, d_xbc)
            return SSM.Mamba2State(
                ssm=PS(*([None] * lead), bax, hax, None, None),
                conv=PS(*([None] * lead), bax, None, None))
        return {
            "mamba_main": mstate(2),
            "mamba_tail": mstate(1) if tail else None,
            "attn_k": kv_cache(1), "attn_v": kv_cache(1),
        }

    if fam == "ssm":
        hax = heads_ax(cfg.n_heads)
        return {
            "mlstm": XL.MLSTMState(
                C=PS(None, None, bax, hax, None, None),
                n=PS(None, None, bax, hax, None),
                m=PS(None, None, bax, hax),
                conv=PS(None, None, bax, None, None)),
            "slstm": XL.SLSTMState(
                c=PS(None, bax, None), n=PS(None, bax, None),
                h=PS(None, bax, None), m=PS(None, bax, None)),
        }

    if fam == "audio":
        return {"self_k": kv_cache(1), "self_v": kv_cache(1),
                "cross_k": kv_cache(1), "cross_v": kv_cache(1)}

    raise ValueError(fam)


def _page_axis(mesh, seq_shard: bool, n_pages: Optional[int]):
    """The mesh axis a paged pool's page dim takes: 'model' when
    sequence-sharded and the pages split evenly over it, else None."""
    mp = model_axis(mesh)
    if (seq_shard and mp is not None
            and (n_pages is None or n_pages % mesh.shape[mp] == 0)):
        return mp
    return None


def page_slabs(mesh, n_pages: int) -> int:
    """How many contiguous slabs a sequence-sharded pool of ``n_pages``
    is split into on ``mesh`` (1 when its page dim is not sharded).
    Slab s holds pages ``[s * n_pages // k, (s + 1) * n_pages // k)``;
    ``page_slab_of`` maps pages to their slab."""
    ax = _page_axis(mesh, True, n_pages)
    return mesh.shape[ax] if ax is not None else 1


def page_slab_of(pages, n_pages: int, slabs: int):
    """The slab of each page in ``pages`` (a numpy array) of a pool
    split into ``slabs`` by ``page_slabs``."""
    return pages // (n_pages // slabs)


def paged_cache_pspecs(cfg, mesh, batch_slots: int, *,
                       seq_shard: bool = False,
                       n_pages: Optional[int] = None,
                       quantized: bool = False):
    """PartitionSpec tree matching ``engine.paged_cache.paged_cache_spec``.

    Pool leaves are ``(L, n_pages, ...)``: with
    ``seq_shard=True`` the *page* dim takes 'model' (each shard owns a
    contiguous slab of the pool — ``dist.decode.
    sharded_paged_flash_decode`` masks foreign pages by count and
    combines the statistics), else the kv-head dim takes 'model' when
    divisible, mirroring the dense layout.  The audio cross cache stays
    slot-dense (batch over data, replicated over 'model': it is
    attended locally per shard in paged mode).  With ``quantized=True``
    the tree grows the int8 pools' fp32 scale-sidecar leaves, sharded
    on the same page (and, for GQA, kv-head) dims as their pools.
    """
    from repro.engine import paged_cache as PC  # local import: no cycle

    PC.check_family(cfg)
    mp = model_axis(mesh)
    dp = _dp_entry(mesh)
    sizes = _axis_sizes(mesh)
    bax = (dp if dp is not None and batch_slots % data_size(mesh) == 0
           else None)
    pageax = _page_axis(mesh, seq_shard, n_pages)
    kvax = (mp if (pageax is None and mp is not None
                   and cfg.n_kv_heads % sizes[mp] == 0) else None)

    def gqa_pool():
        sh = PS(None, pageax, kvax, None, None)   # (L, n_pages, KV, ps, Dh)
        pool = {"k": sh, "v": sh}
        if quantized:
            ssh = PS(None, pageax, kvax)       # (L, n_pages, KV)
            pool["k_scale"] = ssh
            pool["v_scale"] = ssh
        return pool

    def mla_pool():
        latent = PS(None, pageax, None, None)
        pool = {"ckv": latent, "krope": latent}
        if quantized:
            ssh = PS(None, pageax)             # (L, n_pages)
            pool["ckv_scale"] = ssh
            pool["krope_scale"] = ssh
        return pool

    fam = cfg.family
    if fam in ("dense", "vlm"):
        return mla_pool() if cfg.mla is not None else gqa_pool()
    if fam == "moe":
        mk = mla_pool if cfg.mla is not None else gqa_pool
        return {"dense": mk() if cfg.moe.first_k_dense else None,
                "moe": mk()}
    # audio
    pool = gqa_pool()
    cross = PS(None, bax, None, None, None)
    return {"self_k": pool["k"], "self_v": pool["v"],
            "cross_k": cross, "cross_v": cross}


def paged_decode_batch_pspecs(cfg, mesh, global_batch: int, *,
                              seq_shard: bool = False,
                              n_pages: Optional[int] = None,
                              quantized: bool = False):
    """PartitionSpec tree for a paged decode batch
    ({token, cur_len (B,), block_table, cache} [+ enc_lens for
    audio])."""
    out = {
        "token": _batched(mesh, 1, global_batch),
        "cur_len": _batched(mesh, 1, global_batch),
        "block_table": _batched(mesh, 2, global_batch),
        "cache": paged_cache_pspecs(cfg, mesh, global_batch,
                                    seq_shard=seq_shard,
                                    n_pages=n_pages,
                                    quantized=quantized),
    }
    if cfg.family == "audio":
        out["enc_lens"] = _batched(mesh, 1, global_batch)
    return out


def decode_batch_pspecs(cfg, mesh, global_batch: int, *,
                        seq_shard: bool = False):
    """PartitionSpec tree for a decode batch
    ({token, cur_len, cache}, the ``launch.steps.batch_specs`` decode
    layout)."""
    return {
        "token": _batched(mesh, 1, global_batch),
        "cur_len": PS(),
        "cache": cache_pspecs(cfg, mesh, global_batch,
                              seq_shard=seq_shard),
    }


# ----------------------------------------------------------------------
# Pallas kernels in multi-device programs
# ----------------------------------------------------------------------

def multi_device(mesh) -> bool:
    return mesh is not None and mesh.size > 1


def tp_entry(mesh, n: int) -> Optional[str]:
    """'model' when the mesh has a model axis that divides ``n``."""
    mp = model_axis(mesh)
    return mp if mp is not None and n % mesh.shape[mp] == 0 else None


def batch_entry(mesh, batch: int):
    """The data axes for a leading batch dim of size ``batch``, when
    they divide it."""
    dp = _dp_entry(mesh)
    return dp if dp is not None and batch % data_size(mesh) == 0 else None


def per_device(fn, mesh, in_specs, out_specs):
    """``fn`` — a body that calls Pallas kernels — run on each device's
    shard under ``shard_map``: the compiler cannot partition a Mosaic
    kernel, so a multi-device program hands every device its block
    explicitly (tensor-parallel bodies psum over 'model' themselves)."""
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def to_shardings(mesh, tree):
    """PartitionSpec tree -> NamedSharding tree (None leaves pass
    through untouched)."""
    return jax.tree.map(
        lambda ps: NamedSharding(mesh, ps) if isinstance(ps, PS) else ps,
        tree, is_leaf=lambda x: isinstance(x, PS))
