"""DecodeEngine: the explicit-mesh serving surface.

One object owns everything serving needs — the device mesh, the
TP-sharded parameters, the decode-cache PartitionSpecs, and the jitted
prefill/decode step functions — and threads the mesh *explicitly*
through ``lm.prefill`` / ``lm.decode_step`` / ``dist.decode``.  Nothing
on the decode hot path consults the ambient ``with mesh:`` context
(that lookup survives only as a deprecated fallback in
``common.hints``).

Quickstart::

    from repro.configs import get_config, reduced
    from repro.engine import DecodeEngine, EngineConfig

    cfg = reduced(get_config("qwen1.5-0.5b"))
    eng = DecodeEngine(cfg, EngineConfig(batch=4, max_len=48,
                                         mesh_shape=(1, 1)))
    tokens, stats = eng.generate({"tokens": prompt_tokens}, gen=16)

Migration from the pre-engine API: where you wrote
``steps.build_decode(cfg, mesh)`` + hand-rolled ``device_put`` of
params/cache against ``dist.sharding`` pspecs inside ``with mesh:``,
construct a ``DecodeEngine`` instead — it builds the same step
functions and layouts, and the ``with mesh:`` context is no longer
needed because the mesh rides the call chain.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.dist import sharding as SH
from repro.engine import paged_cache
from repro.engine.cache import pad_cache_from_prefill
from repro.launch import steps
from repro.launch.mesh import make_local_mesh
from repro.models import lm


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Serving-shape knobs (everything model-side lives in ModelConfig).

    ``decode_shard`` / ``kernel_impl`` default to None = inherit the
    ModelConfig's setting — a cfg pinned to 'pallas'/'seq' is honored
    unless the EngineConfig overrides it explicitly.

    ``paged=True`` replaces the dense ``(batch, max_len)`` decode cache
    with a paged one (``engine.paged_cache``): a shared pool of
    ``n_pages`` pages of ``page_size`` positions plus per-slot block
    tables, so ``batch`` counts *slots* and ``max_len`` bounds any one
    request (it no longer multiplies into every slot's footprint).
    ``n_pages=None`` sizes the pool for a full dense-equivalent batch
    (batch * ceil(max_len / page_size)); continuous batching
    (``engine.scheduler``) typically runs with a smaller pool.

    ``kv_dtype='int8'`` (paged only) stores the page pools as
    symmetric int8 with fp32 per-page scale sidecars — ~2x fewer HBM
    bytes streamed per decoded token than bf16 pools, dequantized
    inside the flash-decode kernels.

    ``prefix_cache=True`` (paged, dense/moe families) turns on the
    prefix-sharing radix cache in ``engine.scheduler``: admission
    matches the longest cached whole-page prompt prefix, aliases those
    refcounted pages into the slot's block table, and prefills only
    the suffix (``engine.prefix_cache``).

    ``chunked_prefill=True`` (paged, dense/moe families) replaces
    batch-1 whole-prompt admission with chunked prefill inside the
    shared decode step: the scheduler grants a prompt all its pages up
    front and feeds it through the unified mixed step
    (``steps.build_mixed_step``) ``chunk_tokens`` tokens at a time,
    packed next to the decoding slots under a token budget — one long
    prompt no longer stalls decode.  ``chunk_tokens`` must be a
    multiple of ``page_size`` so every non-final chunk ends
    page-aligned (the next chunk's resident prefix is then whole
    pages, exactly the suffix-prefill contract)."""
    batch: int = 1
    max_len: int = 128              # prompt + generation budget
    mesh_shape: Tuple[int, int] = (1, 1)      # (data, model)
    decode_shard: Optional[str] = None   # 'none' | 'seq' (dist.decode)
    kernel_impl: Optional[str] = None    # 'xla' | 'pallas' | 'auto'
    param_strategy: str = "serve"   # dist.sharding param strategy
    paged: bool = False             # paged KV cache + block tables
    page_size: int = 16             # positions per page (paged=True)
    n_pages: Optional[int] = None   # pool size; None = dense-equivalent
    kv_dtype: str = "bf16"          # 'bf16' (model dtype) | 'int8'
    prefix_cache: bool = False      # radix prompt-prefix sharing
    chunked_prefill: bool = False   # mixed prefill/decode steps
    chunk_tokens: int = 32          # prefill tokens per mixed step

    def replace(self, **kw) -> "EngineConfig":
        return dataclasses.replace(self, **kw)


class DecodeEngine:
    """Owns mesh + sharded params + cache pspecs + jitted steps.

    ``params`` may be a ready parameter tree (it is re-laid-out onto
    the engine's mesh) or None to initialize fresh from ``seed``.
    ``mesh`` may be passed explicitly (e.g. a production mesh); by
    default it is built from ``ecfg.mesh_shape`` over local devices.
    """

    def __init__(self, cfg, ecfg: EngineConfig, params=None, mesh=None,
                 seed: int = 0):
        # None in the EngineConfig = inherit the ModelConfig's knob
        ecfg = ecfg.replace(
            kernel_impl=(ecfg.kernel_impl if ecfg.kernel_impl is not None
                         else cfg.kernel_impl),
            decode_shard=(ecfg.decode_shard
                          if ecfg.decode_shard is not None
                          else cfg.decode_shard))
        cfg = cfg.replace(kernel_impl=ecfg.kernel_impl,
                          decode_shard=ecfg.decode_shard)
        self.cfg = cfg
        self.ecfg = ecfg
        self.mesh = mesh if mesh is not None else make_local_mesh(
            *ecfg.mesh_shape)
        if ecfg.kv_dtype not in ("bf16", "int8"):
            raise ValueError(f"EngineConfig.kv_dtype must be 'bf16' or "
                             f"'int8', got {ecfg.kv_dtype!r}")
        if ecfg.kv_dtype == "int8" and not ecfg.paged:
            raise ValueError(
                "kv_dtype='int8' requires paged=True: the dense decode "
                "cache appends in place every step and a growing "
                "per-sequence scale would re-quantize the whole slab "
                "per token — per-page scales make the rewrite O(page)")
        if ecfg.prefix_cache:
            if not ecfg.paged:
                raise ValueError(
                    "prefix_cache=True needs paged=True: prefix "
                    "sharing aliases physical pages through block "
                    "tables")
            if cfg.family not in ("dense", "moe"):
                raise ValueError(
                    f"prefix_cache=True supports the token-only "
                    f"families ('dense', 'moe'); family "
                    f"{cfg.family!r} prepends frontend positions a "
                    "token-keyed prefix index cannot match")
        if ecfg.chunked_prefill:
            if not ecfg.paged:
                raise ValueError(
                    "chunked_prefill=True needs paged=True: chunks "
                    "land in granted pages through the block table")
            if cfg.family not in ("dense", "moe"):
                raise ValueError(
                    f"chunked_prefill=True supports the token-only "
                    f"families ('dense', 'moe'); family "
                    f"{cfg.family!r} prepends frontend positions the "
                    "chunked (suffix-composed) prefill cannot offset")
            if ecfg.chunk_tokens < 1 or \
                    ecfg.chunk_tokens % ecfg.page_size:
                raise ValueError(
                    f"chunk_tokens={ecfg.chunk_tokens} must be a "
                    f"positive multiple of page_size="
                    f"{ecfg.page_size}: every non-final chunk must "
                    "end page-aligned so the next chunk's resident "
                    "prefix is whole pages")
        if ecfg.paged:
            paged_cache.check_family(cfg)
            if ecfg.kv_dtype == "int8" and cfg.family == "audio":
                raise ValueError(
                    "kv_dtype='int8' is unsupported for the audio "
                    "family (slot-dense cross cache stays model-dtype)")
            self.page_size = ecfg.page_size
            self.max_pages = paged_cache.max_pages(ecfg.max_len,
                                                   ecfg.page_size)
            self.n_pages = (ecfg.n_pages if ecfg.n_pages is not None
                            else ecfg.batch * self.max_pages)
        if ecfg.decode_shard == "seq":
            msize = self.mesh.shape.get("model", 1)
            if ecfg.paged:
                if self.n_pages % msize:
                    raise ValueError(
                        f"decode_shard='seq' needs n_pages="
                        f"{self.n_pages} divisible by the model axis "
                        f"({msize})")
            elif ecfg.max_len % msize:
                raise ValueError(
                    f"decode_shard='seq' needs max_len={ecfg.max_len} "
                    f"divisible by the model axis ({msize})")

        self.param_pspecs = SH.param_pspecs(cfg, self.mesh,
                                            ecfg.param_strategy)
        if params is None:
            params = lm.init(cfg, jax.random.PRNGKey(seed))
        self.params = jax.device_put(
            params, SH.to_shardings(self.mesh, self.param_pspecs))

        if ecfg.paged:
            self.cache_pspecs = SH.paged_cache_pspecs(
                cfg, self.mesh, ecfg.batch,
                seq_shard=(ecfg.decode_shard == "seq"),
                n_pages=self.n_pages,
                quantized=(ecfg.kv_dtype == "int8"))
        else:
            self.cache_pspecs = SH.cache_pspecs(
                cfg, self.mesh, ecfg.batch,
                seq_shard=(ecfg.decode_shard == "seq"))
        self.prefill_fn = jax.jit(steps.build_prefill(cfg, mesh=self.mesh))
        self.decode_fn = jax.jit(steps.build_decode(cfg, self.mesh))
        # suffix-only prefill for prefix-cache hits: built for every
        # paged token-only engine (the jit wrapper traces nothing until
        # called), so a Scheduler can enable the cache per-stream even
        # when the EngineConfig default is off
        self.suffix_prefill_fn = (
            jax.jit(steps.build_suffix_prefill(cfg, mesh=self.mesh))
            if ecfg.paged and cfg.family in ("dense", "moe") else None)
        # unified mixed prefill/decode step: built for every paged
        # token-only engine (like suffix_prefill_fn, the jit wrapper
        # traces nothing until called), so a Scheduler can turn
        # chunking on per-stream even when the EngineConfig default is
        # off
        self.mixed_fn = (
            jax.jit(steps.build_mixed_step(cfg, mesh=self.mesh))
            if ecfg.paged and cfg.family in ("dense", "moe") else None)
        self._enc_len = 0           # audio: encoder positions at prefill

    # ------------------------------------------------------------------
    # steps
    # ------------------------------------------------------------------

    def prefill(self, batch: Dict[str, Any]):
        """Prefill ``batch['tokens']`` (B, P) [+ frontend_emb] and build
        the fixed-size, mesh-laid-out decode cache.

        Returns (last-token logits (B, vocab_padded) fp32, cache)."""
        B, P = batch["tokens"].shape
        if B != self.ecfg.batch:
            raise ValueError(f"batch {B} != engine batch {self.ecfg.batch}")
        # encoder-decoder: the cross-attention cache is sized by the
        # ENCODER sequence (frontend_emb), which need not equal the
        # decoder prompt length
        enc_len = (batch["frontend_emb"].shape[1]
                   if self.cfg.is_encdec and "frontend_emb" in batch
                   else P)
        self._enc_len = enc_len
        logits, caches = self.prefill_fn(self.params, batch)
        if self.ecfg.paged:
            cache = paged_cache.init_paged_cache(
                self.cfg, self.n_pages, self.page_size, B,
                enc_len=enc_len, kv_dtype=self.ecfg.kv_dtype,
                shardings=SH.to_shardings(self.mesh, self.cache_pspecs))
            cache = paged_cache.write_prefill(
                self.cfg, cache, caches, self.default_block_table())
        else:
            cache = pad_cache_from_prefill(self.cfg, caches, B,
                                           self.ecfg.max_len,
                                           enc_len=enc_len)
        cache = jax.device_put(
            cache, SH.to_shardings(self.mesh, self.cache_pspecs))
        return logits, cache

    def init_paged_cache(self, enc_len: Optional[int] = None):
        """Zeroed page pools made in their layout on the engine mesh
        (each device makes only its shard) — the starting cache for
        continuous batching (``engine.scheduler`` fills it per admitted
        request).  ``enc_len`` budgets the audio cross cache (default:
        the engine max_len)."""
        if not self.ecfg.paged:
            raise ValueError("init_paged_cache() needs paged=True")
        return paged_cache.init_paged_cache(
            self.cfg, self.n_pages, self.page_size, self.ecfg.batch,
            enc_len=(enc_len if enc_len is not None
                     else self.ecfg.max_len),
            kv_dtype=self.ecfg.kv_dtype,
            shardings=SH.to_shardings(self.mesh, self.cache_pspecs))

    def default_block_table(self) -> jax.Array:
        """Whole-batch identity block table: slot b owns pages
        [b * max_pages, (b+1) * max_pages) — the dense-equivalent
        layout ``generate`` uses.  Continuous batching
        (``engine.scheduler``) builds its own tables from the page
        allocator instead."""
        if not self.ecfg.paged:
            raise ValueError("default_block_table() needs paged=True")
        B, J = self.ecfg.batch, self.max_pages
        if self.n_pages < B * J:
            raise ValueError(
                f"whole-batch paged prefill needs n_pages >= "
                f"batch*max_pages = {B * J}, got {self.n_pages}; "
                "drive an oversubscribed pool through "
                "engine.scheduler.Scheduler instead")
        return (jnp.arange(B, dtype=jnp.int32)[:, None] * J
                + jnp.arange(J, dtype=jnp.int32)[None, :])

    def decode_step(self, token, cur_len, cache, block_table=None):
        """One token for the whole batch: token (B,) int32.

        Dense cache: ``cur_len`` is a scalar (every slot at the same
        position).  Paged (ecfg.paged): ``cur_len`` is a per-slot (B,)
        int32 vector and ``block_table`` (B, W) int32 is required,
        with W <= max_pages covering every slot's live pages — the
        scheduler passes the power-of-two width bucket of the longest
        active slot (``paged_cache.bucket_table_width``), so a step
        stages only live pages; the jitted step compiles once per
        distinct W (at most log2(max_pages)+1 shapes).  Returns
        (logits (B, vocab_padded) fp32, new cache).
        """
        if self.ecfg.paged:
            if block_table is None:
                raise ValueError(
                    "paged decode_step needs the block_table operand "
                    "(engine.default_block_table() for whole-batch "
                    "generation)")
            lens = jnp.asarray(cur_len, jnp.int32)
            if lens.ndim == 0:
                lens = jnp.full((self.ecfg.batch,), lens, jnp.int32)
            dbatch = {"token": token, "cur_len": lens,
                      "block_table": jnp.asarray(block_table, jnp.int32),
                      "cache": cache}
            if self.cfg.family == "audio":
                dbatch["enc_lens"] = jnp.full(
                    (self.ecfg.batch,), self._enc_len, jnp.int32)
            return self.decode_fn(self.params, dbatch)
        return self.decode_fn(self.params, {
            "token": token, "cur_len": jnp.int32(cur_len),
            "cache": cache})

    def prefill_len(self, batch) -> int:
        """Positions the prefill occupied (vlm prepends frontend tokens)."""
        P = batch["tokens"].shape[1]
        if self.cfg.family == "vlm":
            P += self.cfg.frontend_tokens
        return P

    # ------------------------------------------------------------------
    # generation loop
    # ------------------------------------------------------------------

    def generate(self, batch: Dict[str, Any], gen: int,
                 temperature: float = 0.0, seed: int = 0,
                 check_finite: bool = False,
                 ) -> Tuple[jax.Array, Dict[str, float]]:
        """Prefill + ``gen`` greedy (or sampled) decode steps.

        ``check_finite=True`` validates every step's logits and raises
        ``engine.faults.NonFiniteLogitsError`` on NaN/inf instead of
        silently emitting a corrupt stream (it costs one host sync per
        step; the scheduler's batched guard is the serving-path
        equivalent).

        Returns (tokens (B, gen) int32, stats with prefill/decode wall
        times and tok/s)."""
        prefill_tokens = self.prefill_len(batch)
        if prefill_tokens + gen - 1 > self.ecfg.max_len:
            raise ValueError(
                f"prompt {prefill_tokens} + gen {gen} exceeds "
                f"max_len {self.ecfg.max_len}")
        B = batch["tokens"].shape[0]

        t0 = time.time()
        logits, cache = self.prefill(batch)
        logits.block_until_ready()
        t_prefill = time.time() - t0

        base_key = jax.random.PRNGKey(seed)

        def pick(logits, i):
            if temperature > 0:
                # fold_in, NOT PRNGKey(seed + i): additive seeds make
                # step i of seed s and step i-1 of seed s+1 sample with
                # the IDENTICAL key, so adjacent-seed requests in a
                # fleet replay correlated token streams.  fold_in keeps
                # (seed, args) -> tokens deterministic while giving
                # every (seed, step) pair an independent key.
                key = jax.random.fold_in(base_key, i)
                return jax.random.categorical(
                    key, logits / temperature, -1).astype(jnp.int32)
            return jnp.argmax(logits, -1).astype(jnp.int32)

        # first token is always the argmax of the prefill logits (the
        # pre-engine serve CLI's convention)
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        block_table = (self.default_block_table() if self.ecfg.paged
                       else None)
        out = [tok]
        t0 = time.time()
        for i in range(gen - 1):
            logits, cache = self.decode_step(
                tok, prefill_tokens + i, cache, block_table=block_table)
            if check_finite and not bool(jnp.all(jnp.isfinite(logits))):
                from repro.engine.faults import NonFiniteLogitsError
                raise NonFiniteLogitsError(
                    f"non-finite logits at decode step {i}")
            tok = pick(logits, i)
            out.append(tok)
        jax.block_until_ready(tok)
        t_decode = time.time() - t0
        stats = {
            "t_prefill_s": t_prefill,
            "t_decode_s": t_decode,
            "prefill_tok_s": B * prefill_tokens / max(t_prefill, 1e-9),
            "decode_tok_s": B * max(gen - 1, 0) / max(t_decode, 1e-9),
        }
        return jnp.stack(out, 1), stats
