"""Operations and bytes that the algorithm needs, computed from shapes.

``m`` is a configuration file's ``model`` section (a dense decoder).
Decode counts only the slots that decode, and the positions they
attend (``ctx``: the sum over those slots of cached positions + 1).
Bytes are HBM traffic at the served types: bf16 weights and KV pages
(2 bytes an element), fp32 logits.  The peaks these are divided by come
from ``peaks.json``.
"""
from __future__ import annotations

BF16 = 2
F32 = 4


def _vp(m: dict) -> int:
    return -(-m["vocab"] // 128) * 128


def layer_matmul_params(m: dict) -> int:
    d, H, KV, Dh, F = (m["d_model"], m["n_heads"], m["n_kv_heads"],
                       m["d_head"], m["d_ff"])
    return d * (H + 2 * KV) * Dh + H * Dh * d + 3 * d * F


def head_params(m: dict) -> int:
    return m["d_model"] * m["vocab"]


def weight_bytes(m: dict) -> int:
    """Weights one decode step reads: every layer, the norms and the
    head (the whole embedding table when it is the tied head)."""
    L, d = m["n_layers"], m["d_model"]
    per_layer = layer_matmul_params(m) + 2 * d
    if m.get("qkv_bias"):
        per_layer += (m["n_heads"] + 2 * m["n_kv_heads"]) * m["d_head"]
    return BF16 * (L * per_layer + d + _vp(m) * d)


def kv_bytes_per_position(m: dict) -> int:
    return BF16 * 2 * m["n_layers"] * m["n_kv_heads"] * m["d_head"]


def attention_flops(m: dict, ctx: int) -> float:
    """Scores and weighted values over ``ctx`` attended positions."""
    return 4.0 * m["n_layers"] * m["n_heads"] * m["d_head"] * ctx


def decode_flops(m: dict, n: int, ctx: int) -> float:
    matmul = m["n_layers"] * layer_matmul_params(m) + head_params(m)
    return 2.0 * n * matmul + attention_flops(m, ctx)


def decode_bytes(m: dict, n: int, ctx: int) -> float:
    kv = kv_bytes_per_position(m)
    return (weight_bytes(m) + kv * ctx + kv * n
            + F32 * n * _vp(m))


def paged_decode_flops(m: dict, n: int, ctx: int) -> float:
    return attention_flops(m, ctx)


def paged_decode_bytes(m: dict, n: int, ctx: int) -> float:
    """KV pages read, plus queries in and fp32 partials out."""
    L, H, Dh = m["n_layers"], m["n_heads"], m["d_head"]
    return (kv_bytes_per_position(m) * ctx
            + L * n * H * (BF16 * Dh + F32 * (Dh + 2)))


def prefill_flops(m: dict, P: int) -> float:
    """One prompt of ``P`` tokens: every projection, causal attention,
    and the head on the last token."""
    L, H, Dh = m["n_layers"], m["n_heads"], m["d_head"]
    return (2.0 * P * L * layer_matmul_params(m)
            + 2.0 * L * H * Dh * P * (P + 1) + 2.0 * head_params(m))


def roofline_s(flops: float, nbytes: float, peaks: dict) -> float:
    """Least time the chip could take: the larger of the two bounds."""
    return max(flops / peaks["bf16_flops_per_s"],
               nbytes / peaks["hbm_bytes_per_s"])
