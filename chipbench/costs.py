"""What the counts share across architectures: the bytes of the served
types and the roofline.  Each architecture's own operations and bytes
live in its file, ``chipbench/architectures/<name>.py``.
"""
from __future__ import annotations

BF16 = 2
F32 = 4


def roofline_s(flops: float, nbytes: float, peaks: dict) -> float:
    """Least time the chip could take: the larger of the two bounds."""
    return max(flops / peaks["bf16_flops_per_s"],
               nbytes / peaks["hbm_bytes_per_s"])
