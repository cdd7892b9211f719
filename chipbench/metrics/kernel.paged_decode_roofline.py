"""Paged flash-decode kernel: least time for the live KV it must read (or its operations) over its device time."""
from chipbench import measure


def read(rec):
    return measure.kernel_roofline(rec, measure.PAGED_DECODE_KERNEL,
                                   rec.arch.paged_decode_flops,
                                   rec.arch.paged_decode_bytes)
