"""Paged flash-decode kernel: least time for the live KV it must read (or its operations) over its device time."""
from chipbench import costs, measure


def read(rec):
    return measure.kernel_roofline(rec, measure.PAGED_DECODE_KERNEL,
                                   costs.paged_decode_flops,
                                   costs.paged_decode_bytes)
