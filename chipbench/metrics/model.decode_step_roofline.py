"""Least time for a decode step (weights, live KV, operations) over its program's device time."""
from chipbench import measure


def read(rec):
    return measure.decode_step_roofline(rec)
