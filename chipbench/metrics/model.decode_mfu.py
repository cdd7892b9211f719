"""Model operations of the decode steps over their programs' device time, as a share of the bf16 peak."""
from chipbench import measure


def read(rec):
    return measure.decode_mfu(rec)
