"""Operations of the window's whole-prompt prefills over their programs' device time, as a share of the bf16 peak."""
from chipbench import measure


def read(rec):
    return measure.prefill_mfu(rec)
