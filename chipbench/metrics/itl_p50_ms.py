"""Median gap between consecutive output tokens, over every gap that closes in the window."""
from chipbench import measure


def read(rec):
    return measure.pct(measure.itl_ms(rec), 50)
