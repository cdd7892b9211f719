"""95th percentile of the gaps between consecutive output tokens that close in the window."""
from chipbench import measure


def read(rec):
    return measure.pct(measure.itl_ms(rec), 95)
