"""95th percentile of the device time of one whole-prompt prefill program."""
from chipbench import measure


def read(rec):
    return measure.pct([1e3 * p.dur for p in measure.prefill_programs(rec)],
                       95)
