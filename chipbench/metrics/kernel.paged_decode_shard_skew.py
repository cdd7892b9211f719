"""Paged flash-decode kernel: its summed device time in the window's decode programs on the slowest chip over its mean over the chips (1.0 is balanced)."""
from chipbench import measure


def read(rec):
    if rec.trace is None or len(rec.trace.chips) < 2:
        return None
    per_chip = []
    for chip in range(len(rec.trace.chips)):
        progs = rec.trace.modules(measure.DECODE_PROGRAM, chip)
        calls = rec.trace.ops_in(measure.PAGED_DECODE_KERNEL, progs, chip)
        per_chip.append(sum(c.dur for c in calls))
    mean = sum(per_chip) / len(per_chip)
    return max(per_chip) / mean if mean > 0 else None
