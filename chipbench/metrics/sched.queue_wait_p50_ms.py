"""Median wait from due time to the admit call that took the request, host clock, open-loop cells."""
from chipbench import measure


def read(rec):
    return measure.pct(measure.queue_wait_ms(rec), 50)
