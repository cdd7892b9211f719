"""90th percentile of time to first token, timed from each request's due time, over every request due in the window."""
from chipbench import measure


def read(rec):
    return measure.pct(measure.ttft_ms(rec), 90)
