"""Mean device time of one run of the decode step program, from the trace."""
from chipbench import measure


def read(rec):
    return measure.decode_step_ms(rec)
