"""Share of the traced window in which no operation ran on the device."""
from chipbench import measure


def read(rec):
    return measure.idle_share(rec)
