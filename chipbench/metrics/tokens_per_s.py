"""Output tokens emitted in the window over the window's length (it ends at a step boundary)."""
from chipbench import measure


def read(rec):
    return measure.window_tokens(rec) / (rec.t1 - rec.t0)
