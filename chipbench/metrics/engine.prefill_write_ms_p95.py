"""95th percentile of the device time of one pool write of an admission (``jit_write_prefill_step``)."""
from chipbench import spans


def read(rec):
    return spans.prefill_write_ms_p95(rec.trace)
