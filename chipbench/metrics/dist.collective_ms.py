"""Chip 0's device time per decode program in collective operations, from the trace."""
from chipbench import measure

# The instruction names XLA gives collectives on the TPU (read off the
# decode program compiled for a v5e 2x2): GSPMD's own are named by
# kind (``all-reduce.28``, ``all-gather.30``, async halves
# ``-start`` / ``-done``), those written in ``shard_map`` after their
# primitive (``psum.27``, ``pmax.9``).  A collective fused into a
# ``fusion.N`` is not seen by name.
COLLECTIVE = (r"^(all-reduce|all-gather|reduce-scatter|collective-permute"
              r"|all-to-all|psum|pmax|pmin|ppermute)")


def read(rec):
    progs = measure.decode_programs(rec)
    if not progs:
        return None
    ops = rec.trace.ops_in(COLLECTIVE, progs)
    if not ops:
        return None
    return 1e3 * sum(op.dur for op in ops) / len(progs)
