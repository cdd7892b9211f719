"""Readings that a cell's correctness limit is set from, in one process.

    python chipbench/limits.py --workload <cell> --seconds <s> \\
        --seeds 1-12 [--out FILE]

Runs the cell as ``run.py`` would, once per seed, and prints each run's
check readings: the widest served-token gap against the float32
reference of the cell's architecture file, its mean, the share of
tokens that were not the reference's best, the tokens compared, and the
same three for the control on the same sample.  The control (the
file's ``served_gaps(..., control=True)``) is the reference computed
with float8 (e4m3) operands, the precision below the configuration's
bf16: at each position, the gap of the token it puts first.  The limit
in the configuration file lies between the largest sound reading and
the smallest control reading.  The benchmark's own runs never run this.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402

from run import ROOT, run  # noqa: E402


def seeds(text: str):
    out = []
    for part in text.split(","):
        if "-" in part:
            a, b = part.split("-")
            out += list(range(int(a), int(b) + 1))
        elif part:
            out.append(int(part))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    rows = []
    for seed in seeds(args.seeds):
        t = time.monotonic()
        res = run(ROOT, args.workload, seed, args.seconds, 0, control=True,
                  t_start=T_START if not rows else t)
        row = {"seed": seed, "correct": res["correct"],
               "failed": res["failed"],
               **res["window"]["check_readings"],
               "metrics": {k: v["value"] for k, v in res["metrics"].items()}}
        rows.append(row)
        print(json.dumps(row), flush=True)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(rows, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
