"""Find an open-loop cell's knee: the highest arrival rate the served
path sustains without a growing backlog.  One process, one run per rate.

    python chipbench/sweep.py --workload <cell> --seconds <s> \\
        --seed <n> --rates 2,3,4,5,6,7

For each rate it prints tokens/s, the latency tails, the median queue
wait of the requests due in each third of the window (a backlog that
grows shows as a rising series) and the requests still queued when the
window closed.  The rate found is written into the mix file by hand, as
a number; the benchmark's own runs never sweep.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402

from run import ROOT, run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        res = run(ROOT, args.workload, args.seed, args.seconds, 0,
                  rate=rate, t_start=T_START if i == 0 else time.monotonic())
        w = res["window"]
        print(json.dumps({
            "rate_per_s": rate, "correct": res["correct"],
            "attempted": res["attempted"], "failed": res["failed"],
            "tokens_per_s": w["tokens"] / w["seconds"],
            "metrics": {k: v["value"] for k, v in res["metrics"].items()},
            "queue_wait_p50_ms_by_third": w["queue_wait_p50_ms_by_third"],
            "pending_at_end": w["pending_at_end"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
