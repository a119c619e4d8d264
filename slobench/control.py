"""Readings for a cell's correctness limit, on the chip, in one process.

    python3 -m slobench.control --workload <cell> --seeds 1,2,3 \
        --seconds 20 [--control]

For each seed it makes a whole run of the cell (``slobench.run``, window of
``--seconds`` at the cell's own load) and prints the number compared, the
served tokens' widest logit gap, and whether the run came out correct. With
``--control`` the run puts the precision control in the program's place
(the tokens that the reference computed in float8 puts first, read at the
same positions of the same sample), which the limit must fail: the line
then holds both the control's gap and the program's own. The limit in the
configuration file is set between the largest gap of the program over a
dozen seeds or more and the smallest gap of the control.
"""
from __future__ import annotations

import argparse
import json
import sys

from slobench.run import Cell, run_cell


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args(argv)
    for seed in [int(s) for s in args.seeds.split(",")]:
        res = run_cell(Cell.load(args.workload), seed, args.seconds, False,
                       control=args.control)
        gap = res["checks"]["logit_gap"]["value"]
        print(json.dumps({
            "reading": args.workload, "seed": seed, "correct": res["correct"],
            "program_gap": res.get("program_gap", gap),
            "control_gap": gap if args.control else None,
            "tokens": res["checks"]["tokens_compared"]["value"],
            "memory_peak_bytes": res["device"]["memory_peak_bytes"],
            "metrics": res["metrics"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
