"""Reads, on the chip and at a cell's own size, the two readings every
limit of `correct` is set between: the program's own gaps over many seeds
(the lower), and the control's (the upper).  All
seeds in one process, a fresh driver for each; the benchmark's own runs
never run this.

    python perf/calibrate.py --workload <cell> --seeds 12 --controls 3 \\
        [--seconds 8] [--first-seed 1001]

One JSON line per seed on standard output, and the same appended to
`chiprun_out/calibrate-<cell>.jsonl`.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:] = [p for p in sys.path if os.path.abspath(p or ".") != HERE]
sys.path.insert(0, os.path.dirname(HERE))


def main():
    from perf import run

    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=2200000011)
    ap.add_argument("--seconds", type=float, default=8.0)
    args = ap.parse_args()

    out_dir = os.path.join(os.path.dirname(HERE), "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    out = open(os.path.join(out_dir, f"calibrate-{args.workload}.jsonl"), "a")
    for k in range(args.seeds):
        seed = args.first_seed + 7919 * k
        drv = run.build_driver(args.workload, seed)[-1]
        drv.setup(args.seconds)
        record = drv.run(args.seconds, None)
        drv.release()
        line = {"seed": seed, "program": drv.check(record)}
        if k < args.controls:
            line.update(drv.control(record))
        del drv
        text = json.dumps(line)
        print(text, flush=True)
        out.write(text + "\n")
        out.flush()
    return 0


if __name__ == "__main__":
    rc = main()
    sys.stdout.flush()
    os._exit(rc)
