#!/usr/bin/env python3
"""benchmark/control.py — read a cell's `correct` numbers and its
control's, on several seeds in one process.

    python3 benchmark/control.py --workload <name> --seeds 1,2,3 \
        --seconds <s> [--control 0]

For each seed it makes a run as `run.py` does (a short window at the
cell's own load is enough) and prints each number compared beside its
limit; then it puts the plain reference, computed in the precision below
the configuration's, in the program's place and prints the same
numbers.  The limits in the traffic files were set from these two
readings (PERF.md gives them); the benchmark's own runs never run the
control.  On the chip only, like `run.py`.
"""

import argparse
import gc
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import run as harness      # noqa: E402
from benchmark.cells import Cells         # noqa: E402


def read(cells, workload, seeds, seconds, with_control=True,
         platform="tpu", log=harness.say):
    """{"sound": {number: [values]}, "control": {number: [values]}}."""
    out = {"sound": {}, "control": {}, "correct": []}
    for seed in seeds:
        hold = {}
        res = harness.run_cell(cells, workload, seed, seconds, False,
                               platform=platform, log=log, hold=hold)
        kind = hold["ctx"]["cell"]["kind"]
        try:
            out["correct"].append(res["correct"])
            log(f"seed {seed}: {json.dumps(res['metrics'])} correct="
                f"{res['correct']}")
            ctl = kind.control(hold["state"], hold["result"],
                               hold["ctx"]) if with_control else []
        finally:
            kind.close(hold["state"])
        for c in hold["comparisons"]:
            out["sound"].setdefault(c["name"], []).append(c["value"])
        for c in ctl:
            out["control"].setdefault(c["name"], []).append(c["value"])
            log(f"seed {seed}: control {c['name']}: {c['value']:.6g} "
                f"(limit {c['limit']:.6g})")
        del hold
        gc.collect()
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", type=int, choices=(0, 1), default=1)
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    try:
        out = read(Cells(ROOT), args.workload, seeds, args.seconds,
                   bool(args.control))
    except harness.NoChip as exc:
        print(f"[bench] no chip: {exc}", file=sys.stderr, flush=True)
        return 3
    for name, sound in out["sound"].items():
        ctl = out["control"].get(name, [])
        line = f"{name}: sound runs' largest {max(sound):.6g}"
        if ctl:
            line += (f", control's smallest {min(ctl):.6g}, ratio "
                     f"{min(ctl) / max(max(sound), 1e-30):.2f}")
        harness.say(line)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
