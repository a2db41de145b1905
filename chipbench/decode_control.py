"""Readings behind the model-level limits of the ``decode`` arrival kind:
the program's and its controls', over many seeds, in one process.

    python3 chipbench/decode_control.py --workload qwen2-decode-4k \\
        --seconds 30 --seeds 1,2,3

For each seed the cell runs as ``run.py`` runs it (set-up, the window, the
model check of the sampled steps), and one JSON line gives the largest
reading of each limit (relative error, int8 step error, scale error) over
those steps three times: for what the program served, and for each
control of ``decode.CONTROLS`` put in the program's place
(``chipbench/qwen2_reference.py`` with its norms and attention in
bfloat16, or with only its activation scales in bfloat16).  The lower
reading of a limit is the largest the program gives, the upper the
smallest its control gives.  The benchmark's own runs do not run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (ROOT, os.path.join(ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from chipbench import drive, spec  # noqa: E402
from chipbench.arrivals.decode import CONTROLS  # noqa: E402
from chipbench.run import NoAccelerator, require_accelerator  # noqa: E402


def main(argv=None, require=require_accelerator) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--seeds", required=True)
    args = p.parse_args(argv)
    try:
        cell = spec.cell(args.workload)
        device = require(cell.chips)
    except (spec.SpecError, NoAccelerator) as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    for seed in (int(s) for s in args.seeds.split(",")):
        s = drive.setup(cell, seed, args.seconds)
        window = s.window(drive.Spans(False))
        s.sched.close(drain=False)
        arr = s.traffic.arrivals
        row = {"workload": args.workload, "seed": seed,
               "device": device["kind"], "steps": len(window.records)}
        row["program"] = arr.model_check(*arr.served_steps)
        for control in CONTROLS:
            row[control] = arr.model_check(*arr.served_steps, control)
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
