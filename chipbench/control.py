"""Readings behind the limits of ``correct``: the program's and the
control's, over many seeds, in one process.

    python3 chipbench/control.py --workload <cell> --seconds <s> \
        --seeds 1,2,3

For each seed the cell runs as ``run.py`` runs it (set-up, the window at
the cell's own load, the seeded sample of served results), and one JSON
line gives the numbers that ``correct`` compares, twice on the same
sample: for what the program served, and for the control, the reference
computed with every float32 result rounded to bfloat16
(``reference.run(..., bf16=True)``) put in the program's place.  The
lower reading of a limit is the largest the program gives, the upper the
smallest the control gives.  The benchmark's own runs do not run this.
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

from chipbench import correct, drive, spec  # noqa: E402
from chipbench.run import NoAccelerator, require_accelerator  # noqa: E402


def readings(window, traffic, lanes: int, control: bool) -> dict:
    failed = sum(1 for r in window.records
                 if r.error is not None or r.seen is None)
    mismatched, notes = correct.check(window.sample, lanes, control=control)
    checked = {req.kernel for req, _ in window.sample}
    return {"mismatched_results": mismatched, "failed_requests": failed,
            "unchecked_kernels": len(set(traffic.kernels) - checked),
            "checked": len(window.sample), "notes": notes[:3]}


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
        tiers = {}
        for r in window.records:
            tiers[r.tier] = tiers.get(r.tier, 0) + 1
        print(json.dumps({
            "workload": args.workload, "seed": seed,
            "device": device["kind"], "tiers": tiers,
            "program": readings(window, s.traffic, s.cfg.lanes, False),
            "control": readings(window, s.traffic, s.cfg.lanes, True)}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
