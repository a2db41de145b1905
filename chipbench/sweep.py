"""Rate sweep of an open-loop cell: finds the highest rate it sustains.

    python3 chipbench/sweep.py --workload <cell> --seed <n> \
        --seconds <s> --rates 50,100,200

One process, one set-up: for each rate in turn, the cell's traffic at
that rate (seed ``seed + i`` for the i-th rate, so a fresh-program mix
never repeats a program across windows) runs for ``--seconds`` and one
JSON line reports the offered and completed rates, p50 and p95 latency
from the due time, the same for the first and the last third of the
window (a backlog that grows shows as a last third far above the first),
and the generator's lateness.  A rate cell's frozen ``rate_per_s`` is 0.8
of the highest rate whose window kept up.  Not part of a benchmark run.
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
from chipbench.run import NoAccelerator, require_accelerator  # noqa: E402


def _lat(recs):
    return drive.latency_ms(recs, 50), drive.latency_ms(recs, 95)


def main(argv=None, require=require_accelerator) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--rates", required=True)
    args = p.parse_args(argv)
    try:
        cell = spec.cell(args.workload)
        device = require(cell.chips)
    except (spec.SpecError, NoAccelerator) as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        # A new set-up per rate: no promotion heat carries over.
        s = drive.setup(cell, args.seed + i, args.seconds, rate_per_s=rate)
        w = s.window(drive.Spans(False))
        recs = w.records
        n = len(recs)
        done = [r for r in recs if r.seen is not None and r.error is None]
        by_close = sum(1 for r in done if r.seen <= w.close)
        thirds = [recs[:n // 3], recs[-(n // 3):]]
        late = [r.submitted - r.due for r in recs if r.submitted]
        row = {"rate": rate, "offered": n, "served": len(done),
               "completed_per_s_by_close": by_close / args.seconds,
               "p50_ms": drive.latency_ms(recs, 50),
               "p95_ms": drive.latency_ms(recs, 95),
               "first_third_p50_p95": _lat(thirds[0]),
               "last_third_p50_p95": _lat(thirds[1]),
               "drain_after_close_s": w.end - w.close,
               "lateness_max_ms": max(late) * 1e3 if late else None,
               "compiles": w.compiles,
               "reqs_per_dispatch": (
                   (w.stats_after["requests"] - w.stats_before["requests"])
                   / max(1, w.stats_after["dispatches"]
                         - w.stats_before["dispatches"])),
               "device": device["kind"]}
        print(json.dumps(row), flush=True)
        s.sched.close(drain=False)
    return 0


if __name__ == "__main__":
    sys.exit(main())
