"""Benchmark runner: one section per paper table/figure + kernel bench +
the per-target sweep.

    PYTHONPATH=src python -m benchmarks.run [--only fig7,fig10]
    PYTHONPATH=src python -m benchmarks.run --only engine --json BENCH_engine.json
    PYTHONPATH=src python -m benchmarks.run --only engine --quick   # CI smoke
    PYTHONPATH=src python -m benchmarks.run --only targets --targets mve-bs,rvv-1d

Prints ``name,us_per_call,derived`` CSV; ``--json`` also rewrites the
given file (the repo tracks ``BENCH_engine.json`` so the perf trajectory
of the execution engine is versioned alongside the code).  ``--targets``
filters the ``targets`` and ``models`` sections to a comma-separated
subset of the registered target names (docs/TARGETS.md).
"""
from __future__ import annotations

import argparse
import json
import sys
import traceback

from . import paper_claims
from .engine_bench import engine_vs_interp
from .frontend_bench import frontend_overhead, frontend_overhead_quick
from .kernels_bench import kernel_microbench
from .models_bench import models_bench
from .opt_bench import opt_report
from .resilience_bench import resilience_report, resilience_report_quick
from .serving_bench import mve_serving, mve_serving_quick, serving_throughput
from .silicon_bench import silicon_report, silicon_report_quick
from .targets_bench import target_sweep
from .timing_bench import timing_report

SECTIONS = {
    "engine": engine_vs_interp,
    "frontend": frontend_overhead,
    "targets": target_sweep,
    "models": models_bench,
    "timing": timing_report,
    "opt": opt_report,
    "table2": paper_claims.table2_latencies,
    "fig7": paper_claims.fig7_neon,
    "fig8": paper_claims.fig8_gpu,
    "fig9": paper_claims.fig9_gemm_sweep,
    "fig10": paper_claims.fig10_11_rvv,
    "fig12b": paper_claims.fig12b_scaling,
    "fig12c": paper_claims.fig12c_precision,
    "fig13": paper_claims.fig13_schemes,
    "tableV": paper_claims.tableV_area,
    "kernels": kernel_microbench,
    "serving": mve_serving,
    "serving_lm": serving_throughput,
    "resilience": resilience_report,
    "silicon": silicon_report,
}

# sections that understand the reduced-size smoke mode
_QUICK_SECTIONS = {
    "engine": lambda: engine_vs_interp(iters=1, quick=True),
    "frontend": frontend_overhead_quick,
    "opt": lambda: opt_report(quick=True),
    "serving": mve_serving_quick,
    "resilience": resilience_report_quick,
    "targets": lambda **kw: target_sweep(quick=True, **kw),
    "models": lambda **kw: models_bench(quick=True, **kw),
    "serving_lm": lambda: serving_throughput(quick=True),
    "timing": lambda: timing_report(quick=True),
    "silicon": silicon_report_quick,
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated section names")
    ap.add_argument("--json", default=None,
                    help="also write the collected rows to this JSON file")
    ap.add_argument("--quick", action="store_true",
                    help="reduced sizes/iterations where supported")
    ap.add_argument("--targets", default=None,
                    help="comma-separated target names for the `targets` "
                         "section (default: every registered target)")
    args = ap.parse_args()
    only = set(args.only.split(",")) if args.only else None
    target_filter = args.targets.split(",") if args.targets else None

    print("name,us_per_call,derived")
    collected = {}
    failures = 0
    for section, fn in SECTIONS.items():
        if only and section not in only:
            continue
        if args.quick and section in _QUICK_SECTIONS:
            fn = _QUICK_SECTIONS[section]
        if section in ("targets", "models"):
            fn = (lambda fn=fn: fn(only_targets=target_filter))
        try:
            for name, us, derived in fn():
                print(f"{name},{us:.3f},{derived}")
                collected[name] = {"us": us, "derived": derived}
        except Exception as e:                    # keep the run going
            failures += 1
            print(f"{section}/ERROR,0,{type(e).__name__}:{e}",
                  file=sys.stdout)
            traceback.print_exc(file=sys.stderr)
    if args.json:
        if failures:
            print(f"not writing {args.json}: {failures} section(s) failed",
                  file=sys.stderr)
        else:
            # --only runs merge into the existing file so one section can
            # be refreshed without dropping the others' recorded rows
            merged = {}
            if only:
                try:
                    with open(args.json) as f:
                        merged = json.load(f)
                except (OSError, ValueError):
                    merged = {}
            merged.update(collected)
            with open(args.json, "w") as f:
                json.dump(merged, f, indent=2)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
