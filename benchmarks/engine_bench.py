"""Interpreter vs compiled executors (VM and fused) on the pattern sweep.

Measures the executor claims of docs/ENGINE.md on every Section-IV
pattern:

* ``vm/compile_sweep`` — cold start (datapath warmup + lowering + first
  run of all patterns) under the program-as-data VM.  One signature-keyed
  XLA executable serves the whole sweep (``xla_compiles`` in the derived
  column; acceptance bound: <= 2), so cold start is dominated by the
  shared datapath compile — loaded from JAX's persistent cache on any
  machine that has run the suite before, compiled once ever otherwise.
* ``fused/compile_sweep`` — the same cold start under the per-program
  fused engine (one jit trace + XLA compile per program).
* per-pattern steady-state rows for both modes, with the stepwise
  interpreter baseline and speedup in the derived column.
* ``engine/vmap_daxpy_x16`` — vmap-batched throughput after an explicit
  ``warmup()``; ``warmup_us`` carries the AOT compile cost that used to
  hit the first call silently (the 173 ms ``first_call_us`` cliff).

    PYTHONPATH=src python -m benchmarks.engine_bench            # CSV rows
    PYTHONPATH=src python -m benchmarks.engine_bench --json BENCH_engine.json
    PYTHONPATH=src python -m benchmarks.engine_bench --quick    # CI smoke
"""
from __future__ import annotations

import argparse
import json
import time
from typing import List, Tuple

import jax
import numpy as np

from repro.core import (MVEConfig, MVEInterpreter, cache_info,
                        compile_program)
from repro.core import vm
from repro.core.engine import clear_cache
from repro.core.patterns import PATTERNS, run_pattern_batch

QUICK_SET = ["daxpy", "gemm", "spmm", "upsample"]


def _block(tree):
    jax.block_until_ready(tree)


def engine_vs_interp(iters: int = 3, quick: bool = False,
                     ) -> List[Tuple[str, float, str]]:
    # Persist this section's XLA executables across bench runs (the VM
    # datapath compiles once per machine).
    vm.enable_disk_cache()
    return _engine_vs_interp(iters=iters, quick=quick)


def _engine_vs_interp(iters: int, quick: bool,
                      ) -> List[Tuple[str, float, str]]:
    cfg = MVEConfig()
    names = QUICK_SET if quick else sorted(PATTERNS)
    runs = {name: PATTERNS[name]() for name in names}
    rows: List[Tuple[str, float, str]] = []

    # stepwise-interpreter baseline (the semantic oracle), measured once
    oracle = MVEInterpreter(cfg, compiled=False)
    interp_us = {}
    interp_mem = {}
    for name, r in runs.items():
        t0 = time.perf_counter()
        mem_i, _ = oracle.run_stepwise(r.program, r.memory)
        _block(mem_i)
        interp_us[name] = (time.perf_counter() - t0) * 1e6
        interp_mem[name] = np.asarray(mem_i)
    rows.append(("interp/sweep_total", sum(interp_us.values()),
                 f"programs={len(runs)}"))

    for mode in ("vm", "fused"):
        clear_cache()
        if mode == "vm":
            vm.clear_executors()

        # cold start: (datapath warmup +) lowering + first run, all programs
        t0 = time.perf_counter()
        if mode == "vm":
            vm.prewarm(cfg)
        compiled = {n: compile_program(r.program, cfg, mode=mode)
                    for n, r in runs.items()}
        for n, r in runs.items():
            _block(compiled[n].run(r.memory)[0])
        cold_s = time.perf_counter() - t0
        if mode == "vm":
            info = cache_info()
            detail = (f"xla_compiles={info.vm_xla_compiles};"
                      f"vm_signatures={info.vm_signatures}")
        else:
            detail = "xla_compiles={};one_per_program".format(
                sum(cp._jit.compiles for cp in compiled.values()))
        rows.append((f"{mode}/compile_sweep", cold_s * 1e6,
                     f"programs={len(runs)};{detail}"))

        total = 0.0
        for name, r in runs.items():
            t0 = time.perf_counter()
            for _ in range(iters):
                mem_e, _ = compiled[name].run(r.memory)
            _block(mem_e)
            t_e = (time.perf_counter() - t0) / iters
            np.testing.assert_array_equal(interp_mem[name],
                                          np.asarray(mem_e))
            total += t_e
            rows.append((f"{mode}/{name}", t_e * 1e6,
                         f"interp_us={interp_us[name]:.0f};"
                         f"speedup={interp_us[name] / (t_e * 1e6):.1f}x"))
        rows.append((f"{mode}/sweep_total", total * 1e6,
                     f"interp_us={sum(interp_us.values()):.0f};"
                     f"speedup={sum(interp_us.values()) / (total * 1e6):.1f}x"))

    info = cache_info()
    rows.append(("engine/cache", float(info.vm_xla_compiles),
                 f"program_hits={info.program_hits};"
                 f"program_misses={info.program_misses};"
                 f"vm_signatures={info.vm_signatures};"
                 f"vm_hits={info.vm_hits};"
                 f"vm_fallbacks={info.vm_fallbacks}"))

    # vmap batching with an explicit warmup: the AOT compile cost is paid
    # (and reported) up front instead of silently hitting the first call.
    batch, name = 16, "daxpy"
    r0 = PATTERNS[name]()
    t0 = time.perf_counter()
    compile_program(r0.program, cfg).warmup(r0.memory.shape[0], batch=batch)
    warm_us = (time.perf_counter() - t0) * 1e6
    t0 = time.perf_counter()
    _, mems = run_pattern_batch(name, seeds=list(range(batch)))
    _block(mems)
    t_first = time.perf_counter() - t0
    t0 = time.perf_counter()
    _, mems = run_pattern_batch(name, seeds=list(range(batch)))
    _block(mems)
    t_b = time.perf_counter() - t0
    rows.append((f"engine/vmap_{name}_x{batch}", t_b * 1e6,
                 f"per_image_us={t_b / batch * 1e6:.0f};"
                 f"first_call_us={t_first * 1e6:.0f};"
                 f"warmup_us={warm_us:.0f}"))
    return rows


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", default=None,
                    help="also write results to this JSON file")
    ap.add_argument("--quick", action="store_true",
                    help="reduced sweep + 1 iteration (CI smoke)")
    args = ap.parse_args()
    rows = engine_vs_interp(iters=1 if args.quick else 3, quick=args.quick)
    print("name,us_per_call,derived")
    for name, us, derived in rows:
        print(f"{name},{us:.3f},{derived}")
    if args.json:
        payload = {name: {"us": us, "derived": derived}
                   for name, us, derived in rows}
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=2)


if __name__ == "__main__":
    main()
