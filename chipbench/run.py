"""One run of one cell of the on-chip benchmark of MVE program serving.

    python3 chipbench/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

A cell (``BENCHMARK.json``'s ``workloads``) pairs a configuration file
under ``chipbench/configs/`` with a traffic file under
``chipbench/traffic/``.  The run checks that JAX sees as many TPU chips as
the cell asks for (otherwise it exits non-zero and prints no result),
sets up (``drive.setup``: the requests from ``--seed``, an
``MVEScheduler`` with the configuration's settings, every executable the
traffic will use warmed; with the imports, that is ``setup_s``), drives
the window of the traffic's arrival kind, and compares a seeded sample
of what was served with ``chipbench/reference.py``.

With ``--trace 0`` the result reports the cell's end-to-end metrics;
with ``--trace 1`` the window runs under the JAX profiler and the result
reports the cell's per-layer metrics.  Each metric is read by
``chipbench/metrics/<name>.py``.  The last line of standard output is the
result as one JSON object; the numbers compared, each with its limit,
are the last lines of standard error and the result's last key.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (ROOT, os.path.join(ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from chipbench import spec  # noqa: E402

#: Where a traced run writes its profile: a fixed directory in the
#: checkout, emptied at the start of each traced run.
TRACE_DIR = os.path.join(ROOT, ".chipbench", "trace")


class NoAccelerator(Exception):
    pass


def require_accelerator(chips: int) -> dict:
    """The device record of the result; raises unless JAX sees at least
    ``chips`` TPU chips."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoAccelerator(f"needs a TPU, but JAX found {len(devices)} "
                            f"{devices[0].platform} device(s)")
    if len(devices) < chips:
        raise NoAccelerator(f"the cell needs {chips} chips, JAX found "
                            f"{len(devices)}")
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": chips}


def _log(msg: str) -> None:
    print(f"[chipbench] {time.perf_counter() - T0:.3f}s {msg}",
          file=sys.stderr, flush=True)


def _slowest(window, n: int = 5) -> None:
    """The slowest answers of an open-loop window, for the record."""
    recs = [r for r in window.records if r.seen is not None and r.due]
    for r in sorted(recs, key=lambda r: r.due - r.seen)[:n]:
        _log(f"slow: {r.request.kernel} due at {r.due - window.start:.3f}s"
             f" latency {(r.seen - r.due) * 1e3:.1f} ms, submit "
             f"{(r.accepted - r.submitted) * 1e3:.1f} ms, late "
             f"{(r.submitted - r.due) * 1e3:.1f} ms, {r.tier} x{r.batch}")


def _peak_bytes(chips: int):
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.local_devices()[:chips]]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


class Run:
    """What the metric readers read: one run's window, its traffic, its
    set-up time, the reduced trace (traced runs) and the device's peaks."""

    def __init__(self, window, traffic, lanes, peaks, trace=None,
                 setup_s=None):
        self.window = window
        self.traffic = traffic
        self.lanes = lanes
        self.peaks = peaks
        self.trace = trace
        self.setup_s = setup_s
        self._bytes = {}

    def served(self):
        from chipbench.drive import served
        return served(self.window.records)

    def device_us_per_request(self, tier: str):
        """Device time of the ``tier`` executables in the traced window
        over the requests that tier served, in microseconds."""
        if self.trace is None:
            return None
        served = sum(1 for r in self.served() if r.tier == tier)
        seconds = self.trace["module_s"][tier]
        return seconds / served * 1e6 if served and seconds > 0 else None

    def access_bytes(self, request) -> int:
        """Architectural bytes of one request (cached per program text
        for programs without random accesses)."""
        from chipbench import reference
        inst = request.instance
        random = any(i.op.name in ("RLD", "RST") for i in inst.program)
        key = (inst.program, id(inst.memory)) if random else inst.program
        got = self._bytes.get(key)
        if got is None:
            got = self._bytes[key] = reference.access_bytes(
                inst.program, inst.memory, self.lanes)
        return got


def read(metrics, run: Run) -> dict:
    """Each metric's reading; a reader that finds nothing to read leaves
    its metric out."""
    out = {}
    for m in metrics:
        value = spec.reader(m["name"])(run)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def run_cell(cell, args, device: dict, peaks: dict) -> dict:
    import jax

    from chipbench import correct, drive

    s = drive.setup(cell, args.seed, args.seconds)
    _log(f"set up: compile cache {s.cache}, "
         f"{len(s.traffic.programs())} programs, warmed {s.warmed}")

    spans = drive.Spans(bool(args.trace))
    setup = {}

    def on_start():
        if args.trace:
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            os.makedirs(TRACE_DIR)
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0      # spans, not every call
            options.enable_hlo_proto = False
            jax.profiler.start_trace(TRACE_DIR, profiler_options=options)
        setup["s"] = time.perf_counter() - T0

    try:
        window = s.window(spans, on_start)
    finally:
        if args.trace:
            jax.profiler.stop_trace()
    device = dict(device, memory_peak_bytes=_peak_bytes(cell.chips))
    s.sched.close(drain=False)
    s.sched = None

    recs = window.records
    failed = sum(1 for r in recs if r.error is not None or r.seen is None)
    late = [r.submitted - r.due for r in recs
            if r.due is not None and r.submitted is not None]
    if late:
        _log(f"generator lateness: mean {sum(late) / len(late) * 1e3} ms, "
             f"max {max(late) * 1e3} ms over {len(late)} requests")
    _slowest(window)
    _log(f"window {window.close - window.start} s, last answer "
         f"{window.end - window.close} s after the close; "
         f"{len(recs)} requests, {failed} failed; "
         f"compiles in window {window.compiles}")

    result = {"correct": False, "attempted": len(recs), "failed": failed}
    if args.trace:
        from chipbench import trace_reduce
        reduced = trace_reduce.reduce_dir(TRACE_DIR)
        run = Run(window, s.traffic, s.cfg.lanes, peaks, reduced)
        device.update(busy_s=reduced["busy_s"],
                      window_s=reduced["window_s"])
        result.update(metrics=read(cell.per_layer, run), device=device,
                      breakdown=reduced["breakdown"])
    else:
        run = Run(window, s.traffic, s.cfg.lanes, peaks,
                  setup_s=setup["s"])
        result.update(metrics=read(cell.end_to_end, run), device=device)

    t0 = time.perf_counter()
    mismatched, notes = correct.check(window.sample, s.cfg.lanes)
    checked = {req.kernel for req, _ in window.sample}
    ok, checks = correct.verdict({
        "mismatched_results": mismatched, "failed_requests": failed,
        "unchecked_kernels": len(set(s.traffic.kernels) - checked)})
    for note in notes:
        _log(note)
    _log(f"compared {len(window.sample)} served results with the "
         f"reference in {time.perf_counter() - t0} s")
    result["correct"] = ok
    result["checks"] = checks
    for name, c in checks.items():
        print(f"check {name} {c['value']} limit {c['limit']}",
              file=sys.stderr, flush=True)
    return result


def main(argv=None, require=require_accelerator) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)
    try:
        cell = spec.cell(args.workload)
        device = require(cell.chips)
        peaks = spec.peaks(device["kind"])
    except (spec.SpecError, NoAccelerator) as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    result = run_cell(cell, args, device, peaks)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
