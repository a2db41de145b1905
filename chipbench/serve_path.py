"""The serve path's own spans and counters, as the per-layer metrics read
them.

Spans.  The program writes host spans into the profiler's trace
(``repro.core.tracing``); their names are written out here as literals,
so that a rename in the program cannot silently move what the benchmark
reads.  Inside the traced window (the first ``bench.window`` span):

* ``spans``: for each program span found, its count (spans that start in
  the window), its total time and its self time (total less the time of
  the program spans nested in it on the same thread), in seconds;
* ``worker_busy_s``: the union of the serve worker's spans other than
  those that wait for work (:data:`WORKER_WAIT`).  The worker is the
  thread that writes those waiting spans; None where no thread does;
* ``idle_s``: the device's idle time, the window less the union of its
  ``XLA Ops`` intervals, averaged over the chips that ran anything (as
  ``trace_reduce``'s ``busy_s``);
* ``idle_host_s``: the part of ``idle_s`` during which some thread is
  inside a host-work span (:data:`HOST_WORK`); None where the trace holds
  none.

The trace is the one ``trace_reduce`` reduced (the newest under
``run.TRACE_DIR``), read again once a run; the device's operations are
walked only when the trace holds host-work spans.

Counters.  :func:`delta` is the change of one ``SchedulerStats`` field
over the window (``drive.scheduler_stats``); None where the program has
no such field.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

from chipbench.trace_reduce import (OPS_LINE, WINDOW_SPAN, clip, covered,
                                    gaps, union)

PROGRAM_SPANS = ("sched.idle", "sched.window", "sched.group",
                 "sched.promote", "sched.dispatch", "sched.sync",
                 "sched.fetch", "sched.resolve", "engine.lower", "vm.tables")
#: The serve worker's spans that wait for work rather than do it.
WORKER_WAIT = ("sched.idle", "sched.window")
#: Program spans in which a host thread works on the serve path.
HOST_WORK = ("sched.group", "sched.promote", "sched.dispatch", "sched.fetch",
             "sched.resolve", "engine.lower")

Span = Tuple[str, float, float]          # (name, start_ns, end_ns)
Interval = Tuple[float, float]


def delta(run, field: str):
    """``SchedulerStats.<field>`` after the window less before it."""
    w = run.window
    if field not in w.stats_before or field not in w.stats_after:
        return None
    return w.stats_after[field] - w.stats_before[field]


def _span_times(spans: List[Span], w0: float, w1: float, out: dict) -> None:
    """Add one thread's spans to ``out``: name -> count, total, self."""
    stack: List[Tuple[float, str]] = []       # (end, name) of open spans
    for name, s, e in sorted(spans, key=lambda x: (x[1], -x[2])):
        while stack and stack[-1][0] <= s:
            stack.pop()
        inside = max(0.0, min(e, w1) - max(s, w0)) / 1e9
        rec = out.setdefault(name, {"count": 0, "total_s": 0.0,
                                    "self_s": 0.0})
        rec["count"] += w0 <= s < w1
        rec["total_s"] += inside
        rec["self_s"] += inside
        if stack:
            out[stack[-1][1]]["self_s"] -= inside
        stack.append((e, name))


def _intersection(a: Sequence[Interval], b: Sequence[Interval]) -> float:
    """Time covered by both of two sorted lists of disjoint intervals."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def reduce_events(ops: Dict[str, List[Interval]], threads: List[List[Span]],
                  window: Interval) -> dict:
    """The reduction on plain events: ``ops`` maps a device to its
    operations' ``(start_ns, end_ns)``, ``threads`` holds each host
    thread's program spans, ``window`` is the traced window."""
    w0, w1 = window
    times: Dict[str, dict] = {}
    worker: Optional[List[Interval]] = None
    work: List[Interval] = []
    for spans in threads:
        _span_times(spans, w0, w1, times)
        if any(name in WORKER_WAIT for name, _, _ in spans):
            worker = (worker or []) + [
                (s, e) for name, s, e in spans
                if name.startswith("sched.") and name not in WORKER_WAIT]
        work += [(s, e) for name, s, e in spans if name in HOST_WORK]
    idle, idle_host = [], []
    host = union(clip(work, w0, w1))
    for events in ops.values():
        if not events:
            continue
        free = gaps(events, w0, w1)
        idle.append(sum(e - s for s, e in free))
        idle_host.append(_intersection(free, host))
    n = len(idle)
    return {
        "window_s": (w1 - w0) / 1e9,
        "spans": times,
        "worker_busy_s": (covered(clip(worker, w0, w1)) / 1e9
                          if worker is not None else None),
        "idle_s": sum(idle) / n / 1e9 if n else None,
        "idle_host_s": (sum(idle_host) / n / 1e9
                        if n and work else None),
    }


def reduce_profile(profile) -> Optional[dict]:
    """The reduction of a ``jax.profiler.ProfileData``; None where it has
    no ``bench.window`` span."""
    names = frozenset(PROGRAM_SPANS)
    window, threads, ops_lines = None, [], {}
    for plane in profile.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops_lines[plane.name] = line
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans = []
                for e in line.events:
                    name = e.name
                    if name in names:
                        spans.append((name, float(e.start_ns),
                                      float(e.end_ns)))
                    elif name == WINDOW_SPAN and window is None:
                        window = (float(e.start_ns), float(e.end_ns))
                if spans:
                    threads.append(spans)
    if window is None:
        return None
    work = any(name in HOST_WORK for spans in threads for name, _, _ in spans)
    ops = {dev: [(float(e.start_ns), float(e.end_ns)) for e in line.events]
           if work else [] for dev, line in ops_lines.items()}
    return reduce_events(ops, threads, window)


def spans(run) -> Optional[dict]:
    """The reduction of a traced run's profile (None in an untraced run),
    read once and kept on the run."""
    if run.trace is None:
        return None
    if not hasattr(run, "serve_path"):
        from jax.profiler import ProfileData

        from chipbench import trace_reduce
        from chipbench.run import TRACE_DIR
        path = trace_reduce.newest_trace(TRACE_DIR)
        run.serve_path = reduce_profile(ProfileData.from_file(path))
    return run.serve_path
