"""Reduction of a profiler trace (``.xplane.pb``) to the device numbers.

Read with ``jax.profiler.ProfileData``.  The traced window is the host
span ``bench.window`` that the benchmark writes around its window.  On
each TPU plane (``/device:TPU:<n>``) the line ``XLA Ops`` holds one event
per operation run on the device and the line ``XLA Modules`` one event
per executable run.  From them:

* ``busy_s``: the union of the operation intervals inside the window,
  averaged over the chips that ran anything; ``window_s`` the window's
  length; the idle share is ``1 - busy_s / window_s``;
* ``module_s``: device time of the executables of each class, by module
  name (:data:`EXECUTABLES`);
* ``breakdown``: the operations that took most time, each named
  ``<executable>/<operation>`` (the executable whose run contains it),
  and the longest idle gaps, each labelled with the benchmark's host
  spans open in its middle.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Sequence, Tuple

Interval = Tuple[float, float]           # (start, end) in nanoseconds

WINDOW_SPAN = "bench.window"
#: Host spans that label idle gaps, most specific first.
HOST_SPANS = ("client.submit", "client.wait", "client.sleep")
#: Executable classes by module name: the VM datapath is the jit of
#: ``execute`` (``core/vm.py``), a fused program the jit of
#: ``CompiledProgram._execute`` (``core/engine.py``).
EXECUTABLES = {"vm": re.compile(r"^jit_execute\b"),
               "fused": re.compile(r"^jit__execute\b")}
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
TOP = 10


def union(intervals: Sequence[Interval]) -> List[Interval]:
    """Disjoint, sorted intervals covering the same time."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: Sequence[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def covered(intervals: Sequence[Interval]) -> float:
    return sum(e - s for s, e in union(intervals))


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """The idle intervals of ``[lo, hi]`` between busy ones."""
    out, t = [], lo
    for s, e in union(clip(busy, lo, hi)):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def label(t: float, spans: Dict[str, List[Interval]]) -> str:
    """The host spans open at time ``t``, most specific first."""
    open_ = [name for name in HOST_SPANS
             if any(s <= t < e for s, e in spans.get(name, ()))]
    return "+".join(open_) if open_ else "no benchmark span"


def executable_class(module: str) -> str:
    for cls, pattern in EXECUTABLES.items():
        if pattern.search(module):
            return cls
    return "other"


def op_name(hlo: str) -> str:
    """``%fusion.3 = f32[8192]{...} fusion(...)`` -> ``fusion.3``."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def qualify(ops: List[Tuple[str, float, float]],
            modules: List[Tuple[str, float, float]]
            ) -> List[Tuple[str, float, float]]:
    """Name each operation ``<module>/<op>`` by the executable run that
    contains its start (``?`` where none does)."""
    mods = sorted((s, s + d, n) for n, s, d in modules)
    out, j = [], 0
    for name, s, d in sorted(ops, key=lambda e: e[1]):
        while j < len(mods) and mods[j][1] <= s:
            j += 1
        inside = j < len(mods) and mods[j][0] <= s
        out.append((f"{mods[j][2] if inside else '?'}/{op_name(name)}",
                    s, d))
    return out


def reduce_events(ops: Dict[str, List[Tuple[str, float, float]]],
                  modules: Dict[str, List[Tuple[str, float, float]]],
                  spans: Dict[str, List[Interval]]) -> dict:
    """The reduction on plain events.

    ``ops`` and ``modules`` map a device to its ``(name, start_ns,
    duration_ns)`` events; ``spans`` maps a host span name to its
    intervals.  The window is the first ``bench.window`` span.
    """
    w0, w1 = spans[WINDOW_SPAN][0]
    busy, idle, by_op = [], [], {}
    module_s = {"vm": 0.0, "fused": 0.0, "other": 0.0}
    for dev, events in ops.items():
        if not events:
            continue
        intervals = clip([(s, s + d) for _, s, d in events], w0, w1)
        busy.append(covered(intervals))
        for name, s, d in qualify(events, modules.get(dev, [])):
            inside = clip([(s, s + d)], w0, w1)
            if inside:
                by_op[name] = by_op.get(name, 0.0) + covered(inside)
        idle += gaps(intervals, w0, w1)
    for dev, events in modules.items():
        for name, s, d in events:
            inside = clip([(s, s + d)], w0, w1)
            if inside:
                module_s[executable_class(name)] += covered(inside) / 1e9
    window_s = (w1 - w0) / 1e9
    busy_s = sum(busy) / len(busy) / 1e9 if busy else 0.0
    top_ops = sorted(by_op.items(), key=lambda kv: -kv[1])[:TOP]
    top_gaps = sorted(idle, key=lambda g: g[0] - g[1])[:TOP]
    return {
        "window_s": window_s,
        "busy_s": busy_s,
        "chips": len(busy),
        "module_s": module_s,
        "breakdown": {
            "device_ops": [[n, t / 1e9] for n, t in top_ops],
            "idle_gaps": [[label((s + e) / 2, spans), (e - s) / 1e9]
                          for s, e in top_gaps],
        },
    }


def _events(line) -> List[Tuple[str, float, float]]:
    return [(e.name, float(e.start_ns), float(e.duration_ns))
            for e in line.events]


def reduce_profile(profile) -> dict:
    """The reduction of a ``jax.profiler.ProfileData``."""
    ops, modules, spans = {}, {}, {}
    for plane in profile.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops[plane.name] = _events(line)
                elif line.name == MODULES_LINE:
                    modules[plane.name] = _events(line)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == WINDOW_SPAN or e.name in HOST_SPANS:
                        spans.setdefault(e.name, []).append(
                            (float(e.start_ns), float(e.end_ns)))
    if WINDOW_SPAN not in spans:
        raise ValueError(f"the trace has no {WINDOW_SPAN!r} span")
    return reduce_events(ops, modules, spans)


def newest_trace(directory: str) -> str:
    found = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return max(found, key=os.path.getmtime)


def reduce_dir(directory: str) -> dict:
    from jax.profiler import ProfileData
    return reduce_profile(ProfileData.from_file(newest_trace(directory)))
