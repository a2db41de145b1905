"""Device time of each decode tile kind in a traced run, by executable.

The program names the fused executables of a decode tile kind
``jit__execute.dec_<kind>`` (``repro.core.engine.name_program``), which
``trace_reduce.EXECUTABLES`` still classes as the fused tier.  Inside the
traced window (the first ``bench.window`` span), the ``XLA Modules``
events of each TPU plane whose name carries a kind are summed per kind:
every served tile's device time is attributed by the executable that ran
it, whatever session or stage was in flight.  Tiles served by the VM tier
share its executables and cannot be attributed; the readers count fused
tiles only.  The trace is the newest under ``run.TRACE_DIR``, read once a
run.
"""
from __future__ import annotations

import re
from typing import Dict, List, Optional, Tuple

from chipbench.trace_reduce import MODULES_LINE, WINDOW_SPAN, clip, covered

KIND = re.compile(r"^jit__execute\.dec_(\w+)\(")


def reduce_events(modules: List[Tuple[str, float, float]],
                  window: Tuple[float, float]) -> Dict[str, float]:
    """Seconds of device time per kind: ``modules`` holds
    ``(name, start_ns, end_ns)`` events of every TPU plane."""
    out: Dict[str, float] = {}
    for name, s, e in modules:
        m = KIND.match(name)
        if m:
            inside = covered(clip([(s, e)], *window))
            out[m.group(1)] = out.get(m.group(1), 0.0) + inside / 1e9
    return out


def reduce_profile(profile) -> Optional[Dict[str, float]]:
    window, modules = None, []
    for plane in profile.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == MODULES_LINE:
                    modules += [(e.name, float(e.start_ns), float(e.end_ns))
                                for e in line.events]
        elif plane.name.startswith("/host:") and window is None:
            for line in plane.lines:
                for e in line.events:
                    if e.name == WINDOW_SPAN:
                        window = (float(e.start_ns), float(e.end_ns))
                        break
    return reduce_events(modules, window) if window is not None else None


def kind_seconds(run) -> Optional[Dict[str, float]]:
    """Device seconds per tile kind in a traced run's window (None in an
    untraced run), kept on the run."""
    if run.trace is None:
        return None
    if not hasattr(run, "decode_kinds"):
        from jax.profiler import ProfileData

        from chipbench import trace_reduce
        from chipbench.run import TRACE_DIR
        run.decode_kinds = reduce_profile(ProfileData.from_file(
            trace_reduce.newest_trace(TRACE_DIR)))
    return run.decode_kinds


def counters(run) -> Optional[dict]:
    """The decode arrival kind's record of the window (None elsewhere)."""
    return getattr(run.window, "decode", None)


def roofline(run, kinds) -> Optional[float]:
    """Architectural bytes of the fused tiles of ``kinds`` served in the
    window (``reference.access_bytes`` of each kind's program, which moves
    the same bytes whatever its data) at the chip's HBM peak, over those
    kinds' device time, in %."""
    secs, dec = kind_seconds(run), counters(run)
    if not secs or dec is None:
        return None
    seconds = sum(secs.get(k, 0.0) for k in kinds)
    served = {k: dec["served_fused"].get(k, 0) for k in kinds}
    if seconds <= 0 or not sum(served.values()):
        return None
    from chipbench.traffic import Request
    examples = run.traffic.arrivals.examples
    total = sum(n * run.access_bytes(Request(0, k, examples[k]))
                for k, n in served.items() if n)
    return total / run.peaks["hbm_bytes_per_s"] / seconds * 100.0


def device_us_per_step(run, kinds) -> Optional[float]:
    secs = kind_seconds(run)
    steps = len(run.served())
    if not secs or not steps:
        return None
    seconds = sum(secs.get(k, 0.0) for k in kinds)
    return seconds / steps * 1e6 if seconds > 0 else None
