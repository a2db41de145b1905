"""Set-up, and what every arrival kind's window records.

:func:`setup` builds one cell's traffic and scheduler and warms every
executable the window will use.  The window itself belongs to the
traffic's arrival kind (``chipbench/arrivals/<kind>.py``): its clients
submit through ``MVEScheduler.submit`` and see a result when its ticket
resolves, with memory image, registers and Tag in host memory.  A
latency runs from the request's due time in the schedule to the moment a
client sees its result; the program's own time stamps are not used.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
from typing import Dict, List, Optional

import numpy as np

#: How long after the window closes a client waits for answers still due.
LATE_S = 60.0
#: The host span around the measured window.
WINDOW = "bench.window"


class Spans:
    """Host spans written into the profiler's trace when tracing."""

    def __init__(self, on: bool):
        self.on = on

    def __call__(self, name: str):
        if not self.on:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)


class CompileCounter:
    """Counts XLA compilations (persistent-cache loads included) while
    armed, through ``jax.monitoring``."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.armed = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, event, duration, **kwargs):
        if self.armed and event == self.EVENT:
            self.count += 1


@dataclasses.dataclass
class Record:
    request: object                      # traffic.Request
    due: Optional[float] = None          # host clock, open loop
    submitted: Optional[float] = None    # submit() entered
    accepted: Optional[float] = None     # submit() returned
    seen: Optional[float] = None         # the client saw the result
    tier: Optional[str] = None
    batch: int = 0
    error: Optional[str] = None


@dataclasses.dataclass
class Window:
    records: List[Record]
    start: float
    close: float
    end: float                           # last answer seen, or gave up
    stats_before: Dict[str, int]
    stats_after: Dict[str, int]
    compiles: int
    sample: list                         # (Request, ServeResult)


def enable_cache() -> str:
    """JAX's persistent compilation cache where the program keeps it
    (``JAX_COMPILATION_CACHE_DIR``, else ``.jax_cache/`` in the checkout),
    holding every executable however fast it compiled, so that only the
    first run of a cell in a checkout compiles."""
    import jax
    from repro.core import vm
    path = vm.enable_disk_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def served(records: List[Record]) -> List[Record]:
    """The records answered with a result."""
    return [r for r in records if r.seen is not None and r.error is None]


def latency_ms(records: List[Record], q: float) -> Optional[float]:
    """The ``q``-th percentile, in ms, of the answered requests' latencies,
    each from its due time; None where none was answered."""
    lat = [(r.seen - r.due) * 1e3 for r in served(records)
           if r.due is not None]
    return float(np.percentile(lat, q)) if lat else None


def scheduler_stats(sched) -> Dict[str, int]:
    return dict(dataclasses.asdict(sched.stats))


def finish(rec: Record, ticket, sampler, now: float) -> None:
    """Record the answer of a resolved ticket, offering it to the sample
    compared with the reference."""
    rec.seen = now
    try:
        res = ticket.result(timeout=0)
    except Exception as e:          # a typed scheduler error
        rec.error = f"{type(e).__name__}: {e}"
        return
    rec.tier, rec.batch = res.tier, res.batch_size
    sampler.offer(rec.request.kernel, (rec.request, res))


# ---------------------------------------------------------------------------
# Set-up.
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Setup:
    cache: str                           # the compile cache's directory
    cfg: object                          # MVEConfig
    traffic: object                      # traffic.Traffic
    sched: object                        # MVEScheduler
    counter: CompileCounter
    warmed: Dict[str, int]

    def window(self, spans, on_start=None) -> Window:
        return self.traffic.arrivals.window(self.sched, self.counter, spans,
                                            on_start)


def setup(cell, seed: int, seconds: float, **params) -> Setup:
    """Everything a run does before its window: the compile cache, the
    traffic from ``seed`` (``params`` override the traffic file's), the
    scheduler at the configuration's settings, and every executable the
    window will use, warmed."""
    from chipbench.traffic import Traffic
    from repro.core import MVEConfig
    from repro.runtime.scheduler import MVEScheduler

    cache = enable_cache()
    cfg = MVEConfig(**cell.config["machine"])
    traffic = Traffic(cell.config, dict(cell.traffic, **params), seed,
                      seconds)
    counter = CompileCounter()
    sched = MVEScheduler(cfg=cfg, background=True, **cell.config["server"])
    warmed = warm(sched, traffic, cfg)
    # The pre-built traffic is the benchmark's, not the server's: keep it
    # out of the collector's scans in the window.
    gc.collect()
    gc.freeze()
    return Setup(cache, cfg, traffic, sched, counter, warmed)


def warm(sched, traffic, cfg) -> Dict[str, int]:
    """Compile, or load from the persistent cache, and run once every
    executable the window will use.

    A program the run submits once is served by the VM tier: its
    signature's executable is warmed, and then the lowered programs are
    dropped from the program cache, so that the window lowers each one
    in ``submit()`` as a client's first submission would.  A program the
    run submits again and again is promoted to the fused tier through the
    scheduler itself, and its fused executable is run at every batch
    bucket up to ``max_batch``.
    """
    from repro.core import vm
    from repro.core.engine import clear_cache, compile_program

    vm.prewarm(cfg)
    progs = traffic.programs()
    once = [inst for inst, n in progs if n == 1]
    many = [inst for inst, n in progs if n > 1]
    signatures = {}
    for inst in once:
        cp = compile_program(inst.program, cfg)
        # (tier, VM signature): one run loads the signature's executable
        signatures.setdefault(cp.batch_group_key(inst.memory)[:2],
                              (cp, inst))
    for cp, inst in signatures.values():
        cp.run(inst.memory)
    if once:
        clear_cache()
    tickets = [sched.submit(inst.program, inst.memory)
               for inst in many for _ in range(sched.promote_after or 0)]
    for t in tickets:
        t.result()
    buckets = 0
    for inst in many:
        fused = compile_program(inst.program, cfg, mode="fused")
        fused.run(inst.memory)
        b = 2
        while b <= sched.max_batch:
            fused.run_batch(np.stack([inst.memory] * b))
            buckets += 1
            b *= 2
    return {"vm_signatures": len(signatures), "promoted": len(many),
            "fused_buckets": buckets + len(many)}


