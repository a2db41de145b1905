"""Signature-batched multi-tenant scheduler for MVE program serving.

The execution stack so far serves one caller at a time: every
``CompiledProgram.run`` pays its own host round trip (pad + upload +
dispatch + sync + copy-back), so a realistic serving stream — many
logical clients submitting daxpy/gemv/spmm/conv programs concurrently,
the Swan workload mix of Table III — leaves both the 8192-lane SIMD
array's batch dimension and JAX's async dispatch queue idle.  This
module adds the missing layer: an :class:`MVEScheduler` that accepts
``submit(program, memory)`` requests from many clients, coalesces them,
and executes each group as one batched dispatch.

Scheduling policy (docs/SERVING.md has the design note):

* Pending requests are bucketed by :meth:`CompiledProgram.batch_group_key`
  — for VM-routed requests that is the **VM signature bucket** (plus the
  program and memory geometry), so every group's dispatch replays through
  one signature-keyed XLA executable; groups of one signature are
  dispatched back to back to keep that executable hot.
* Within a bucket, requests for the *same* program are padded to a
  power-of-two batch (bounded by ``max_batch``), their memory images
  stacked, and executed as **one** ``run_batch`` (vmapped) dispatch.
* Two executor tiers, exactly like a tiered JIT: every program can run
  through the **VM tier** immediately (the signature-shared executable —
  zero per-program XLA compiles, which is what keeps a stream of
  data-dependent programs, e.g. one spmm program per sparsity pattern,
  servable at all), and a program whose submission count reaches
  ``promote_after`` is **promoted to the fused tier**, its own
  per-program batched executable.  ``promote_after=None`` disables
  promotion (pure-VM scheduler).
* All group dispatches of a drain cycle are enqueued asynchronously
  (``run_batch_async`` / ``run_async``); the scheduler syncs once per
  cycle, not once per request.
* Every served request, batched or alone, first try or retry, ends in
  one answer step (:meth:`MVEScheduler._answer`): injected finalize
  faults, the sampled audit, device arrays for a device image's answer,
  and the :class:`ServeResult`.

Targets: ``submit(..., target="rvv-1d")`` accepts any registered
:mod:`repro.targets` target — requests bucket per target (compilations
are tagged so one target's entries never alias another's) and the
resolved machine config rides on the ticket; unknown or
geometry-mismatched targets raise a readable
:class:`~repro.core.isa.ProgramError` (docs/TARGETS.md).

Determinism: with ``background=False`` (default) nothing executes until
:meth:`MVEScheduler.drain`, which processes every pending request on the
calling thread — submission order decides batch composition, so tests
replay identical schedules.  With ``background=True`` a worker thread
forms batches with a ``max_batch``/``max_wait_ms`` window policy, and
:meth:`submit` returns tickets that resolve concurrently.

Failure semantics (docs/SERVING.md "Failure semantics"; the components
live in :mod:`repro.resilience`): every ``submit()`` resolves, with a
result or a typed :class:`~repro.resilience.errors.SchedulerError`.  A
failed batch is bisected until the poisoned request is isolated; that
request is retried with backoff down the ladder *fused → VM → stepwise
oracle*, under per-tier circuit breakers, and quarantined when every
tier fails.  Per-request deadlines, a bounded admission queue, a
supervised background worker and a sampled bit-exact audit
(:class:`~repro.resilience.audit.ResultAuditor`) complete it.  Pass
``injector=FaultInjector(plan)`` to run a deterministic chaos schedule
against these paths (``tests/test_resilience.py``).

Device images: a request whose memory image is a ``jax.Array`` is
stacked, run and answered on the device, its answer's memory, registers
and Tag device arrays on every path (docs/SERVING.md "Device images").

Results are bit-identical to per-request ``CompiledProgram.run`` (and
therefore to the stepwise oracle): batching only stacks independent
memory images along a vmapped axis.  ``tests/test_conformance.py``
fuzzes that equivalence across all four executors.
"""
from __future__ import annotations

import contextlib
import dataclasses
import itertools
import threading
import time
from collections import OrderedDict
from typing import Callable, Dict, List, Optional, Tuple

import jax
import numpy as np

from ..core import isa
from ..core.cost import TraceEvent
from ..core.engine import (CompiledProgram, cache_info, compile_program,
                           split_rows, take_row)
from ..core.machine import MVEConfig, next_pow2
from ..core.tracing import (DISPATCH, FETCH, GROUP, IDLE, PROMOTE, RESOLVE,
                            SYNC, WINDOW, add_copies, counting, span,
                            to_device, to_host)
from ..resilience.audit import ResultAuditor
from ..resilience.breaker import CircuitBreaker, RetryPolicy
from ..resilience.errors import (CancelledError, DeadlineExceededError,
                                 InjectedWorkerDeath, QuarantinedError,
                                 QueueFullError, SchedulerClosedError,
                                 SchedulerError, WorkerDiedError)
from .health import HeartbeatMonitor, StragglerDetector

# Bookkeeping bounds: a long-lived server facing an endless stream of
# fresh (data-dependent) programs must not grow per-program state without
# limit — mirrors the engine's bounded program LRU.
_SEEN_CAP = 4096          # submission counters (promotion heat)
_PROMOTED_CAP = 64        # fused-tier executables pinned by the scheduler
_BUCKET_STAT_CAP = 4096   # distinct group keys tracked for stats
_QUARANTINE_CAP = 1024    # poisoned program keys remembered

#: name of the (single) serving worker in the heartbeat monitor
_WORKER_HOST = "serve-worker"


class ServeResult:
    """Per-request outcome, duck-type compatible with
    :class:`repro.core.engine.ExecutionResult` for the common fields.

    ``tier`` records which executor produced it: ``"vm"`` / ``"fused"``
    (``batch_size`` 1 for an un-batched dispatch) or ``"oracle"``
    (stepwise-interpreter fallback of the degradation ladder).

    ``trace`` is materialized lazily for batched results (a fresh copy of
    the compile-time static trace): serving loops that never read it pay
    nothing for it.  So are the answers of a batch of device images
    (``rows``, a :class:`DeviceRows`): ``memory``, ``regs`` and ``tag``
    come out of the batch's device outputs when first read, on the
    reading thread, not on the serve worker's.
    """

    __slots__ = ("_memory", "_regs", "_tag", "_rows", "_row", "batch_size",
                 "tier", "_trace", "_trace_fn", "kernel", "_operands")

    def __init__(self, memory: np.ndarray, regs: Dict[int, np.ndarray],
                 tag: np.ndarray, batch_size: int, tier: str,
                 trace: Optional[List[TraceEvent]] = None,
                 trace_fn: Optional[Callable[[], List[TraceEvent]]] = None,
                 kernel=None, rows: Optional["DeviceRows"] = None,
                 row: int = 0):
        self._memory = memory
        self._regs = regs
        self._tag = tag
        self._rows = rows
        self._row = row
        self.batch_size = batch_size   # how many requests shared the dispatch
        self.tier = tier               # "vm" | "fused" | "oracle"
        self._trace = trace
        self._trace_fn = trace_fn
        self.kernel = kernel           # frontend Kernel, when submitted as one
        self._operands = None

    @property
    def memory(self):
        if self._memory is None:
            self._memory = self._rows.memory(self._row)
        return self._memory

    @property
    def regs(self) -> Dict[int, np.ndarray]:
        if self._regs is None:
            self._regs, self._tag = self._rows.state(self._row)
        return self._regs

    @property
    def tag(self):
        if self._tag is None:
            self._regs, self._tag = self._rows.state(self._row)
        return self._tag

    @property
    def trace(self) -> List[TraceEvent]:
        if self._trace is None:
            self._trace = self._trace_fn() if self._trace_fn else []
        return self._trace

    @property
    def operands(self) -> Optional[Dict[str, np.ndarray]]:
        """Results read back by operand name (kernel submissions only);
        materialised lazily like ``trace``."""
        if self._operands is None and self.kernel is not None:
            self._operands = self.kernel.unpack(self.memory)
        return self._operands

    def __repr__(self) -> str:
        return (f"ServeResult(tier={self.tier!r}, "
                f"batch_size={self.batch_size}, "
                f"memory.shape={tuple(np.shape(self.memory))})")


class DeviceRows:
    """The device outputs of one batched dispatch of device images, read
    per request on demand: every request's memory by one jitted split of
    the batch on the first read (:func:`~repro.core.engine.split_rows`),
    a request's registers and Tag by one jitted read
    (:func:`~repro.core.engine.take_row`)."""

    def __init__(self, memory, regs, tag):
        self._memory = memory
        self._state = (regs, tag)
        self._split = None
        self._lock = threading.Lock()

    def memory(self, row: int):
        with self._lock:
            if self._split is None:
                self._split = split_rows(self._memory)
                self._memory = None
        return self._split[row]

    def state(self, row: int):
        return take_row(self._state, row)


class Ticket:
    """Future-like handle returned by :meth:`MVEScheduler.submit`.

    Resolution is race-safe and exactly-once: the first of {scheduler
    result, scheduler error, :meth:`cancel`, :meth:`MVEScheduler.close`}
    wins and the rest are no-ops, so a timed-out :meth:`result` waiter
    can always cancel without racing an in-flight resolution.
    """

    def __init__(self, rid: int, program, memory, cp: CompiledProgram,
                 submitted_at: Optional[float] = None, kernel=None,
                 cfg: Optional[MVEConfig] = None,
                 target: Optional[str] = None,
                 deadline: Optional[float] = None):
        self.rid = rid
        self.program = program
        self.memory = memory
        self.cp = cp
        self.kernel = kernel
        self.cfg = cfg                 # machine config this request runs under
        self.target = target           # registered target name (None=default)
        self.deadline = deadline       # absolute perf_counter() deadline
        self.submitted_at = submitted_at if submitted_at is not None \
            else time.perf_counter()
        self.queued_at: Optional[float] = None   # entered the pending queue
        self.taken_at: Optional[float] = None    # a serve cycle took it
        self.done_at: Optional[float] = None
        self._event = threading.Event()
        self._resolve_lock = threading.Lock()
        self._result: Optional[ServeResult] = None
        self._error: Optional[BaseException] = None

    def done(self) -> bool:
        return self._event.is_set()

    def result(self, timeout: Optional[float] = None) -> ServeResult:
        """Block until the request is served (or ``timeout`` seconds).

        A ``TimeoutError`` does **not** orphan the ticket: it stays
        pending and will still be resolved by the scheduler — call
        :meth:`cancel` to resolve it now and drop the request."""
        if not self._event.wait(timeout):
            raise TimeoutError(
                f"request {self.rid} not served in time "
                f"(ticket still pending; cancel() to abandon it)")
        if self._error is not None:
            raise self._error
        return self._result

    def error(self) -> Optional[BaseException]:
        """The resolution error, if the ticket failed (non-blocking)."""
        return self._error if self._event.is_set() else None

    def cancel(self) -> bool:
        """Resolve the ticket with
        :class:`~repro.resilience.errors.CancelledError` if it is still
        pending.  Returns ``True`` when the cancellation won the race,
        ``False`` when the ticket was already resolved (its result/error
        stands).  The scheduler skips cancelled tickets at dispatch."""
        return self._resolve(error=CancelledError(
            f"request {self.rid} cancelled by client"))

    @property
    def latency(self) -> float:
        """Submit-to-completion wall time in seconds."""
        if self.done_at is None:
            raise RuntimeError("request not finished")
        return self.done_at - self.submitted_at

    def _resolve(self, result=None, error=None) -> bool:
        """First resolution wins; returns whether this call resolved."""
        with self._resolve_lock:
            if self._event.is_set():
                return False
            self._result, self._error = result, error
            self.done_at = time.perf_counter()
            self._event.set()
            return True


@dataclasses.dataclass
class SchedulerStats:
    """Counters since construction (see also :meth:`cache_info` and
    :meth:`MVEScheduler.health`)."""

    requests: int = 0
    dispatches: int = 0          # executable launches (any tier)
    batched_requests: int = 0    # requests served by a >1 dispatch
    vm_batches: int = 0
    fused_batches: int = 0
    singles: int = 0
    promotions: int = 0          # programs promoted to the fused tier
    drains: int = 0
    max_batch_seen: int = 0
    signature_buckets: int = 0   # distinct group keys seen
    # -- resilience (PR 7) -------------------------------------------------
    retries: int = 0             # single-request re-executions after failure
    bisections: int = 0          # failed batches split to isolate poison
    recovered: int = 0           # requests served after >= 1 failure
    oracle_serves: int = 0       # requests served by the stepwise oracle tier
    demotions: int = 0           # tier steps down the fused->vm->oracle ladder
    quarantines: int = 0         # requests resolved with QuarantinedError
    quarantine_rejects: int = 0  # submissions rejected while quarantined
    breaker_opens: int = 0       # circuit-breaker open transitions
    breaker_skips: int = 0       # dispatches skipped because a breaker was open
    promotion_failures: int = 0  # fused-tier compiles that failed
    deadline_misses: int = 0     # requests resolved with DeadlineExceededError
    sheds: int = 0               # requests shed by the bounded admission queue
    audit_checked: int = 0       # served results integrity-audited
    audit_corrected: int = 0     # audited results replaced by the reference
    worker_restarts: int = 0     # background worker deaths survived
    worker_errors: int = 0       # serve-cycle failures caught by the backstop
    # -- serve path (docs/SERVING.md "Observability") ----------------------
    taken: int = 0               # requests a serve cycle took from the queue
    queue_wait_s: float = 0.0    # their summed time in the queue
    h2d_bytes: int = 0           # bytes copied host -> device by dispatches
    d2h_bytes: int = 0           # bytes copied device -> host by finalizes
    resident_requests: int = 0   # device-image requests answered on the device

    @property
    def batch_efficiency(self) -> float:
        """Mean requests per dispatch — the lane-saturation proxy."""
        return self.requests / self.dispatches if self.dispatches else 0.0


@dataclasses.dataclass
class _DispatchCtx:
    """Everything the recovery path needs to replay a group dispatch."""

    key: tuple                   # target-tagged signature bucket
    runner: CompiledProgram      # the group's tier: fused or its base tier

    @property
    def bkey(self) -> tuple:
        """The circuit-breaker key of the group's tier."""
        return (self.key, self.runner.mode)

    @property
    def routed_vm(self) -> bool:
        """Whether the group routes to the VM (after the target tag)."""
        return self.key[1] == "vm"

    @property
    def resident(self) -> bool:
        """Whether the group's images are device arrays: the bucket's last
        element (``batch_group_key``), so a group never mixes the two."""
        return self.key[-1]


class MVEScheduler:
    """Multi-tenant MVE program scheduler with signature batching and
    self-healing failure semantics (module docstring; docs/SERVING.md).

    Parameters
    ----------
    cfg: machine config shared by every request (one lane geometry).
    max_batch: largest fused-tier dispatch; groups beyond it are split.
    vm_max_batch: largest VM-tier dispatch.  The vmapped while-loop
        datapath stops gaining past small batches on the CPU substrate
        (measured sweet spot ~4), while the fused tier keeps scaling.
    promote_after: submissions of one program after which it is compiled
        into the fused tier (``None`` disables promotion).
    background: serve from a worker thread (``max_wait_ms`` batching
        window) instead of explicit :meth:`drain` calls.
    max_queue: bound on the pending-request queue (``None`` = unbounded).
    admission: ``"block"`` (submit waits for space — needs a concurrent
        drainer, i.e. ``background=True`` or another thread calling
        :meth:`drain`) or ``"shed"`` (resolve immediately with
        ``QueueFullError``).
    breaker: :class:`~repro.resilience.breaker.CircuitBreaker` keyed per
        ``(signature bucket, tier)``; open tiers are skipped (degradation
        ladder fused → vm → oracle).
    quarantine_cooldown_s: how long a poisoned program key is rejected
        before one probe submission is allowed again.
    audit_rate: share of served results re-executed on the other
        executor and compared bit for bit
        (:class:`~repro.resilience.audit.ResultAuditor`); 0 disables.
    injector: :class:`~repro.resilience.faults.FaultInjector` executing a
        deterministic chaos plan against this scheduler's serve paths.
    """

    def __init__(self, cfg: Optional[MVEConfig] = None, max_batch: int = 16,
                 vm_max_batch: int = 4,
                 promote_after: Optional[int] = 2,
                 background: bool = False, max_wait_ms: float = 2.0,
                 max_queue: Optional[int] = None,
                 admission: str = "block",
                 breaker: Optional[CircuitBreaker] = None,
                 quarantine_cooldown_s: float = 30.0,
                 audit_rate: float = 0.0, injector=None):
        self.cfg = cfg or MVEConfig()
        # Batch caps are floored to powers of two: dispatch stacks are
        # padded to the next power of two, so a non-pow2 cap would let a
        # padded dispatch exceed it.
        self.max_batch = _floor_pow2(max(1, int(max_batch)))
        self.vm_max_batch = _floor_pow2(max(1, int(vm_max_batch)))
        self.promote_after = promote_after
        self.max_wait_ms = max_wait_ms
        if admission not in ("block", "shed"):
            raise ValueError(f"unknown admission policy {admission!r}")
        self.max_queue = max_queue
        self.admission = admission
        self.retry = RetryPolicy()
        self.quarantine_cooldown_s = quarantine_cooldown_s
        self.stats = SchedulerStats()
        # Threshold > (1 + default max_retries): one permanently poisoned
        # request exhausting its per-tier retries must not open a breaker
        # that healthy siblings of the same signature bucket share.
        self._breaker = breaker or CircuitBreaker(threshold=5,
                                                  cooldown_s=5.0)
        self._injector = injector
        self._auditor = ResultAuditor(self._oracle, audit_rate,
                                      injector=injector) \
            if audit_rate > 0.0 else None
        self._heartbeat = HeartbeatMonitor(hosts=[], timeout_s=10.0)
        self._stragglers = StragglerDetector(window=8)
        self._sleep = time.sleep           # patchable in tests
        self._rid = itertools.count()
        self._lock = threading.Lock()
        self._serve_lock = threading.Lock()      # drain() vs worker _serve
        self._pending: List[Ticket] = []
        # program key -> submissions (bounded LRU: promotion heat only)
        self._seen: "OrderedDict[Tuple, int]" = OrderedDict()
        self._promoted: "OrderedDict[Tuple, CompiledProgram]" = OrderedDict()
        self._quarantined: "OrderedDict[Tuple, float]" = OrderedDict()
        self._oracles: Dict[MVEConfig, object] = {}
        self._group_keys_seen = set()
        self._wake = threading.Condition(self._lock)
        self._space = threading.Condition(self._lock)  # queue has room
        self._closed = False
        self._worker: Optional[threading.Thread] = None
        if background:
            self._worker = threading.Thread(
                target=self._worker_main, daemon=True, name="mve-scheduler")
            self._worker.start()

    # -- client API --------------------------------------------------------
    def _resolve_target(self, target) -> Tuple[MVEConfig, Optional[str]]:
        """(machine config, cache tag) for one submission's target.

        Unknown names raise :class:`~repro.core.isa.ProgramError` naming
        every registered target; a target whose machine geometry cannot
        share this scheduler's lane/CB layout is rejected the same way —
        both used to surface as ``KeyError``-shaped internal failures.
        """
        if target is None:
            return self.cfg, None
        from .. import targets as _targets
        tgt = _targets.get_target(target)      # ProgramError when unknown
        cfg = tgt.machine_config(self.cfg)
        if (cfg.lanes, cfg.num_cbs) != (self.cfg.lanes, self.cfg.num_cbs):
            raise isa.ProgramError(
                f"target {tgt.name!r} needs machine geometry "
                f"(lanes={cfg.lanes}, cbs={cfg.num_cbs}) but this "
                f"scheduler batches for (lanes={self.cfg.lanes}, "
                f"cbs={self.cfg.num_cbs}); submit it to a scheduler "
                f"built with that geometry.  Registered targets: "
                f"{', '.join(_targets.list_targets())}")
        return cfg, tgt.name

    def submit(self, program: isa.Program, memory=None,
               target=None, deadline_s: Optional[float] = None) -> Ticket:
        """Enqueue one program execution; returns a :class:`Ticket`.

        ``program`` is a raw instruction sequence plus a flat memory
        image, or a frontend :class:`~repro.frontend.Kernel` plus a dict
        of named operand arrays (or nothing — declared inits apply);
        kernel submissions read results back by name through
        ``ticket.result().operands``.

        ``target`` selects a registered :mod:`repro.targets` target (a
        name or instance).  Execution is bit-identical on every target —
        the scheduler's value per target is *bucketing*: requests are
        grouped per target (so per-target compilations never alias,
        ``cache_info().per_target``) and the resolved machine config
        rides on the ticket for downstream pricing.  Unknown or
        geometry-mismatched targets raise a
        :class:`~repro.core.isa.ProgramError` naming the registered
        targets.

        ``deadline_s`` bounds this request's submit-to-resolution time
        (default: none); past it the ticket resolves with
        ``DeadlineExceededError`` instead of retrying further.

        The returned ticket **always resolves** — with a
        :class:`ServeResult` or a typed
        :class:`~repro.resilience.errors.SchedulerError`.

        Thread-safe; callable from any number of client threads.  In
        deterministic mode nothing runs until :meth:`drain`."""
        submitted_at = time.perf_counter()   # before the (cold) compile
        cfg, tag = self._resolve_target(target)
        kernel = None
        if hasattr(program, "plan") and hasattr(program, "program"):
            kernel = program
            if memory is None or isinstance(memory, dict):
                memory = kernel.pack(memory)  # named arrays / inits
            # else: an already-packed flat memory image — pass through
            program = kernel.program
        elif memory is None:
            raise TypeError("raw program submissions need a memory image")
        cp = compile_program(kernel or program, cfg, cache_tag=tag)
        t = Ticket(next(self._rid), tuple(program), memory, cp,
                   submitted_at=submitted_at, kernel=kernel,
                   cfg=cfg, target=tag,
                   deadline=None if deadline_s is None
                   else submitted_at + deadline_s)
        with self._lock:
            if self._closed:
                raise SchedulerClosedError("scheduler is closed")
            if self.max_queue is not None:
                while len(self._pending) >= self.max_queue \
                        and not self._closed:
                    if self.admission == "shed":
                        self.stats.sheds += 1
                        t._resolve(error=QueueFullError(
                            f"admission queue full "
                            f"({self.max_queue} pending); request "
                            f"{t.rid} shed — back off and resubmit"))
                        return t
                    self._space.wait(timeout=0.05)
                if self._closed:
                    raise SchedulerClosedError("scheduler closed while "
                                               "waiting for queue space")
            self.stats.requests += 1
            pk = (t.program, cfg, tag)
            self._seen[pk] = self._seen.get(pk, 0) + 1
            self._seen.move_to_end(pk)
            while len(self._seen) > _SEEN_CAP:
                self._seen.popitem(last=False)
            t.queued_at = time.perf_counter()
            self._pending.append(t)
            self._wake.notify()
        return t

    def drain(self) -> None:
        """Serve every pending request on the calling thread and return
        when all are resolved — the deterministic mode tests replay."""
        while True:
            with self._lock:
                batch = self._take()
            if not batch:
                return
            self._serve(batch)

    def close(self, drain: bool = True) -> None:
        """Shut down: stop the background worker, optionally serve what
        is still pending (``drain=True``, the default), then resolve
        every ticket that remains unresolved with a typed
        :class:`~repro.resilience.errors.SchedulerClosedError` — no
        waiter is ever left hanging on a closed scheduler."""
        with self._lock:
            self._closed = True
            self._wake.notify_all()
            self._space.notify_all()
        if self._worker is not None:
            self._worker.join(timeout=30)
            self._worker = None
        if drain:
            self.drain()
        with self._lock:
            leftovers, self._pending = self._pending, []
        for t in leftovers:
            t._resolve(error=SchedulerClosedError(
                f"scheduler closed before request {t.rid} was served"))

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    def count_copies(self, h2d: int = 0, d2h: int = 0) -> None:
        """Count host-device copies that a client makes itself for its
        requests (uploads into its device images, fetches of parts of
        their answers) in ``stats.h2d_bytes`` / ``d2h_bytes``, beside the
        serve cycle's own; thread-safe."""
        add_copies(self.stats, h2d, d2h)

    def warm_resident(self, program, memory_size: int) -> None:
        """Compile, without running ``program``, what serving it from
        device images of ``memory_size`` words on the fused tier takes
        beyond its own executables: a singleton's copy of its image and,
        at each batch bucket up to ``max_batch``, the device stack, the
        split of the memories and the read of a request's registers and
        Tag (:meth:`CompiledProgram.warmup_resident`)."""
        buckets = tuple(1 << i for i in range(1, self.max_batch.bit_length()))
        compile_program(program, self.cfg, mode="fused").warmup_resident(
            memory_size, buckets)

    def cache_info(self):
        """The engine/VM compile-cache counters this scheduler feeds —
        promotion compiles land in the same program LRU, VM dispatches in
        the same signature-keyed executable cache
        (:func:`repro.core.engine.cache_info`)."""
        return cache_info()

    def health(self) -> Dict:
        """One structured snapshot of the runtime's failure state:
        worker liveness/heartbeats, per-tier straggler flags, open
        circuit breakers, quarantine population, and the
        retry/shed/deadline/audit counters — the payload a mesh-level
        coordinator would scrape (ROADMAP device-mesh item)."""
        with self._lock:
            pending = len(self._pending)
            quarantined = len(self._quarantined)
            worker = self._worker
        st = self.stats
        snap = {
            "pending": pending,
            "closed": self._closed,
            "worker": {
                "alive": worker.is_alive() if worker is not None else None,
                "restarts": st.worker_restarts,
                "errors": st.worker_errors,
                "dead_hosts": self._heartbeat.dead_hosts(),
            },
            "stragglers": self._stragglers.stragglers(),
            "breakers": {"open": self._breaker.snapshot(),
                         "opens": st.breaker_opens,
                         "skips": st.breaker_skips},
            "quarantine": {"active": quarantined,
                           "total": st.quarantines,
                           "rejects": st.quarantine_rejects},
            "counters": {
                "requests": st.requests,
                "retries": st.retries,
                "bisections": st.bisections,
                "recovered": st.recovered,
                "oracle_serves": st.oracle_serves,
                "demotions": st.demotions,
                "deadline_misses": st.deadline_misses,
                "sheds": st.sheds,
                "promotion_failures": st.promotion_failures,
            },
            "audit": ({"checked": st.audit_checked,
                       "mismatches": st.audit_corrected}
                      if self._auditor is not None else None),
            "injected": (self._injector.counts()
                         if self._injector is not None else None),
        }
        return snap

    # -- background worker -------------------------------------------------
    def _worker_main(self) -> None:
        """Supervisor shell around :meth:`_serve_loop`: a worker death
        (injected or real) re-queues whatever the dead incarnation held
        and restarts the loop — zero orphaned tickets, invisible to
        clients."""
        while True:
            try:
                self._serve_loop()
                return                          # clean close
            except (KeyboardInterrupt, SystemExit):
                raise
            except BaseException:
                with self._lock:
                    self.stats.worker_restarts += 1
                    if self._closed and not self._pending:
                        return
                continue

    def _serve_loop(self) -> None:
        while True:
            batch: List[Ticket] = []
            try:
                with self._lock:
                    if not self._pending and not self._closed:
                        with span(IDLE):
                            while not self._pending and not self._closed:
                                self._wake.wait()
                    if self._closed and not self._pending:
                        return
                    deadline = time.perf_counter() + self.max_wait_ms / 1e3
                    # batching window: wait for more work until the window
                    # closes or a full batch is ready
                    if len(self._pending) < self.max_batch \
                            and not self._closed:
                        with span(WINDOW):
                            while (len(self._pending) < self.max_batch
                                   and not self._closed):
                                left = deadline - time.perf_counter()
                                if left <= 0 or \
                                        not self._wake.wait(timeout=left):
                                    break
                    batch = self._take()
                self._heartbeat.beat(_WORKER_HOST)
                if self._injector is not None:
                    self._injector.worker_tick()   # may kill this worker
                if batch:
                    self._serve(batch)
            except InjectedWorkerDeath:
                # Simulated thread death: put the work back for the next
                # incarnation (the supervisor restarts us) and die.
                self._requeue(batch)
                raise
            except (KeyboardInterrupt, SystemExit) as e:
                # Re-raise after resolving: in-flight tickets must never
                # be dropped on the interpreter-shutdown path.
                self.stats.worker_errors += 1
                for t in batch:
                    t._resolve(error=WorkerDiedError(
                        f"serving worker interrupted "
                        f"({type(e).__name__})"))
                raise
            except BaseException as e:   # pragma: no cover - backstop
                # _serve() has its own per-ticket error handling; anything
                # that still escapes is an internal error — account for it
                # and resolve, never drop.
                self.stats.worker_errors += 1
                for t in batch:
                    t._resolve(error=e)

    def _take(self) -> List[Ticket]:
        """Pop every pending ticket (caller holds ``_lock``), stamping
        ``taken_at`` and counting each one's time in the queue."""
        batch, self._pending = self._pending, []
        self._space.notify_all()
        now = time.perf_counter()
        for t in batch:
            t.taken_at = now
            self.stats.queue_wait_s += now - t.queued_at
        self.stats.taken += len(batch)
        return batch

    def _requeue(self, batch: List[Ticket]) -> None:
        alive = [t for t in batch if not t.done()]
        if not alive:
            return
        with self._lock:
            now = time.perf_counter()
            for t in alive:
                t.queued_at = now
            self._pending[:0] = alive       # head: preserve arrival order
            self._wake.notify_all()

    # -- the scheduling core -----------------------------------------------
    def _serve(self, batch: List[Ticket]) -> None:
        """Group -> dispatch (async) -> finalize -> recover, one sync per
        healthy cycle.

        Serialized with ``_serve_lock``: an explicit :meth:`drain` racing
        the background worker must not interleave stats/promotion
        bookkeeping (each still serves only tickets it popped itself).
        The ``finally`` backstop upholds the resolution guarantee even
        against internal scheduler bugs."""
        with self._serve_lock, counting(self.stats):
            try:
                self._serve_locked(batch)
            except (KeyboardInterrupt, SystemExit):
                raise
            except BaseException as e:
                self.stats.worker_errors += 1
                for t in batch:
                    t._resolve(error=e)
            finally:
                for t in batch:
                    if t._resolve(error=SchedulerError(
                            f"request {t.rid} fell through the serve "
                            f"cycle (internal scheduler error)")):
                        self.stats.worker_errors += 1

    def _serve_locked(self, batch: List[Ticket]) -> None:
        with span(GROUP):
            buckets = self._group(batch)

        inflight = []   # (ctx, tickets, finalize_thunk)
        for key, groups in buckets.items():
            # Same signature bucket back to back: every VM group replays
            # through the same signature-keyed executable while it is hot.
            for tickets in groups.values():
                with span(GROUP):
                    tickets = self._admit(tickets)
                    fused, due = self._promotion(key, tickets[0]) \
                        if tickets else (None, False)
                if not tickets:
                    continue
                if due:
                    fused = self._promote(key, tickets[0])
                ctx = _DispatchCtx(key=key, runner=fused
                                   if fused is not None else tickets[0].cp)
                if not self._breaker.allow(ctx.bkey):
                    # Tier breaker open: skip the batched dispatch and
                    # walk each request down the degradation ladder.
                    self.stats.breaker_skips += 1
                    for t in tickets:
                        self._serve_one_resilient(ctx, t, None)
                    continue
                # Only VM-routed requests on their base tier get the VM
                # tier's batch cap; fused-routed ones (non-float32-
                # canonical images, VM fallbacks) batch at the fused cap.
                cap = self.vm_max_batch if ctx.routed_vm and fused is None \
                    else self.max_batch
                for chunk in _chunks(tickets, cap):
                    try:
                        inflight.append((ctx, chunk, self._dispatch(
                            ctx, chunk, ctx.runner)))
                    except Exception as e:
                        self._record_failure(ctx.bkey)
                        self._recover_group(ctx, chunk, e)

        for ctx, tickets, finalize in inflight:
            t0 = time.perf_counter()
            try:
                results = finalize()
            except Exception as e:
                self._record_failure(ctx.bkey)
                self._recover_group(
                    ctx, [t for t in tickets if not t.done()], e)
                continue
            with span(RESOLVE):
                self._stragglers.record(ctx.runner.mode,
                                        time.perf_counter() - t0)
                self._breaker.record_success(ctx.bkey)
                for t, r in zip(tickets, results):
                    t._resolve(result=r)

    def _group(self, batch: List[Ticket]):
        """Drop what is resolved or past its deadline, and bucket the
        rest: signature bucket -> (program, bucket) -> tickets."""
        self.stats.drains += 1
        now = time.perf_counter()
        live: List[Ticket] = []
        for t in batch:
            if t.done():                    # cancelled / shed / pre-resolved
                continue
            if t.deadline is not None and now > t.deadline:
                self.stats.deadline_misses += 1
                t._resolve(error=DeadlineExceededError(
                    f"request {t.rid} missed its deadline before "
                    f"dispatch"))
                continue
            live.append(t)

        buckets: "OrderedDict[tuple, OrderedDict[tuple, List[Ticket]]]" = \
            OrderedDict()
        for t in live:
            # Per-target signature bucketing: the leading tag keeps one
            # target's groups from stacking with another's even when the
            # VM signature coincides (their cost models differ; pricing
            # rides on the ticket's target).
            key = (t.target,) + tuple(t.cp.batch_group_key(t.memory))
            gkey = (t.program, key)
            buckets.setdefault(key, OrderedDict()).setdefault(
                gkey, []).append(t)
            if len(self._group_keys_seen) < _BUCKET_STAT_CAP:
                self._group_keys_seen.add(key)
        self.stats.signature_buckets = len(self._group_keys_seen)
        return buckets

    def _admit(self, tickets: List[Ticket]) -> List[Ticket]:
        """A group's tickets still to serve: unresolved ones, unless their
        program is quarantined (then each resolves with
        ``QuarantinedError``)."""
        tickets = [t for t in tickets if not t.done()]
        if not tickets:
            return tickets
        pk = (tickets[0].program, tickets[0].cfg, tickets[0].target)
        if not self._quarantine_active(pk):
            return tickets
        self.stats.quarantine_rejects += len(tickets)
        for t in tickets:
            t._resolve(error=QuarantinedError(
                f"request {t.rid}: program is quarantined "
                f"after repeated failures (cooldown "
                f"{self.quarantine_cooldown_s:.0f}s)"))
        return []

    def _promotion(self, key, ticket
                   ) -> Tuple[Optional[CompiledProgram], bool]:
        """The lookups of promotion: ``(fused, due)``, the fused-tier
        executable of a hot program (``None`` while it stays in its base
        tier, when promotion is off, the program already runs fused, or the
        fused tier's breaker is open) and whether its promotion compile is
        due (:meth:`_promote`).  Promotion heat and the fused compilation
        are both per ``(program, config, target)`` — one target's
        promotion never serves (or evicts) another's."""
        if self.promote_after is None or ticket.cp.mode == "fused":
            return None, False
        if not self._breaker.allow((key, "fused")):
            self.stats.breaker_skips += 1
            return None, False
        pk = (ticket.program, ticket.cfg, ticket.target)
        hot = self._promoted.get(pk)
        if hot is not None:
            self._promoted.move_to_end(pk)
            return hot, False
        return None, self._seen.get(pk, 0) >= self.promote_after

    def _promote(self, key, ticket) -> Optional[CompiledProgram]:
        """Compile a hot program into the fused tier.  A failed promotion
        compile is a tier failure, not a request failure: the group still
        serves on its base tier."""
        pk = (ticket.program, ticket.cfg, ticket.target)
        try:
            with span(PROMOTE):
                if self._injector is not None:
                    self._injector.compile([ticket.rid], tier="fused")
                hot = compile_program(list(pk[0]), ticket.cfg, mode="fused",
                                      cache_tag=ticket.target)
        except Exception:
            self.stats.promotion_failures += 1
            self._record_failure((key, "fused"))
            return None
        self._promoted[pk] = hot
        while len(self._promoted) > _PROMOTED_CAP:
            self._promoted.popitem(last=False)
        self.stats.promotions += 1
        return hot

    def _record_failure(self, bkey) -> bool:
        """A failure on a tier's breaker; whether it opened the breaker."""
        opened = self._breaker.record_failure(bkey)
        self.stats.breaker_opens += opened
        return opened

    # -- recovery ladder ---------------------------------------------------
    def _recover_group(self, ctx: _DispatchCtx, tickets: List[Ticket],
                       err: Optional[BaseException]) -> None:
        """Bisect a failed group until the poison is isolated: clean
        halves re-dispatch *batched* (shielded from one-shot injected
        faults — the retry semantics), single failures walk the
        per-request resilient path."""
        tickets = [t for t in tickets if not t.done()]
        if not tickets:
            return
        if len(tickets) == 1:
            self._serve_one_resilient(ctx, tickets[0], err)
            return
        self.stats.bisections += 1
        mid = len(tickets) // 2
        for half in (tickets[:mid], tickets[mid:]):
            try:
                results = self._dispatch(ctx, half, ctx.runner,
                                         shielded=True)()
            except Exception as e:
                self._recover_group(ctx, half, e)
                continue
            for t, r in zip(half, results):
                if t._resolve(result=r):
                    self.stats.recovered += 1

    def _serve_one_resilient(self, ctx: _DispatchCtx, t: Ticket,
                             first_err: Optional[BaseException]) -> None:
        """Serve one request through the degradation ladder
        fused → vm → stepwise oracle, with bounded retry + backoff per
        tier, deadline checks before every attempt, and quarantine as
        the end state."""
        if t.done():
            return
        last = first_err
        attempts = 0
        # the group's tier, the base tier when that is another, the oracle
        ladder = [ctx.runner] + ([t.cp] if t.cp.mode != ctx.runner.mode
                                 else []) + [None]
        for runner in ladder:
            tier = runner.mode if runner is not None else "oracle"
            bkey = (ctx.key, tier)
            if tier != "oracle" and not self._breaker.allow(bkey):
                self.stats.breaker_skips += 1
                self.stats.demotions += 1       # skipped == stepped down
                continue
            for delay in itertools.chain([0.0], self.retry.delays()):
                if delay > 0:
                    self._sleep(delay)
                if t.done():
                    return
                if t.deadline is not None \
                        and time.perf_counter() > t.deadline:
                    self.stats.deadline_misses += 1
                    t._resolve(error=DeadlineExceededError(
                        f"request {t.rid} exceeded its deadline after "
                        f"{attempts} recovery attempt(s)"))
                    return
                attempts += 1
                if attempts > 1 or first_err is not None:
                    self.stats.retries += 1
                try:
                    # Shielded: one-shot injected faults do not re-fire (a
                    # retry runs on a fresh resource), but rid-bound
                    # sticky faults (a poisoned request) still do.
                    r = self._dispatch(ctx, [t], runner, shielded=True)()[0] \
                        if runner is not None else self._oracle_answer(ctx, t)
                except Exception as e:
                    last = e
                    if tier != "oracle" and self._record_failure(bkey):
                        break               # tier just opened: demote now
                    continue
                if tier != "oracle":
                    self._breaker.record_success(bkey)
                if t._resolve(result=r):
                    if first_err is not None or attempts > 1:
                        self.stats.recovered += 1
                return
            self.stats.demotions += 1
        # Every tier (oracle included) failed: isolate the poison.
        self._quarantine_request(ctx, t, last, attempts)

    def _oracle_answer(self, ctx: _DispatchCtx, t: Ticket) -> ServeResult:
        """Serve one request on the stepwise oracle, shielded."""
        if self._injector is not None:
            self._injector.dispatch([t.rid], "oracle", shielded=True)
        self.stats.dispatches += 1
        self.stats.singles += 1
        self.stats.max_batch_seen = max(self.stats.max_batch_seen, 1)
        self.stats.oracle_serves += 1
        mem, st = self._oracle(t.cfg).run_stepwise(list(t.program), t.memory)
        regs, tag = st.regs, st.tag
        if not ctx.resident:
            mem, tag = np.asarray(mem), np.asarray(tag)
            regs = {r: np.asarray(v) for r, v in regs.items()}
        return self._answer(ctx, [t], "oracle", None, (mem, regs, tag),
                            st.trace, shielded=True)[0]

    def _oracle(self, cfg: MVEConfig):
        o = self._oracles.get(cfg)
        if o is None:
            from ..core.interp import MVEInterpreter
            o = self._oracles[cfg] = MVEInterpreter(cfg, compiled=False)
        return o

    # -- quarantine --------------------------------------------------------
    def _quarantine_active(self, pk) -> bool:
        with self._lock:
            ts = self._quarantined.get(pk)
            if ts is None:
                return False
            if time.monotonic() - ts >= self.quarantine_cooldown_s:
                del self._quarantined[pk]   # parole: allow one probe
                return False
            return True

    def _quarantine_request(self, ctx: _DispatchCtx, t: Ticket,
                            last: Optional[BaseException],
                            attempts: int) -> None:
        pk = (t.program, t.cfg, t.target)
        with self._lock:
            self._quarantined[pk] = time.monotonic()
            self._quarantined.move_to_end(pk)
            while len(self._quarantined) > _QUARANTINE_CAP:
                self._quarantined.popitem(last=False)
        self.stats.quarantines += 1
        err = QuarantinedError(
            f"request {t.rid} failed on every tier after {attempts} "
            f"attempt(s); program quarantined for "
            f"{self.quarantine_cooldown_s:.0f}s "
            f"(last error: {type(last).__name__ if last else 'n/a'}: "
            f"{last})", attempts=attempts)
        err.__cause__ = last
        t._resolve(error=err)

    # -- dispatch ----------------------------------------------------------
    def _dispatch(self, ctx: _DispatchCtx, tickets: List[Ticket],
                  runner: CompiledProgram, shielded: bool = False):
        """Launch one group on ``runner`` asynchronously; returns a
        finalize thunk that answers it (:meth:`_answer`).  A shielded
        dispatch is a recovery run: the engine's deep fault hooks are
        suspended while it runs and finalizes."""
        n = len(tickets)
        if n == 1:
            tier, rows = runner.mode, 1
        else:
            tier = runner.mode if ctx.routed_vm else "fused"
            # Pad the stack to a power of two so each program compiles at
            # most log2(max_batch) batched executables; padded rows replay
            # the first request's image and are dropped after the dispatch.
            rows = next_pow2(n)
        inj = self._injector
        shield = inj.suspended() if shielded and inj is not None \
            else contextlib.nullcontext()
        resident = ctx.resident
        stats = self.stats
        with span(DISPATCH, tier=tier, n=n, rows=rows):
            if inj is not None:
                inj.dispatch([t.rid for t in tickets], runner.mode,
                             shielded=shielded)
            with shield:
                if n == 1:
                    # Singleton: skip the vmap wrapper (and get the exact
                    # random-access trace for free via finalize_run).
                    pending = runner.run_async(tickets[0].memory)
                    stats.singles += 1
                else:
                    mems = [t.memory if resident else np.asarray(t.memory)
                            for t in tickets]
                    mems += [mems[0]] * (rows - n)
                    # the engine stacks a list of device images on the
                    # device
                    pending = runner.run_batch_async(
                        mems if resident else np.stack(mems))
                    stats.batched_requests += n
                    if tier == "fused":
                        stats.fused_batches += 1
                    else:
                        stats.vm_batches += 1
            stats.dispatches += 1
            stats.max_batch_seen = max(stats.max_batch_seen, n)

        def finalize():
            with shield:
                with span(SYNC):
                    runner.wait(pending)
                with span(FETCH):
                    if n == 1:
                        mem, state = runner.finalize_run(pending)
                        regs, tag, trace = state.regs, state.tag, state.trace
                    else:
                        mem, regs, tag = runner.finalize_batch(pending)
                        trace = None
                    if not resident:
                        # One device->host transfer per array (not per
                        # request): a batch's per-request views slice host
                        # memory.
                        mem = to_host(mem)
                        if n > 1:
                            tag = to_host(tag)
                            regs = {r: to_host(v) for r, v in regs.items()}
            with span(RESOLVE):
                return self._answer(ctx, tickets, tier, runner.mode,
                                    (mem, regs, tag), trace, shielded)
        return finalize

    def _answer(self, ctx: _DispatchCtx, tickets: List[Ticket], tier: str,
                mode: Optional[str], answer, trace,
                shielded: bool) -> List[ServeResult]:
        """The one step every served request ends in, whatever its path:
        the injector's finalize faults (not when ``shielded``), the
        sampled audit of the answer as it came back, a device request's
        answer as device arrays, and each ticket's :class:`ServeResult`.

        ``answer`` is ``(memory, regs, tag)`` of one request or, for more
        than one ticket, of their batch, stacked; ``mode`` is the
        executor that ran it (``None`` for the stepwise oracle, whose
        answers are not audited) and ``trace`` a lone request's trace.
        A batch of device images is split when its answers are read, off
        this thread (:class:`DeviceRows`)."""
        mem, regs, tag = answer
        n = len(tickets)
        inj, auditor, stats = self._injector, self._auditor, self.stats
        if inj is not None and not shielded:
            mem = inj.finalize([t.rid for t in tickets], mode, mem,
                               {t.rid: b for b, t in enumerate(tickets)})
        fixes = {}      # row -> the audit's reference, served in its place
        if auditor is not None and mode is not None:
            for b, t in enumerate(tickets):
                if not auditor.should_audit(t.rid):
                    continue
                stats.audit_checked += 1
                ref = auditor.check(t.program, t.memory, t.cfg,
                                    mem[b] if n > 1 else mem,
                                    tag[b] if n > 1 else tag, mode)
                if ref is not None:
                    stats.audit_corrected += 1
                    self._record_failure((ctx.key, mode))
                    fixes[b] = ref
        if n == 1 and fixes:
            mem, regs, tag = fixes.pop(0)
        stayed, rows, trace_fn = False, None, None
        if ctx.resident:
            mem, regs, tag, stayed = _device_arrays(mem, regs, tag)
            if n > 1:
                rows = DeviceRows(mem, regs, tag)
        if n > 1:
            cp = tickets[0].cp

            def trace_fn():
                # Deferred static_trace access too: unread traces cost
                # nothing on the dispatch hot path.
                return [dataclasses.replace(ev) for ev in cp.static_trace]
        out = []
        for b, t in enumerate(tickets):
            kept = stayed
            if b in fixes:
                row = fixes[b]
                if ctx.resident:
                    *row, kept = _device_arrays(*row)
            elif n == 1:
                row = (mem, regs, tag)
            elif rows is not None:
                row = (None, None, None)         # read from ``rows``
            else:
                row = (mem[b], {r: v[b] for r, v in regs.items()}, tag[b])
            stats.resident_requests += kept
            out.append(ServeResult(*row, batch_size=n, tier=tier,
                                   trace=trace, trace_fn=trace_fn,
                                   kernel=t.kernel, rows=rows, row=b))
        return out


def _device_arrays(mem, regs, tag):
    """``(mem, regs, tag, stayed)``: an answer's arrays on the device,
    what a path left on the host uploaded (and counted); ``stayed`` is
    whether nothing was."""
    if all(isinstance(a, jax.Array) for a in (mem, tag, *regs.values())):
        return mem, regs, tag, True

    def up(a):
        return a if isinstance(a, jax.Array) else to_device(a)
    return up(mem), {r: up(v) for r, v in regs.items()}, up(tag), False


def _chunks(seq: List, n: int):
    for i in range(0, len(seq), n):
        yield seq[i:i + n]


def _floor_pow2(n: int) -> int:
    return 1 << (int(n).bit_length() - 1)
