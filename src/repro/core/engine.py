"""Compiled MVE execution engine: whole-program compile + fused execution.

The step interpreter (:class:`repro.core.interp.MVEInterpreter`) walks a
program one instruction at a time, paying Python dispatch and host/device
round trips on every step.  This module exploits a structural property of
the ISA: *all* addressing state lives in control registers written by
config instructions with immediate operands, so a single symbolic pass over
the program can resolve every per-instruction address vector, lane mask,
CB mask and :class:`~repro.core.cost.TraceEvent` ahead of time.  What
remains — the data path — is emitted as one fused ``jax.jit`` function for
the whole program, with ``jax.vmap`` support for evaluating the same
program over a batch of memory images.

Static vs dynamic split (design note: docs/ENGINE.md):

  static  — control-register evolution, per-lane addresses of strided
            accesses, dimension/lane/CB masks, trace metadata;
  dynamic — register values, the Tag predicate latch, memory contents,
            and the addresses of random-base accesses (Eq. 1), whose
            pointer arrays are fetched from memory at run time.

Random-base accesses are the one place the trace is data-dependent: their
exact cache-line count depends on the pointer values, so the jitted
function also returns those address vectors and :meth:`CompiledProgram.run`
fills the ``lines`` field after execution.  Everything else about the
trace is emitted at compile time.

Bit-exactness.  The engine must reproduce the step interpreter bit for bit,
but XLA:CPU selects instructions with FP-contraction enabled: any ``fmul``
directly feeding an ``fadd`` in one fused loop becomes an FMA, skipping the
intermediate rounding that per-instruction eager execution performs.  The
fix is architectural rather than a compiler flag (none exists): every
register write-back is guarded by its instruction's *own* dimension-mask
vector, streamed in as run-time data (one row per instruction).  LLVM
cannot prove two mask rows equal, so the selects survive optimization and
no multiply result ever reaches an add without an intervening rounding
point — exactly the semantics of distinct in-cache instructions.

The interpreter remains the semantic oracle: ``tests/test_engine.py``
asserts bit-identical memory results and identical trace events on every
registered pattern.

Execution modes.  ``compile_program(..., mode=...)`` selects the executor:

  "vm"    — (default) the program-as-data virtual machine
            (:mod:`repro.core.vm`, docs/ENGINE.md "VM lowering"): the step
            list is lowered to dense tensors and executed by one pre-jitted
            ``lax.while_loop``/``lax.switch`` datapath shared by *every*
            program with the same signature, so data-dependent program
            streams (one spmm program per sparsity pattern) never recompile
            XLA;
  "fused" — one jitted straight-line function per program: peak
            steady-state throughput once its (per-program) compile is paid.

Both modes run/run_batch/trace identically and are equivalence-tested
against the stepwise oracle.
"""
from __future__ import annotations

import copy
import dataclasses
import functools
import threading
from collections import OrderedDict
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import isa
from .isa import Instr, Op
from .cost import TraceEvent
from .machine import (JNP_DTYPE, ControlState, MVEConfig, apply_config,
                      cbs_from_lane_mask, flatten_indices, lane_dim_mask,
                      shift_store_layout, store_shape, stream_shape,
                      touched_lines)
from .tracing import LOWER, span, to_device, to_host
from .vm import AotJit, VMProgram, VMUnsupported, fire_fault_hook
from .vm import cache_info as _vm_cache_info
from .vm import set_fault_hook  # noqa: F401  (re-export: one hook registry)


class ExecutorError(RuntimeError):
    """Base of the typed executor failures.

    The execution stack used to let whatever exception an executor's
    internals raised — an XLA error at sync time, a numpy shape error
    three frames deep — escape to callers untyped, which made the serving
    runtime's failure handling guesswork.  Each subclass names the
    boundary that failed; the original exception is chained as
    ``__cause__``.  User-input errors (:class:`~repro.core.isa.ProgramError`,
    ``TypeError``/``ValueError`` from malformed arguments) are *not*
    wrapped: they mean "fix the request", not "the executor failed".
    """


class CompileError(ExecutorError):
    """Compiling/lowering a program to an executable failed."""


class DispatchError(ExecutorError):
    """Launching an execution (single or batched) failed."""


class FinalizeError(ExecutorError):
    """Materializing results of a dispatched execution failed."""


# exception types that pass through untyped (user errors / control flow)
_PASSTHROUGH = (isa.ProgramError, VMUnsupported, TypeError, ValueError,
                ExecutorError)


@dataclasses.dataclass
class _Step:
    """One vector/scalar instruction with its statically resolved context."""

    instr: Instr
    lane_mask: Optional[np.ndarray] = None   # per-lane dimension mask
    cb_mask: Optional[np.ndarray] = None
    event: Optional[TraceEvent] = None
    mask_slot: Optional[int] = None          # row in the runtime mask stack
    addr: Optional[np.ndarray] = None        # static element addresses
    store_layout: Optional[tuple] = None     # machine.store_layout result
    # random-base (Eq. 1) accesses: pointer slice + static inner offsets
    ptr_base: Optional[int] = None
    top_len: Optional[int] = None
    top_idx: Optional[np.ndarray] = None
    offsets: Optional[np.ndarray] = None
    rand_slot: Optional[int] = None          # index into returned addresses


@dataclasses.dataclass
class ExecutionResult:
    """Duck-type compatible with :class:`repro.core.interp.MachineState`.

    ``operands`` materialises named, shaped result views lazily when the
    program was compiled from a frontend kernel (:mod:`repro.frontend`)
    — reading results by name costs nothing until accessed."""

    memory: jnp.ndarray
    regs: Dict[int, jnp.ndarray]
    tag: jnp.ndarray
    ctrl: ControlState
    trace: List[TraceEvent]
    kernel: Optional[object] = None       # frontend Kernel, if any
    _operands: Optional[Dict[str, np.ndarray]] = dataclasses.field(
        default=None, repr=False)

    @property
    def operands(self) -> Optional[Dict[str, np.ndarray]]:
        """Results read back by operand name (``None`` for raw programs)."""
        if self._operands is None and self.kernel is not None:
            self._operands = self.kernel.unpack(self.memory)
        return self._operands


class CompiledProgram:
    """An MVE program lowered to one fused JAX function + a static trace.

    Use :func:`compile_program` (cached) rather than constructing directly.
    The compiled object is memory-image independent: it executes any image
    of a given size (or a vmapped batch of them) without re-tracing.
    """

    def __init__(self, program: isa.Program, cfg: MVEConfig,
                 mode: str = "fused"):
        self.cfg = cfg
        self.program = tuple(program)
        self.kernel = None    # frontend Kernel when compiled from one
        self._kernels_seen = None      # WeakSet of accepted kernels
        self._kernel_conflict = False  # distinct kernels share this text
        self.steps: List[_Step] = []
        self.n_random = 0
        # Build-time checks (readable one-line errors) before the walk:
        # a malformed program fails here, not deep inside addressing
        # resolution.  Lenient mode — executors keep accepting programs
        # that deliberately rely on clip/drop semantics.
        isa.validate(self.program, wordlines=cfg.wordlines)
        self._compile_walk()
        self._masks = None       # built lazily: only the fused path streams
        self._zeros = None       # the mask stack / power-on register row
        self._fused_fn = _named_execute(self._execute,
                                        _NAMES.get(self.program))
        self._jit = AotJit(self._fused_fn, donate_argnums=(0,))
        self._batch_jit = None
        self._vm: Optional[VMProgram] = None
        self.mode = mode
        if mode == "vm":
            try:
                self._vm = VMProgram(self.steps, cfg, self.n_random)
            except VMUnsupported:
                global _VM_FALLBACKS
                _VM_FALLBACKS += 1
                self.mode = "fused"

    # -- compilation -------------------------------------------------------
    def _compile_walk(self) -> None:
        """Symbolically execute config ops; resolve every access statically.

        The per-lane work (masks, offsets, line counts, store layouts)
        depends on the control state, not on the instruction: it comes
        from the walk memo (:func:`_walk_memo`), and an instruction's base
        only shifts it."""
        cfg = self.cfg
        lanes = cfg.lanes
        ctrl = ControlState()
        no_cbs = _no_cbs(cfg.num_cbs)
        n_masked = 0
        for instr in self.program:
            op = instr.op
            if op in isa.CONFIG_OPS:
                apply_config(ctrl, instr)
                self.steps.append(_Step(instr, event=TraceEvent(
                    op=op, dtype=None, elements=0, cb_mask=no_cbs)))
                continue
            if op is Op.SCALAR:
                self.steps.append(_Step(instr, event=TraceEvent(
                    op=op, dtype=None, elements=0, cb_mask=no_cbs,
                    scalar_count=instr.scalar_count)))
                continue

            dims = ctrl.active_dims()
            dim_mask = ctrl.dim_mask.tobytes()
            lane_mask, cbm, elements = _walk_memo(
                ("lanes", lanes, cfg.num_cbs, dims, dim_mask),
                lambda: _lane_entry(dims, ctrl.dim_mask, cfg))
            step = _Step(instr, lane_mask=lane_mask, cb_mask=cbm,
                         mask_slot=n_masked)
            n_masked += 1

            if op in isa.MEMORY_OPS:
                store = op in (Op.SST, Op.RST)
                random = op in (Op.RLD, Op.RST)
                strides = ctrl.resolve_strides(instr.modes or (), store)
                shape = (lanes, dims, strides)
                run, segs, uniq, offsets, top_idx = _walk_memo(
                    ("offsets", random) + shape,
                    lambda: _offsets_entry(dims, strides, lanes, random))
                if random:
                    step.ptr_base = instr.base
                    step.top_len = dims[-1]
                    step.top_idx = top_idx
                    step.offsets = offsets
                    step.rand_slot = self.n_random
                    self.n_random += 1
                    lines = 0          # filled from run-time addresses
                else:
                    addr = offsets + instr.base
                    step.addr = addr
                    if store:
                        step.store_layout = shift_store_layout(_walk_memo(
                            ("store", dim_mask) + shape,
                            lambda: store_shape(offsets, lane_mask)),
                            instr.base, addr, lane_mask)
                    lines = _lines(instr, offsets, addr, lane_mask,
                                   ("lines", dim_mask) + shape)
                step.event = TraceEvent(op, instr.dtype, elements, cbm,
                                        segments=segs, contiguous_run=run,
                                        unique_elements=uniq, lines=lines)
            else:
                step.event = TraceEvent(op, instr.dtype, elements, cbm)
            self.steps.append(step)
        self.final_ctrl = copy.deepcopy(ctrl)
        self.n_vector = n_masked    # vector instructions (mask rows)

    # -- fused data path ---------------------------------------------------
    def _execute(self, memory, masks, zeros):
        """The whole program as one traced JAX computation.

        Mirrors :meth:`MVEInterpreter._step` semantics exactly (the
        equivalence tests depend on it) with all addressing constant-folded.
        ``masks`` carries one dimension-mask row per vector instruction and
        ``zeros`` the power-on register value; both arrive as run-time data
        so each write-back keeps its own rounding point (see the module
        docstring on FP contraction).
        """
        cfg = self.cfg
        regs: Dict[int, jnp.ndarray] = {}
        tag = jnp.ones(cfg.lanes, dtype=bool)
        rand_addrs: List[jnp.ndarray] = [None] * self.n_random
        hi = memory.shape[0] - 1

        for step in self.steps:
            instr = step.instr
            op = instr.op
            if op in isa.CONFIG_OPS or op is Op.SCALAR:
                continue

            dt = JNP_DTYPE.get(instr.dtype, jnp.float32)
            jmask = masks[step.mask_slot]
            if step.mask_slot and step.mask_slot % FUSE_SPAN == 0:
                memory, regs, tag = jax.lax.optimization_barrier(
                    (memory, regs, tag))

            def old(vd, dt=dt):
                v = regs.get(vd)
                return (zeros if v is None else v).astype(dt)

            if op in (Op.SLD, Op.RLD):
                addr = self._address_vector(step, memory)
                if step.rand_slot is not None:
                    rand_addrs[step.rand_slot] = addr
                gathered = memory[jnp.clip(addr, 0, hi)].astype(dt)
                regs[instr.vd] = jnp.where(jmask, gathered, old(instr.vd))
                continue
            if op in (Op.SST, Op.RST):
                src = old(instr.vs1).astype(memory.dtype)
                if step.rand_slot is not None:
                    # Runtime addresses: masked lanes dropped out of
                    # bounds; later lanes win on address collisions
                    # (scatter order matches a sequential loop).
                    addr = self._address_vector(step, memory)
                    rand_addrs[step.rand_slot] = addr
                    memory = memory.at[jnp.where(jmask, addr, -1)].set(
                        src, mode="drop")
                    continue
                layout = step.store_layout
                if layout[0] == "contig":
                    # Dense store (addr = base + lane): a slice blend
                    # instead of XLA:CPU's scalar scatter loop.  Lanes
                    # past the end of memory are dropped, as before.
                    base = layout[1]
                    w = min(cfg.lanes, memory.shape[0] - base)
                    if w > 0:
                        window = memory[base:base + w]
                        memory = memory.at[base:base + w].set(
                            jnp.where(jmask[:w], src[:w], window))
                elif layout[0] == "scatter":
                    # Pre-sorted collision-ordered indices: masked lanes
                    # and all but the last writer per address are out of
                    # bounds, so one sorted-unique drop-scatter keeps
                    # last-lane-wins semantics without the old gather.
                    memory = memory.at[jnp.asarray(layout[1])].set(
                        src[jnp.asarray(layout[2])], mode="drop",
                        indices_are_sorted=True, unique_indices=True)
                # ("none",): fully masked store — no effect
                continue

            def finish(result, instr=instr, jmask=jmask, dt=dt, old=old):
                result = result.astype(dt)
                keep = jmask
                if instr.predicated:
                    keep = keep & tag
                regs[instr.vd] = jnp.where(keep, result, old(instr.vd))

            if op is Op.SET_DUP:
                finish(jnp.full(cfg.lanes, instr.imm, dtype=dt))
                continue
            if op is Op.CPY:
                finish(old(instr.vs1))
                continue
            if op is Op.CVT:
                v = regs.get(instr.vs1)
                src = zeros if v is None else v
                finish(src.astype(dt))
                continue

            a = old(instr.vs1)
            b = old(instr.vs2) if instr.vs2 is not None else None

            if op is Op.ADD:
                finish(a + b)
            elif op is Op.SUB:
                finish(a - b)
            elif op is Op.MUL:
                finish(a * b)
            elif op is Op.MIN:
                finish(jnp.minimum(a, b))
            elif op is Op.MAX:
                finish(jnp.maximum(a, b))
            elif op is Op.XOR:
                finish(a ^ b)
            elif op is Op.AND:
                finish(a & b)
            elif op is Op.OR:
                finish(a | b)
            elif op is Op.SHI:
                if instr.dtype.is_float:
                    raise ValueError("shift on float register")
                amt = instr.imm
                finish(a << amt if amt >= 0 else a >> (-amt))
            elif op is Op.ROTI:
                bits = instr.dtype.bits
                amt = instr.imm % bits
                ua = a.astype(jnp.uint32 if bits <= 32 else jnp.uint64)
                finish(((ua << amt) | (ua >> (bits - amt))).astype(dt))
            elif op is Op.SHR:
                finish(a << b.astype(jnp.int32))
            elif op in isa.COMPARE_OPS:
                cmp = {Op.GT: a > b, Op.GTE: a >= b, Op.LT: a < b,
                       Op.LTE: a <= b, Op.EQ: a == b, Op.NEQ: a != b}[op]
                tag = jnp.where(jmask, cmp, tag)
            else:
                raise NotImplementedError(f"op {op}")

        return memory, regs, tag, rand_addrs

    @staticmethod
    def _address_vector(step: _Step, memory):
        """Element addresses: constant for strided, traced for random-base
        (the pointer array is part of the data, Eq. 1)."""
        if step.addr is not None:
            return jnp.asarray(step.addr)
        ptrs = memory[step.ptr_base: step.ptr_base + step.top_len]
        ptrs = ptrs.astype(jnp.int32)
        return ptrs[step.top_idx] + jnp.asarray(step.offsets)

    # -- public API --------------------------------------------------------
    def _fused_operands(self):
        """Mask-stack / zeros operands of the fused function (uploaded on
        first fused execution only — the VM path never needs them)."""
        if self._masks is None:
            masks = [s.lane_mask for s in self.steps
                     if s.mask_slot is not None]
            self._masks = jnp.asarray(np.stack(masks)) if masks else \
                jnp.zeros((0, self.cfg.lanes), dtype=bool)
            self._zeros = jnp.zeros(self.cfg.lanes, dtype=jnp.float32)
        return self._masks, self._zeros

    def _donatable(self, memory) -> jnp.ndarray:
        """The executables donate (write through) their memory operand, so
        it must be a jax-owned buffer: a fresh copy protects caller-owned
        device arrays and prevents zero-copy aliasing of caller numpy
        buffers (same-dtype CPU device_put does not copy)."""
        return to_device(memory)

    @staticmethod
    def _vm_memory_dtype(memory) -> bool:
        """True when the memory image is float32-canonical (float64 or
        float32 — what every pattern and the 32-bit-mode eager executors
        use); reads ``memory.dtype`` without materializing the array."""
        dtype = getattr(memory, "dtype", None)
        if dtype is None:
            dtype = np.asarray(memory).dtype
        return np.dtype(dtype) in (np.float64, np.float32)

    def _use_vm(self, memory) -> bool:
        """Route through the VM datapath unless the memory dtype needs the
        exact eager semantics of the per-program fused function."""
        return self.mode == "vm" and self._vm_memory_dtype(memory)

    def _bound_kernel(self):
        """The kernel whose plan names this program's operands; raises a
        readable error when there is none or when the binding is
        ambiguous (several non-equivalent kernels share the text)."""
        if self.kernel is None:
            if self._kernel_conflict:
                raise TypeError(
                    "this program text was compiled from multiple "
                    "distinct kernels (different operand plans or init "
                    "data) — pack explicitly with kernel.pack(...) or "
                    "execute via kernel.run()/kernel.run_batch()")
            raise TypeError(
                "named-operand execution needs a frontend kernel: "
                "compile with compile_program(kernel) or pass a flat "
                "memory image")
        return self.kernel

    def _as_memory(self, memory):
        """Accept a flat memory image or — when this program was compiled
        from a frontend kernel — a dict of named operand arrays."""
        if isinstance(memory, dict):
            return self._bound_kernel().pack(memory)
        return memory

    def run_async(self, memory):
        """Dispatch one execution without blocking on host results.

        Returns an opaque pending handle for :meth:`finalize_run`.  JAX's
        async dispatch (CPU included) keeps computing while the caller
        prepares the next request, so a serving loop
        (:mod:`repro.runtime.scheduler`) pays one sync per drain cycle
        instead of one per request."""
        fire_fault_hook("engine.dispatch", tier=self.mode)
        try:
            memory = self._as_memory(memory)
            if self._use_vm(memory):
                return ("vm", self._vm.run_async(memory))
            masks, zeros = self._fused_operands()
            return ("fused", self._jit(self._donatable(memory), masks,
                                       zeros))
        except _PASSTHROUGH:
            raise
        except Exception as e:
            raise DispatchError(f"dispatch failed ({self.mode} mode): "
                                f"{type(e).__name__}: {e}") from e

    @staticmethod
    def wait(pending) -> None:
        """Block until a :meth:`run_async` or :meth:`run_batch_async`
        dispatch's device outputs are ready, copying nothing (the wait
        that finalizing would otherwise hide in its first copy)."""
        try:
            jax.block_until_ready(pending[1])
        except Exception as e:
            raise FinalizeError(f"waiting for a dispatch failed: "
                                f"{type(e).__name__}: {e}") from e

    def finalize_run(self, pending) -> Tuple[jnp.ndarray, ExecutionResult]:
        """Materialize a :meth:`run_async` dispatch into ``(mem, state)``."""
        fire_fault_hook("engine.finalize", tier=self.mode)
        try:
            kind, out = pending
            if kind == "vm":
                mem, regs, tag, rand_addrs = self._vm.finalize(out)
            else:
                mem, regs, tag, rand_addrs = out
        except _PASSTHROUGH:
            raise
        except Exception as e:
            raise FinalizeError(f"finalize failed ({self.mode} mode): "
                                f"{type(e).__name__}: {e}") from e
        trace = self._finalize_trace(rand_addrs)
        # Fresh ctrl/trace objects per run: callers may mutate the returned
        # state (the stepwise oracle hands out fresh state too), and this
        # CompiledProgram is shared through the compile cache.
        state = ExecutionResult(memory=mem, regs=dict(regs), tag=tag,
                                ctrl=copy.deepcopy(self.final_ctrl),
                                trace=trace, kernel=self.kernel)
        return mem, state

    def run(self, memory) -> Tuple[jnp.ndarray, ExecutionResult]:
        """Execute on one memory image; returns ``(memory, state)`` exactly
        like :meth:`MVEInterpreter.run` (trace included).  Dispatches to
        the VM datapath or the per-program fused function per ``mode``."""
        return self.finalize_run(self.run_async(memory))

    def run_batch(self, memories) -> Tuple[jnp.ndarray,
                                           Dict[int, jnp.ndarray],
                                           jnp.ndarray]:
        """Evaluate the program over a leading batch of memory images.

        Returns ``(memories, regs, tag)`` with a leading batch axis on
        every array.  No trace is produced: the cost-model trace of a
        batched run is that of any single element (and for random-base
        programs each element may touch different cache lines — use
        :meth:`run` on a representative image to price it).
        """
        return self.finalize_batch(self.run_batch_async(memories))

    def run_batch_async(self, memories):
        """Dispatch a batched execution without blocking (see
        :meth:`run_async`); finalize with :meth:`finalize_batch`.

        ``memories`` may also be a list of device images (``jax.Array``):
        one jitted stack puts them into a fresh buffer on the device,
        which the fused executable takes as its donated operand, so they
        are neither copied to the host nor copied twice."""
        fire_fault_hook("engine.dispatch", tier=self.mode)
        try:
            if isinstance(memories, dict):
                memories = self._bound_kernel().pack_batch(memories)
            stacked = isinstance(memories, list) and all(
                isinstance(m, jax.Array) for m in memories)
            if stacked:
                memories = _stack_images(tuple(memories))
            if self._use_vm(memories):
                return ("vm", self._vm.run_batch_async(memories))
            masks, zeros = self._fused_operands()
            mem, regs, tag, _ = self._get_batch_jit()(
                memories if stacked else self._donatable(memories), masks,
                zeros)
            return ("fused", (mem, dict(regs), tag))
        except _PASSTHROUGH:
            raise
        except Exception as e:
            raise DispatchError(f"batch dispatch failed ({self.mode} "
                                f"mode): {type(e).__name__}: {e}") from e

    def finalize_batch(self, pending):
        """``(memories, regs, tag)`` of a :meth:`run_batch_async`
        dispatch: host arrays from the VM, device arrays from the fused
        function."""
        fire_fault_hook("engine.finalize", tier=self.mode)
        try:
            kind, out = pending
            if kind == "vm":
                return self._vm.finalize_batch(out)
            return out
        except _PASSTHROUGH:
            raise
        except Exception as e:
            raise FinalizeError(f"batch finalize failed ({self.mode} "
                                f"mode): {type(e).__name__}: {e}") from e

    def batch_group_key(self, memory) -> tuple:
        """Scheduling key: requests whose keys are equal can be stacked
        into one ``run_batch`` dispatch and — under ``mode="vm"`` — share
        one signature-keyed XLA executable.  The key is the VM signature
        bucket for VM-routed requests (program identity rides along:
        batching stacks *memories* under one program) and the program
        itself for fused-routed ones, and ends with whether the image is
        a device array."""
        mem = np.asarray(memory) if not hasattr(memory, "shape") else memory
        size = int(mem.shape[-1])
        dtype = str(getattr(mem, "dtype", "float64"))
        # the last element keeps device images and host images apart: a
        # batch is stacked on the device or on the host, never both
        resident = isinstance(memory, jax.Array)
        if self._use_vm(memory):
            return ("vm", self._vm._signature(size), size, dtype, resident)
        return ("fused", id(self), size, dtype, resident)

    def _get_batch_jit(self) -> AotJit:
        if self._batch_jit is None:
            self._batch_jit = AotJit(
                jax.vmap(self._fused_fn, in_axes=(0, None, None)),
                donate_argnums=(0,))
        return self._batch_jit

    def warmup(self, memory_size, batch: Optional[int] = None,
               dtype=jnp.float32) -> "CompiledProgram":
        """AOT-compile (``.lower().compile()``) the executable for a memory
        geometry, removing the silent first-call compile cliff.

        ``memory_size`` is an element count (or an example memory image);
        pass ``batch`` to warm the vmapped batch executable instead.
        Returns ``self`` so calls chain with :func:`compile_program`.
        """
        if not isinstance(memory_size, int):
            memory_size = int(np.asarray(memory_size).shape[-1])
        dtype = jax.dtypes.canonicalize_dtype(dtype)
        # Warm the executor run() will actually pick for this dtype: the
        # VM datapath for float32-canonical images, the fused jit
        # otherwise (matching ``_use_vm``).
        if self.mode == "vm" and np.dtype(dtype) in (np.float64, np.float32):
            self._vm.warmup(memory_size, batch)
            return self
        shape = (memory_size,) if batch is None else (batch, memory_size)
        mem = jax.ShapeDtypeStruct(shape, dtype)
        masks, zeros = self._fused_operands()
        if batch is None:
            self._jit.warmup(mem, masks, zeros)
        else:
            self._get_batch_jit().warmup(mem, masks, zeros)
        return self

    def warmup_resident(self, memory_size: int,
                        batches: Tuple[int, ...] = ()) -> "CompiledProgram":
        """Compile, without running the program, what the fused tier
        takes beyond the program's own executables to serve device
        images of ``memory_size`` words: the copy a singleton makes of its
        image and, for each size in ``batches``, the stack of that many
        images (:meth:`run_batch_async`), the split of the batch's
        memories (:func:`split_rows`) and the read of one request's
        registers and Tag (:func:`take_row`).  Returns ``self``."""
        image = jnp.zeros(memory_size, jnp.float32)
        jax.block_until_ready(self._donatable(image))
        if not batches:
            return self
        masks, zeros = self._fused_operands()
        _, regs, tag, _ = jax.eval_shape(self._fused_fn, image, masks,
                                         zeros)
        for b in batches:
            state = jax.tree.map(lambda a: jnp.zeros((b,) + a.shape, a.dtype),
                                 (dict(regs), tag))
            stacked = _stack_images((image,) * b)
            jax.block_until_ready((split_rows(stacked),
                                   take_row(state, 0)))
        return self

    def _finalize_trace(self, rand_addrs) -> List[TraceEvent]:
        trace: List[TraceEvent] = []
        for step in self.steps:
            ev = step.event
            if step.rand_slot is not None:
                addr = to_host(rand_addrs[step.rand_slot]).astype(np.int64)
                ev = dataclasses.replace(ev, lines=touched_lines(
                    addr, step.lane_mask, step.instr.dtype.nbytes))
            else:
                ev = dataclasses.replace(ev)
            trace.append(ev)
        return trace

    @property
    def static_trace(self) -> List[TraceEvent]:
        """The compile-time trace; exact unless the program uses
        random-base accesses (then run() fills the ``lines`` fields)."""
        return [s.event for s in self.steps]


# ---------------------------------------------------------------------------
# Device images: a batch of ``jax.Array`` memory images is stacked, run and
# split on the device (docs/SERVING.md "Device images").
# ---------------------------------------------------------------------------

@jax.jit
def _stack_images(images):
    return jnp.stack(images)


@jax.jit
def split_rows(batch):
    """The rows of a batched device array as device arrays of their own,
    in one jitted call."""
    return [batch[i] for i in range(batch.shape[0])]


@jax.jit
def take_row(tree, row):
    """Row ``row`` of every array in ``tree``; the row is traced, so one
    executable serves every row."""
    return jax.tree.map(lambda a: a[row], tree)


#: A fused program gets an optimization barrier on its memory, registers
#: and Tag every ``FUSE_SPAN`` vector instructions.  Every write-back
#: selects on the register's old value, so each value feeds two
#: consumers, and XLA's fusion of such a chain grows faster than its
#: length: a 486-instruction attention tile took 62 s to compile for a
#: v5e at batch 16 unbroken, 24 s with a barrier every 128 and 9 s every
#: 32.  A barrier is an identity, so results are unchanged.
FUSE_SPAN = 32


# ---------------------------------------------------------------------------
# Executable names.  A fused program's jit is the module ``jit__execute``
# in a device trace; a program registered under a name becomes
# ``jit__execute.<name>`` (single and batched alike), so the device time
# of each kind of program can be read by executable.  The ``jit__execute``
# prefix, which the benchmark classes as the fused tier, is kept.
# ---------------------------------------------------------------------------

_NAMES: Dict[tuple, str] = {}


def name_program(program, name: str) -> None:
    """Name the fused executables of ``program`` (a raw program or a
    frontend kernel) ``jit__execute.<name>`` in device traces.  Takes
    effect for compilations made after the call."""
    if not name.replace("_", "").isalnum():
        raise ValueError(f"executable name {name!r}: letters, digits and "
                         f"'_' only")
    if hasattr(program, "plan") and hasattr(program, "program"):
        program = program.program
    _NAMES[tuple(program)] = name


def _named_execute(fn, name: Optional[str]):
    """``fn`` itself, or a wrapper whose ``__name__`` names the jitted
    module ``jit__execute.<name>``."""
    if name is None:
        return fn

    def execute(memory, masks, zeros):
        return fn(memory, masks, zeros)

    execute.__name__ = execute.__qualname__ = f"_execute.{name}"
    return execute


# ---------------------------------------------------------------------------
# Compile cache: programs are tuples of frozen Instr, so they hash.  Bounded
# LRU — data-dependent program streams (e.g. one program per sparsity
# pattern) would otherwise retain a lowering per variant forever.  Under
# ``mode="vm"`` an eviction only drops host-side tables; the XLA executable
# lives in the signature cache (:mod:`repro.core.vm`) and is never retraced.
# ---------------------------------------------------------------------------

_CACHE: "OrderedDict[tuple, CompiledProgram]" = OrderedDict()
_CACHE_CAPACITY = 256
_CACHE_LOCK = threading.RLock()   # submit() may compile from many threads
_HITS = _MISSES = _EVICTIONS = 0
_VM_FALLBACKS = 0
# Per-target LRU counters: cache_tag -> [hits, misses].  Tagged compiles
# (one tag per :mod:`repro.targets` target) get their own key space, so
# an RVV or Neon compilation of a program never aliases — or evicts in
# place of — the MVE entry for the same text.
_TAG_COUNTS: Dict[str, List[int]] = {}

#: Default execution mode: ``"vm"`` (program-as-data datapath, one XLA
#: compilation per signature) or ``"fused"`` (one jitted function per
#: program — peak steady-state throughput).  The stepwise interpreter
#: remains the semantic oracle for both.
DEFAULT_MODE = "vm"


# ---------------------------------------------------------------------------
# Walk memo: the compile walk's per-lane work (lane and CB masks, address
# offsets, line counts, store layouts) depends only on the control state it
# reads, which repeats across programs — a stream of fresh programs differs
# in bases and counts, not in shapes.  Each entry is keyed on everything its
# computation reads, and an instruction's base only shifts the result, so a
# hit is exact by construction (docs/ENGINE.md "Walk memo").  Entries are
# read-only (steps share them) and bounded by the bytes they hold.
# ---------------------------------------------------------------------------

_WALK: "OrderedDict[tuple, Tuple[object, int]]" = OrderedDict()
_WALK_CAPACITY_BYTES = 64 << 20    # ~1000 8192-lane int64 rows
_WALK_ENTRY_BYTES = 256            # charged per entry besides its arrays
_WALK_BYTES = _WALK_HITS = _WALK_MISSES = 0


def _walk_memo(key: tuple, compute):
    """The memo's entry for ``key``, computed on a miss.  The lock covers
    the bookkeeping only, never the numpy work: a racing duplicate
    computes the same value and the first insertion wins."""
    global _WALK_BYTES, _WALK_HITS, _WALK_MISSES
    with _CACHE_LOCK:
        got = _WALK.get(key)
        if got is not None:
            _WALK_HITS += 1
            _WALK.move_to_end(key)
            return got[0]
        _WALK_MISSES += 1
    value = compute()
    size = _WALK_ENTRY_BYTES
    for v in value if isinstance(value, tuple) else ():
        if isinstance(v, np.ndarray):
            v.setflags(write=False)
            size += v.nbytes
    entry = (value, size)
    with _CACHE_LOCK:
        got = _WALK.setdefault(key, entry)
        if got is entry:
            _WALK_BYTES += size
            while _WALK_BYTES > _WALK_CAPACITY_BYTES:
                _WALK_BYTES -= _WALK.popitem(last=False)[1][1]
    return got[0]


@functools.lru_cache(maxsize=16)
def _no_cbs(num_cbs: int) -> np.ndarray:
    """The CB mask of config and scalar ops: no CB takes part."""
    row = np.zeros(num_cbs, dtype=bool)
    row.setflags(write=False)
    return row


def _lane_entry(dims, dim_mask, cfg: MVEConfig) -> tuple:
    """(lane mask, CB mask, element count) of a vector instruction."""
    lane_mask = lane_dim_mask(dims, dim_mask, cfg.lanes)
    return (lane_mask, cbs_from_lane_mask(lane_mask, cfg),
            int(lane_mask.sum()))


def _offsets_entry(dims, strides, lanes: int, random: bool) -> tuple:
    """(run, segments, unique elements, offsets, top_idx) of a memory
    access: ``stream_shape``'s triple and the base-free element offsets
    (a random access leaves its top dimension to the pointer array,
    ``top_idx``)."""
    coords = flatten_indices(dims, lanes)
    inner = len(dims) - 1 if random else len(dims)
    offsets = np.zeros(lanes, dtype=np.int64)
    for d in range(inner):
        offsets += np.where(coords[:, d] >= 0, coords[:, d], 0) * strides[d]
    top_idx = (np.clip(coords[:, inner], 0, dims[-1] - 1) if random
               else None)
    return stream_shape(dims, strides, lanes) + (offsets, top_idx)


def _lines(instr: Instr, offsets, addr, lane_mask, key: tuple) -> int:
    """``touched_lines`` of a static access.  Where an element's size
    divides the line, a base moved by whole lines moves every line index
    by one, so the count depends on the base only modulo a line."""
    nbytes = instr.dtype.nbytes
    if 64 % nbytes:
        return touched_lines(addr, lane_mask, nbytes)
    rem = instr.base % (64 // nbytes)
    return _walk_memo(key + (nbytes, rem), lambda: touched_lines(
        offsets + rem, lane_mask, nbytes))


@dataclasses.dataclass(frozen=True)
class EngineCacheInfo:
    """Snapshot of the compile caches (see :func:`cache_info`)."""

    program_hits: int          # compile_program served from the LRU
    program_misses: int        # fresh compile walks (+ VM lowerings)
    program_evictions: int
    program_size: int
    vm_fallbacks: int          # vm-mode requests lowered to fused instead
    vm_signatures: int         # distinct VM executables alive
    vm_hits: int               # VM executor-cache hits
    vm_xla_compiles: int       # distinct VM XLA compilations (incl. batch)
    instr_hashes: int          # Instr hashes computed (first uses only)
    walk_hits: int             # compile-walk memo lookups served
    walk_misses: int           # compile-walk memo lookups computed
    # cache_tag -> {"hits": n, "misses": n} for target-tagged compiles
    # (docs/TARGETS.md); untagged compiles count only in the totals above.
    per_target: Dict[str, Dict[str, int]] = dataclasses.field(
        default_factory=dict)


def cache_info() -> EngineCacheInfo:
    """Hit/miss/eviction counters for the program LRU plus the VM
    signature-keyed executable cache — the observability handle for the
    "compile the machine once" contract (docs/ENGINE.md).  ``per_target``
    breaks the LRU counters down by compile tag, one per registered
    :mod:`repro.targets` target that has compiled anything.
    ``instr_hashes`` counts the instruction hashes computed since the
    process started: each :class:`~repro.core.isa.Instr` computes its
    hash once, so a lookup of a program seen before adds nothing.
    ``walk_hits`` / ``walk_misses`` count the compile walk's memo lookups
    (per-lane work reused / computed) since the last :func:`clear_cache`."""
    v = _vm_cache_info()
    with _CACHE_LOCK:
        per_target = {tag: {"hits": c[0], "misses": c[1]}
                      for tag, c in _TAG_COUNTS.items()}
    return EngineCacheInfo(
        program_hits=_HITS, program_misses=_MISSES,
        program_evictions=_EVICTIONS, program_size=len(_CACHE),
        vm_fallbacks=_VM_FALLBACKS, vm_signatures=v.signatures,
        vm_hits=v.hits, vm_xla_compiles=v.xla_compiles,
        instr_hashes=isa.instr_hashes(), walk_hits=_WALK_HITS,
        walk_misses=_WALK_MISSES, per_target=per_target)


def _attach_kernel(cp: CompiledProgram, kernel) -> CompiledProgram:
    """Bind a frontend kernel to a (shared, cached) compilation.

    Distinct kernels can emit identical program text with *different*
    operand plans or init data; serving the first kernel's data to the
    second would be silent corruption.  Equivalent kernels (same plan,
    same inits) share the binding; a non-equivalent one poisons it, so
    dict-of-operands execution on this object raises instead of packing
    the wrong kernel's data (``kernel.run()`` is never ambiguous — it
    packs with its own plan before dispatch).
    """
    if kernel is None or kernel is cp.kernel:
        return cp
    import weakref
    with _CACHE_LOCK:
        if cp._kernels_seen is None:
            cp._kernels_seen = weakref.WeakSet()
        if kernel in cp._kernels_seen:
            return cp
        if cp.kernel is None and not cp._kernel_conflict:
            cp.kernel = kernel
        elif cp.kernel is not None and not cp.kernel.equivalent(kernel):
            cp.kernel = None
            cp._kernel_conflict = True
        cp._kernels_seen.add(kernel)
    return cp


def _count_tag(tag: Optional[str], hit: bool) -> None:
    """Record a tagged LRU hit/miss (caller holds ``_CACHE_LOCK``)."""
    if tag is None:
        return
    counts = _TAG_COUNTS.setdefault(tag, [0, 0])
    counts[0 if hit else 1] += 1


def compile_program(program: isa.Program,
                    cfg: MVEConfig | None = None,
                    mode: str | None = None,
                    cache_tag: Optional[str] = None,
                    opt_level: Optional[int] = None) -> CompiledProgram:
    """Compile (with caching) an MVE program for the given machine config.

    Accepts a raw instruction sequence or a frontend
    :class:`~repro.frontend.Kernel` — for kernels, ``run``/``run_batch``
    additionally accept a dict of named operand arrays and results are
    read back by name (``state.operands``).

    The returned :class:`CompiledProgram` is memory-image independent: the
    same object executes any number of images (or a vmapped batch) without
    re-tracing, and repeated calls with an equal program return the cached
    compilation.  ``mode`` selects the executor (default
    :data:`DEFAULT_MODE`): ``"vm"`` shares one XLA executable across every
    program with the same signature; ``"fused"`` emits one jitted function
    per program.  Programs the VM cannot host fall back to fused
    (``cache_info().vm_fallbacks``).

    ``cache_tag`` namespaces the LRU key: compilations made on behalf of
    one :mod:`repro.targets` target (the target's name) never alias —
    or compete in LRU order with — another target's entries for the same
    program text, and ``cache_info().per_target`` reports hits/misses
    per tag.

    ``opt_level`` (default ``None`` = no optimization) runs the program
    through the :mod:`repro.opt` pass pipeline before compilation — the
    optimized text is just another program, so caching, signatures and
    executors compose unchanged (docs/OPTIMIZER.md).
    """
    global _HITS, _MISSES, _EVICTIONS
    cfg = cfg or MVEConfig()
    mode = mode or DEFAULT_MODE
    if mode not in ("vm", "fused"):
        raise ValueError(f"unknown engine mode {mode!r}")
    kernel = None
    if hasattr(program, "plan") and hasattr(program, "program"):
        kernel = program            # a frontend Kernel (duck-typed:
        program = kernel.program    # no core -> frontend import cycle)
    if opt_level:
        from .. import opt          # late: opt sits above core
        program = opt.optimize(program, level=opt_level)
    key = (tuple(program), cfg, mode, cache_tag)
    with _CACHE_LOCK:
        cp = _CACHE.get(key)
        if cp is not None:
            _HITS += 1
            _count_tag(cache_tag, hit=True)
            _CACHE.move_to_end(key)
            return _attach_kernel(cp, kernel)
    # Construct outside the lock: a multi-ms compile walk must not stall
    # concurrent lookups (scheduler submit() runs on many client threads).
    # A racing duplicate construction is possible but harmless — the
    # first insertion wins below and the loser is dropped.
    fire_fault_hook("engine.compile", tier=mode)
    try:
        with span(LOWER):
            built = CompiledProgram(program, cfg, mode=mode)
    except _PASSTHROUGH:
        raise
    except Exception as e:
        raise CompileError(f"compile walk failed ({mode} mode): "
                           f"{type(e).__name__}: {e}") from e
    with _CACHE_LOCK:
        cp = _CACHE.get(key)
        if cp is not None:
            _HITS += 1
            _count_tag(cache_tag, hit=True)
            _CACHE.move_to_end(key)
            return _attach_kernel(cp, kernel)
        _MISSES += 1
        _count_tag(cache_tag, hit=False)
        cp = _CACHE[key] = built
        _attach_kernel(cp, kernel)
        if cp.mode != mode:
            # VM-unsupported fallback: alias the fused key too, so an
            # explicit mode="fused" request reuses this compilation
            # instead of walking and tracing the same program again.
            _CACHE.setdefault((key[0], key[1], cp.mode, cache_tag), cp)
        while len(_CACHE) > _CACHE_CAPACITY:
            _CACHE.popitem(last=False)
            _EVICTIONS += 1
    return cp


def clear_cache() -> None:
    """Drop all cached compilations and the walk memo, and reset their
    counters (tests / memory pressure).  VM executables persist — clear
    them separately via :func:`repro.core.vm.clear_executors` when
    measuring cold starts."""
    global _HITS, _MISSES, _EVICTIONS, _VM_FALLBACKS
    global _WALK_BYTES, _WALK_HITS, _WALK_MISSES
    with _CACHE_LOCK:
        _CACHE.clear()
        _TAG_COUNTS.clear()
        _HITS = _MISSES = _EVICTIONS = 0
        _VM_FALLBACKS = 0
        _WALK.clear()
        _WALK_BYTES = _WALK_HITS = _WALK_MISSES = 0
