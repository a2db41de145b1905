"""Program-as-data MVE virtual machine: one XLA executable per signature.

The fused engine (:mod:`repro.core.engine`) emits one ``jax.jit`` function
*per program*, so a data-dependent program stream — one spmm program per
sparsity pattern, one gemm per tile shape — retraces and recompiles XLA on
every variant; ``BENCH_engine.json`` recorded 3.59 s of compilation against
33 ms of execution for the 14-pattern sweep.  This module removes the
per-program compile by treating the program itself as *data*:

* the static step list produced by the engine's compile walk is lowered to
  dense tensors — an opcode/subcode table, packed register operands and
  immediates, flag bits, and compact deduplicated address-pattern / mask /
  scatter-index tables referenced by per-slot row indices;
* a single pre-jitted ``lax.while_loop`` (dynamic trip count: padded slots
  are never executed) steps over instruction slots, dispatching through
  ``lax.switch`` op-group handlers over a fixed ``(n_regs, lanes)``
  register file instead of a Python dict;
* the jitted executable is keyed only by a static *signature* —
  ``(lanes, n_regs, slot bucket, memory bucket, random bucket, pattern
  bucket, mask bucket, scatter bucket)`` — so every program with the same
  signature (all 14 patterns, every spmm sparsity variant, every seed)
  reuses one XLA compilation.

Bit-exactness discipline (the stepwise interpreter stays the oracle):

* JAX runs in its default 32-bit mode, so every architectural value fits
  32 bits.  The register file holds int32 *bit patterns*: integer values
  are stored sign-extended (wrapped to their declared width), floats are
  stored as their float32 bits (float16 extends exactly).  Per-slot flag
  bits record how each operand register is currently stored — that
  evolution is static, like everything else about MVE addressing.
* Integer ops compute in natively-wrapping int32 on operands wrapped to
  the instruction width, then re-wrap — exactly the eager per-dtype
  semantics.  Float ops compute on dtype-rounded operands, in f16 where
  the result rounds (add/sub/mul), so every instruction keeps its own
  rounding point; ``while_loop`` iterations are hard boundaries, which
  also makes the fused path's FP-contraction workaround unnecessary here.
* Memory stores use the layouts of :func:`repro.core.machine.store_layout`:
  contiguous stores become slice blends, everything else a collision-
  ordered ``mode="drop"`` scatter behind ``lax.cond`` (XLA:CPU scatter
  costs ~1 ms per 8K lanes; a skipped cond costs ~30 us).

The one datapath compile a process ever pays can also be cached across
processes via JAX's persistent compilation cache (:func:`enable_disk_cache`,
called by the entry points that want it: ``chip_smoke.py`` and
``benchmarks/engine_bench.py``), and :func:`prewarm` can overlap it with
program lowering on a background thread.

Design note with the full tensor encoding: docs/ENGINE.md ("VM lowering").
"""
from __future__ import annotations

import dataclasses
import functools
import os
import threading
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from . import isa
from .isa import DType, Op
from .machine import MVEConfig, OOB_BASE, next_pow2
from .tracing import TABLES, span, to_device, to_host

# -- signature buckets ------------------------------------------------------
N_REGS = 8           # dense register-file slots (virtual regs are remapped)
MIN_SLOTS = 128      # instruction-slot bucket floor
MIN_MEM = 131072     # memory bucket floor (elements)
MIN_PATTERNS = 32    # address-pattern table floor (rows)
MIN_MASKS = 16       # mask table floor (rows)

# -- flag columns (bool table) ----------------------------------------------
F_WRITES_REG, F_WRITES_TAG, F_BLEND, F_SCATTER, F_RAND, F_PRED, \
    F_A_ISF, F_B_ISF, F_OLD_ISF, F_F16, F_FLOAT, F_SETDUP, F_LOAD = range(13)
N_FLAGS = 13

# -- int columns (int32 table) ----------------------------------------------
I_OPC, I_VD, I_VS1, I_VS2, I_SUB, I_SBASE, I_IMM, I_AMT, I_BMA, I_MASK, \
    I_SIGN, I_LO, I_HI, I_SROW, I_AROW, I_ABASE, I_PROW, I_PBASE, \
    I_MROW = range(19)
N_INTS = 19

# -- opcodes (lax.switch branch indices) ------------------------------------
# Few, wide branches: XLA compile (and trace) time scales with the number
# of switch arms, so moves/shifts ride as ALU subcodes instead of arms.
(OPC_NOP, OPC_LOAD, OPC_STORE, OPC_INT, OPC_FLOAT, OPC_CMP) = range(6)

# subcodes
_INT_SUB = {Op.ADD: 0, Op.SUB: 1, Op.MUL: 2, Op.MIN: 3, Op.MAX: 4,
            Op.XOR: 5, Op.AND: 6, Op.OR: 7}
SUB_SHI, SUB_ROTI, SUB_SHR, SUB_MOVE_I = 8, 9, 10, 11
_FLT_SUB = {Op.ADD: 0, Op.SUB: 1, Op.MUL: 2, Op.MIN: 3, Op.MAX: 4}
SUB_MOVE_F = 5
_CMP_SUB = {Op.GT: 0, Op.GTE: 1, Op.LT: 2, Op.LTE: 3, Op.EQ: 4, Op.NEQ: 5}

# numpy views of the canonical int32 register file, per final dtype
_NP_DTYPE = {DType.B: np.uint8, DType.W: np.int16, DType.DW: np.int32,
             DType.QW: np.int32, DType.HF: np.float16, DType.F: np.float32}


class VMUnsupported(Exception):
    """Program cannot be lowered to the VM (e.g. too many live registers);
    :func:`repro.core.engine.compile_program` falls back to the fused path."""


# ---------------------------------------------------------------------------
# Executor-boundary fault hook (chaos engineering; repro.resilience).
# ---------------------------------------------------------------------------

# One process-wide hook shared by the VM and the fused engine (engine.py
# imports these — vm is the lower layer, so the registry lives here).  A
# :class:`repro.resilience.FaultInjector` installs its ``engine_hook`` to
# inject failures/latency at the real executor boundaries; ``None`` (the
# default) costs one attribute load per dispatch.
_FAULT_HOOK = None
_FAULT_HOOK_LOCK = threading.Lock()


def set_fault_hook(hook):
    """Install ``hook(site, **ctx)`` at the executor boundaries; returns
    the previous hook (restore it when done).  Sites fired here:
    ``vm.dispatch`` / ``vm.finalize``; :mod:`repro.core.engine` adds
    ``engine.compile`` / ``engine.dispatch`` / ``engine.finalize``."""
    global _FAULT_HOOK
    with _FAULT_HOOK_LOCK:
        prev, _FAULT_HOOK = _FAULT_HOOK, hook
    return prev


def fire_fault_hook(site: str, **ctx) -> None:
    hook = _FAULT_HOOK
    if hook is not None:
        hook(site, **ctx)


#: The compile cache's directory when ``JAX_COMPILATION_CACHE_DIR`` is
#: unset: one fixed path inside the checkout (listed in ``.gitignore``).
#: The path is part of what the cache finds again, so it must not move.
CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_disk_cache() -> str:
    """Turn on JAX's persistent compilation cache, so the VM's "compile
    the machine once" holds per machine, not per process.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own setting and is
    used as it is; otherwise the cache lives at :data:`CACHE_DIR`.
    Returns the directory in use.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = CACHE_DIR
        os.makedirs(path, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    return path


# ---------------------------------------------------------------------------
# AOT-capable jit wrapper (shared with the fused engine).
# ---------------------------------------------------------------------------

class AotJit:
    """``jax.jit`` plus explicit AOT warmup and a compile counter.

    The executable that ``jit_fn.lower(...).compile()`` returns is not
    one the jit's own call path dispatches to, so calling the wrapped
    function afterwards would trace again.  This wrapper keeps the AOT
    executable and routes calls with matching (shape, dtype) signatures
    to it, so :meth:`warmup` genuinely removes the first-call compile
    cliff.
    ``compiles`` counts distinct XLA compilations this wrapper triggered.
    """

    def __init__(self, fn, donate_argnums=()):
        self._jit = jax.jit(fn, donate_argnums=donate_argnums)
        self._aot = {}
        self._seen = set()
        self._lock = threading.Lock()
        self.compiles = 0

    @staticmethod
    def _key(args):
        return tuple((tuple(a.shape), str(a.dtype)) for a in args)

    def __call__(self, *args):
        key = self._key(args)
        compiled = self._aot.get(key)
        if compiled is not None:
            return compiled(*args)
        if key in self._seen:            # already compiled via the jit path
            return self._jit(*args)
        # First call for this key: the lock makes a call issued while a
        # background warmup (e.g. ``prewarm(block=False)``) is mid-compile
        # wait for that compile instead of racing a duplicate trace+compile.
        with self._lock:
            compiled = self._aot.get(key)
            if compiled is not None:
                return compiled(*args)
            out = self._jit(*args)
            if key not in self._seen:
                self._seen.add(key)
                self.compiles += 1
            return out

    def warmup(self, *args):
        """AOT-compile for the given (abstract or concrete) arguments."""
        key = self._key(args)
        with self._lock:
            if key not in self._aot:
                abstract = [jax.ShapeDtypeStruct(a.shape, a.dtype)
                            for a in args]
                self._aot[key] = self._jit.lower(*abstract).compile()
                if key not in self._seen:
                    self._seen.add(key)
                    self.compiles += 1
            return self._aot[key]


# ---------------------------------------------------------------------------
# Op-group handlers: 9 lax.switch branches over pre-cast operands.
# Operand reads/casts are hoisted into the loop body (they are shared by
# every group), keeping each branch small — XLA compile time of the switch
# scales with total branch HLO.
# ---------------------------------------------------------------------------

_F16, _F32, _I32, _U32 = jnp.float16, jnp.float32, jnp.int32, jnp.uint32


def _canon_f(x):
    return lax.bitcast_convert_type(x, _I32)


def _build_branches(lanes: int):
    def no_cmp():
        return jnp.zeros(lanes, dtype=bool)

    def wrap(v, iv):
        w = v & iv[I_MASK]
        return w - ((w & iv[I_SIGN]) << 1)

    def select8(sub, r):
        return jnp.where(
            sub < 4,
            jnp.where(sub < 2, jnp.where(sub == 0, r[0], r[1]),
                      jnp.where(sub == 2, r[2], r[3])),
            jnp.where(sub < 6, jnp.where(sub == 4, r[4], r[5]),
                      jnp.where(sub == 6, r[6], r[7])))

    def select5(sub, r):
        return jnp.where(sub < 2, jnp.where(sub == 0, r[0], r[1]),
                         jnp.where(sub == 2, r[2],
                                   jnp.where(sub == 3, r[3], r[4])))

    def cmp_select(sub, gt, lt, eq):
        return jnp.where(sub < 2, jnp.where(sub == 0, gt, gt | eq),
                         jnp.where(sub < 4, jnp.where(sub == 2, lt, lt | eq),
                                   jnp.where(sub == 4, eq, ~eq)))

    def out_row(fl, keep, ri, rf, old_i, old_f):
        """Write-back row: result under ``keep``, else the old value cast
        to the instruction dtype (mirrors the eager ``finish``)."""
        oi = jnp.where(keep, ri, old_i)
        of = _canon_f(jnp.where(keep, rf, old_f))
        return jnp.where(fl[F_FLOAT], of, oi)

    def h_nop(a_i, b_i, old_i, a_f, b_f, old_f, loaded, keep, fl, iv, fimm):
        return old_i, no_cmp()

    def h_load(a_i, b_i, old_i, a_f, b_f, old_f, loaded, keep, fl, iv, fimm):
        # Clamp to the dtype range before the int conversion: the eager
        # executors' direct f32->narrow astype saturates (XLA converts
        # saturate), and the clamp reproduces that bit for bit.
        clamped = jnp.clip(loaded, iv[I_LO].astype(_F32),
                           iv[I_HI].astype(_F32))
        gi = wrap(clamped.astype(_I32), iv)
        gf = jnp.where(fl[F_F16], loaded.astype(_F16).astype(_F32), loaded)
        return out_row(fl, keep, gi, gf, old_i, old_f), no_cmp()

    def h_store(a_i, b_i, old_i, a_f, b_f, old_f, loaded, keep, fl, iv,
                fimm):
        # Source lane values as memory words (f32), canonicalized so the
        # loop body can bitcast them back for the blend/scatter.
        return _canon_f(jnp.where(fl[F_FLOAT], a_f, a_i.astype(_F32))), \
            no_cmp()

    def h_int(a_i, b_i, old_i, a_f, b_f, old_f, loaded, keep, fl, iv, fimm):
        sub = iv[I_SUB]
        binop = select8(sub, [
            a_i + b_i, a_i - b_i, a_i * b_i, jnp.minimum(a_i, b_i),
            jnp.maximum(a_i, b_i), a_i ^ b_i, a_i & b_i, a_i | b_i])
        amt, bma = iv[I_AMT], iv[I_BMA]
        r_shi = (a_i << amt) >> bma           # one of amt/bma is zero
        ua = lax.bitcast_convert_type(a_i, _U32)
        r_rot = lax.bitcast_convert_type(
            (ua << amt.astype(_U32)) | (ua >> bma.astype(_U32)), _I32)
        r_shr = a_i << b_i                    # vshr: shift by register
        mv = jnp.where(fl[F_SETDUP], iv[I_IMM], a_i)   # vsetdup/vcpy/vcvt
        hi = jnp.where(sub == SUB_SHI, r_shi,
                       jnp.where(sub == SUB_ROTI, r_rot,
                                 jnp.where(sub == SUB_SHR, r_shr, mv)))
        r = jnp.where(sub < 8, binop, hi)
        return out_row(fl, keep, wrap(r, iv), a_f, old_i, old_f), no_cmp()

    def h_float(a_i, b_i, old_i, a_f, b_f, old_f, loaded, keep, fl, iv,
                fimm):
        # Operands are already rounded to the instruction dtype; min/max
        # pick an operand (no rounding), add/sub/mul must round in f16.
        a16, b16 = a_f.astype(_F16), b_f.astype(_F16)
        f16 = fl[F_F16]

        def rounded(f32_r, f16_r):
            return jnp.where(f16, f16_r.astype(_F32), f32_r)

        sub = iv[I_SUB]
        mvf = jnp.where(fl[F_SETDUP], fimm, a_f)       # vsetdup/vcpy/vcvt
        r = select5(sub, [
            rounded(a_f + b_f, a16 + b16),
            rounded(a_f - b_f, a16 - b16),
            rounded(a_f * b_f, a16 * b16),
            jnp.minimum(a_f, b_f), jnp.maximum(a_f, b_f)])
        r = jnp.where(sub == SUB_MOVE_F, mvf, r)
        return out_row(fl, keep, a_i, r, old_i, old_f), no_cmp()

    def h_cmp(a_i, b_i, old_i, a_f, b_f, old_f, loaded, keep, fl, iv,
              fimm):
        # dtype-rounded float operands compare identically in f32
        # (exact subset), so one branch serves every compare dtype.
        isf = fl[F_FLOAT]
        gt = jnp.where(isf, a_f > b_f, a_i > b_i)
        lt = jnp.where(isf, a_f < b_f, a_i < b_i)
        eq = jnp.where(isf, a_f == b_f, a_i == b_i)
        return old_i, cmp_select(iv[I_SUB], gt, lt, eq)

    return [h_nop, h_load, h_store, h_int, h_float, h_cmp]


# ---------------------------------------------------------------------------
# The signature-keyed executable.
# ---------------------------------------------------------------------------

def _make_execute(lanes: int, n_regs: int, slots: int):
    branches = _build_branches(lanes)

    def execute(memory, mem_hi, n_steps, ints, flags, fimm,
                pat_t, mask_t, scat_t, perm_t):
        regfile = jnp.zeros((n_regs, lanes), dtype=jnp.int32)
        tag = jnp.ones(lanes, dtype=bool)
        addrs_out = jnp.zeros((slots, lanes), dtype=jnp.int32)

        def read_operand(bits, isf, iv):
            """Canonical bits -> (wrapped int value, f32 numeric value).

            Float-stored registers read as integers clamp to the
            instruction dtype's range first: the eager executors cast with
            a direct (saturating) XLA convert, and clamp-then-convert
            reproduces that exactly for narrow dtypes."""
            as_f = lax.bitcast_convert_type(bits, _F32)
            f32 = jnp.where(isf, as_f, bits.astype(_F32))
            clamped = jnp.clip(as_f, iv[I_LO].astype(_F32),
                               iv[I_HI].astype(_F32))
            i_raw = jnp.where(isf, clamped.astype(_I32), bits)
            w = i_raw & iv[I_MASK]
            return w - ((w & iv[I_SIGN]) << 1), f32

        def body(carry):
            i, memory, regfile, tag, addrs_out = carry
            iv = ints[i]
            fl = flags[i]
            pat_row = lax.dynamic_index_in_dim(pat_t, iv[I_AROW],
                                               keepdims=False)
            addr_static = pat_row + iv[I_ABASE]
            mask_row = lax.dynamic_index_in_dim(mask_t, iv[I_MROW],
                                                keepdims=False)

            def rand_addr(_):
                ptr_pat = lax.dynamic_index_in_dim(pat_t, iv[I_PROW],
                                                   keepdims=False)
                ptr_idx = jnp.clip(ptr_pat + iv[I_PBASE], 0, mem_hi)
                return memory[ptr_idx].astype(jnp.int32) + addr_static

            addr = lax.cond(fl[F_RAND], rand_addr,
                            lambda _: addr_static, None)
            loaded = lax.cond(
                fl[F_LOAD],
                lambda _: memory[jnp.clip(addr, 0, mem_hi)],
                lambda _: jnp.zeros(lanes, dtype=memory.dtype), None)

            a_i, a_f32 = read_operand(regfile[iv[I_VS1]], fl[F_A_ISF], iv)
            b_i, b_f32 = read_operand(regfile[iv[I_VS2]], fl[F_B_ISF], iv)
            old_raw = regfile[iv[I_VD]]
            old_i, old_f32 = read_operand(old_raw, fl[F_OLD_ISF], iv)
            f16 = fl[F_F16]
            a_f = jnp.where(f16, a_f32.astype(_F16).astype(_F32), a_f32)
            b_f = jnp.where(f16, b_f32.astype(_F16).astype(_F32), b_f32)
            old_f = jnp.where(f16, old_f32.astype(_F16).astype(_F32),
                              old_f32)
            keep = mask_row & jnp.where(fl[F_PRED], tag, True)

            row, cmp = lax.switch(iv[I_OPC], branches, a_i, b_i, old_i,
                                  a_f, b_f, old_f, loaded, keep, fl, iv,
                                  fimm[i])

            regfile = regfile.at[iv[I_VD]].set(
                jnp.where(fl[F_WRITES_REG], row, old_raw))
            tag = jnp.where(fl[F_WRITES_TAG] & mask_row, cmp, tag)

            def blend(mem):
                base = iv[I_SBASE]
                window = lax.dynamic_slice(mem, (base,), (lanes,))
                src = lax.bitcast_convert_type(row, jnp.float32)
                return lax.dynamic_update_slice(
                    mem, jnp.where(mask_row, src, window), (base,))

            def scatter(mem):
                sidx = lax.dynamic_index_in_dim(scat_t, iv[I_SROW],
                                                keepdims=False)
                prow = lax.dynamic_index_in_dim(perm_t, iv[I_SROW],
                                                keepdims=False)
                idx = jnp.where(fl[F_RAND],
                                jnp.where(mask_row, addr, -1), sidx)
                src = lax.bitcast_convert_type(row, jnp.float32)[prow]
                return mem.at[idx].set(src, mode="drop")

            memory = lax.cond(fl[F_BLEND], blend, lambda m: m, memory)
            memory = lax.cond(fl[F_SCATTER], scatter, lambda m: m, memory)
            addrs_out = lax.cond(
                fl[F_RAND],
                lambda ao: lax.dynamic_update_slice(ao, addr[None], (i, 0)),
                lambda ao: ao, addrs_out)
            return i + 1, memory, regfile, tag, addrs_out

        _, memory, regfile, tag, addrs_out = lax.while_loop(
            lambda c: c[0] < n_steps, body,
            (jnp.int32(0), memory, regfile, tag, addrs_out))
        return memory, regfile, tag, addrs_out

    return execute


class _Executor:
    """One compiled VM datapath (single-image jit + vmapped batch jit)."""

    def __init__(self, sig: Tuple[int, ...]):
        self.sig = sig
        lanes, n_regs, slots = sig[0], sig[1], sig[2]
        fn = _make_execute(lanes, n_regs, slots)
        self.single = AotJit(fn, donate_argnums=(0,))
        self.batch = AotJit(jax.vmap(fn, in_axes=(0,) + (None,) * 9),
                            donate_argnums=(0,))

    def table_structs(self):
        """Abstract (shape, dtype) of the table operands for this sig."""
        lanes, _, slots = self.sig[0], self.sig[1], self.sig[2]
        pat, msk, scat = self.sig[5], self.sig[6], self.sig[7]
        sds = jax.ShapeDtypeStruct
        return (sds((slots, N_INTS), jnp.int32),
                sds((slots, N_FLAGS), jnp.bool_),
                sds((slots,), jnp.float32),
                sds((pat, lanes), jnp.int32),
                sds((msk, lanes), jnp.bool_),
                sds((scat, lanes), jnp.int32),
                sds((scat, lanes), jnp.int32))


_EXECUTORS: Dict[Tuple[int, ...], _Executor] = {}
_EXECUTORS_LOCK = threading.Lock()
_HITS = 0


def _executor(sig: Tuple[int, ...]) -> _Executor:
    global _HITS
    with _EXECUTORS_LOCK:
        ex = _EXECUTORS.get(sig)
        if ex is None:
            ex = _EXECUTORS[sig] = _Executor(sig)
        else:
            _HITS += 1
    return ex


def default_signature(cfg: MVEConfig | None = None,
                      mem_size: int = MIN_MEM) -> Tuple[int, ...]:
    """The signature every bucket-floor program maps to — all 14 Section-IV
    patterns and their data-dependent variants share this one executable."""
    cfg = cfg or MVEConfig()
    bucket = next_pow2(max(mem_size, MIN_MEM))
    return (cfg.lanes, N_REGS, MIN_SLOTS, bucket, MIN_SLOTS, MIN_PATTERNS,
            MIN_MASKS, 1)


def prewarm(cfg: MVEConfig | None = None, mem_size: int = MIN_MEM,
            block: bool = True) -> Optional[threading.Thread]:
    """AOT-compile (or load from the persistent cache) the default-
    signature datapath.  With ``block=False`` the compile runs on a daemon
    thread so callers can lower programs concurrently; join the returned
    thread (or just call :meth:`VMProgram.run`) before timing executions.
    """
    sig = default_signature(cfg, mem_size)

    def _warm():
        ex = _executor(sig)
        scalar = jax.ShapeDtypeStruct((), jnp.int32)
        mem = jax.ShapeDtypeStruct((sig[3] + sig[0],), jnp.float32)
        ex.single.warmup(mem, scalar, scalar, *ex.table_structs())

    if block:
        _warm()
        return None
    t = threading.Thread(target=_warm, daemon=True, name="mve-vm-prewarm")
    t.start()
    return t


def clear_executors() -> None:
    """Drop all signature-keyed executables (tests / cold-start measures).
    The on-disk XLA cache (when enabled) is unaffected."""
    global _HITS
    with _EXECUTORS_LOCK:
        _EXECUTORS.clear()
        _HITS = 0


@dataclasses.dataclass(frozen=True)
class VMCacheInfo:
    signatures: int          # distinct executors alive
    hits: int                # executor-cache hits
    xla_compiles: int        # distinct XLA compilations (incl. batch/AOT)


def cache_info() -> VMCacheInfo:
    compiles = sum(ex.single.compiles + ex.batch.compiles
                   for ex in _EXECUTORS.values())
    return VMCacheInfo(signatures=len(_EXECUTORS), hits=_HITS,
                       xla_compiles=compiles)


# ---------------------------------------------------------------------------
# Lowering: engine steps -> dense tensors.
# ---------------------------------------------------------------------------

_I32_MIN, _I32_MAX = -(1 << 31), (1 << 31) - 1


def _dtype_fields(dt: DType) -> Tuple[int, int, int, int, bool, bool]:
    """(wrap_mask, sign_bit, clamp_lo, clamp_hi, is_float, is_f16) for the
    32-bit datapath.  QW runs as a 32-bit integer — identical to the eager
    paths, which also canonicalize int64 to int32 under JAX's default
    32-bit mode.  clamp_lo/hi bound float->int reads so they saturate like
    the eager executors' direct converts (for 32-bit targets the f32->i32
    convert saturates natively, so the bounds are the i32 extremes)."""
    if dt.is_float:
        return -1, 0, _I32_MIN, _I32_MAX, True, dt is DType.HF
    bits = min(dt.bits, 32)
    if bits >= 32:
        return -1, 0, _I32_MIN, _I32_MAX, False, False
    mask = (1 << bits) - 1
    if dt is DType.B:
        return mask, 0, 0, mask, False, False
    sign = 1 << (bits - 1)
    return mask, sign, -sign, sign - 1, False, False


def _wrap_host(value: int, mask: int, sign: int) -> int:
    if mask == -1:                   # full 32-bit register
        v = int(value) & 0xFFFFFFFF
        return v - (1 << 32) if v >= (1 << 31) else v
    v = int(value) & mask
    if sign and v & sign:
        v -= sign << 1
    return v


class _RowInterner:
    """Deduplicate (lanes,) rows; returns stable row indices."""

    def __init__(self, first_row: np.ndarray):
        self.rows = [first_row]
        self._index = {first_row.tobytes(): 0}

    def add(self, row: np.ndarray) -> int:
        key = row.tobytes()
        idx = self._index.get(key)
        if idx is None:
            idx = self._index[key] = len(self.rows)
            self.rows.append(row)
        return idx


class VMProgram:
    """A program lowered to VM tensors; executes via the signature cache.

    Built by :class:`repro.core.engine.CompiledProgram` in ``mode="vm"``;
    raises :class:`VMUnsupported` when the program does not fit the fixed
    datapath (more than ``N_REGS`` live registers).
    """

    def __init__(self, steps, cfg: MVEConfig, n_random: int):
        self.cfg = cfg
        self.n_random = n_random
        lanes = cfg.lanes
        with span(TABLES):
            self._lower(steps, lanes)
            self.slots_bucket = next_pow2(max(self.n_steps, MIN_SLOTS))
            self._pad_tables(lanes)

    # -- lowering ----------------------------------------------------------
    def _lower(self, steps, lanes: int) -> None:
        regmap: Dict[int, int] = {}
        stored_float: Dict[int, bool] = {}
        final_dtype: Dict[int, DType] = {}

        def slot_of(vreg: Optional[int]) -> int:
            if vreg is None:
                return 0
            if vreg not in regmap:
                if len(regmap) >= N_REGS:
                    raise VMUnsupported(
                        f"program uses more than {N_REGS} live registers")
                regmap[vreg] = len(regmap)
            return regmap[vreg]

        ints: List[np.ndarray] = []
        flags: List[np.ndarray] = []
        fimm: List[float] = []
        patterns = _RowInterner(np.zeros(lanes, dtype=np.int32))
        masks = _RowInterner(np.zeros(lanes, dtype=bool))
        self._scat_rows: List[np.ndarray] = []
        self._perm_rows: List[np.ndarray] = []
        self.rand_slot_to_step: List[int] = [0] * self.n_random
        self.max_blend_base = 0

        for step in steps:
            instr = step.instr
            op = instr.op
            if op in isa.CONFIG_OPS or op is Op.SCALAR:
                continue                       # pure no-ops in the datapath

            iv = np.zeros(N_INTS, dtype=np.int64)
            fl = np.zeros(N_FLAGS, dtype=bool)
            fv = 0.0
            dt = instr.dtype
            mask, sign, lo, hi, is_f, is_f16 = _dtype_fields(dt)
            iv[I_MASK], iv[I_SIGN] = mask, sign
            iv[I_LO], iv[I_HI] = lo, hi
            fl[F_FLOAT], fl[F_F16] = is_f, is_f16
            # The eager executors honor the Tag latch only on compute
            # write-backs (their ``finish``); memory ops use the lane mask
            # alone — mirror that exactly.
            fl[F_PRED] = instr.predicated and op not in isa.MEMORY_OPS
            iv[I_MROW] = masks.add(step.lane_mask)

            def src(vreg, col, fl=fl):
                s = slot_of(vreg)
                fl[col] = stored_float.get(s, False)
                return s

            def dst(vreg, fl=fl, iv=iv):
                s = slot_of(vreg)
                iv[I_VD] = s
                fl[F_OLD_ISF] = stored_float.get(s, False)
                fl[F_WRITES_REG] = True
                return s

            def wrote(vreg, slot, is_float=is_f, dt=dt):
                stored_float[slot] = is_float
                final_dtype[vreg] = dt

            def static_addr(iv=iv, step=step):
                base = int(step.instr.base)
                iv[I_ABASE] = base
                iv[I_AROW] = patterns.add(
                    (step.addr - base).astype(np.int32))

            def rand_addr(iv=iv, fl=fl, step=step, at=len(ints)):
                fl[F_RAND] = True
                iv[I_AROW] = patterns.add(step.offsets.astype(np.int32))
                iv[I_PBASE] = int(step.ptr_base)
                iv[I_PROW] = patterns.add(step.top_idx.astype(np.int32))
                self.rand_slot_to_step[step.rand_slot] = at

            if op in (Op.SLD, Op.RLD):
                iv[I_OPC] = OPC_LOAD
                fl[F_LOAD] = True
                s = dst(instr.vd)
                if step.rand_slot is not None:
                    rand_addr()
                else:
                    static_addr()
                wrote(instr.vd, s)
            elif op in (Op.SST, Op.RST):
                iv[I_OPC] = OPC_STORE
                iv[I_VS1] = src(instr.vs1, F_A_ISF)
                if step.rand_slot is not None:
                    fl[F_SCATTER] = True
                    rand_addr()
                else:
                    layout = step.store_layout
                    if layout[0] == "contig":
                        fl[F_BLEND] = True
                        iv[I_SBASE] = layout[1]
                        self.max_blend_base = max(self.max_blend_base,
                                                  layout[1])
                    elif layout[0] == "scatter":
                        fl[F_SCATTER] = True
                        iv[I_SROW] = len(self._scat_rows) + 1  # row 0 shared
                        self._scat_rows.append(layout[1])
                        self._perm_rows.append(layout[2])
                    else:                      # fully masked store: no-op
                        continue
            elif op is Op.SET_DUP:
                iv[I_OPC] = OPC_FLOAT if is_f else OPC_INT
                iv[I_SUB] = SUB_MOVE_F if is_f else SUB_MOVE_I
                fl[F_SETDUP] = True
                s = dst(instr.vd)
                if is_f:
                    fv = float(np.float32(np.float16(instr.imm))) if is_f16 \
                        else float(np.float32(instr.imm))
                else:
                    iv[I_IMM] = _wrap_host(int(instr.imm), mask, sign)
                wrote(instr.vd, s)
            elif op in (Op.CPY, Op.CVT):
                iv[I_OPC] = OPC_FLOAT if is_f else OPC_INT
                iv[I_SUB] = SUB_MOVE_F if is_f else SUB_MOVE_I
                iv[I_VS1] = src(instr.vs1, F_A_ISF)
                s = dst(instr.vd)
                wrote(instr.vd, s)
            elif op in isa.COMPARE_OPS:
                iv[I_OPC] = OPC_CMP
                fl[F_WRITES_TAG] = True
                iv[I_SUB] = _CMP_SUB[op]
                iv[I_VS1] = src(instr.vs1, F_A_ISF)
                iv[I_VS2] = src(instr.vs2, F_B_ISF)
            elif op in (Op.SHI, Op.ROTI, Op.SHR):
                if is_f:
                    raise ValueError("shift on float register")
                iv[I_OPC] = OPC_INT
                iv[I_SUB] = {Op.SHI: SUB_SHI, Op.ROTI: SUB_ROTI,
                             Op.SHR: SUB_SHR}[op]
                if op is Op.SHI:
                    iv[I_AMT] = max(instr.imm, 0)
                    iv[I_BMA] = max(-instr.imm, 0)
                elif op is Op.ROTI:
                    # Mirror the eager expression exactly: amt = imm % bits
                    # with the *declared* width; the u32 datapath then
                    # matches the eager u32-canonicalized rotate for every
                    # in-range amount.
                    amt = instr.imm % dt.bits
                    iv[I_AMT], iv[I_BMA] = amt, dt.bits - amt
                iv[I_VS1] = src(instr.vs1, F_A_ISF)
                if instr.vs2 is not None:
                    iv[I_VS2] = src(instr.vs2, F_B_ISF)
                s = dst(instr.vd)
                wrote(instr.vd, s, is_float=False)
            else:
                table = _FLT_SUB if is_f else _INT_SUB
                if op not in table:
                    raise ValueError(f"op {op} on dtype {dt}")
                iv[I_OPC] = OPC_FLOAT if is_f else OPC_INT
                iv[I_SUB] = table[op]
                iv[I_VS1] = src(instr.vs1, F_A_ISF)
                iv[I_VS2] = src(instr.vs2, F_B_ISF)
                s = dst(instr.vd)
                wrote(instr.vd, s)

            ints.append(iv)
            flags.append(fl)
            fimm.append(fv)

        self.n_steps = len(ints)
        self._ints = ints
        self._flags = flags
        self._fimm = fimm
        self._patterns = patterns
        self._masks = masks
        self.final_dtype = final_dtype
        self.regmap = regmap

    def _pad_tables(self, lanes: int) -> None:
        slots = self.slots_bucket
        self.pat_bucket = next_pow2(max(len(self._patterns.rows),
                                        MIN_PATTERNS))
        self.mask_bucket = next_pow2(max(len(self._masks.rows), MIN_MASKS))
        self.scat_bucket = next_pow2(len(self._scat_rows) + 1)  # + row 0
        ints = np.zeros((slots, N_INTS), dtype=np.int32)
        flags = np.zeros((slots, N_FLAGS), dtype=bool)
        fimm = np.zeros(slots, dtype=np.float32)
        pat_t = np.zeros((self.pat_bucket, lanes), dtype=np.int32)
        mask_t = np.zeros((self.mask_bucket, lanes), dtype=bool)
        n = self.n_steps
        if n:
            ints[:n] = np.stack(self._ints).astype(np.int32)
            flags[:n] = np.stack(self._flags)
            fimm[:n] = np.asarray(self._fimm, dtype=np.float32)
        pat_t[:len(self._patterns.rows)] = np.stack(self._patterns.rows)
        mask_t[:len(self._masks.rows)] = np.stack(self._masks.rows)
        if self._scat_rows:
            scat = np.zeros((self.scat_bucket, lanes), dtype=np.int64)
            perm = np.tile(np.arange(lanes, dtype=np.int32),
                           (self.scat_bucket, 1))
            scat[0] = OOB_BASE + np.arange(lanes, dtype=np.int64)
            for i, row in enumerate(self._scat_rows):
                scat[i + 1] = row
            for i, row in enumerate(self._perm_rows):
                perm[i + 1] = row
            scat_t = jnp.asarray(np.minimum(
                scat, np.iinfo(np.int32).max).astype(np.int32))
            perm_t = jnp.asarray(perm)
        else:
            scat_t = _empty_scat_table(lanes)
            perm_t = _identity_perm_table(lanes)
        self.tables = (jnp.asarray(ints), jnp.asarray(flags),
                       jnp.asarray(fimm), jnp.asarray(pat_t),
                       jnp.asarray(mask_t), scat_t, perm_t)
        # Observability: live row counts of the deduplicated tables
        # (before bucket padding).  The optimizer's IR-level CSE shrinks
        # the *instruction stream*; these counters let benchmarks and
        # tests show how that composes with the VM's own row interning
        # (``benchmarks/opt_bench.py``).
        self.table_rows = {
            "steps": self.n_steps,
            "patterns": len(self._patterns.rows),
            "masks": len(self._masks.rows),
            "scatters": len(self._scat_rows),
        }
        del (self._ints, self._flags, self._fimm, self._patterns,
             self._masks, self._scat_rows, self._perm_rows)

    # -- execution ---------------------------------------------------------
    def _signature(self, mem_size: int) -> Tuple[int, ...]:
        bucket = next_pow2(max(mem_size, MIN_MEM, self.max_blend_base + 1))
        # Random-access bucket: one address row per slot, so programs with
        # and without random ops share one executable (docs/ENGINE.md).
        return (self.cfg.lanes, N_REGS, self.slots_bucket, bucket,
                self.slots_bucket, self.pat_bucket, self.mask_bucket,
                self.scat_bucket)

    def _pad_memory(self, memory, bucket: int) -> np.ndarray:
        mem = to_host(memory)       # a device image is fetched, and counted
        buf = np.zeros(mem.shape[:-1] + (bucket + self.cfg.lanes,),
                       dtype=np.float32)
        buf[..., : mem.shape[-1]] = mem
        return buf

    def _args(self, mem_size: int):
        return (jnp.int32(mem_size - 1), jnp.int32(self.n_steps))

    def run_async(self, memory):
        """Dispatch one memory image without waiting: returns an opaque
        pending handle whose device buffers are still being computed.
        Pass it to :meth:`finalize` to materialize host results — the
        split lets a serving loop (:mod:`repro.runtime.scheduler`) enqueue
        many executions back to back and pay one sync, instead of a
        host round trip per request."""
        fire_fault_hook("vm.dispatch", tier="vm")
        mem_size = np.shape(memory)[0]
        sig = self._signature(mem_size)
        ex = _executor(sig)
        # A fresh buffer: the executable donates (and therefore writes
        # through) it — it must be jax-owned, not a zero-copy alias of the
        # short-lived numpy padding buffer.
        buf = to_device(self._pad_memory(memory, sig[3]))
        out = ex.single(buf, *self._args(mem_size), *self.tables)
        return (mem_size, out)

    def finalize(self, pending):
        """Host results of a :meth:`run_async` dispatch (blocks on it).

        Memory and registers come back as host (numpy) views of the fixed-
        shape device outputs: slicing/casting them on device would compile
        one trivial XLA executable per distinct program geometry, defeating
        the signature sharing.
        """
        fire_fault_hook("vm.finalize", tier="vm")
        mem_size, (mem, regfile, tag, addrs) = pending
        return (np.array(to_host(mem)[:mem_size]), self._regs(regfile),
                tag, self._rand_addrs(addrs))

    def run(self, memory):
        """Execute one memory image; returns ``(mem, regs, tag, rand)``
        with ``rand`` the per-random-op address vectors for the trace."""
        return self.finalize(self.run_async(memory))

    def run_batch_async(self, memories):
        """Batched :meth:`run_async`: one vmapped dispatch over a leading
        batch of memory images; finalize with :meth:`finalize_batch`."""
        fire_fault_hook("vm.dispatch", tier="vm")
        mems = to_host(memories)
        mem_size = mems.shape[-1]
        sig = self._signature(mem_size)
        ex = _executor(sig)
        buf = to_device(self._pad_memory(mems, sig[3]))
        out = ex.batch(buf, *self._args(mem_size), *self.tables)
        return (mem_size, out)

    def finalize_batch(self, pending):
        fire_fault_hook("vm.finalize", tier="vm")
        mem_size, (mem, regfile, tag, _) = pending
        return (np.array(to_host(mem)[..., :mem_size]),
                self._regs(regfile, batched=True), tag)

    def run_batch(self, memories):
        return self.finalize_batch(self.run_batch_async(memories))

    def warmup(self, mem_size: int, batch: Optional[int] = None) -> None:
        sig = self._signature(mem_size)
        ex = _executor(sig)
        padded = sig[3] + self.cfg.lanes
        scalar = jax.ShapeDtypeStruct((), jnp.int32)
        if batch is None:
            m = jax.ShapeDtypeStruct((padded,), jnp.float32)
            ex.single.warmup(m, scalar, scalar, *self.tables)
        else:
            m = jax.ShapeDtypeStruct((batch, padded), jnp.float32)
            ex.batch.warmup(m, scalar, scalar, *self.tables)

    # -- result reconstruction ---------------------------------------------
    def _regs(self, regfile, batched: bool = False):
        """Typed register values, reconstructed host-side in numpy (no
        per-program XLA dispatches; values are bit-identical to the eager
        executors' typed arrays)."""
        rf = np.array(to_host(regfile))   # owned copy
        regs = {}
        for vreg, s in self.regmap.items():
            dt = self.final_dtype.get(vreg)
            if dt is None:
                continue
            row = np.ascontiguousarray(rf[:, s] if batched else rf[s])
            if dt.is_float:
                val = row.view(np.float32)
                if dt is DType.HF:
                    val = val.astype(np.float16)
            else:
                val = row.astype(_NP_DTYPE[dt])
            regs[vreg] = val
        return regs

    def _rand_addrs(self, addrs_out):
        if not self.n_random:
            return []
        addrs = to_host(addrs_out)
        return [addrs[self.rand_slot_to_step[r]].astype(np.int64)
                for r in range(self.n_random)]


@functools.lru_cache(maxsize=16)
def _empty_scat_table(lanes: int):
    row = np.minimum(OOB_BASE + np.arange(lanes, dtype=np.int64),
                     np.iinfo(np.int32).max).astype(np.int32)
    return jnp.asarray(row[None, :])


@functools.lru_cache(maxsize=16)
def _identity_perm_table(lanes: int):
    return jnp.asarray(np.arange(lanes, dtype=np.int32)[None, :])
