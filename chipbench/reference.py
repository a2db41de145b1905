"""Plain reference of the MVE ISA, one instruction at a time, in numpy.

This is the yardstick that decides ``correct``.  It imports nothing of
the system under test: it reads the request as a client sends it (the
instruction list and the flat memory image) and computes what the
architecture defines (paper Section III; the repository's docs/ISA.md):

* registers are ``lanes``-wide vectors, memory is a flat array of
  float32 words addressed in elements;
* a strided load or store (``vsld``/``vsst``) walks up to four dimensions
  whose lengths and strides sit in control registers, lane ``i`` holding
  the element whose multi-dimensional index is ``i`` written x-fastest
  (Algorithm 1); stride modes are 0 (replicate), 1 (sequential),
  2 (derived: S_i = S_{i-1} * L_{i-1}) and 3 (the stride register);
* a random load or store (``vrld``/``vrst``) takes one base pointer per
  element of the highest dimension from memory (Equation 1);
* a dimension mask (one bit per element of the highest dimension) turns
  lanes off: a masked lane keeps its old register value, a masked store
  is dropped, and on colliding store addresses the later lane wins;
* ``predicated`` arithmetic also keeps the old value where the Tag latch
  is clear; compares write the Tag latch on the active lanes.

Instruction fields are read by name (``instr.op.name``,
``instr.dtype.name``), so the reference depends only on the request's
encoding.  ``bf16=True`` rounds every float32 arithmetic result to
bfloat16: the control that has to come out as not correct.
"""
from __future__ import annotations

from typing import Dict, Sequence, Tuple

import numpy as np

MAX_DIMS = 4
MAX_TOP_DIM = 256

# Register element types.  64-bit integers run as 32-bit ones, as JAX
# does in its default 32-bit mode (the machine's QW registers are
# architecturally 64-bit; no served kernel uses them).
DTYPES = {"B": np.uint8, "W": np.int16, "DW": np.int32, "QW": np.int32,
          "HF": np.float16, "F": np.float32}
NBYTES = {"B": 1, "W": 2, "DW": 4, "QW": 8, "HF": 2, "F": 4}

_CONFIG = {"SET_DIMC", "SET_DIML", "SET_LDSTR", "SET_STSTR", "SET_MASK",
           "UNSET_MASK", "SET_WIDTH"}
_COMPARE = {"GT": np.greater, "GTE": np.greater_equal, "LT": np.less,
            "LTE": np.less_equal, "EQ": np.equal, "NEQ": np.not_equal}
_BINARY = {"ADD": np.add, "SUB": np.subtract, "MUL": np.multiply,
           "MIN": np.minimum, "MAX": np.maximum, "XOR": np.bitwise_xor,
           "AND": np.bitwise_and, "OR": np.bitwise_or}


class Control:
    """The controller's configuration registers."""

    def __init__(self):
        self.dim_count = 1
        self.dim_lens = [1] * MAX_DIMS
        self.ld_strides = [0] * MAX_DIMS
        self.st_strides = [0] * MAX_DIMS
        self.dim_mask = np.ones(MAX_TOP_DIM, dtype=bool)

    def apply(self, instr) -> None:
        op = instr.op.name
        if op == "SET_DIMC":
            self.dim_count = instr.imm
        elif op == "SET_DIML":
            self.dim_lens[instr.dim] = instr.length
        elif op == "SET_LDSTR":
            self.ld_strides[instr.dim] = instr.stride
        elif op == "SET_STSTR":
            self.st_strides[instr.dim] = instr.stride
        elif op == "SET_MASK":
            self.dim_mask[instr.mask_index] = True
        elif op == "UNSET_MASK":
            self.dim_mask[instr.mask_index] = False
        # SET_WIDTH only sizes the register file; it changes no value.

    def dims(self) -> Tuple[int, ...]:
        return tuple(self.dim_lens[:self.dim_count])

    def strides(self, modes: Sequence[int], store: bool) -> Tuple[int, ...]:
        crs = self.st_strides if store else self.ld_strides
        out = []
        for d in range(self.dim_count):
            mode = modes[d] if d < len(modes) else 1
            if mode == 0:
                s = 0
            elif mode == 1:
                s = 1
            elif mode == 2:
                s = out[d - 1] * self.dim_lens[d - 1] if d else 1
            elif mode == 3:
                s = crs[d]
            else:
                raise ValueError(f"bad stride mode {mode}")
            out.append(s)
        return tuple(out)


def lane_coords(dims: Tuple[int, ...], lanes: int) -> np.ndarray:
    """(lanes, len(dims)) multi-dimensional index of each lane, x fastest;
    -1 on lanes past the product of the lengths."""
    lane = np.arange(lanes, dtype=np.int64)
    active = lane < int(np.prod(dims))
    rem = np.where(active, lane, 0)
    coords = np.full((lanes, len(dims)), -1, dtype=np.int64)
    for d, length in enumerate(dims):
        coords[:, d] = np.where(active, rem % length, -1)
        rem //= length
    return coords


def lane_mask(ctrl: Control, coords: np.ndarray) -> np.ndarray:
    top = coords[:, -1]
    return (top >= 0) & ctrl.dim_mask[np.clip(top, 0, MAX_TOP_DIM - 1)]


def addresses(ctrl: Control, instr, memory: np.ndarray, lanes: int,
              store: bool) -> Tuple[np.ndarray, np.ndarray]:
    """Element address and active flag of each lane for one access."""
    dims = ctrl.dims()
    coords = lane_coords(dims, lanes)
    strides = ctrl.strides(instr.modes or (), store)
    inner = np.where(coords >= 0, coords, 0)
    if instr.op.name in ("RLD", "RST"):
        top_len = dims[-1]
        ptrs = memory[instr.base:instr.base + top_len].astype(np.int64)
        addr = ptrs[np.clip(coords[:, -1], 0, top_len - 1)]
        for d in range(len(dims) - 1):
            addr = addr + inner[:, d] * strides[d]
    else:
        addr = instr.base + inner @ np.asarray(strides, dtype=np.int64)
    return addr, lane_mask(ctrl, coords)


def _round_bf16(x: np.ndarray) -> np.ndarray:
    """float32 -> nearest bfloat16 (ties to even), kept as float32."""
    bits = x.view(np.uint32).astype(np.uint64)
    bits = (bits + 0x7FFF + ((bits >> 16) & 1)) & 0xFFFF0000
    out = bits.astype(np.uint32).view(np.float32)
    return np.where(np.isnan(x), x, out)


def run(program: Sequence, memory, lanes: int, bf16: bool = False):
    """Execute ``program`` on a copy of ``memory``.

    Returns ``(memory, regs, tag)``: the float32 memory image, the final
    value of every register the program wrote (typed as its last write),
    and the per-lane Tag latch.
    """
    mem = np.asarray(memory).astype(np.float32)
    ctrl = Control()
    regs: Dict[int, np.ndarray] = {}
    tag = np.ones(lanes, dtype=bool)
    with np.errstate(all="ignore"):
        for instr in program:
            op = instr.op.name
            if op in _CONFIG:
                ctrl.apply(instr)
                continue
            if op == "SCALAR":
                continue
            dname = instr.dtype.name if instr.dtype is not None else "F"
            dt = DTYPES[dname]

            def reg(r, as_dt=dt):
                v = regs.get(r)
                if v is None:
                    return np.zeros(lanes, dtype=as_dt)
                return v.astype(as_dt)

            if op in ("SLD", "RLD"):
                addr, active = addresses(ctrl, instr, mem, lanes, False)
                got = mem[np.clip(addr, 0, mem.shape[0] - 1)].astype(dt)
                regs[instr.vd] = np.where(active, got, reg(instr.vd))
                continue
            if op in ("SST", "RST"):
                addr, active = addresses(ctrl, instr, mem, lanes, True)
                src = reg(instr.vs1).astype(np.float32)
                ok = active & (addr >= 0) & (addr < mem.shape[0])
                idx, val = addr[ok], src[ok]
                # Later lanes win on colliding addresses.
                _, last = np.unique(idx[::-1], return_index=True)
                keep = len(idx) - 1 - last
                mem[idx[keep]] = val[keep]
                continue

            coords = lane_coords(ctrl.dims(), lanes)
            active = lane_mask(ctrl, coords)
            if op in _COMPARE:
                a, b = reg(instr.vs1), reg(instr.vs2)
                tag = np.where(active, _COMPARE[op](a, b), tag)
                continue
            if op == "SET_DUP":
                result = np.full(lanes, instr.imm).astype(dt)
            elif op == "CPY":
                result = reg(instr.vs1)
            elif op == "CVT":
                src = regs.get(instr.vs1)
                if src is None:
                    src = np.zeros(lanes, dtype=np.float32)
                result = src.astype(dt)
            elif op in _BINARY:
                result = _BINARY[op](reg(instr.vs1), reg(instr.vs2))
            elif op == "SHI":
                a, amt = reg(instr.vs1), instr.imm
                result = a << amt if amt >= 0 else a >> (-amt)
            elif op == "ROTI":
                bits = NBYTES[dname] * 8
                amt = instr.imm % bits
                ua = reg(instr.vs1).astype(np.uint32)
                result = (ua << np.uint32(amt)) | (ua >> np.uint32(bits - amt))
            elif op == "SHR":
                result = reg(instr.vs1) << reg(instr.vs2).astype(np.int32)
            else:
                raise NotImplementedError(f"op {op}")
            result = np.asarray(result).astype(dt)
            if bf16 and dt is np.float32:
                result = _round_bf16(result)
            keep = active & tag if instr.predicated else active
            regs[instr.vd] = np.where(keep, result, reg(instr.vd))
    return mem, regs, tag


def access_bytes(program: Sequence, memory, lanes: int) -> int:
    """Architectural bytes one execution moves between memory and the
    register file: for each vector load and store, the distinct element
    addresses its active lanes touch times the element size.  Replicated
    (stride-0) lanes count once.  Random-access pointers are read from
    ``memory``, the request's input image (no served kernel writes the
    pointer array it walks)."""
    mem = np.asarray(memory)
    ctrl = Control()
    total = 0
    for instr in program:
        op = instr.op.name
        if op in _CONFIG:
            ctrl.apply(instr)
        elif op in ("SLD", "RLD", "SST", "RST"):
            addr, active = addresses(ctrl, instr, mem, lanes,
                                     op in ("SST", "RST"))
            total += np.unique(addr[active]).size * NBYTES[instr.dtype.name]
    return total
