"""MVE machine geometry and controller state.

Models the cache architecture of Section V: a 256 KB L2 slice repurposed as
32 compute-capable 8 KB SRAM arrays.  Each array has 256 bitlines; data
elements are transposed onto bitlines (Neural Cache layout), so every bitline
is one SIMD lane:

    lanes = num_arrays * bitlines = 32 * 256 = 8192

Arrays are grouped 4-per-Control-Block (CB); each CB has one FSM and can be
masked off per-instruction by the dimension-level mask (Section V-B).

A physical register (PR) occupies ``width`` wordlines out of 256, so the
number of live PRs is ``wordlines // width`` (Section III-B: constant vector
length, *variable* register count).

The addressing semantics (stride modes, dimension flattening, masking) are
documented with worked examples in docs/ISA.md.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import List, Tuple

import jax.numpy as jnp
import numpy as np

from .isa import MAX_DIMS, MAX_TOP_DIM, DType, Instr, Op, ProgramError

# Byte data in the mobile kernels (pixels, characters) is unsigned; wider
# integer types model the signed variants (the ISA has both, Section III-F).
JNP_DTYPE = {
    DType.B: jnp.uint8,
    DType.W: jnp.int16,
    DType.DW: jnp.int32,
    DType.QW: jnp.int64,
    DType.HF: jnp.float16,
    DType.F: jnp.float32,
}


@dataclasses.dataclass(frozen=True)
class MVEConfig:
    """Geometry + compute-scheme knobs (Table IV `MVE` row by default)."""

    num_arrays: int = 32          # 4 cache ways x 8 arrays
    bitlines: int = 256           # SIMD lanes per array
    wordlines: int = 256          # bits of register file per lane
    arrays_per_cb: int = 4        # Section V-B (Duality-Cache granularity)
    scheme: str = "bs"            # bs | bp | bh | ac
    bh_segment_bits: int = 4      # EVE segment width for the bh scheme
    freq_ghz: float = 2.8         # clocked with the core (Table IV)

    #: Compute schemes of Section II-B the cost models understand.
    KNOWN_SCHEMES = ("bs", "bp", "bh", "ac")

    def __post_init__(self) -> None:
        """Geometry sanity checks at construction time.

        A bad geometry (non-power-of-two array dimensions, an array count
        the CB grouping can't divide, an unknown scheme) used to flow
        silently into ``lanes``/``effective_lanes`` and produce nonsense
        lane counts far downstream; reject it here with a readable
        :class:`ProgramError` instead.
        """
        for field, value in (("num_arrays", self.num_arrays),
                             ("arrays_per_cb", self.arrays_per_cb)):
            if not (isinstance(value, int) and value > 0):
                raise ProgramError(
                    f"MVEConfig.{field} must be a positive int, "
                    f"got {value!r}")
        for field, value in (("bitlines", self.bitlines),
                             ("wordlines", self.wordlines),
                             ("bh_segment_bits", self.bh_segment_bits)):
            if not (isinstance(value, int) and value > 0
                    and value & (value - 1) == 0):
                raise ProgramError(
                    f"MVEConfig.{field} must be a positive power of two "
                    f"(the bitline/wordline decoders are binary trees), "
                    f"got {value!r}")
        if self.num_arrays % self.arrays_per_cb:
            raise ProgramError(
                f"MVEConfig.num_arrays={self.num_arrays} is not divisible "
                f"by arrays_per_cb={self.arrays_per_cb}; control blocks "
                f"must group whole arrays (Section V-B)")
        if self.scheme not in self.KNOWN_SCHEMES:
            raise ProgramError(
                f"unknown compute scheme {self.scheme!r}; known schemes: "
                f"{', '.join(self.KNOWN_SCHEMES)}")
        if self.freq_ghz <= 0:
            raise ProgramError(
                f"MVEConfig.freq_ghz must be positive, got {self.freq_ghz!r}")

    @property
    def lanes(self) -> int:
        return self.num_arrays * self.bitlines

    @property
    def num_cbs(self) -> int:
        return self.num_arrays // self.arrays_per_cb

    @property
    def lanes_per_cb(self) -> int:
        return self.bitlines * self.arrays_per_cb

    def num_physical_registers(self, width_bits: int) -> int:
        """Variable register count: 256 wordlines / live register width."""
        return self.wordlines // max(width_bits, 1)

    def effective_lanes(self, width_bits: int) -> int:
        """SIMD lanes available under each compute scheme (Section II-B).

        bs: every bitline is a lane.
        bp: n-bit data lies horizontally -> 8K/n lanes (VRAM).
        bh: p-bit segments lie horizontally -> 8K/p lanes (EVE).
        ac: bit-slices lie horizontally across arrays; lanes = wordlines x
            arrays/bits ~= 8K/ (bits/arrays)... CAPE keeps 8K-element tiles,
            we model the same lane count as bs (latency differs).
        """
        if self.scheme == "bs":
            return self.lanes
        if self.scheme == "bp":
            return self.lanes // max(width_bits, 1)
        if self.scheme == "bh":
            return self.lanes // max(self.bh_segment_bits, 1)
        if self.scheme == "ac":
            return self.lanes
        raise ValueError(f"unknown scheme {self.scheme!r}")


@dataclasses.dataclass
class ControlState:
    """The controller CRs (Section III-B / V-B)."""

    dim_count: int = 1
    dim_lens: List[int] = dataclasses.field(
        default_factory=lambda: [1] * MAX_DIMS)
    ld_strides: List[int] = dataclasses.field(
        default_factory=lambda: [0] * MAX_DIMS)
    st_strides: List[int] = dataclasses.field(
        default_factory=lambda: [0] * MAX_DIMS)
    # one mask bit per element of the highest dimension (max 256)
    dim_mask: np.ndarray = dataclasses.field(
        default_factory=lambda: np.ones(MAX_TOP_DIM, dtype=bool))
    kernel_width: int = 32

    def active_dims(self) -> Tuple[int, ...]:
        return tuple(self.dim_lens[: self.dim_count])

    def active_elements(self) -> int:
        return int(np.prod(self.active_dims()))

    def resolve_strides(self, modes: Tuple[int, ...], store: bool
                        ) -> Tuple[int, ...]:
        """Resolve 2-bit stride modes to absolute strides (Section III-C).

        mode 2 derives S_i = S_{i-1} * L_{i-1} with S_{-1} = 1, which is the
        "dense row-major continuation" stride.
        """
        crs = self.st_strides if store else self.ld_strides
        strides = []
        prev = 1
        for d in range(self.dim_count):
            mode = modes[d] if d < len(modes) else 1
            if mode == 0:
                s = 0
            elif mode == 1:
                s = 1
            elif mode == 2:
                prev_len = self.dim_lens[d - 1] if d > 0 else 1
                s = (strides[d - 1] if d > 0 else 1) * prev_len
            elif mode == 3:
                s = crs[d]
            else:
                raise ValueError(f"bad stride mode {mode}")
            strides.append(s)
            prev = s
        return tuple(strides)


def flatten_indices(dims: Tuple[int, ...], lanes: int) -> np.ndarray:
    """Map lane id -> multi-dim logical index, x fastest (Figure 5).

    Returns an int array of shape (lanes, len(dims)); lanes beyond
    prod(dims) are marked inactive with -1 in every coordinate.
    Memoized: compile walks resolve the same (dims, lanes) pair for every
    instruction under one configuration, and the result is pure.  Treat
    the returned array as read-only.
    """
    return _flatten_indices_cached(tuple(dims), lanes)


@functools.lru_cache(maxsize=512)
def _flatten_indices_cached(dims: Tuple[int, ...], lanes: int) -> np.ndarray:
    total = int(np.prod(dims))
    lane = np.arange(lanes, dtype=np.int64)
    coords = np.full((lanes, len(dims)), -1, dtype=np.int64)
    active = lane < total
    rem = np.where(active, lane, 0)
    for d, length in enumerate(dims):       # d=0 is x (fastest)
        coords[:, d] = np.where(active, rem % length, -1)
        rem = rem // length
    coords.setflags(write=False)
    return coords


def lane_dim_mask(dims: Tuple[int, ...], dim_mask: np.ndarray,
                  lanes: int) -> np.ndarray:
    """Expand the highest-dimension mask CR to a per-lane boolean mask."""
    coords = flatten_indices(dims, lanes)
    top = coords[:, len(dims) - 1]
    active = top >= 0
    top_clipped = np.clip(top, 0, len(dim_mask) - 1)
    return active & dim_mask[top_clipped]


def apply_config(ctrl: ControlState, instr: Instr) -> None:
    """Apply one config instruction to the control registers.

    Shared by the step interpreter, the program compiler
    (:mod:`repro.core.engine`), and the RVV lowering — the config ops are
    what both execution paths resolve *statically* (docs/ENGINE.md).
    """
    op = instr.op
    if op is Op.SET_DIMC:
        ctrl.dim_count = instr.imm
    elif op is Op.SET_DIML:
        # The mask CR only covers the first MAX_TOP_DIM elements of the
        # highest dimension (Section III-E); longer highest dims are
        # legal but can only be dimension-masked on that prefix.
        ctrl.dim_lens[instr.dim] = instr.length
    elif op is Op.SET_LDSTR:
        ctrl.ld_strides[instr.dim] = instr.stride
    elif op is Op.SET_STSTR:
        ctrl.st_strides[instr.dim] = instr.stride
    elif op is Op.SET_MASK:
        ctrl.dim_mask[instr.mask_index] = True
    elif op is Op.UNSET_MASK:
        ctrl.dim_mask[instr.mask_index] = False
    elif op is Op.SET_WIDTH:
        ctrl.kernel_width = instr.imm
    else:
        raise ValueError(f"not a config op: {op}")


def config_cell(instr: Instr) -> Tuple:
    """The control-register *cell* a config instruction writes.

    Cells are the unit of the optimizer's dead-config analysis and the
    frontend's duplicate-emission suppression: two writes touch the same
    architectural state iff they have the same cell.
    """
    op = instr.op
    if op is Op.SET_DIMC:
        return ("dimc",)
    if op is Op.SET_DIML:
        return ("diml", instr.dim)
    if op is Op.SET_LDSTR:
        return ("ldstr", instr.dim)
    if op is Op.SET_STSTR:
        return ("ststr", instr.dim)
    if op in (Op.SET_MASK, Op.UNSET_MASK):
        return ("mask", instr.mask_index)
    if op is Op.SET_WIDTH:
        return ("width",)
    raise ValueError(f"not a config op: {op}")


def read_config_cell(ctrl: ControlState, cell: Tuple):
    """Current value of one config cell (see :func:`config_cell`)."""
    kind = cell[0]
    if kind == "dimc":
        return ctrl.dim_count
    if kind == "diml":
        return ctrl.dim_lens[cell[1]]
    if kind == "ldstr":
        return ctrl.ld_strides[cell[1]]
    if kind == "ststr":
        return ctrl.st_strides[cell[1]]
    if kind == "mask":
        return bool(ctrl.dim_mask[cell[1]])
    if kind == "width":
        return ctrl.kernel_width
    raise ValueError(f"unknown config cell {cell!r}")


def stream_shape(dims: Tuple[int, ...], strides: Tuple[int, ...],
                 lanes: int) -> Tuple[int, int, int]:
    """(contiguous run, segments, unique elements) of a strided access.

    Cost-model metadata: stride-0 dims are replication (free through the
    TMU crossbar); among the rest, runs grow while each stride equals the
    current dense run size (mode-2 "derived" accesses collapse to a single
    contiguous run).
    """
    nz = sorted((s, ln) for ln, s in zip(dims, strides) if s != 0)
    run, segments, unique = 1, 1, 1
    for s, ln in nz:
        unique *= ln
        if s == run:
            run *= ln
        else:
            segments *= ln
    return run, segments, min(unique, lanes)


def touched_lines(addr: np.ndarray, mask: np.ndarray, nbytes: int) -> int:
    """Exact 64-byte cache lines covered by a masked address stream."""
    if not mask.any():
        return 0
    return int(np.unique((addr[mask] * nbytes) // 64).size)


def next_pow2(n: int) -> int:
    """Smallest power of two >= n (bucketing helper for the executors)."""
    return 1 << max(int(n) - 1, 0).bit_length()


# Out-of-bounds scatter sentinel base.  Dropped lanes get ``_OOB + lane`` so
# a sorted-unique index vector stays sorted and unique after masking (JAX
# ``mode="drop"`` scatters skip out-of-bounds rows; every modeled memory is
# far below 2**30 elements).
OOB_BASE = 1 << 30


def store_layout(addr: np.ndarray, mask: np.ndarray):
    """Classify a static store's per-lane addresses for the executors.

    Both the fused engine and the VM avoid XLA:CPU's scalar scatter loop
    (~1 ms per 8K-lane scatter) whenever the layout allows:

    * ``("none",)``            — no active lane; the store is a no-op.
    * ``("contig", base)``     — every active lane ``l`` writes ``base + l``
      (true for all dense row-major-continuation stores, i.e. every static
      store in the Section-IV patterns): executable as a slice blend.
    * ``("scatter", idx, perm)`` — general case: ``idx`` is a sorted,
      unique, collision-resolved index vector (masked lanes and all but the
      last writer of each address are pushed out of bounds, preserving the
      last-lane-wins scatter order) and ``perm`` reorders the source lanes
      to match.
    """
    if not mask.any():
        return ("none",)
    delta = addr[mask] - np.flatnonzero(mask)
    base = int(delta[0])
    if base >= 0 and (delta == base).all():
        return ("contig", base)
    return ("scatter",) + _scatter_layout(addr, mask)[:2]


def _scatter_layout(addr: np.ndarray, mask: np.ndarray):
    """``store_layout``'s scatter rows ``(idx, perm)``, with the number of
    winning lanes and their highest address."""
    lane = np.arange(addr.shape[0], dtype=np.int64)
    # Keep, per distinct address, only the highest active lane (last wins).
    act = np.flatnonzero(mask)
    order_a = np.argsort(addr[act], kind="stable")
    sorted_a = addr[act][order_a]
    last = np.ones(len(act), dtype=bool)
    last[:-1] = sorted_a[:-1] != sorted_a[1:]
    winners = act[order_a[last]]
    key = OOB_BASE + lane
    key[winners] = addr[winners]
    perm = np.argsort(key, kind="stable")
    return (key[perm].astype(np.int64), perm.astype(np.int32),
            len(winners), int(sorted_a[-1]))


def store_shape(offsets: np.ndarray, mask: np.ndarray) -> tuple:
    """The base-free part of :func:`store_layout` for the addresses
    ``base + offsets``, computed once for every base.

    Which lanes win is shift-invariant, and so is the sort order of the
    scatter keys while every winning address stays below ``OOB_BASE``.
    :func:`shift_store_layout` adds the base back.
    """
    if not mask.any():
        return ("none",)
    delta = offsets[mask] - np.flatnonzero(mask)
    if (delta == delta[0]).all():
        return ("contig", int(delta[0]))
    return ("scatter",) + _scatter_layout(offsets, mask)


def shift_store_layout(shape: tuple, base: int, addr: np.ndarray,
                       mask: np.ndarray):
    """``store_layout(addr, mask)`` for ``addr = base + offsets``, from
    ``store_shape(offsets, mask)``; the few cases whose shift is not exact
    (a negative contiguous base, a winner at or above ``OOB_BASE``) are
    classified from ``addr`` itself."""
    kind = shape[0]
    if kind == "none":
        return shape
    if kind == "contig" and shape[1] + base >= 0:
        return ("contig", shape[1] + base)
    if kind == "scatter":
        _, idx, perm, winners, top = shape
        if top < OOB_BASE and top + base < OOB_BASE:
            idx = idx.copy()
            idx[:winners] += base
            return ("scatter", idx, perm)
    return store_layout(addr, mask)


def load_shape(dims: Tuple[int, ...], strides: Tuple[int, ...],
               mask: np.ndarray, lanes: int) -> tuple:
    """How a static load of ``base + offsets`` reads memory, computed
    once for every base (the base only places the window).

    * ``("none",)`` — no active lane: nothing is read.
    * ``("box", span, levels, perm, shape, bshape, used)`` — the lanes up
      to the last active one read a box: broadcast (stride-0) dimensions
      over nested strided ones, whose positive strides, sorted, each
      clear the span of those below.  The words lie in the window
      ``[base, base + span)``.  ``levels`` holds ``(extent, stride)``
      per nested dimension, largest stride first: the window reshaped
      level by level and cut to what the level below reads.  ``perm``
      transposes the levels to lane order (dimension 0 fastest),
      ``shape`` (1 for a broadcast) and ``bshape`` broadcast them, and
      the first ``used`` words of the flattened box are lanes ``0..``.
    * ``("gather",)`` — anything else (negative or overlapping strides).

    The highest dimension is cut after the last active lane's index in
    it, so dimension-masked rows past it need not lie in memory.
    """
    if not mask.any():
        return ("none",)
    last = int(np.flatnonzero(mask)[-1])
    top = int(flatten_indices(dims, lanes)[last, -1]) + 1
    dims = tuple(dims[:-1]) + (top,)
    used = min(lanes, int(np.prod(dims)))
    merged: List[List[int]] = []          # [extent, stride], lane order
    for n, s in zip(dims, strides):
        if n == 1:
            continue
        if merged and s == merged[-1][0] * merged[-1][1]:
            merged[-1][0] *= n            # dense continuation, or broadcast
        else:
            merged.append([n, s])
    if any(s < 0 for _, s in merged):
        return ("gather",)
    order = sorted((i for i, (_, s) in enumerate(merged) if s),
                   key=lambda i: merged[i][1])
    levels, span = [], 1
    for i in order:
        n, s = merged[i]
        if s < span:                      # overlaps the levels below
            return ("gather",)
        levels.append((n, s))
        span += (n - 1) * s
    order.reverse()
    perm = tuple(order.index(i) for i in reversed(range(len(merged)))
                 if merged[i][1])
    shape = tuple(n if s else 1 for n, s in reversed(merged))
    bshape = tuple(n for n, _ in reversed(merged))
    return ("box", span, tuple(levels[::-1]), perm, shape, bshape, used)


def cbs_touched(dims: Tuple[int, ...], dim_mask: np.ndarray,
                cfg: MVEConfig) -> np.ndarray:
    """Which control blocks have at least one active lane (mask bit-vector

    the controller keeps per instruction, Section V-B)."""
    return cbs_from_lane_mask(lane_dim_mask(dims, dim_mask, cfg.lanes), cfg)


def cbs_from_lane_mask(lane_mask: np.ndarray, cfg: MVEConfig) -> np.ndarray:
    """CB participation derived from an already-expanded lane mask (the
    compile walks have one in hand; avoids re-expanding the dim mask)."""
    per_cb = lane_mask.reshape(cfg.num_cbs, cfg.lanes_per_cb)
    return per_cb.any(axis=1)
