"""One static load of each address pattern the fused tier tells apart
(``machine.load_shape``), for the executor equivalence tests.

Each case is a program that sets up the control registers, loads ``v0``
once and stores it densely at ``OUT``, with the number of static loads
the fused tier lowers as slices and as gathers.  The memory holds
``MEM`` words; a case that reads past its end exercises the gather's
clip.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import engine, isa
from repro.core.isa import DType

MEM = 16384
OUT = 9000


def _program(dims, ld_strides=None, modes=(), base=0, dtype=DType.F,
             unmask=()):
    prog = [isa.vsetwidth(32), isa.vsetdimc(len(dims))]
    prog += [isa.vsetdiml(d, n) for d, n in enumerate(dims)]
    prog += [isa.vsetldstr(d, s) for d, s in (ld_strides or {}).items()]
    prog += [isa.vunsetmask(i) for i in unmask]
    prog.append(isa.vsld(dtype, 0, base, *modes))
    prog += [isa.vsetmask(i) for i in unmask]
    prog.append(isa.vsst(DType.F if dtype.is_float else DType.DW, 0, OUT,
                         *([1] + [2] * (len(dims) - 1))))
    return prog


#: name -> (program, (loads sliced, loads gathered))
LOAD_CASES = {
    # a dense run (the GEMV tiles' weight rows)
    "contiguous": (_program((100,), modes=(1,), base=7), (1, 0)),
    # 64 words repeated over 128 lanes (the GEMV tiles' x: BCAST, SEQ)
    "broadcast": (_program((4, 10), modes=(0, 1), base=7), (1, 0)),
    # a column walk: lanes step by 8 words, rows by 1
    "permuted_2d": (_program((5, 6), {0: 8}, modes=(3, 1), base=11),
                    (1, 0)),
    # three nested strides given out of order
    "box_3d": (_program((3, 4, 5), {0: 40, 1: 1, 2: 7}, modes=(3, 3, 3),
                        base=5), (1, 0)),
    # the highest dimension's last row masked: the box is cut after the
    # last active row, so it fits where the whole box would run off
    "dim_masked": (_program((8, 6), modes=(1, 2), base=MEM - 44,
                            unmask=(1, 5)), (1, 0)),
    # more elements than lanes: lanes past the last are not loaded
    "wider_than_lanes": (_program((128, 100), modes=(1, 2), base=3),
                         (1, 0)),
    # a narrow saturating read of a strided box
    "narrow_int": (_program((7, 3), {1: 9}, modes=(1, 3), base=300,
                            dtype=DType.B), (1, 0)),
    # the window ends at the last word of memory
    "to_the_end": (_program((100,), modes=(1,), base=MEM - 100), (1, 0)),
    # the window runs one word off the end: gathered, with its clip
    "off_the_end": (_program((100,), modes=(1,), base=MEM - 99), (0, 1)),
    # strides that overlap (addresses repeat): gathered
    "overlapping": (_program((5, 6), {1: 3}, modes=(1, 3), base=11),
                    (0, 1)),
    # a negative stride: gathered
    "negative_stride": (_program((6,), {0: -2}, modes=(3,), base=40),
                        (0, 1)),
}


def load_memories(seed: int, count: int):
    """``count`` memory images of ``MEM`` words, with small integers,
    fractions and values past the narrow integer ranges."""
    rng = np.random.default_rng(seed)
    mems = []
    for _ in range(count):
        mem = rng.integers(-300, 300, MEM).astype(np.float64)
        mem[::3] = np.round(rng.normal(0, 1e3, len(mem[::3])), 2)
        mems.append(mem)
    return mems


def lowered_loads(program, size: int):
    """``(sliced, gathered, static loads)`` of ``program`` traced on the
    fused tier for ``size`` words, from a cleared compile cache (nothing
    is compiled or run)."""
    engine.clear_cache()
    cp = engine.compile_program(program, mode="fused")
    masks, zeros = cp._fused_operands()
    jax.eval_shape(cp._fused_fn, jax.ShapeDtypeStruct((size,), jnp.float32),
                   masks, zeros)
    info = engine.cache_info()
    return (info.loads_sliced, info.loads_gathered,
            sum(s.instr.op is isa.Op.SLD for s in cp.steps))
