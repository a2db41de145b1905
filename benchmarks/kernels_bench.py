"""Host wall-clock microbenchmarks of the Pallas kernels (compiled on
TPU, interpreted elsewhere) against the pure-jnp oracles — validates
dispatch overhead and gives a per-op cost sheet for the serving path.
Rows do not name a device; on a CPU they time the Pallas interpreter."""
from __future__ import annotations

import time
from typing import List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ref
from repro.kernels.bitplane_gemm import bitplane_matmul, int8_matmul
from repro.kernels.flash_attention import flash_attention

RNG = np.random.default_rng(0)


def _time(fn, *args, iters: int = 5) -> float:
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e6


def kernel_microbench() -> List[Tuple[str, float, str]]:
    rows = []

    # int8 GEMM 256x256x256
    x = jnp.asarray(RNG.integers(-128, 128, (256, 256)).astype(np.int8))
    w = jnp.asarray(RNG.integers(-128, 128, (256, 256)).astype(np.int8))
    t_i8 = _time(int8_matmul, x, w)
    t_bp = _time(bitplane_matmul, x, w)
    t_rf = _time(ref.int8_matmul_ref, x, w)
    rows.append(("kernels/int8_matmul_pallas", t_i8, "256^3"))
    rows.append(("kernels/bitplane_matmul_pallas", t_bp,
                 f"planes=8;vs_direct={t_bp/max(t_i8,1e-9):.1f}x"))
    rows.append(("kernels/int8_matmul_ref", t_rf, ""))

    # flash attention 2x4x256x64
    q = jnp.asarray(RNG.standard_normal((2, 4, 256, 64)).astype(np.float32))
    k = jnp.asarray(RNG.standard_normal((2, 4, 256, 64)).astype(np.float32))
    v = jnp.asarray(RNG.standard_normal((2, 4, 256, 64)).astype(np.float32))
    t_fa = _time(lambda a, b, c: flash_attention(a, b, c, causal=True),
                 q, k, v)
    t_fr = _time(lambda a, b, c: ref.flash_attention_ref(a, b, c,
                                                         causal=True),
                 q, k, v)
    rows.append(("kernels/flash_attention_pallas", t_fa, "2x4x256x64"))
    rows.append(("kernels/flash_attention_ref", t_fr,
                 f"ratio={t_fa/t_fr:.1f}x"))

    # MVE pattern execution through the pluggable target API
    # (docs/TARGETS.md): one loop over every registered target — the
    # wall clock is the shared functional engine (identical work, so the
    # rows double as a dispatch-overhead check), the derived column the
    # per-target modeled cycles the cost models assign the same run.
    from repro import targets
    from repro.core.patterns import PATTERNS
    run = PATTERNS["transpose"]()
    for tname in targets.list_targets():
        art = targets.compile(run.program, target=tname)
        t_eng = _time(lambda m: art.run(m)[0], run.memory)
        tl = art.timeline()
        rows.append((f"kernels/mve_transpose/{tname}", t_eng,
                     f"512x49;model_cycles={tl.total_cycles:.0f}"))
    return rows
