"""Section IV's data-parallel patterns, executed and priced.

Runs every Swan-library pattern — all of them built with the tracing
kernel frontend (docs/FRONTEND.md) — through the MVE execution engine
(docs/ENGINE.md; the default program-as-data VM shares one XLA executable
across the whole sweep, validating numerics per pattern), prices it on
the bit-serial engine vs the 1-D RVV lowering, and shows the same
multi-dim access through the jnp vsld/vsst oracles (gather + scatter =
the transpose pattern).

    PYTHONPATH=src python examples/mve_patterns.py
"""
import jax.numpy as jnp
import numpy as np

from repro.core import MVEConfig, cache_info, cost, rvv
from repro.core.patterns import PATTERNS, run_pattern
from repro.kernels.ref import mdgather_ref, mdscatter_ref


def main():
    cfg = MVEConfig()
    print(f"{'pattern':14s} {'library':12s} {'dim':4s} "
          f"{'mve_us':>8s} {'rvv_us':>8s} {'speedup':>8s}")
    for name in sorted(PATTERNS):
        run = PATTERNS[name]()
        mem_after, state = run_pattern(run, cfg)     # compiled engine
        run.check(np.asarray(mem_after), state)      # always validate
        tl = cost.simulate(state.trace, cfg)
        trace_rvv, _ = rvv.compile_to_rvv(run.program)
        tl_rvv = cost.simulate(trace_rvv, cfg)
        print(f"{name:14s} {run.library:12s} {run.dim:4s} "
              f"{tl.us(2.8):8.2f} {tl_rvv.us(2.8):8.2f} "
              f"{tl_rvv.total_cycles / tl.total_cycles:7.2f}x")

    info = cache_info()
    print(f"\n{len(PATTERNS)} programs executed through "
          f"{info.vm_signatures} VM signature(s) / "
          f"{info.vm_xla_compiles} XLA compilation(s)")

    print("\nvsld/vsst oracles: matrix transpose via gather + scatter")
    m = jnp.arange(64.0, dtype=jnp.float32)
    cols = mdgather_ref(m, dims=(8, 8), strides=(8, 1), base=0)
    out = mdscatter_ref(jnp.zeros_like(m), cols, dims=(8, 8),
                        strides=(1, 8), base=0)
    np.testing.assert_allclose(np.asarray(out).reshape(8, 8),
                               np.asarray(m).reshape(8, 8).T)
    print("  8x8 transpose through the multi-dim strided access: OK")


if __name__ == "__main__":
    main()
