"""Compile the main-path programs for a described TPU v5e chip.

Nothing runs: each test lowers and compiles for one chip of a ``v5e:2x2``
topology that the installed TPU compiler describes without the chip
being attached.  That catches what interpret-mode tests cannot — Mosaic
refusing a kernel, a program too large for the device — at no chip
time.  The topology is built in a fixture (never at import), so every
test worker collects the same tests and only the one given this file
loads the TPU library; where it cannot be described, the tests skip.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")   # no compiler logs
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:       # no TPU compiler / library lock held
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """The described chip, with the persistent compile cache off: what is
    compiled for an unattached chip cannot be read back here."""
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _on(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _tree_on(sharding, tree):
    return jax.tree.map(lambda a: _on(sharding, a.shape, a.dtype), tree)


# ---------------------------------------------------------------------------
# MVE VM datapath at the default signature bucket.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("batch", [None, 4])
def test_vm_datapath_default_signature(one_chip, batch):
    from repro.core import vm
    sig = vm.default_signature()
    assert sig == (8192, 8, 128, 131072, 128, 32, 16, 1)
    lanes, n_regs, slots = sig[:3]
    fn = vm._make_execute(lanes, n_regs, slots)
    mem_shape = (sig[3] + sig[0],)
    if batch is not None:
        fn = jax.vmap(fn, in_axes=(0,) + (None,) * 9)
        mem_shape = (batch,) + mem_shape
    scalar = _on(one_chip, (), jnp.int32)
    tables = [_on(one_chip, t.shape, t.dtype)
              for t in vm._Executor(sig).table_structs()]
    compiled = jax.jit(fn, donate_argnums=(0,)).lower(
        _on(one_chip, mem_shape, jnp.float32), scalar, scalar,
        *tables).compile()
    mem_out = compiled.out_info[0]
    assert mem_out.shape == mem_shape


# ---------------------------------------------------------------------------
# Pallas kernels: compiled through Mosaic, never the interpreter.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seq", [1024, 8192])
def test_flash_attention_qwen2_prefill(one_chip, seq):
    """qwen2-0.5b prefill attention: 14 heads of dim 64, bf16."""
    from repro.kernels.flash_attention import flash_attention
    qkv = _on(one_chip, (1, 14, seq, 64), jnp.bfloat16)
    fn = functools.partial(flash_attention, causal=True)
    compiled = jax.jit(fn).lower(qkv, qkv, qkv).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("kernel", ["int8_matmul", "bitplane_matmul"])
def test_int8_gemm_qwen2_mlp(one_chip, kernel):
    """256 tokens through qwen2-0.5b's MLP up-projection (896 -> 4864)."""
    from repro.kernels import bitplane_gemm
    fn = getattr(bitplane_gemm, kernel)
    compiled = fn.lower(_on(one_chip, (256, 896), jnp.int8),
                        _on(one_chip, (896, 4864), jnp.int8)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.out_info.shape == (256, 4864)
    assert compiled.out_info.dtype == jnp.int32


# ---------------------------------------------------------------------------
# qwen2-0.5b at published widths.
# ---------------------------------------------------------------------------

def test_qwen2_decode_step(one_chip):
    """One continuous-batching decode step: 8 slots, max_seq 2048."""
    from repro.configs.qwen2_0_5b import CONFIG
    from repro.models.common import abstract_tree
    from repro.models.lm import LM
    model = LM(CONFIG)
    params = _tree_on(one_chip, model.abstract_params())
    cache = _tree_on(one_chip, abstract_tree(model.cache_defs(8, 2048)))
    compiled = jax.jit(model.decode_step).lower(
        params, cache, _on(one_chip, (8, 1), jnp.int32),
        _on(one_chip, (8,), jnp.int32)).compile()
    logits = compiled.out_info[0]
    assert logits.shape == (8, CONFIG.padded_vocab)
    assert "tpu_custom_call" not in compiled.as_text()


def test_qwen2_pallas_prefill(one_chip):
    """``use_pallas`` prefill compiles the flash kernel through Mosaic."""
    import dataclasses

    from repro.configs.qwen2_0_5b import CONFIG
    from repro.models.lm import LM
    model = LM(dataclasses.replace(CONFIG, use_pallas=True))
    params = _tree_on(one_chip, model.abstract_params())
    tokens = _on(one_chip, (1, 512), jnp.int32)
    compiled = jax.jit(model.prefill).lower(
        params, {"tokens": tokens, "positions": tokens}).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.out_info[0].shape == (1, CONFIG.padded_vocab)


# ---------------------------------------------------------------------------
# Qwen2-0.5B decode tiles on the fused tier, at the published widths.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kind,batch", [("attn", 16), ("gemv_d", None)])
def test_qwen2_decode_tile_fused(one_chip, kind, batch):
    """The longest decode tile batched 16 (its write-back chain broken by
    ``engine.FUSE_SPAN`` barriers) and the GEMV tile, named by kind."""
    from repro.configs.qwen2_0_5b import CONFIG
    from repro.core.engine import compile_program
    from repro.nn import qwen2
    sh = qwen2.Shapes.of(CONFIG, split=512, max_splits=16)
    tile = qwen2.tiles(sh)[kind]
    cp = compile_program(tile.program, mode="fused")
    masks, zeros = cp._fused_operands()
    fn, shape = cp._fused_fn, (tile.kernel.plan.size,)
    if batch is not None:
        fn = jax.vmap(fn, in_axes=(0, None, None))
        shape = (batch,) + shape
    compiled = jax.jit(fn, donate_argnums=(0,)).lower(
        _on(one_chip, shape, jnp.float32),
        _on(one_chip, masks.shape, masks.dtype),
        _on(one_chip, zeros.shape, zeros.dtype)).compile()
    assert compiled.as_text().startswith(f"HloModule jit__execute.dec_{kind}")
    assert compiled.out_info[0].shape == shape
