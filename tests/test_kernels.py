"""Per-kernel shape/dtype sweeps: Pallas kernels vs their ref oracles
(interpreted on CPU, compiled on TPU — repro.kernels.platform), and the
MVE gather/scatter oracles vs Algorithm 1 written as literal loops."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.kernels import ref
from repro.kernels.bitplane_gemm import bitplane_matmul, int8_matmul
from repro.kernels.flash_attention import flash_attention
from repro.kernels.ops import quantized_matmul

RNG = np.random.default_rng(0)


# ---------------------------------------------------------------------------
# mdgather (vsld semantics)
# ---------------------------------------------------------------------------

def _loop_addresses(dims, strides, base):
    """Algorithm 1 as literal nested loops, x (dims[0]) fastest."""
    addrs = []
    for lane in range(int(np.prod(dims))):
        rem, addr = lane, base
        for length, stride in zip(dims, strides):
            addr += (rem % length) * stride
            rem //= length
        addrs.append(addr)
    return np.asarray(addrs, dtype=np.int64)


def _loop_gather(src, dims, strides, base):
    return np.asarray(src)[_loop_addresses(dims, strides, base)]


def _loop_scatter(dst, vals, dims, strides, base):
    """Lanes stored in order, so the highest lane wins a collision."""
    out = np.array(dst)
    for lane, addr in enumerate(_loop_addresses(dims, strides, base)):
        out[addr] = np.asarray(vals)[lane]
    return out


@st.composite
def gather_case(draw):
    ndim = draw(st.integers(1, 4))
    dims = tuple(draw(st.integers(1, 6)) for _ in range(ndim))
    strides = tuple(draw(st.sampled_from([0, 1, 2, 3, 7]))
                    for _ in range(ndim))
    base = draw(st.integers(0, 8))
    return dims, strides, base


@settings(max_examples=20, deadline=None)
@given(gather_case())
def test_mdgather_matches_ref(case):
    dims, strides, base = case
    span = base + sum((l - 1) * s for l, s in zip(dims, strides)) + 1
    src = jnp.asarray(RNG.standard_normal(span + 8).astype(np.float32))
    got = ref.mdgather_ref(src, dims, strides, base)
    np.testing.assert_array_equal(got, _loop_gather(src, dims, strides, base))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.int32, jnp.bfloat16])
def test_mdgather_dtypes(dtype):
    src = jnp.arange(4096).astype(dtype)
    dims, strides = (4, 8, 16), (1, 0, 5)
    got = ref.mdgather_ref(src, dims, strides, 3)
    want = _loop_gather(src, dims, strides, 3)
    assert got.dtype == src.dtype
    np.testing.assert_array_equal(np.asarray(got, np.float64),
                                  np.asarray(want, np.float64))


def test_mdgather_large_lane_count():
    """The paper's full 8192-lane geometry."""
    src = jnp.asarray(RNG.standard_normal(1 << 15).astype(np.float32))
    dims, strides = (128, 64), (1, 128)          # 8192 lanes
    got = ref.mdgather_ref(src, dims, strides, 0)
    np.testing.assert_array_equal(got, _loop_gather(src, dims, strides, 0))


# ---------------------------------------------------------------------------
# bitplane / int8 GEMM
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("m,k,n", [(8, 16, 8), (128, 128, 128),
                                   (100, 60, 200), (130, 96, 257)])
def test_int8_matmul_exact(m, k, n):
    x = jnp.asarray(RNG.integers(-128, 128, (m, k)).astype(np.int8))
    w = jnp.asarray(RNG.integers(-128, 128, (k, n)).astype(np.int8))
    want = ref.int8_matmul_ref(x, w)
    np.testing.assert_array_equal(int8_matmul(x, w), want)
    np.testing.assert_array_equal(bitplane_matmul(x, w), want)
    np.testing.assert_array_equal(ref.bitplane_matmul_ref(x, w), want)


def test_bitplane_nbits4():
    """4-bit weights use 4 planes; values in [-8, 7]."""
    x = jnp.asarray(RNG.integers(-128, 128, (32, 32)).astype(np.int8))
    w4 = RNG.integers(-8, 8, (32, 32)).astype(np.int8)
    got = bitplane_matmul(x, jnp.asarray(w4), nbits=4)
    want = ref.int8_matmul_ref(x, jnp.asarray(w4))
    np.testing.assert_array_equal(got, want)


def test_quantized_matmul_close_to_float():
    x = jnp.asarray(RNG.standard_normal((64, 96)).astype(np.float32))
    w = RNG.standard_normal((96, 32)).astype(np.float32)
    wq, ws = ref.quantize_rowwise_ref(jnp.asarray(w.T))
    got = quantized_matmul(x, wq.T, ws[:, 0], force_pallas=True)
    want = x @ w
    rel = np.abs(np.asarray(got) - np.asarray(want)) / \
        (np.abs(np.asarray(want)) + 1.0)
    assert rel.mean() < 0.02


def test_quantize_roundtrip_bound():
    x = jnp.asarray(RNG.standard_normal((16, 256)).astype(np.float32))
    q, s = ref.quantize_rowwise_ref(x)
    err = np.abs(np.asarray(q, np.float32) * np.asarray(s) - np.asarray(x))
    assert err.max() <= np.abs(np.asarray(x)).max() / 127.0 + 1e-6


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("sq,sk,causal,d", [
    (128, 128, True, 64), (1, 128, True, 64), (77, 200, True, 64),
    (64, 64, False, 128), (128, 128, True, 128), (33, 95, False, 64),
])
def test_flash_attention_sweep(sq, sk, causal, d):
    q = jnp.asarray(RNG.standard_normal((2, 3, sq, d)).astype(np.float32))
    k = jnp.asarray(RNG.standard_normal((2, 3, sk, d)).astype(np.float32))
    v = jnp.asarray(RNG.standard_normal((2, 3, sk, d)).astype(np.float32))
    got = flash_attention(q, k, v, causal=causal, block_q=64, block_k=64)
    want = ref.flash_attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(got, want, rtol=3e-5, atol=3e-5)


def test_flash_attention_bf16():
    q = jnp.asarray(RNG.standard_normal((1, 2, 96, 64))).astype(jnp.bfloat16)
    k = jnp.asarray(RNG.standard_normal((1, 2, 96, 64))).astype(jnp.bfloat16)
    v = jnp.asarray(RNG.standard_normal((1, 2, 96, 64))).astype(jnp.bfloat16)
    got = flash_attention(q, k, v, causal=True, block_q=32, block_k=32)
    want = ref.flash_attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=0.06, atol=0.06)


def test_flash_matches_chunked_model_path():
    """The model's jnp chunked attention and the Pallas kernel agree."""
    from repro.models.attention import chunked_attention
    q = jnp.asarray(RNG.standard_normal((2, 64, 8, 64)).astype(np.float32))
    k = jnp.asarray(RNG.standard_normal((2, 64, 2, 64)).astype(np.float32))
    v = jnp.asarray(RNG.standard_normal((2, 64, 2, 64)).astype(np.float32))
    jnp_path = chunked_attention(q, k, v, causal=True, chunk=16)
    pallas_path = chunked_attention(q, k, v, causal=True, use_pallas=True)
    np.testing.assert_allclose(jnp_path, pallas_path, rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# mdscatter (vsst semantics)
# ---------------------------------------------------------------------------

@settings(max_examples=15, deadline=None)
@given(gather_case())
def test_mdscatter_matches_ref(case):
    dims, strides, base = case
    span = base + sum((l - 1) * s for l, s in zip(dims, strides)) + 1
    total = int(np.prod(dims))
    dst = jnp.asarray(RNG.standard_normal(span + 8).astype(np.float32))
    vals = jnp.asarray(RNG.standard_normal(total).astype(np.float32))
    got = ref.mdscatter_ref(dst, vals, dims, strides, base)
    np.testing.assert_array_equal(
        got, _loop_scatter(dst, vals, dims, strides, base))


def test_mdscatter_collision_last_lane_wins():
    """Stride-0 output dims collide; the highest lane's value lands."""
    dst = jnp.zeros(8, jnp.float32)
    vals = jnp.asarray([1.0, 2.0, 3.0, 4.0, 5.0, 6.0])
    got = ref.mdscatter_ref(dst, vals, (3, 2), (1, 0), 2)
    np.testing.assert_array_equal(
        got, _loop_scatter(dst, vals, (3, 2), (1, 0), 2))
    np.testing.assert_array_equal(np.asarray(got[2:5]), [4.0, 5.0, 6.0])


def test_mdscatter_roundtrip_with_gather():
    """scatter(gather(x)) over the same bijective layout = identity;
    storing with the transposed strides performs the transpose (the
    Section IV pattern)."""
    src = jnp.asarray(RNG.standard_normal(64).astype(np.float32))
    dims = (8, 8)
    vals = ref.mdgather_ref(src, dims, (8, 1), 0)     # read columns
    same = ref.mdscatter_ref(jnp.zeros_like(src), vals, dims, (8, 1), 0)
    np.testing.assert_allclose(same, src)
    trans = ref.mdscatter_ref(jnp.zeros_like(src), vals, dims, (1, 8), 0)
    np.testing.assert_allclose(
        np.asarray(trans).reshape(8, 8),
        np.asarray(src).reshape(8, 8).T)
