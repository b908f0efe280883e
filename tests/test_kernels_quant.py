"""Quantized matmul kernel sweeps vs the int32-accumulation oracle:
randomized shapes/blockings, exact accumulator checks, and saturation
cases with operands pinned near qmin/qmax."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.quant_matmul import (quant_matmul, quant_matmul_acc_ref,
                                        quant_matmul_ref,
                                        quant_matmul_requant_ref)
from repro.kernels.quant_matmul.ops import (quantize_activations,
                                            quantize_weights)

try:
    import hypothesis
    import hypothesis.strategies as st
except ImportError:  # dev-only dependency (requirements.txt)
    hypothesis = None


@pytest.mark.parametrize("M,K,N,bm,bn,bk", [
    (64, 64, 64, 32, 32, 32),
    (100, 70, 50, 32, 16, 32),     # non-divisible
    (16, 256, 8, 16, 8, 64),
    (1, 64, 128, 8, 64, 32),
])
def test_quant_matmul_matches_ref(M, K, N, bm, bn, bk):
    x = jax.random.normal(jax.random.key(7), (M, K))
    w = jax.random.normal(jax.random.key(8), (K, N))
    xq, sx = quantize_activations(x)
    wq, sw = quantize_weights(w)
    got = quant_matmul(xq, wq, sx, sw, block_m=bm, block_n=bn, block_k=bk)
    ref = quant_matmul_ref(xq, wq, sx, sw)
    assert jnp.max(jnp.abs(got - ref)) < 1e-4


def test_int8_error_vs_fp32_is_small():
    x = jax.random.normal(jax.random.key(7), (128, 128))
    w = jax.random.normal(jax.random.key(8), (128, 64))
    xq, sx = quantize_activations(x)
    wq, sw = quantize_weights(w)
    got = quant_matmul(xq, wq, sx, sw)
    ref = x @ w
    rel = float(jnp.max(jnp.abs(got - ref)) / jnp.max(jnp.abs(ref)))
    assert rel < 0.05


# ---------------------------------------------------------------------------
# int32-accumulation oracle (the proper reference, ISSUE 4 satellite)
# ---------------------------------------------------------------------------

def test_acc_ref_is_exact_int32():
    """The accumulator reference is bit-exact integer math — spot-check
    against a float64 computation that cannot round at these sizes."""
    rng = np.random.default_rng(0)
    xq = rng.integers(-128, 128, (37, 211), np.int64)
    wq = rng.integers(-128, 128, (211, 19), np.int64)
    acc = quant_matmul_acc_ref(jnp.asarray(xq, jnp.int8),
                               jnp.asarray(wq, jnp.int8))
    assert acc.dtype == jnp.int32
    want = (xq.astype(np.float64) @ wq.astype(np.float64)).astype(np.int64)
    assert np.array_equal(np.asarray(acc, np.int64), want)


def test_kernel_matches_acc_ref_at_qmin_qmax():
    """Operands pinned at the int8 extremes: the worst-case accumulator
    (K * 128 * 128) must come through the kernel's int32 VMEM scratch
    exactly — a 16-bit or fp16 accumulator would wrap/round here."""
    K = 512
    xq = jnp.full((32, K), -128, jnp.int8)
    wq = jnp.concatenate([jnp.full((K, 8), -128, jnp.int8),
                          jnp.full((K, 8), 127, jnp.int8)], axis=1)
    acc = quant_matmul_acc_ref(xq, wq)
    assert int(acc.max()) == K * 128 * 128         # 8.4M: needs 32 bits
    sx, sw = jnp.float32(1.0), jnp.ones((16,), jnp.float32)
    got = quant_matmul(xq, wq, sx, sw, block_k=128)
    ref = quant_matmul_ref(xq, wq, sx, sw)
    assert jnp.array_equal(got, ref)               # fp32 of exact ints


def test_requant_ref_saturates_at_qmax():
    """Accumulators far beyond the output range clip exactly at ±127
    through the fixed-point requantize — never wrap."""
    K = 64
    xq = jnp.concatenate([jnp.full((4, K), 127, jnp.int8),
                          jnp.full((4, K), -128, jnp.int8)])
    wq = jnp.full((K, 8), 127, jnp.int8)
    # out_scale tiny -> every accumulator saturates
    out = quant_matmul_requant_ref(xq, wq, 1.0, np.ones(8), out_scale=1.0)
    assert out.dtype == jnp.int8
    assert jnp.array_equal(out[:4], jnp.full((4, 8), 127, jnp.int8))
    assert jnp.array_equal(out[4:], jnp.full((4, 8), -127, jnp.int8))


def test_requant_ref_tracks_float_requantize():
    """Away from saturation the integer requantize tracks the real-valued
    rescale to within 1 LSB (7-bit mantissa + double rounding)."""
    rng = np.random.default_rng(3)
    xq = jnp.asarray(rng.integers(-128, 128, (64, 96)), jnp.int8)
    wq = jnp.asarray(rng.integers(-128, 128, (96, 32)), jnp.int8)
    sx = 0.013
    sw = np.exp(rng.uniform(np.log(1e-3), np.log(3e-2), 32))
    out_scale = 1.7
    got = np.asarray(quant_matmul_requant_ref(xq, wq, sx, sw, out_scale),
                     np.float64)
    acc = np.asarray(quant_matmul_acc_ref(xq, wq), np.float64)
    want = np.clip(np.round(acc * sx * sw[None, :] / out_scale), -127, 127)
    # 7-bit multiplier: <=0.8% scale error -> max |err| ~ 1 LSB off-sat
    assert np.abs(got - want).max() <= 2.0
    assert np.abs(got - want).mean() < 0.5


if hypothesis is not None:
    @hypothesis.given(
        st.integers(1, 96), st.integers(1, 200), st.integers(1, 48),
        st.sampled_from([8, 16, 32]), st.sampled_from([8, 16, 32]),
        st.sampled_from([16, 64, 128]),
        st.booleans(),
    )
    @hypothesis.settings(max_examples=25, deadline=None)
    def test_quant_matmul_matches_ref_random(M, K, N, bm, bn, bk,
                                             extreme):
        """Randomized shapes x blockings; ``extreme`` draws operands
        from {qmin, 0, qmax} so block boundaries see saturated
        accumulator magnitudes."""
        rng = np.random.default_rng(M * 1000 + K * 10 + N)
        if extreme:
            xq = rng.choice([-128, 0, 127], (M, K)).astype(np.int8)
            wq = rng.choice([-128, 0, 127], (K, N)).astype(np.int8)
        else:
            xq = rng.integers(-128, 128, (M, K), np.int64).astype(np.int8)
            wq = rng.integers(-128, 128, (K, N), np.int64).astype(np.int8)
        sx = 0.02
        sw = jnp.asarray(rng.uniform(1e-3, 2e-2, N), jnp.float32)
        xq, wq = jnp.asarray(xq), jnp.asarray(wq)
        # unit scales expose the kernel's int32 accumulator (|acc| <
        # 2^24, exact in fp32): integer accumulation is bit-exact
        acc = quant_matmul(xq, wq, 1.0, jnp.ones((N,), jnp.float32),
                           block_m=bm, block_n=bn, block_k=bk)
        assert jnp.array_equal(
            acc, quant_matmul_acc_ref(xq, wq).astype(jnp.float32))
        # the fp32 dequantize multiplies the same accumulator by sx*sw
        # in another order than the reference: within a few ULP
        got = quant_matmul(xq, wq, sx, sw,
                           block_m=bm, block_n=bn, block_k=bk)
        ref = quant_matmul_ref(xq, wq, sx, sw)
        np.testing.assert_allclose(got, ref, rtol=2 ** -21, atol=0)
