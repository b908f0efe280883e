"""Fixed-point quantization (paper Table 2: "Precision: 16-bit fixed point").

Symmetric Q-format: value = int * 2^-frac_bits. The paper's CUs multiply
16-bit operands into 32-bit accumulators; we reproduce that numerically
(int arithmetic in int32) and provide the int8 variant that is TPU-native
(MXU int8 x int8 -> int32), used by kernels/quant_matmul.

The int8 streaming-inference path (src/repro/quant/,
kernels/wave_replay_q/) shares the primitives at the bottom of this
module: symmetric [-127, 127] int8 quantize/dequantize, and the
requantize step — the paper's "write back at operand precision" move,
where the 32-bit accumulator is scaled down to the next layer's 8-bit
operand format by an integer fixed-point multiply + rounding shift
(``requantize_i32``). The multiplier/shift pairs are derived host-side
by ``requant_params``; keeping the arithmetic pure int32 (JAX x64 stays
off) means the Pallas kernel epilogue and the int32 reference model
execute the *same* ops and therefore agree bit for bit.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class QFormat:
    bits: int = 16
    frac_bits: int = 8

    @property
    def scale(self) -> float:
        return float(2 ** self.frac_bits)

    @property
    def qmax(self) -> int:
        return 2 ** (self.bits - 1) - 1

    @property
    def qmin(self) -> int:
        return -(2 ** (self.bits - 1))

    @property
    def dtype(self):
        return {8: jnp.int8, 16: jnp.int16, 32: jnp.int32}[self.bits]

    @property
    def lsb(self) -> float:
        return 1.0 / self.scale

    @property
    def max_value(self) -> float:
        return self.qmax / self.scale


def quantize(x: jax.Array, q: QFormat) -> jax.Array:
    """Round-to-nearest-even, saturating."""
    xi = jnp.clip(jnp.round(x.astype(jnp.float32) * q.scale), q.qmin, q.qmax)
    return xi.astype(q.dtype)


def dequantize(xq: jax.Array, q: QFormat) -> jax.Array:
    return xq.astype(jnp.float32) * q.lsb


def calibrate_frac_bits(x, bits: int = 16) -> QFormat:
    """Max-abs calibration: largest frac_bits with no saturation."""
    amax = float(jnp.max(jnp.abs(x)))
    if amax == 0.0:
        return QFormat(bits, bits - 1)
    int_bits = max(0, int(jnp.ceil(jnp.log2(amax + 1e-30))) + 1)
    frac = max(0, bits - 1 - int_bits)
    return QFormat(bits, frac)


def fixed_point_matmul(aq: jax.Array, bq: jax.Array,
                       qa: QFormat, qb: QFormat,
                       out_q: QFormat | None = None):
    """Integer matmul with 32-bit accumulation (the paper's CU datapath).

    Returns float if out_q is None, else requantized ints."""
    acc = jnp.matmul(aq.astype(jnp.int32), bq.astype(jnp.int32))
    scale = qa.lsb * qb.lsb
    if out_q is None:
        return acc.astype(jnp.float32) * scale
    # requantize: shift from (fa+fb) frac bits to out frac bits
    shift = (qa.frac_bits + qb.frac_bits) - out_q.frac_bits
    if shift >= 0:
        # round-half-up in integer domain
        r = (acc + (1 << (shift - 1) if shift > 0 else 0)) >> shift
    else:
        r = acc << (-shift)
    return jnp.clip(r, out_q.qmin, out_q.qmax).astype(out_q.dtype)


def fake_quant(x: jax.Array, q: QFormat) -> jax.Array:
    """Quantize-dequantize (for accuracy studies); straight-through grad."""
    def fwd(x):
        return dequantize(quantize(x, q), q)
    return x + jax.lax.stop_gradient(fwd(x) - x)


# ---------------------------------------------------------------------------
# int8 streaming-inference primitives (ISSUE 4: the quantized megakernel
# path). Symmetric, zero-point-free: padding zeros stay exact zeros in
# the integer domain, so the schedule's uniform-grid padding contributes
# exact 0 to every int32 accumulation — the same invariant the fp32
# executors rely on.
# ---------------------------------------------------------------------------

INT8_QMAX = 127            # symmetric [-127, 127]: |q| == |-q| exactly

# Exact-accumulation fan bound for computing int8 x int8 -> int32
# products through an fp32 matmul: every partial sum of a gemm over
# ``fan`` products of magnitude <= 127*127 stays an exact fp32 integer
# as long as fan * 127^2 < 2^24. The int8 megakernel splits its fan
# (K*K*channels) into chunks of at most this many input channels' worth
# of products and accumulates the chunks in the int32 VMEM scratch —
# the paper's 32-bit-accumulator-in-SRAM story is literally what makes
# the fast fp32 MXU/gemm path exact.
EXACT_FP32_FAN = (1 << 24) // (INT8_QMAX * INT8_QMAX)       # 1040


def quantize_int8_sym(x: jax.Array, scale) -> jax.Array:
    """fp32 -> symmetric int8: clip(round(x / scale), -127, 127).

    ``jnp.round`` (half-to-even) everywhere — the entry quantization is
    part of the bit-exactness contract between the kernel path and the
    int32 reference model, so there is exactly one rounding rule."""
    q = jnp.round(x.astype(jnp.float32) / scale)
    return jnp.clip(q, -INT8_QMAX, INT8_QMAX).astype(jnp.int8)


def dequantize_int8(q: jax.Array, scale) -> jax.Array:
    return q.astype(jnp.float32) * scale


def rounding_rshift(v: jax.Array, s) -> jax.Array:
    """Arithmetic right shift with round-half-up: round(v / 2^s).

    ``v`` int32; ``s`` a non-negative static int or int32 array (e.g.
    per-output-channel shifts). Callers guarantee |v| + 2^(s-1) < 2^31.
    """
    s = jnp.asarray(s, jnp.int32)
    bias = jnp.where(s > 0, jnp.left_shift(1, jnp.maximum(s - 1, 0)), 0)
    return jnp.right_shift(v + bias, s)


def requantize_i32(acc: jax.Array, m: jax.Array, shift: jax.Array,
                   pre_shift: int = 0, relu: bool = False) -> jax.Array:
    """int32 accumulator -> int8 output: fixed-point multiply + shift.

    ``y = clip(round(acc * m / 2^shift), lo, 127)`` computed entirely in
    int32 (no int64 — JAX x64 stays off): a rounding pre-shift by the
    static ``pre_shift`` first makes headroom so ``(acc >> p) * m``
    cannot overflow, then the per-channel 7-bit multiplier ``m`` and the
    remaining ``shift - pre_shift`` rounding shift apply the scale
    ``m * 2^-shift ~= s_in * s_w / s_out`` (derived by
    ``requant_params``). ``relu=True`` folds max(x, 0) into the lower
    clip bound — exactly fp32 ReLU-then-quantize for symmetric scales.
    Deterministic integer ops only, shared verbatim by the Pallas kernel
    epilogue and the int32 reference model (bit-exact by construction).
    """
    return requantize_clip(acc, m, shift, pre_shift,
                           relu).astype(jnp.int8)


def requantize_clip(acc: jax.Array, m: jax.Array, shift: jax.Array,
                    pre_shift: int = 0, relu: bool = False) -> jax.Array:
    """``requantize_i32`` before the final int8 cast: the clipped
    values, still int32 — what kernel epilogues keep in their int32
    scratch until the pooled tile is written."""
    v = rounding_rshift(acc, pre_shift) if pre_shift else acc
    v = v * m.astype(jnp.int32)
    v = rounding_rshift(v, jnp.asarray(shift, jnp.int32) - pre_shift)
    lo = 0 if relu else -INT8_QMAX
    return jnp.clip(v, lo, INT8_QMAX)


def residual_add_clip(q: jax.Array, r: jax.Array, relu: bool) -> jax.Array:
    """The int8 accumulation-buffer add before its int8 cast: int32 sum
    of two same-scale operands, ReLU folded into the clip
    (``wave_replay_q.kernel.residual_add_i8`` casts the result)."""
    s = q.astype(jnp.int32) + r.astype(jnp.int32)
    lo = 0 if relu else -INT8_QMAX
    return jnp.clip(s, lo, INT8_QMAX)


def requant_params(scale_ratio, acc_bound: int, bits_m: int = 7):
    """Host-side: fixed-point (m, shift, pre_shift) for ``requantize_i32``.

    ``scale_ratio`` (out_c,) float64 = s_in * s_w[c] / s_out — the real
    multiplier the requantize step approximates as ``m * 2^-shift`` with
    ``m`` a ``bits_m``-bit normalised mantissa (m in [2^(bits_m-1),
    2^bits_m - 1], <= 0.8% scale error at 7 bits — far below the int8
    quantization floor). ``acc_bound`` bounds |acc + bias| so the static
    per-layer ``pre_shift`` guarantees (acc >> p) * m < 2^31.

    Returns (m int32 (out_c,), shift int32 (out_c,), pre_shift int).
    """
    r = np.maximum(np.asarray(scale_ratio, np.float64), 1e-30)
    m_hi = float(2 ** bits_m - 1)
    # headroom: (acc_bound >> p) * m_hi (+ rounding bias) must fit int31
    need = np.log2(max(acc_bound, 1) * m_hi) if acc_bound > 0 else 0.0
    pre_shift = max(0, int(np.ceil(need)) - 30)
    shift = np.floor(np.log2(m_hi / r)).astype(np.int64)
    m = np.round(r * np.exp2(shift)).astype(np.int64)
    # normalise after rounding: keep m in [2^(bits_m-1), 2^bits_m - 1]
    low = m < 2 ** (bits_m - 1)
    shift = np.where(low, shift + 1, shift)
    m = np.where(low, np.round(r * np.exp2(shift)), m).astype(np.int64)
    high = m > m_hi
    shift = np.where(high, shift - 1, shift)
    m = np.where(high, np.round(r * np.exp2(shift)), m).astype(np.int64)
    # the kernel computes shift - pre_shift: keep it a valid >= 0 shift.
    # Where the clip moves a shift, re-derive m AT the clipped shift —
    # keeping the old mantissa would silently misscale by the clipped
    # factor (ratios below ~2^-31 degrade to a denormal m < 2^(bits_m-1)
    # instead, ratios too large saturate at m = 2^bits_m - 1)
    clipped = np.clip(shift, pre_shift, 31)
    moved = clipped != shift
    m = np.where(moved, np.round(r * np.exp2(clipped)), m)
    shift = clipped
    m = np.clip(m, 1, m_hi)
    return (m.astype(np.int32), shift.astype(np.int32), pre_shift)
