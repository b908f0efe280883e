"""int32-accumulation reference for the quantized megakernel.

The oracle the bit-exactness gate compares against: a plain int32
``conv_general_dilated`` (every product and sum exact), the SAME
``requantize_i32`` the kernel epilogue calls, and an int8 max-pool.
Because integer addition is associative, any schedule the kernel
replays — chains, chunks, per-group gemms, exact-fp32 fan splits —
must reproduce these bits exactly; a single differing int8 value is a
datapath bug, never "rounding".
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.decomposition import ConvLayer
from repro.core.quantization import requantize_i32


def maxpool_int(x: jax.Array, window: int, stride: int = 0) -> jax.Array:
    """VALID max-pool over integer activations (int8-safe init)."""
    stride = stride or window
    return lax.reduce_window(
        x, jnp.array(jnp.iinfo(x.dtype).min, x.dtype), lax.max,
        (1, window, window, 1), (1, stride, stride, 1), "VALID")


def quant_layer_ref(layer: ConvLayer, xq: jax.Array, wq: jax.Array,
                    bq: jax.Array, m: jax.Array, shift: jax.Array,
                    *, pre_shift: int = 0, relu: bool = False,
                    fuse_pool: bool = False,
                    residual: "jax.Array | None" = None) -> jax.Array:
    """One quantized CONV(+POOL) layer, int32 end to end.

    ``xq`` (B, H, W, Cin) int8; ``wq`` (K, K, Cin/groups, Cout) int8;
    ``bq``/``m``/``shift`` (Cout,) int32. ``residual`` (int8, the
    layer's output geometry and calibrated output scale) reproduces the
    kernel's accumulation-buffer add: requantize WITHOUT the ReLU clip,
    int32-add the shortcut, then ReLU-clip (``residual_add_i8``).
    Returns int8 — post-pool dims when ``fuse_pool``."""
    l = layer
    # int8 x int8 -> int32: every product and sum exact, on the TPU's
    # integer MXU path as on the CPU
    acc = lax.conv_general_dilated(
        xq.astype(jnp.int8), wq.astype(jnp.int8),
        window_strides=(l.stride, l.stride),
        padding=[(l.pad, l.pad), (l.pad, l.pad)],
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        feature_group_count=l.groups,
        preferred_element_type=jnp.int32)
    acc = acc + bq.astype(jnp.int32)
    q = requantize_i32(acc, m, shift, pre_shift,
                       relu=relu and residual is None)
    if residual is not None:
        if fuse_pool:
            raise ValueError(f"{l.name}: residual add cannot fuse with "
                             f"the pool epilogue")
        from repro.kernels.wave_replay_q.kernel import residual_add_i8
        q = residual_add_i8(q, residual, relu)
    if fuse_pool:
        if l.pool <= 1:
            raise ValueError(f"{l.name}: fuse_pool without a pool")
        q = maxpool_int(q, l.pool, l.pool_stride or l.pool)
    return q


def quant_layer_ref_from_quant(layer: ConvLayer, xq: jax.Array, quant,
                               relu: bool = False,
                               fuse_pool: bool = False,
                               residual: "jax.Array | None" = None
                               ) -> jax.Array:
    """Unpack a ``LayerQuant`` (quant/calibrate.py) into the oracle."""
    wq, bq, m, shift = quant.device_arrays()
    return quant_layer_ref(layer, xq, wq, bq, m, shift,
                           pre_shift=quant.pre_shift, relu=relu,
                           fuse_pool=fuse_pool, residual=residual)
