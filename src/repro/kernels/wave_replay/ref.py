"""Pure-XLA oracle for the wave-replay megakernel: direct conv + bias
(+ residual add + ReLU + overlapping max-pool), NHWC, matching the
layer declaration and the kernel epilogue's op order."""
import jax.numpy as jnp
from jax import lax


def wave_replay_ref(layer, x, w, b=None, *, relu: bool = False,
                    fuse_pool: bool = False, residual=None):
    l = layer
    y = lax.conv_general_dilated(
        x.astype(jnp.float32), w.astype(jnp.float32),
        window_strides=(l.stride, l.stride),
        padding=[(l.pad, l.pad), (l.pad, l.pad)],
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        feature_group_count=l.groups, precision=lax.Precision.HIGHEST)
    if b is not None:
        y = y + b.astype(jnp.float32)
    if residual is not None:          # accumulation-buffer add, pre-ReLU
        if fuse_pool:
            raise ValueError(f"{l.name}: residual add cannot fuse with "
                             f"the pool epilogue")
        y = y + residual.astype(jnp.float32)
    if relu:
        y = jnp.maximum(y, 0.0)
    if fuse_pool:
        ps = l.pool_stride or l.pool
        y = lax.reduce_window(y, -jnp.inf, lax.max, (1, l.pool, l.pool, 1),
                              (1, ps, ps, 1), "VALID")
    return y
