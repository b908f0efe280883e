"""Pure-jnp oracle: conv (VALID) + ReLU + non-overlapping max-pool."""
import jax.numpy as jnp
from jax import lax


def conv_pool_ref(x, w, *, stride: int = 1, pool: int = 2,
                  relu: bool = True):
    y = lax.conv_general_dilated(
        x.astype(jnp.float32), w.astype(jnp.float32),
        window_strides=(stride, stride), padding="VALID",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=lax.Precision.HIGHEST)
    if relu:
        y = jnp.maximum(y, 0.0)
    return lax.reduce_window(y, -jnp.inf, lax.max, (1, pool, pool, 1),
                             (1, pool, pool, 1), "VALID")
