"""Pure-jnp oracle for the streaming conv kernel."""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


def conv2d_ref(x: jax.Array, w: jax.Array, *, stride: int = 1,
               pad: int = 0) -> jax.Array:
    """x (B,H,W,Cin), w (K,K,Cin,Cout) -> fp32 (B,Ho,Wo,Cout)."""
    return lax.conv_general_dilated(
        x.astype(jnp.float32), w.astype(jnp.float32),
        window_strides=(stride, stride),
        padding=[(pad, pad), (pad, pad)],
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=lax.Precision.HIGHEST)
