"""Plain float32 operations for the reference networks.

Nothing here imports the program under test. ``conv`` is one
``lax.conv_general_dilated`` at ``Precision.HIGHEST`` (on a TPU the
default float32 conv is a single bfloat16 pass). ``conv_bf16x3`` is the
control: the same conv at the next precision down, three bfloat16
passes (XLA's ``Precision.HIGH``), written out so that it means the
same on every backend; the CPU ignores ``Precision.HIGH``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


def conv(x, w, stride: int, pad: int, groups: int):
    """x (B, H, W, Cin), w (K, K, Cin/groups, Cout) -> (B, Ho, Wo, Cout)."""
    return lax.conv_general_dilated(
        x, w, window_strides=(stride, stride),
        padding=[(pad, pad), (pad, pad)],
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        feature_group_count=groups, precision=lax.Precision.HIGHEST)


def _split_bf16(a):
    """``a`` as hi + lo, each a bfloat16 value held in float32. Rounded
    by ``reduce_precision``: XLA may fold a float32 -> bfloat16 ->
    float32 round trip away, which would make lo zero."""
    hi = lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)
    lo = lax.reduce_precision(a - hi, exponent_bits=8, mantissa_bits=7)
    return hi, lo


def conv_bf16x3(x, w, stride: int, pad: int, groups: int):
    """``conv`` as three bfloat16 passes (XLA's ``Precision.HIGH``):
    hi*hi + hi*lo + lo*hi, each product exact in float32, the lo*lo term
    dropped."""
    xh, xl = _split_bf16(x)
    wh, wl = _split_bf16(w)
    return (conv(xh, wh, stride, pad, groups)
            + conv(xh, wl, stride, pad, groups)
            + conv(xl, wh, stride, pad, groups))


def maxpool(x, window: int, stride: int):
    return lax.reduce_window(x, -jnp.inf, lax.max, (1, window, window, 1),
                             (1, stride, stride, 1), "VALID")


def conv_node(x, params, node: dict, conv_fn=conv, relu: bool = True):
    """One conv node: conv, bias, optional ReLU, optional max-pool."""
    w, b = params[node["name"]]
    y = conv_fn(x, w, node["stride"], node["pad"], node["groups"]) + b
    if relu:
        y = jnp.maximum(y, 0)
    if node["pool"] > 1:
        y = maxpool(y, node["pool"], node["pool_stride"])
    return y


def init_params(nodes, key):
    """He-normal weights and small normal biases for every conv node,
    in float32. Biases are nonzero so that the fused bias epilogues are
    part of what the comparison sees. Call it under ``jax.jit``."""
    params = {}
    for i, n in enumerate(nodes):
        kw, kb = jax.random.split(jax.random.fold_in(key, i))
        fan_in = n["kernel"] * n["kernel"] * (n["in_c"] // n["groups"])
        w = jax.random.normal(
            kw, (n["kernel"], n["kernel"], n["in_c"] // n["groups"],
                 n["out_c"]), jnp.float32) * (2.0 / fan_in) ** 0.5
        b = 0.1 * jax.random.normal(kb, (n["out_c"],), jnp.float32)
        params[n["name"]] = (w, b)
    return params


def out_hw(h: int, kernel: int, stride: int, pad: int) -> int:
    return (h + 2 * pad - kernel) // stride + 1


def pooled_hw(h: int, pool: int, pool_stride: int) -> int:
    return h if pool <= 1 else (h - pool) // pool_stride + 1
