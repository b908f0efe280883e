"""Streaming max-pool Pallas kernel (paper §4.3).

The paper's pooling module: a comparator + feedback register scanning the
pool window as rows stream past, reconfigurable to kernel 2 or 3 with
stride down to kernel-1 (AlexNet's overlapping 3/2). Row blocks stream
through VMEM with an element-indexed halo of (pool - stride) rows —
the scratchpad's buffered intermediate rows.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import element_block

NEG = -3.0e38


def _pool_kernel(x_ref, o_ref, *, pool: int, ps: int, R: int, W_out: int):
    x = x_ref[0]                               # (R_in, W_in, C)
    C = x.shape[-1]
    acc = jnp.full((R, W_out, C), NEG, jnp.float32)
    for ky in range(pool):
        for kx in range(pool):
            sl = jax.lax.slice(
                x, (ky, kx, 0),
                (ky + (R - 1) * ps + 1, kx + (W_out - 1) * ps + 1, C),
                (ps, ps, 1)).astype(jnp.float32)
            acc = jnp.maximum(acc, sl)         # comparator + feedback reg
    o_ref[...] = acc[None].astype(o_ref.dtype)


def maxpool_stream_raw(x: jax.Array, *, pool: int, stride: int = 0,
                       row_block: int = 8, interpret: bool = True):
    """x (B, H, W, C) -> (B, H_out, W_out, C), VALID pooling."""
    ps = stride or pool
    B, H, W, C = x.shape
    H_out = (H - pool) // ps + 1
    W_out = (W - pool) // ps + 1
    R = min(row_block, H_out)
    n_rb = -(-H_out // R)

    H_pad = (n_rb * R - 1) * ps + pool
    W_pad = (W_out - 1) * ps + pool
    x = jnp.pad(x, ((0, 0), (0, max(0, H_pad - H)), (0, max(0, W_pad - W)),
                    (0, 0)), constant_values=NEG)[:, :H_pad, :W_pad]
    R_in = (R - 1) * ps + pool

    kern = functools.partial(_pool_kernel, pool=pool, ps=ps, R=R, W_out=W_out)
    out = pl.pallas_call(
        kern,
        out_shape=jax.ShapeDtypeStruct((B, n_rb * R, W_out, C), x.dtype),
        grid=(B, n_rb),
        in_specs=[pl.BlockSpec(element_block((1, R_in, W_pad, C)),
                               lambda b, r: (b, r * R * ps, 0, 0))],
        out_specs=pl.BlockSpec((1, R, W_out, C), lambda b, r: (b, r, 0, 0)),
        interpret=interpret,
    )(x)
    return out[:, :H_out]
