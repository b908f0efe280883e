"""Streaming conv2d Pallas kernel — the paper's CU engine array + column
buffer, TPU-native (DESIGN.md §2).

Dataflow mapping:
  * row-block streaming with an element-indexed halo window <- 2xN row
    buffer
    (each grid step's input block carries its own K-stride halo rows, so
    the convolution never stalls at block boundaries — paper §3)
  * weights resident across the row grid (weight-stationary CUs, §4.2)
  * grid dims (cout_blocks, cin_blocks) = the paper's feature / kernel
    decomposition (§5), executed inside one kernel launch
  * stride>1 handled by subsampled im2col gather — work is never issued
    for skipped taps (the EN_Ctrl clock-gating analogue)
  * im2col patches are built in VMEM and hit the MXU as one
    (R*W_out, K*K*Cin_blk) @ (K*K*Cin_blk, Cout_blk) matmul.

Layout: NHWC, input pre-padded (VALID inside). fp32 accumulation in the
revisited output block (zeroed on the first cin step).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import element_block


def _conv_kernel(x_ref, w_ref, o_ref, *, K: int, stride: int, R: int,
                 W_out: int, n_ci: int):
    """One grid step: (batch b, row-block r, cout-block co, cin-block ci)."""
    ci = pl.program_id(3)

    @pl.when(ci == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    x = x_ref[0]                      # (R_in, W_in, Ci) halo-inclusive
    cin = x.shape[-1]
    patches = []
    for ky in range(K):
        for kx in range(K):
            sl = jax.lax.slice(
                x,
                (ky, kx, 0),
                (ky + (R - 1) * stride + 1, kx + (W_out - 1) * stride + 1,
                 cin),
                (stride, stride, 1))          # (R, W_out, Ci)
            patches.append(sl)
    pat = jnp.concatenate(patches, axis=-1)   # (R, W_out, K*K*Ci)
    pat = pat.reshape(R * W_out, K * K * cin)
    w = w_ref[...].reshape(K * K * cin, -1)   # (K*K*Ci, Co)
    acc = jax.lax.dot_general(
        pat, w, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)   # (R*W_out, Co)
    o_ref[...] += acc.reshape(1, R, W_out, -1)


def conv2d_stream_raw(x: jax.Array, w: jax.Array, *, stride: int = 1,
                      row_block: int = 8, cout_block: int = 128,
                      cin_block: int = 128,
                      interpret: bool | None = None) -> jax.Array:
    """x (B, H, W, Cin) pre-padded; w (K, K, Cin, Cout). VALID conv.

    ``interpret=None`` auto-detects the backend: compiled on TPU,
    interpreter elsewhere. Returns (B, H_out, W_out, Cout) float32.
    """
    if interpret is None:
        from repro.kernels.common import pallas_interpret_default
        interpret = pallas_interpret_default()
    B, H, W, Cin = x.shape
    K, _, _, Cout = w.shape
    H_out = (H - K) // stride + 1
    W_out = (W - K) // stride + 1

    R = min(row_block, H_out)
    n_rb = -(-H_out // R)
    co_b = min(cout_block, Cout)
    n_co = -(-Cout // co_b)
    ci_b = min(cin_block, Cin)
    n_ci = -(-Cin // ci_b)

    # pad/trim so every block window is exactly in-bounds
    H_pad = (n_rb * R - 1) * stride + K
    W_pad = (W_out - 1) * stride + K
    x = jnp.pad(x, ((0, 0), (0, max(0, H_pad - H)), (0, max(0, W_pad - W)),
                    (0, n_ci * ci_b - Cin)))[:, :H_pad, :W_pad]
    w = jnp.pad(w, ((0, 0), (0, 0), (0, n_ci * ci_b - Cin),
                    (0, n_co * co_b - Cout)))

    R_in = (R - 1) * stride + K       # rows needed per block (incl. halo)

    kern = functools.partial(_conv_kernel, K=K, stride=stride, R=R,
                             W_out=W_out, n_ci=n_ci)
    out = pl.pallas_call(
        kern,
        out_shape=jax.ShapeDtypeStruct((B, n_rb * R, W_out, n_co * co_b),
                                       jnp.float32),
        grid=(B, n_rb, n_co, n_ci),
        in_specs=[
            # halo-overlapping row windows need element (pl.Element)
            # indexing: offsets are in elements for every dim
            pl.BlockSpec(element_block((1, R_in, W_pad, ci_b)),
                         lambda b, r, co, ci: (b, r * R * stride, 0,
                                               ci * ci_b)),
            pl.BlockSpec((K, K, ci_b, co_b),
                         lambda b, r, co, ci: (0, 0, ci, co)),
        ],
        out_specs=pl.BlockSpec((1, R, W_out, co_b),
                               lambda b, r, co, ci: (b, r, 0, co)),
        interpret=interpret,
    )(x, w)
    return out[:, :H_out, :, :Cout]
