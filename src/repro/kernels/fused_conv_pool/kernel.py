"""Fused conv + ReLU + max-pool Pallas kernel (paper §4.3).

The paper buffers CU outputs in a scratchpad and pools them before they
ever return to DRAM. Here the conv row-block's fp32 accumulator is pooled
in VMEM on the last cin step — the conv->pool intermediate never leaves
on-chip memory. Pooling is a subsampled-slice max over the accumulator
(the same gather trick the conv uses for strided im2col), so overlapping
pools (stride < pool, e.g. AlexNet's 3/2) work too: each grid block
computes exactly the conv rows its pooled rows need, re-deriving the
(pool - stride)-row overlap instead of passing it between blocks.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import element_block, pool_max_subsampled


def _kernel(x_ref, w_ref, o_ref, acc_ref, *, K: int, stride: int, R: int,
            W_out: int, n_ci: int, pool: int, ps: int, RP: int, WP: int,
            relu: bool):
    ci = pl.program_id(3)

    @pl.when(ci == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[0]
    cin = x.shape[-1]
    patches = []
    for ky in range(K):
        for kx in range(K):
            patches.append(jax.lax.slice(
                x, (ky, kx, 0),
                (ky + (R - 1) * stride + 1, kx + (W_out - 1) * stride + 1,
                 cin), (stride, stride, 1)))
    pat = jnp.concatenate(patches, axis=-1).reshape(R * W_out, K * K * cin)
    w = w_ref[...].reshape(K * K * cin, -1)
    acc_ref[...] += jax.lax.dot_general(
        pat, w, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).reshape(R, W_out, -1)

    @pl.when(ci == n_ci - 1)
    def _finish():
        a = acc_ref[...]
        if relu:
            a = jnp.maximum(a, 0.0)
        # in-VMEM pooling: (R, W_out, C) -> (RP, WP, C); shared with
        # the wave-replay megakernel epilogue
        o_ref[...] = pool_max_subsampled(a, pool=pool, stride=ps,
                                         out_h=RP, out_w=WP)[None]


def fused_conv_pool_raw(x: jax.Array, w: jax.Array, *, stride: int = 1,
                        pool: int = 2, pool_stride: int = 0,
                        relu: bool = True, row_block: int = 8,
                        cout_block: int = 128, cin_block: int = 128,
                        interpret: bool | None = None):
    """x (B,H,W,Cin) pre-padded, w (K,K,Cin,Cout). VALID conv + max pool
    fused; ``pool_stride`` 0 means ``pool`` (non-overlapping), values
    below ``pool`` overlap (AlexNet 3/2). Returns the pooled fp32 map.
    ``interpret=None`` auto-detects: compiled on TPU, interpreter off it.
    """
    if interpret is None:
        from repro.kernels.common import pallas_interpret_default
        interpret = pallas_interpret_default()
    ps = pool_stride or pool
    if ps > pool:
        raise ValueError(f"pool_stride {ps} > pool {pool} would skip rows")
    B, H, W, Cin = x.shape
    K, _, _, Cout = w.shape
    H_out = (H - K) // stride + 1
    W_out = (W - K) // stride + 1
    if H_out < pool or W_out < pool:
        raise ValueError(
            f"conv output {H_out}x{W_out} smaller than pool {pool}")
    Hp_out = (H_out - pool) // ps + 1
    Wp_out = (W_out - pool) // ps + 1

    RP = max(1, min((row_block - pool) // ps + 1, Hp_out))
    R = (RP - 1) * ps + pool        # conv rows computed per grid block
    n_rb = -(-Hp_out // RP)
    co_b = min(cout_block, Cout)
    n_co = -(-Cout // co_b)
    ci_b = min(cin_block, Cin)
    n_ci = -(-Cin // ci_b)

    # the last block's pooled rows reach conv row (n_rb-1)*RP*ps + R
    H_need = ((n_rb - 1) * RP * ps + R - 1) * stride + K
    W_need = (W_out - 1) * stride + K
    x = jnp.pad(x, ((0, 0), (0, max(0, H_need - H)),
                    (0, max(0, W_need - W)),
                    (0, n_ci * ci_b - Cin)))[:, :H_need, :W_need]
    w = jnp.pad(w, ((0, 0), (0, 0), (0, n_ci * ci_b - Cin),
                    (0, n_co * co_b - Cout)))
    R_in = (R - 1) * stride + K

    kern = functools.partial(_kernel, K=K, stride=stride, R=R, W_out=W_out,
                             n_ci=n_ci, pool=pool, ps=ps, RP=RP, WP=Wp_out,
                             relu=relu)
    out = pl.pallas_call(
        kern,
        out_shape=jax.ShapeDtypeStruct(
            (B, n_rb * RP, Wp_out, n_co * co_b), jnp.float32),
        grid=(B, n_rb, n_co, n_ci),
        in_specs=[
            pl.BlockSpec(element_block((1, R_in, W_need, ci_b)),
                         lambda b, r, co, ci: (b, r * RP * ps * stride, 0,
                                               ci * ci_b)),
            pl.BlockSpec((K, K, ci_b, co_b),
                         lambda b, r, co, ci: (0, 0, ci, co)),
        ],
        out_specs=pl.BlockSpec((1, RP, Wp_out, co_b),
                               lambda b, r, co, ci: (b, r, 0, co)),
        scratch_shapes=[pltpu.VMEM((R, W_out, co_b), jnp.float32)],
        interpret=interpret,
    )(x, w)
    return out[:, :Hp_out, :, :Cout]
