"""Persistent wave-replay Pallas megakernel (ISSUE 3 tentpole).

One ``pallas_call`` replays a whole CONV layer's wave schedule. The grid
iterates (tile, wave) with the wave (in-channel-group) axis innermost,
so for each tile the VMEM scratch accumulator is zeroed at chain start
and carried across the entire partial-sum chain — the software analogue
of the paper's 128 KB partial-sum SRAM bank: **partials never round-trip
HBM**, unlike the wave executor whose per-wave conv results accumulate
into an HBM-resident buffer.

Control path: a static int32 operand table (``KernelProgram.table``,
core/schedule.py) is scalar-prefetched to SMEM — the §3 command decoder
stream. BlockSpec index maps read it to steer every DMA: the
halo-inclusive input window origin (element offsets, so
overlapping halos are *indexed*, never materialised as fresh copies the
way the wave executor's vmapped gather stacks them), the wave's
channel-group offsets into input/weights, and the output block index.

Epilogue (last wave of each tile's chain): bias, then the optional
residual add, channel LayerNorm and activation (ReLU or exact GELU),
then the optional in-VMEM max-pool over the accumulator (re-deriving
the (pool - stride)-row overlap per tile, like fused_conv_pool), then a
masked write that zeroes the grid-padding lanes — the conv->pool
intermediate and every partial sum live only in VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.schedule import (KERNEL_OP_COLS, OP_IY, OP_TX, OP_TY,
                                 OP_VC, OP_VR, KernelProgram, batch_grid)
from repro.core.graph import NORM_EPS
from repro.kernels.common import (activate, at_tile_col, channel_norm,
                                  conv_rows, element_block,
                                  fold_taps_weights, lane_load, mask_tile,
                                  megakernel_geometry, megakernel_vmem,
                                  pool_tile, space_to_depth,
                                  space_to_depth_weights, stage_lanes,
                                  strided, tap_fold_factor, weight_block)


def _replay_kernel(tbl_ref, x_ref, w_ref, b_ref, *refs,
                   K: int, stride: int, col_step: int, acc_h: int,
                   acc_w: int, n_waves: int, pool: int, ps: int,
                   blk_h: int, blk_w: int, tiles_w: int, act,
                   norm: bool, n_out: int, fuse_pool: bool,
                   residual: bool, groups: int, staged: bool,
                   fold: bool):
    """One grid step: batch block (program_id 0), tile t (id 1), chain
    position k (id 2). The batch axis is outermost, so each batch
    block's tiles replay their full partial-sum chains before the next
    block starts — the scratch accumulator is recycled across blocks.

    ``refs`` are ``[r_ref] [n_ref] o_ref acc_ref [pool_ref] [xs_ref]``:
    with ``residual`` the residual activation rows of this tile, added
    to the accumulator after bias, before the norm and activation (the
    paper's accumulation-SRAM add); with ``norm`` the (2, C) gamma and
    beta of the channel LayerNorm over the ``n_out`` real output
    channels, which with GELU runs one output row at a time so its
    temporaries stay a row wide; with ``fuse_pool`` the lane-tiled pool
    scratch; with ``staged`` (strided taps) the lane-tiled copy of one
    image's window that strided loads read. ``fold`` runs each row's
    K*K taps as one dot (``tap_fold_factor``).

    The window ``x_ref`` spans the full buffer width; tile column ``j``
    starts its taps at the static column ``j * col_step``.
    ``conv_rows`` runs the grouped / depthwise / dense body
    (kernels/common.py).
    """
    refs = list(refs)
    r_ref = refs.pop(0) if residual else None
    n_ref = refs.pop(0) if norm else None
    o_ref, acc_ref = refs.pop(0), refs.pop(0)
    pool_ref = refs.pop(0) if fuse_pool else None
    xs_ref = refs.pop(0) if staged else None
    t = pl.program_id(1)
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():                      # chain start: zero the psum bank
        acc_ref[...] = jnp.zeros_like(acc_ref)

    bb, cin = x_ref.shape[0], x_ref.shape[-1]
    out_c = acc_ref.shape[-1]
    rowwise = (norm or act == "gelu") and not fuse_pool

    def conv_image(col0, b, carry):
        if staged:
            stage_lanes(xs_ref, x_ref[b])

        def load(r, kx, c0, cw):
            cols = strided(col0 + kx, acc_w, stride)
            if staged:
                return lane_load(xs_ref, (), r, cols, c0, cw)
            return x_ref[b, r, cols, c0:c0 + cw]

        def wtap(ky, kx, c0, cw, o0, ow):
            return w_ref[ky, kx, c0:c0 + cw, o0:o0 + ow]

        conv_rows(acc_ref, b, load, wtap, K=K, stride=stride,
                  acc_h=acc_h, acc_w=acc_w, cin=cin, out_c=out_c,
                  groups=groups, fold=fold)
        return carry

    def conv_col(j):
        jax.lax.fori_loop(
            0, bb, functools.partial(conv_image, j * col_step), 0)

    at_tile_col(tbl_ref[k, t, OP_TX], tiles_w, conv_col)

    @pl.when(k == n_waves - 1)
    def _epilogue():                  # chain end: finish in VMEM, write once
        vr, vc = tbl_ref[k, t, OP_VR], tbl_ref[k, t, OP_VC]

        def finish_row(j, b, i, carry):
            # bias, residual, norm, activation on output row i alone
            cols = slice(j * blk_w, (j + 1) * blk_w)
            a = acc_ref[b, i] + b_ref[...]
            if residual:
                a = a + r_ref[b, i, cols, :]
            if norm:
                a = channel_norm(a, n_ref[0:1, :], n_ref[1:2, :], n_out,
                                 NORM_EPS)
            a = activate(a, act)
            col = jax.lax.broadcasted_iota(jnp.int32, a.shape, 0)
            o_ref[b, i, cols, :] = jnp.where((i < vr) & (col < vc), a,
                                             jnp.zeros_like(a))
            return carry

        def finish(j, b, carry):
            if rowwise:
                return jax.lax.fori_loop(
                    0, blk_h, functools.partial(finish_row, j, b), carry)
            cols = slice(j * blk_w, (j + 1) * blk_w)
            a = acc_ref[b] + b_ref[...]
            if residual:              # accumulation-buffer add, pre-ReLU
                a = a + r_ref[b, :, cols, :]
            a = activate(a, act)
            if fuse_pool:
                # overlapping pools (ps < pool) re-derive their overlap
                # rows in-block, as strided loads of the parked tile
                a = pool_tile(pool_ref, a, pool=pool, ps=ps,
                              blk_h=blk_h, blk_w=blk_w)
            # masked write: zero the uniform-grid padding lanes so the
            # padded output is deterministic (VR/VC table columns)
            o_ref[b, :, cols, :] = mask_tile(a, vr, vc)
            return carry

        at_tile_col(tbl_ref[k, t, OP_TX], tiles_w, lambda j: jax.lax.fori_loop(
            0, bb, functools.partial(finish, j), 0))


def wave_replay_raw(kp: KernelProgram, x: jax.Array, w: jax.Array,
                    b: jax.Array, table: jax.Array,
                    residual: jax.Array | None = None,
                    norm: jax.Array | None = None,
                    interpret: bool | None = None) -> jax.Array:
    """Launch the persistent megakernel for one layer.

    ``x`` (B, pad_h, pad_w, in_c_pad) pre-padded to the program's buffer
    geometry; ``w`` (K, K, w_in_pad, out_c_pad); ``b`` (1, out_c_pad)
    fp32 (zeros when the layer has no bias); ``table`` the program's
    (n_waves, n_tiles, 8) int32 operand table. Programs lowered with
    ``residual=True`` additionally take the residual activation at the
    padded output geometry (B, out_h_pad, out_w_pad, out_c_pad) fp32 —
    each tile's rows are DMA'd alongside the output rows and added in
    the epilogue. Programs lowered with ``norm=True`` take ``norm``, the
    (2, out_c_pad) fp32 gamma and beta rows (zeros past ``out_c``). The
    batch axis rides the grid in blocks of
    ``kp.batch_block`` images (outermost axis); ragged batches are
    zero-padded to whole blocks here and cropped on return (zero
    images convolve to exact zeros, so real rows are untouched).
    Returns the padded (B, out_h_pad, out_w_pad, out_c_pad) fp32
    output (masked lanes are exact zeros); the caller crops to the
    valid dims.
    """
    if interpret is None:
        from repro.kernels.common import pallas_interpret_default
        interpret = pallas_interpret_default()
    g = kp.wave.program
    l = g.layer
    B = x.shape[0]
    if x.shape != (B, kp.pad_h, kp.pad_w, kp.in_c_kpad):
        raise ValueError(
            f"{l.name}: megakernel input {x.shape} != padded "
            f"({B}, {kp.pad_h}, {kp.pad_w}, {kp.in_c_kpad})")
    if w.shape != (l.kernel, l.kernel, kp.w_in_kpad, g.out_c_pad):
        raise ValueError(
            f"{l.name}: megakernel weights {w.shape} != padded "
            f"({l.kernel}, {l.kernel}, {kp.w_in_kpad}, {g.out_c_pad})")
    if table.shape != (kp.n_chain, kp.n_tiles, KERNEL_OP_COLS):
        raise ValueError(
            f"{l.name}: operand table {table.shape} != "
            f"({kp.n_chain}, {kp.n_tiles}, {KERNEL_OP_COLS})")
    if kp.residual:
        want = (B, kp.out_h_pad, kp.out_w_pad, kp.out_c_pad)
        if residual is None or residual.shape != want:
            raise ValueError(
                f"{l.name}: residual program wants a residual operand "
                f"of shape {want}, got "
                f"{None if residual is None else residual.shape}")
    elif residual is not None:
        raise ValueError(
            f"{l.name}: program lowered without residual=True cannot "
            f"take a residual operand")
    if kp.norm != (norm is not None) or (
            norm is not None and norm.shape != (2, kp.out_c_pad)):
        raise ValueError(
            f"{l.name}: program lowered with norm={kp.norm} got norm "
            f"operand {None if norm is None else norm.shape}, wants "
            f"{(2, kp.out_c_pad) if kp.norm else None}")

    # batch as a first-class grid axis (ISSUE 8): bb images per step,
    # padded to whole blocks (zeros accumulate exact 0.0) and cropped
    n_bb, bb = batch_grid(B, kp.batch_block)
    if n_bb * bb != B:
        x = jnp.pad(x, ((0, n_bb * bb - B), (0, 0), (0, 0), (0, 0)))
        if kp.residual:
            residual = jnp.pad(
                residual, ((0, n_bb * bb - B), (0, 0), (0, 0), (0, 0)))
    # narrow strided layers fold the stride into channels (conv1:
    # 227x227x3 -> 57x57x48), turning strided taps into plain ones;
    # those whose taps then fit two lane tiles run each row's taps as
    # one dot (the ResNet stem: 16 taps of 12 channels -> 192)
    s, k_eff, stride, ih, full_w, c_eff, _ = megakernel_geometry(kp)
    q = tap_fold_factor(l, kp.c_width, kp.n_chain)
    x = space_to_depth(x, s)
    w = fold_taps_weights(space_to_depth_weights(w, s), q)
    vmem = megakernel_vmem(kp, bb=bb)
    in_specs = [
        # halo windows: table-driven element offsets along the rows
        # (overlap is indexed in place, never copied out); each window
        # spans the full width, the tile's columns offset the taps
        pl.BlockSpec(element_block((bb, ih, full_w, c_eff)),
                     lambda bi, t, k, tbl: (
                         bi * bb, tbl[k, t, OP_IY] // s, 0,
                         k * c_eff if kp.n_chain > 1 else 0)),
        pl.BlockSpec(weight_block(kp, q),
                     lambda bi, t, k, tbl: (0, 0, k, 0)),
        pl.BlockSpec((1, kp.out_c_pad), lambda bi, t, k, tbl: (0, 0)),
    ]
    operands = [table, x, w, b]
    # output (and residual) blocks hold a tile row across every tile
    # column; consecutive tiles of one row revisit the same block
    out_block = (bb, kp.blk_h, kp.out_w_pad, kp.out_c_pad)
    if kp.residual:
        in_specs.append(pl.BlockSpec(
            out_block, lambda bi, t, k, tbl: (bi, tbl[k, t, OP_TY], 0, 0)))
        operands.append(residual)
    if kp.norm:
        in_specs.append(pl.BlockSpec((2, kp.out_c_pad),
                                     lambda bi, t, k, tbl: (0, 0)))
        operands.append(norm)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,        # the SMEM operand table
        grid=(n_bb, kp.n_tiles, kp.n_chain),
        in_specs=in_specs,
        out_specs=pl.BlockSpec(
            out_block, lambda bi, t, k, tbl: (bi, tbl[k, t, OP_TY], 0, 0)),
        # the psum SRAM bank (one tile's chain lives here, never in
        # HBM), then the lane-tiled pool and strided-window staging
        scratch_shapes=[pltpu.VMEM(sh, dt) for sh, dt in vmem.scratch],
    )
    kern = functools.partial(
        _replay_kernel, K=k_eff, stride=stride,
        col_step=kp.blk_w * kp.pool_stride * stride,
        acc_h=kp.acc_h, acc_w=kp.acc_w,
        n_waves=kp.n_chain, pool=kp.pool, ps=kp.pool_stride,
        blk_h=kp.blk_h, blk_w=kp.blk_w, tiles_w=kp.tiles_w, act=kp.act,
        norm=kp.norm, n_out=l.out_c, fuse_pool=kp.fuse_pool,
        residual=kp.residual, groups=kp.groups, staged=stride > 1,
        fold=q > 1)
    y = pl.pallas_call(
        kern,
        out_shape=jax.ShapeDtypeStruct(
            (n_bb * bb, kp.out_h_pad, kp.out_w_pad, kp.out_c_pad),
            jnp.float32),
        grid_spec=grid_spec,
        compiler_params=vmem.compiler_params(l.name, interpret),
        interpret=interpret,
    )(*operands)
    return y[:B] if n_bb * bb != B else y
