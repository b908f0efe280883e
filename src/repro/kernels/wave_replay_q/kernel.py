"""Quantized (int8) persistent wave-replay megakernel (ISSUE 4 tentpole).

The dtype-parameterised sibling of ``kernels/wave_replay``: the SAME
``KernelProgram`` schedule (grid, SMEM operand table, halo windows,
masked writes — quantization does not perturb the planner), with the
datapath swapped for the paper's fixed-point CU pipeline:

  * operands are int8 (activations per-tensor-scaled, weights
    per-output-channel), one precision notch below the paper's 16-bit
    words — the TPU MXU's native quantized format (DESIGN.md §6);
  * the VMEM scratch accumulator is **int32** — the paper's 32-bit
    partial-sum SRAM bank, carried across each tile's in-channel chain
    with zero HBM round-trips;
  * the epilogue requantizes on write-back: int32 accumulator + int32
    bias -> fixed-point multiply + rounding shift
    (``core/quantization.py::requantize_i32``) -> int8 in the *next
    layer's* operand scale, with ReLU folded into the clip bounds and
    the max-pool running on int8 in VMEM.

Exactness: every int8 x int8 product and every accumulation is computed
EXACTLY, so kernel output matches the int32 reference model bit for
bit. The in-tile reduction runs as fp32 im2col matmuls — fast on every
backend — split into fan chunks of at most ``EXACT_FP32_FAN`` products
(fan * 127^2 < 2^24), which keeps every fp32 partial sum an exactly
representable integer; chunks are cast back and summed in int32
(``precision=HIGHEST`` pins the TPU MXU to its exact fp32 passes).
Integer addition is associative, so chain order, chunking, and grouping
cannot change a single bit — unlike the fp32 megakernel, which matches
its references only to rounding tolerance.

Grouped layers run true per-group gemms against the natural
(K, K, in_c/groups, out_c) weight layout — since ISSUE 10 the fp32
megakernel shares this layout (the block-diagonal dense expansion is
gone from every executor path), so both precisions pay only the real
``K*K*(Cin/g)*Cout`` flops and weight DMA. Depthwise layers
(``groups == Cin``, per-group fan 1) skip the gemm loop entirely and
run a K*K-tap elementwise int32 multiply-accumulate — int8 products
are exact in int32, so bit-exactness is preserved without unrolling
``Cin`` one-wide gemms.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.quantization import (EXACT_FP32_FAN, requantize_clip,
                                     residual_add_clip)
from repro.core.schedule import (KERNEL_OP_COLS, OP_IY, OP_TX, OP_TY,
                                 OP_VC, OP_VR, KernelProgram, batch_grid)
from repro.kernels.common import (at_tile_col, conv_rows, element_block,
                                  lane_load, mask_tile, megakernel_geometry,
                                  megakernel_vmem, pool_tile,
                                  space_to_depth, space_to_depth_weights,
                                  stage_lanes, strided)


def exact_channel_chunk(kernel: int) -> int:
    """Max input channels per fp32 sub-gemm such that the gemm fan
    (K*K*channels) keeps every partial sum an exact fp32 integer."""
    c = EXACT_FP32_FAN // (kernel * kernel)
    if c < 1:
        raise ValueError(
            f"kernel {kernel}x{kernel}: a single channel's fan "
            f"{kernel * kernel} already exceeds the exact-fp32 bound "
            f"{EXACT_FP32_FAN}")
    return c


def residual_add_i8(q: jax.Array, r: jax.Array,
                    relu: bool) -> jax.Array:
    """The int8 accumulation-buffer add: both operands live in the SAME
    calibrated scale (calibration unifies add-operand scales), so the
    sum is plain int32 addition followed by the ReLU-folded int8 clip —
    deterministic integer ops shared verbatim by the kernel epilogue
    and the int32 reference model (bit-exact by construction)."""
    return residual_add_clip(q, r, relu).astype(jnp.int8)


def _replay_q_kernel(tbl_ref, x_ref, w_ref, bq_ref, m_ref, s_ref, *refs,
                     K: int, stride: int, col_step: int, acc_h: int,
                     acc_w: int, n_waves: int, pool: int, ps: int,
                     blk_h: int, blk_w: int, tiles_w: int, relu: bool,
                     fuse_pool: bool, groups: int, c_sub: int,
                     pre_shift: int, masked: bool, residual: bool):
    """One grid step: batch block (program_id 0), tile t (id 1), chain
    position k (id 2) — the batch axis outermost, like the fp32 kernel.

    ``refs`` are ``[r_ref] o_ref acc_ref xs_ref ws_ref [pool_ref]``.
    The int8 window (one image at a time) and the step's weights are
    staged into fp32 scratch — exact, they hold int8 values — where the
    shared ``conv_rows`` body reads its taps (``xs_ref`` lane-tiled, so
    strided taps load from it too); ``c_sub`` caps the channels per
    exact-fp32 dot — either the worst-case ``exact_channel_chunk``
    bound, or the calibrated weight-aware bound (``LayerQuant.
    fan_chunk``). The int32 ``acc_ref`` is the paper's 32-bit psum
    bank. ``masked`` is statically False when the tile grid covers the
    valid output exactly, dropping the write-mask pass. ``r_ref``
    (``residual``) holds the int8 residual rows at the layer's
    calibrated OUTPUT scale, added after requantization with the ReLU
    folded into the final clip.
    """
    refs = list(refs)
    r_ref = refs.pop(0) if residual else None
    o_ref, acc_ref, xs_ref, ws_ref = refs[:4]
    pool_ref = refs[4] if fuse_pool else None
    t = pl.program_id(1)
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():                  # chain start: zero the int32 psum bank
        acc_ref[...] = jnp.zeros_like(acc_ref)

    bb, cin = x_ref.shape[0], x_ref.shape[-1]
    out_c = acc_ref.shape[-1]
    ws_ref[...] = w_ref[...].astype(jnp.float32)

    def conv_image(col0, b, carry):
        stage_lanes(xs_ref, x_ref[b].astype(jnp.float32))

        def load(r, kx, c0, cw):
            return lane_load(xs_ref, (), r, strided(col0 + kx, acc_w, stride),
                             c0, cw)

        def wtap(ky, kx, c0, cw, o0, ow):
            return ws_ref[ky, kx, c0:c0 + cw, o0:o0 + ow]

        conv_rows(acc_ref, b, load, wtap, K=K, stride=stride,
                  acc_h=acc_h, acc_w=acc_w, cin=cin, out_c=out_c,
                  groups=groups, exact_chunk=c_sub)
        return carry

    at_tile_col(tbl_ref[k, t, OP_TX], tiles_w, lambda j: jax.lax.fori_loop(
        0, bb, functools.partial(conv_image, j * col_step), 0))

    @pl.when(k == n_waves - 1)
    def _epilogue():              # requantize-on-writeback, all in VMEM
        vr, vc = tbl_ref[k, t, OP_VR], tbl_ref[k, t, OP_VC]

        def finish(j, b, carry):
            cols = slice(j * blk_w, (j + 1) * blk_w)
            a = acc_ref[b] + bq_ref[...]
            # the residual add runs pre-ReLU: requantize without the
            # ReLU clip, add the int8 shortcut (same scale), then clip
            q = requantize_clip(a, m_ref[...], s_ref[...], pre_shift,
                                relu=relu and not residual)
            if residual:
                q = residual_add_clip(q, r_ref[b, :, cols, :], relu)
            if fuse_pool:
                q = pool_tile(pool_ref, q, pool=pool, ps=ps,
                              blk_h=blk_h, blk_w=blk_w)
            if masked:
                q = mask_tile(q, vr, vc)
            o_ref[b, :, cols, :] = q.astype(jnp.int8)
            return carry

        at_tile_col(tbl_ref[k, t, OP_TX], tiles_w, lambda j: jax.lax.fori_loop(
            0, bb, functools.partial(finish, j), 0))


def q_weight_fan(kp: KernelProgram) -> int:
    """Weight fan-in dim of one grid step's int8 weight *block*.

    Since ISSUE 10 both precisions share the schedule's natural layout:
    ``fan_width`` IS the per-group fan for grouped layers and the
    chain-chunk slice width for ungrouped ones."""
    return kp.fan_width


def q_weight_full_fan(kp: KernelProgram) -> int:
    """Fan-in dim of the int8 kernel's *full* weight operand: grouped
    layers keep their natural per-group fan (single-step chains read it
    whole, ``w_in_kpad == fan_width``); ungrouped ones pad to
    ``w_in_kpad`` and slice per chain step, exactly like fp32."""
    return kp.w_in_kpad


def wave_replay_q_raw(kp: KernelProgram, xq: jax.Array, wq: jax.Array,
                      bq: jax.Array, m: jax.Array, shift: jax.Array,
                      table: jax.Array, *, pre_shift: int = 0,
                      fan_chunk: "int | None" = None,
                      residual: "jax.Array | None" = None,
                      interpret: bool | None = None) -> jax.Array:
    """Launch the int8 megakernel for one layer.

    ``xq`` (B, pad_h, pad_w, in_c_kpad) int8 pre-padded to the
    program's buffer geometry; ``wq`` (K, K, q_weight_fan, out_c_pad)
    int8 in natural per-group layout; ``bq``/``m``/``shift``
    (1, out_c_pad) int32; ``table`` the SAME (n_chain, n_tiles, 8)
    operand table the fp32 kernel replays. ``fan_chunk`` caps input
    channels per exact sub-gemm: ``None`` applies the worst-case
    ``exact_channel_chunk`` bound; calibrated callers pass
    ``LayerQuant.fan_chunk`` (weight-aware, usually unchunked). Returns
    the padded int8 output (masked lanes exact 0); the caller crops.
    """
    if interpret is None:
        from repro.kernels.common import pallas_interpret_default
        interpret = pallas_interpret_default()
    g = kp.wave.program
    l = g.layer
    B = xq.shape[0]
    if kp.norm or kp.act == "gelu":
        raise ValueError(f"{l.name}: the int8 epilogue has no norm or "
                         f"gelu")
    if l.groups > 1:
        # grouped plans have single-step chains (planner invariant) and
        # group-aligned features, so out_c_pad == out_c and the in-body
        # group loop can address acc columns statically
        if kp.n_chain != 1 or g.out_c_pad != l.out_c:
            raise ValueError(
                f"{l.name}: grouped int8 kernel expects a single-step "
                f"chain over the full out_c (got n_chain={kp.n_chain}, "
                f"out_c_pad={g.out_c_pad})")
    if xq.dtype != jnp.int8 or wq.dtype != jnp.int8:
        raise ValueError(
            f"{l.name}: int8 kernel operands must be int8 "
            f"(got x {xq.dtype}, w {wq.dtype})")
    if xq.shape != (B, kp.pad_h, kp.pad_w, kp.in_c_kpad):
        raise ValueError(
            f"{l.name}: int8 megakernel input {xq.shape} != padded "
            f"({B}, {kp.pad_h}, {kp.pad_w}, {kp.in_c_kpad})")
    if wq.shape != (l.kernel, l.kernel, q_weight_full_fan(kp),
                    g.out_c_pad):
        raise ValueError(
            f"{l.name}: int8 megakernel weights {wq.shape} != "
            f"({l.kernel}, {l.kernel}, {q_weight_full_fan(kp)}, "
            f"{g.out_c_pad})")
    for name, arr in (("bias_q", bq), ("m", m), ("shift", shift)):
        if arr.shape != (1, g.out_c_pad) or arr.dtype != jnp.int32:
            raise ValueError(
                f"{l.name}: {name} must be int32 (1, {g.out_c_pad}), "
                f"got {arr.dtype} {arr.shape}")
    if table.shape != (kp.n_chain, kp.n_tiles, KERNEL_OP_COLS):
        raise ValueError(
            f"{l.name}: operand table {table.shape} != "
            f"({kp.n_chain}, {kp.n_tiles}, {KERNEL_OP_COLS})")
    if kp.residual:
        want = (B, kp.out_h_pad, kp.out_w_pad, g.out_c_pad)
        if residual is None or residual.shape != want \
                or residual.dtype != jnp.int8:
            raise ValueError(
                f"{l.name}: residual program wants an int8 residual of "
                f"shape {want}, got "
                f"{None if residual is None else residual.shape}")
    elif residual is not None:
        raise ValueError(
            f"{l.name}: program lowered without residual=True cannot "
            f"take a residual operand")

    step_in_c = l.in_c // l.groups if l.groups > 1 else kp.c_width
    c_sub = exact_channel_chunk(l.kernel) if fan_chunk is None \
        else max(1, min(int(fan_chunk), step_in_c))
    # batch rides the grid in blocks of kp.batch_block images, exactly
    # like the fp32 kernel; zero-padded images quantize/accumulate to
    # exact integer zeros, so cropping recovers the real rows bit-exact
    n_bb, bb = batch_grid(B, kp.batch_block)
    if n_bb * bb != B:
        xq = jnp.pad(xq, ((0, n_bb * bb - B), (0, 0), (0, 0), (0, 0)))
        if kp.residual:
            residual = jnp.pad(
                residual, ((0, n_bb * bb - B), (0, 0), (0, 0), (0, 0)))
    # the same stride folding as the fp32 kernel; channel-major folding
    # keeps each c_sub channel chunk one contiguous range of s*s*c_sub
    s, k_eff, stride, ih, full_w, c_eff, f_eff = megakernel_geometry(kp)
    xq = space_to_depth(xq, s)
    wq = space_to_depth_weights(wq, s)
    vmem = megakernel_vmem(kp, quantized=True, bb=bb)
    out_block = (bb, kp.blk_h, kp.out_w_pad, g.out_c_pad)
    in_specs = [
        pl.BlockSpec(element_block((bb, ih, full_w, c_eff)),
                     lambda bi, t, k, tbl: (
                         bi * bb, tbl[k, t, OP_IY] // s, 0,
                         k * c_eff if kp.n_chain > 1 else 0)),
        # natural per-group weights: grouped layers read the whole
        # (single-step) tensor, ungrouped ones the chain chunk's fan rows
        pl.BlockSpec((k_eff, k_eff, f_eff, g.out_c_pad),
                     lambda bi, t, k, tbl: (0, 0, k, 0)),
        pl.BlockSpec((1, g.out_c_pad), lambda bi, t, k, tbl: (0, 0)),
        pl.BlockSpec((1, g.out_c_pad), lambda bi, t, k, tbl: (0, 0)),
        pl.BlockSpec((1, g.out_c_pad), lambda bi, t, k, tbl: (0, 0)),
    ]
    operands = [table, xq, wq, bq, m, shift]
    if kp.residual:
        # the int8 shortcut reads the row blocks the output writes
        in_specs.append(pl.BlockSpec(
            out_block, lambda bi, t, k, tbl: (bi, tbl[k, t, OP_TY], 0, 0)))
        operands.append(residual)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,        # the SMEM operand table
        grid=(n_bb, kp.n_tiles, kp.n_chain),
        in_specs=in_specs,
        out_specs=pl.BlockSpec(
            out_block, lambda bi, t, k, tbl: (bi, tbl[k, t, OP_TY], 0, 0)),
        # the paper's 32-bit psum SRAM bank, plus the fp32 staging of
        # one image's window and of the step's weights
        scratch_shapes=[pltpu.VMEM(sh, dt) for sh, dt in vmem.scratch],
    )
    # write masks are only live where the uniform tile grid overhangs
    # the valid output; exact grids skip the mask pass statically
    masked = kp.out_h_pad != kp.out_h or kp.out_w_pad != kp.out_w
    kern = functools.partial(
        _replay_q_kernel, K=k_eff, stride=stride,
        col_step=kp.blk_w * kp.pool_stride * stride,
        acc_h=kp.acc_h, acc_w=kp.acc_w,
        n_waves=kp.n_chain, pool=kp.pool, ps=kp.pool_stride,
        blk_h=kp.blk_h, blk_w=kp.blk_w, tiles_w=kp.tiles_w, relu=kp.relu,
        fuse_pool=kp.fuse_pool, groups=l.groups, c_sub=c_sub * s * s,
        pre_shift=pre_shift, masked=masked, residual=kp.residual)
    yq = pl.pallas_call(
        kern,
        out_shape=jax.ShapeDtypeStruct(
            (n_bb * bb, kp.out_h_pad, kp.out_w_pad, g.out_c_pad),
            jnp.int8),
        grid_spec=grid_spec,
        compiler_params=vmem.compiler_params(l.name, interpret),
        interpret=interpret,
    )(*operands)
    return yq[:B] if n_bb * bb != B else yq
