"""Whole-graph persistent wave-replay kernel (ISSUE 6 tentpole, fp32).

ONE ``pallas_call`` replays a fused CHAIN of conv nodes: the grid is the
concatenation of every node's (tile, chain) steps and the SMEM operand
table (``GraphKernelProgram``, core/schedule.py) grows NODE/K dispatch
plus flat weight/bias offsets. Inter-layer activations never round-trip
HBM — each liveness interval owns a VMEM arena slot (``plan_arena``):
producers write their masked epilogue blocks at the value's layout pad,
conv consumers window the halo back out of the slot, and residual
operands read their blocks from the slot that held the shortcut — Du et
al.'s layer-sequencing controller walking one set of SRAM banks.

Each node's steps replay its per-layer ``KernelProgram`` verbatim (same
tiles, same ``conv_rows`` body and accumulation order, same masked
epilogue), so a fused chain's output matches the per-layer
megakernel's — bit for bit except where a strided node reads its input
from the arena with strided taps while its per-layer launch folds the
stride into channels (``s2d_factor``), a reordering of fp32 sums.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.quantization import requantize_clip, residual_add_clip
from repro.core.schedule import (GRAPH_OP_COLS, GOP_BOFF, GOP_IY, GOP_K,
                                 GOP_NODE, GOP_OY, GOP_TX, GOP_TY, GOP_VC,
                                 GOP_VR, GOP_WOFF, GraphKernelProgram,
                                 batch_grid)
from repro.kernels.common import (LaunchVmem, at_tile_col, conv_rows,
                                  element_block, lane_load, lane_tiles,
                                  mask_tile, pool_tile, s2d_factor,
                                  space_to_depth, space_to_depth_weights,
                                  stage_lanes, strided)
from repro.kernels.wave_replay import ops as _ops


@dataclasses.dataclass(frozen=True)
class NodeGeometry:
    """How one chain node's step reads its input and weights.

    ``windowed`` marks the head node reading the chain input from HBM
    through the table-steered window (``s2d``: stride folded into its
    channels, like the per-layer launch); every other node reads an
    arena slot. ``staged`` nodes have strided taps, loaded from a
    lane-tiled copy of the window (so are int8 head windows). Weights
    sit in the stacked buffer as (K*K*fan, out_c) rows at the folded
    geometry.
    """
    windowed: bool
    s2d: int
    K: int
    stride: int
    c_width: int
    fan: int
    ih: int
    iw: int
    staged: bool

    @property
    def w_rows(self) -> int:
        return self.K * self.K * self.fan


def node_geometry(gkp: GraphKernelProgram, ni: int) -> NodeGeometry:
    kp = gkp.nodes[ni].kp
    l = kp.wave.program.layer
    windowed = ni == 0 and not gkp.input_in_arena
    s = s2d_factor(l, kp.c_width) if windowed else 1
    return NodeGeometry(
        windowed=windowed, s2d=s, K=-(-l.kernel // s), stride=l.stride // s,
        c_width=kp.c_width * s * s, fan=kp.fan_width * s * s,
        ih=-(-kp.ih // s), iw=-(-kp.iw // s),
        # int8 windows are staged as fp32 too (their values are exact)
        staged=l.stride // s > 1 or (windowed and gkp.quantized))


def stacked_shapes(gkp: GraphKernelProgram):
    """(rows, cols) of one stacked weight row and the bias row width."""
    geos = [node_geometry(gkp, i) for i in range(len(gkp.nodes))]
    return (max(g.w_rows for g in geos), gkp.b_max), geos


def chain_scratch(gkp: GraphKernelProgram, geos, dtype, acc_dtype):
    """Scratch shared by a chain's steps, per batch block of ``bb``:
    the arena slots, one accumulator at the largest node extent, the
    lane-tiled pool scratch, the lane-tiled window staging and (int8)
    the fp32 staging of a step's weights."""
    bb = gkp.batch_block
    out = [((bb,) + sh, dtype) for sh in gkp.arena.slot_shapes]
    out.append(((bb,) + gkp.acc_shape(), acc_dtype))
    pooled = [s.kp for s in gkp.nodes if s.kp.fuse_pool]
    if pooled:
        n = max(lane_tiles(kp.out_c_pad)[0] for kp in pooled)
        out.append(((n, max(kp.acc_h for kp in pooled),
                     max(kp.acc_w for kp in pooled),
                     max(lane_tiles(kp.out_c_pad)[1] for kp in pooled)),
                    acc_dtype))
    staged = [g for g in geos if g.staged]
    if staged:
        out.append(((max(lane_tiles(g.c_width)[0] for g in staged),
                     max(g.ih for g in staged), max(g.iw for g in staged),
                     max(lane_tiles(g.c_width)[1] for g in staged)),
                    jnp.float32))
    if gkp.quantized:                 # the step's weights, staged fp32
        out.append(((max(g.w_rows for g in geos), gkp.b_max), jnp.float32))
    return out


def x_block_shape(gkp: GraphKernelProgram, bb: int) -> tuple:
    """The chain input's block: the head's window (full width at its
    folded geometry) or, when the input lives in the arena, each batch
    block's whole padded input."""
    h0 = gkp.nodes[0].kp
    if gkp.input_in_arena:
        return (bb, h0.pad_h, h0.pad_w, h0.in_c_kpad)
    geo = node_geometry(gkp, 0)
    return (bb, geo.ih, -(-h0.pad_w // geo.s2d), geo.c_width)


def graph_kernel_vmem(gkp: GraphKernelProgram,
                      bb: "int | None" = None) -> LaunchVmem:
    """The VMEM a fused-chain launch holds at ``bb`` images per grid
    step (default ``gkp.batch_block``): the input window, one step's
    weight and vector rows and the output block (double-buffered), and
    ``chain_scratch``. int8 chains keep int32 arena slots and psums.
    Both chain launchers take their scratch shapes from here."""
    bb = gkp.batch_block if bb is None else bb
    (rows, b_max), geos = stacked_shapes(gkp)
    kl = gkp.out_kp
    io = jnp.int8 if gkp.quantized else jnp.float32
    acc_dt = jnp.int32 if gkp.quantized else jnp.float32
    blocks = ((x_block_shape(gkp, bb), io), ((1, rows, gkp.b_max), io)) \
        + (((1, 1, b_max), acc_dt),) * (3 if gkp.quantized else 1) \
        + (((bb, kl.blk_h, kl.out_w_pad, kl.out_c_pad), io),)
    scratch = chain_scratch(dataclasses.replace(gkp, batch_block=bb), geos,
                            acc_dt, acc_dt)
    return LaunchVmem(blocks, tuple(scratch),
                      ((gkp.acc_shape(), acc_dt),) * 3)


def _node_step(tbl_ref, x_ref, w_ref, vec_refs, o_ref, refs,
               gkp: GraphKernelProgram, geo: NodeGeometry, ni: int, t,
               quant=None):
    """Replay node ``ni``'s per-layer grid step at flat step ``t``.

    ``vec_refs`` is this node's bias row (fp32) or its (bias, m, shift)
    rows (int8); ``quant`` is None for fp32, else the node's static
    ``(pre_shift, c_sub)`` — int8 arena slots hold int32 values, the
    step's weights and head window are staged as exact fp32, and the
    epilogue requantizes on write-back.
    """
    slots, acc_ref, pool_ref, xs_ref, ws_ref = refs
    spec = gkp.nodes[ni]
    kp = spec.kp
    l = kp.wave.program.layer
    last = ni == len(gkp.nodes) - 1
    k = tbl_ref[t, GOP_K]
    ty = tbl_ref[t, GOP_TY]
    tx = tbl_ref[t, GOP_TX]
    ah, aw, oc = kp.acc_h, kp.acc_w, kp.out_c_pad
    bb = acc_ref.shape[0]
    col_step = kp.blk_w * kp.pool_stride * l.stride // geo.s2d

    if not last:
        osi = gkp.arena.slot_of(spec.out_value)

        # this node's first flat step: clear its output slot so masked
        # lanes and never-written channels read as the exact zeros the
        # per-layer path's pad_operands/pad_residual would supply
        @pl.when(t == gkp.node_steps[ni])
        def _zero_slot():
            slots[osi][...] = jnp.zeros_like(slots[osi])

    @pl.when(k == 0)
    def _init():                      # chain start: zero the psum bank
        acc_ref[:, :ah, :aw, :oc] = jnp.zeros_like(
            acc_ref[:, :ah, :aw, :oc])

    if geo.windowed:                  # table-steered halo window
        row0, col_base, cbase = 0, 0, 0
    else:
        # window the halo straight out of the producer's arena slot:
        # the node-boundary "reload" is an index, not an HBM round-trip
        iv = gkp.arena.value(spec.in_value)
        src = slots[gkp.arena.slot_of(spec.in_value)]
        row0 = iv.pad[0] - l.pad + ty * (kp.blk_h * kp.pool_stride
                                         * l.stride)
        col_base = iv.pad[1] - l.pad
        cbase = k * kp.c_width if l.groups == 1 and kp.n_chain > 1 else 0
    if quant is not None:
        ws_ref[0:geo.w_rows, 0:oc] = \
            w_ref[0, 0:geo.w_rows, 0:oc].astype(jnp.float32)
        wsrc = ws_ref
    else:
        wsrc = w_ref.at[0]

    def conv_image(col0, b, carry):
        if geo.staged:
            if geo.windowed:
                win = x_ref[b, :, col0:col0 + geo.iw, :]
            else:
                win = src[b, pl.ds(row0, geo.ih), col0:col0 + geo.iw,
                          pl.ds(cbase, geo.c_width)]
            stage_lanes(xs_ref, win.astype(jnp.float32))

        def load(r, kx, c0, cw):
            if geo.staged:
                return lane_load(xs_ref, (), r,
                                 strided(kx, aw, geo.stride), c0, cw)
            cols = strided(col0 + kx, aw, geo.stride)
            if geo.windowed:
                return x_ref[b, r, cols, c0:c0 + cw]
            return src[b, row0 + r, cols,
                       pl.ds(cbase + c0, cw)].astype(jnp.float32)

        def wtap(ky, kx, c0, cw, o0, ow):
            r = (ky * geo.K + kx) * geo.fan + c0
            return wsrc[r:r + cw, o0:o0 + ow]

        conv_rows(acc_ref, b, load, wtap, K=geo.K, stride=geo.stride,
                  acc_h=ah, acc_w=aw, cin=geo.c_width, out_c=oc,
                  groups=l.groups,
                  exact_chunk=None if quant is None
                  else quant[1] * geo.s2d * geo.s2d)
        return carry

    at_tile_col(tx, kp.tiles_w, lambda j: jax.lax.fori_loop(
        0, bb, functools.partial(conv_image, col_base + j * col_step), 0))

    @pl.when(k == kp.n_chain - 1)
    def _epilogue():                  # node boundary: finish in VMEM
        vr, vc = tbl_ref[t, GOP_VR], tbl_ref[t, GOP_VC]
        residual = spec.residual_value is not None

        def residual_rows(j, b):
            rv = gkp.arena.value(spec.residual_value)
            rsi = gkp.arena.slot_of(spec.residual_value)
            c = rv.pad[1] + j * kp.blk_w
            return slots[rsi][b, pl.ds(rv.pad[0] + ty * kp.blk_h, kp.blk_h),
                              c:c + kp.blk_w, 0:oc]

        def finish(j, b, carry):
            a = acc_ref[b, 0:ah, 0:aw, 0:oc] + vec_refs[0][0, :, 0:oc]
            if quant is None:
                if residual:
                    a = a + residual_rows(j, b)
                if kp.relu:
                    a = jnp.maximum(a, 0.0)
            else:
                # the residual add runs pre-ReLU: requantize without the
                # ReLU clip, add the int8 shortcut (same scale), clip
                a = requantize_clip(a, vec_refs[1][0, :, 0:oc],
                                    vec_refs[2][0, :, 0:oc], quant[0],
                                    relu=kp.relu and not residual)
                if residual:
                    a = residual_add_clip(a, residual_rows(j, b), kp.relu)
            if kp.fuse_pool:
                a = pool_tile(pool_ref, a, pool=kp.pool,
                              ps=kp.pool_stride, blk_h=kp.blk_h,
                              blk_w=kp.blk_w)
            val = mask_tile(a, vr, vc)
            if last:
                o_ref[b, :, j * kp.blk_w:(j + 1) * kp.blk_w, :] = \
                    val.astype(o_ref.dtype)
            else:
                ov = gkp.arena.value(spec.out_value)
                wc = min(oc, gkp.arena.slot_shapes[osi][2])
                c = ov.pad[1] + j * kp.blk_w
                slots[osi][b, pl.ds(ov.pad[0] + ty * kp.blk_h, kp.blk_h),
                           c:c + kp.blk_w, 0:wc] = val[..., :wc]
            return carry

        at_tile_col(tx, kp.tiles_w, lambda j: jax.lax.fori_loop(
            0, bb, functools.partial(finish, j), 0))


def split_scratch(gkp: GraphKernelProgram, geos, scratch):
    """(slots, acc_ref, pool_ref, xs_ref, ws_ref) in the order
    ``chain_scratch`` lays them out (None where a chain needs none)."""
    n = len(gkp.arena.slot_shapes)
    rest = list(scratch[n + 1:])
    pool_ref = rest.pop(0) if any(s.kp.fuse_pool for s in gkp.nodes) \
        else None
    xs_ref = rest.pop(0) if any(g.staged for g in geos) else None
    ws_ref = rest.pop(0) if gkp.quantized else None
    return scratch[:n], scratch[n], pool_ref, xs_ref, ws_ref


def graph_replay_kernel(tbl_ref, x_ref, wf_ref, *refs,
                        gkp: GraphKernelProgram, geos, quants=None):
    """One fused grid step: the table's NODE column picks which node's
    per-layer step body runs; everything else is baked in statically.
    ``refs`` are the stacked vector operands (bias; or bias, m, shift
    for int8 — ``quants`` then holds each node's static
    ``(pre_shift, c_sub)``), the output, and the ``chain_scratch``."""
    n_vec = 1 if quants is None else 3
    vec_refs, o_ref = refs[:n_vec], refs[n_vec]
    scratch = split_scratch(gkp, geos, refs[n_vec + 1:])
    slots = scratch[0]
    t = pl.program_id(1)
    if gkp.input_in_arena:
        # the chain input has in-chain consumers beyond the head conv
        # (e.g. a shortcut): stage the whole padded input into its slot
        # — once per batch block (t restarts at 0 for every block, and
        # the x_ref block carries that block's images)
        iv = gkp.arena.value(gkp.input_value)
        isi = gkp.arena.slot_of(gkp.input_value)
        h0 = gkp.nodes[0].kp
        pad0 = gkp.nodes[0].kp.wave.program.layer.pad
        dy, dx = iv.pad[0] - pad0, iv.pad[1] - pad0

        @pl.when(t == 0)
        def _stage_input():
            slots[isi][...] = jnp.zeros_like(slots[isi])
            slots[isi][:, dy:dy + h0.pad_h, dx:dx + h0.pad_w,
                       0:h0.in_c_kpad] = x_ref[...].astype(slots[isi].dtype)
    nd = tbl_ref[t, GOP_NODE]
    for ni in range(len(gkp.nodes)):
        @pl.when(nd == ni)
        def _run(ni=ni):
            _node_step(tbl_ref, x_ref, wf_ref, vec_refs, o_ref, scratch,
                       gkp, geos[ni], ni, t,
                       None if quants is None else quants[ni])


def x_block_spec(gkp: GraphKernelProgram, bb: int):
    """The chain input's BlockSpec (``x_block_shape``): the head's
    window moves to the table's IY row and its chain chunk's channels;
    an arena-resident input is one whole block per batch block."""
    h0 = gkp.nodes[0].kp
    shape = x_block_shape(gkp, bb)
    if gkp.input_in_arena:
        return pl.BlockSpec(shape, lambda bi, t, tbl: (bi, 0, 0, 0))
    s, c = node_geometry(gkp, 0).s2d, shape[-1]
    return pl.BlockSpec(
        element_block(shape),
        lambda bi, t, tbl: (bi * bb, tbl[t, GOP_IY] // s, 0,
                            tbl[t, GOP_K] * c if h0.n_chain > 1 else 0))


def wave_replay_graph_raw(gkp: GraphKernelProgram, x: jax.Array,
                          wf: jax.Array, bf: jax.Array, table: jax.Array,
                          interpret: bool | None = None) -> jax.Array:
    """Launch one fused chain as ONE persistent pallas_call.

    ``x`` is the chain input pre-padded to the head program's buffer
    geometry; ``wf``/``bf`` are the stacked (w_total, rows, cols) /
    (b_total, 1, b_max) fp32 weight and bias buffers of
    ``pack_graph_weights``; ``table`` the (total_steps, 14) int32
    operand table. The grid iterates (batch block, flat step) — each
    block of ``gkp.batch_block`` images replays the whole chain through
    its own arena slice; ragged batches are zero-padded to whole blocks
    and cropped on return. Returns the final node's padded
    (B, out_h_pad, out_w_pad, out_c_pad) fp32 output.
    """
    if interpret is None:
        from repro.kernels.common import pallas_interpret_default
        interpret = pallas_interpret_default()
    h0, kl = gkp.nodes[0].kp, gkp.out_kp
    B = x.shape[0]
    (rows, b_max), geos = stacked_shapes(gkp)
    if x.shape != (B, h0.pad_h, h0.pad_w, h0.in_c_kpad):
        raise ValueError(
            f"graph kernel input {x.shape} != padded "
            f"({B}, {h0.pad_h}, {h0.pad_w}, {h0.in_c_kpad})")
    if wf.shape != (gkp.w_total, rows, gkp.b_max):
        raise ValueError(f"stacked weights {wf.shape} != "
                         f"({gkp.w_total}, {rows}, {gkp.b_max})")
    if bf.shape != (gkp.b_total, 1, b_max):
        raise ValueError(f"stacked bias {bf.shape} != "
                         f"({gkp.b_total}, 1, {b_max})")
    if table.shape != (gkp.total_steps, GRAPH_OP_COLS):
        raise ValueError(
            f"graph table {table.shape} != "
            f"({gkp.total_steps}, {GRAPH_OP_COLS})")

    # batch blocks as the outermost grid axis (ISSUE 8): each block of
    # bb images replays the whole chain; padding images are zeros
    n_bb, bb = batch_grid(B, gkp.batch_block)
    if n_bb * bb != B:
        x = jnp.pad(x, ((0, n_bb * bb - B), (0, 0), (0, 0), (0, 0)))
    x = space_to_depth(x, geos[0].s2d)
    out_block = (bb, kl.blk_h, kl.out_w_pad, kl.out_c_pad)
    vmem = graph_kernel_vmem(gkp, bb)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,        # the SMEM operand table
        grid=(n_bb, gkp.total_steps),
        in_specs=[
            x_block_spec(gkp, bb),
            # each step's own row of the stacked chain buffers: VMEM
            # holds one step's weights, never the whole chain's
            pl.BlockSpec((1, rows, gkp.b_max),
                         lambda bi, t, tbl: (tbl[t, GOP_WOFF], 0, 0)),
            pl.BlockSpec((1, 1, b_max),
                         lambda bi, t, tbl: (tbl[t, GOP_BOFF], 0, 0)),
        ],
        out_specs=pl.BlockSpec(
            out_block, lambda bi, t, tbl: (bi, tbl[t, GOP_OY], 0, 0)),
        # the activation arena + one shared psum bank (per batch block)
        scratch_shapes=[pltpu.VMEM(sh, dt) for sh, dt in vmem.scratch],
    )
    y = pl.pallas_call(
        functools.partial(graph_replay_kernel, gkp=gkp, geos=geos),
        out_shape=jax.ShapeDtypeStruct(
            (n_bb * bb, kl.out_h_pad, kl.out_w_pad, kl.out_c_pad),
            jnp.float32),
        grid_spec=grid_spec,
        compiler_params=vmem.compiler_params(gkp.nodes[0].name, interpret),
        interpret=interpret,
    )(table, x, wf, bf)
    return y[:B] if n_bb * bb != B else y


def stack_weight_rows(gkp: GraphKernelProgram, weights, dtype):
    """Per chain step, the node's (K, K, fan, out_c) weight chunk at its
    folded geometry, flattened to (K*K*fan, out_c) rows and padded into
    one row of the stacked (w_total, rows, cols) buffer."""
    (rows, _), geos = stacked_shapes(gkp)
    chunks = []
    for spec, geo, w in zip(gkp.nodes, geos, weights):
        kp = spec.kp
        g = kp.wave.program
        l = g.layer
        wp = jnp.pad(w.astype(dtype),
                     ((0, 0), (0, 0), (0, kp.w_in_kpad - w.shape[2]),
                      (0, g.out_c_pad - l.out_c)))
        for kk in range(kp.n_chain):
            c = space_to_depth_weights(
                wp[:, :, kk * kp.fan_width:(kk + 1) * kp.fan_width, :],
                geo.s2d).reshape(geo.w_rows, g.out_c_pad)
            chunks.append(jnp.pad(c, ((0, rows - geo.w_rows),
                                      (0, gkp.b_max - g.out_c_pad))))
    return jnp.stack(chunks)


def stack_vector_rows(gkp: GraphKernelProgram, vecs, fill=0):
    """One (out_c,) vector per node -> the stacked (b_total, 1, b_max)
    buffer, padded channels set to ``fill``."""
    return jnp.stack([
        jnp.pad(v, (0, gkp.b_max - v.shape[0]),
                constant_values=fill)[None] for v in vecs])


def pack_graph_weights(gkp: GraphKernelProgram, weights):
    """(w, b) per chain node -> stacked fp32 weight and bias buffers.

    Weights keep their natural per-group layout (grouped layers are
    single-step, so the whole (K, K, in_c/groups, out_c) tensor is one
    row); each chain step's fan slice becomes its own row at the
    program's WOFF — exactly the block each step fetches.
    """
    if len(weights) != len(gkp.nodes):
        raise ValueError(f"{len(weights)} weight pairs for "
                         f"{len(gkp.nodes)} chain nodes")
    bvecs = []
    for spec, (_, b) in zip(gkp.nodes, weights):
        l = spec.kp.wave.program.layer
        bias = jnp.zeros((l.out_c,), jnp.float32) if b is None \
            else b.astype(jnp.float32)
        bvecs.append(jnp.pad(bias, (0, spec.kp.out_c_pad - l.out_c)))
    return (stack_weight_rows(gkp, [w for w, _ in weights], jnp.float32),
            stack_vector_rows(gkp, bvecs))


def wave_replay_graph(gkp: GraphKernelProgram, x: jax.Array, weights,
                      table: jax.Array | None = None,
                      interpret: bool | None = None) -> jax.Array:
    """Execute a fused conv chain as ONE persistent pallas_call.

    ``x`` (B, in_h, in_w, in_c) is the chain input's natural activation;
    ``weights`` is a (w, b) pair per chain node in chain order. Returns
    the final node's valid (B, out_h, out_w, out_c) fp32 output — the
    per-layer megakernel's result, node by node.
    """
    # one launch for the whole chain, attributed to the head node
    with _ops.launches.record(gkp.nodes[0].name, "graphkernel"):
        if table is None:
            table = jnp.asarray(gkp.operand_table())
        xp = _ops.pad_input(gkp.nodes[0].kp, x)
        wf, bf = pack_graph_weights(gkp, weights)
        y = wave_replay_graph_raw(gkp, xp, wf, bf, table,
                                  interpret=interpret)
    kl = gkp.out_kp
    return y[:, :kl.out_h, :kl.out_w, :gkp.out_layer.out_c]
