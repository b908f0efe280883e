"""Whole-graph persistent int8 wave-replay kernel (ISSUE 6, int8 twin).

The quantized sibling of ``kernels/wave_replay/graph.py``, sharing its
node step: ONE ``pallas_call`` replays a fused chain of conv nodes with
the int8 datapath — arena slots holding int8 values (as int32, the
32-bit words the TPU's strided loads read), the shared int32 psum bank,
exact-fp32 sub-gemms, and the requantize-on-writeback epilogue whose
residual add reads the shortcut's slot at the calibrated output
scale. Integer arithmetic is associative, so a fused
chain's output is bit-identical to the per-layer int8 megakernel and to
the int32 reference model.

Requant vectors ride alongside the stacked bias buffer: three int32
stacked operands (bias, m, shift) share the table's BOFF rows, padded
channels carrying m=0 / shift=31 so their lanes requantize to exact 0.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.schedule import (GRAPH_OP_COLS, GOP_BOFF, GOP_OY, GOP_WOFF,
                                 GraphKernelProgram, batch_grid)
from repro.kernels.common import space_to_depth
from repro.kernels.wave_replay.graph import (graph_kernel_vmem,
                                             graph_replay_kernel,
                                             stack_vector_rows,
                                             stack_weight_rows,
                                             stacked_shapes, x_block_spec)
from repro.kernels.wave_replay.ops import pad_input
from repro.kernels.wave_replay_q import ops as _ops
from repro.kernels.wave_replay_q.kernel import exact_channel_chunk


def wave_replay_graph_q_raw(gkp: GraphKernelProgram, xq: jax.Array,
                            wf: jax.Array, bf: jax.Array, mf: jax.Array,
                            sf: jax.Array, table: jax.Array, *,
                            pre_shifts, fan_chunks,
                            interpret: bool | None = None) -> jax.Array:
    """Launch one fused int8 chain as ONE persistent pallas_call.

    ``xq`` int8 pre-padded to the head program's buffer geometry;
    ``wf`` the stacked (w_total, rows, cols) int8 weights;
    ``bf``/``mf``/``sf`` the stacked (b_total, 1, b_max) int32 bias /
    requant-multiplier / shift rows sharing the BOFF rows;
    ``pre_shifts``/``fan_chunks`` one entry per chain node
    (``LayerQuant`` statics). Returns the final node's padded int8
    output.
    """
    if interpret is None:
        from repro.kernels.common import pallas_interpret_default
        interpret = pallas_interpret_default()
    if not gkp.quantized:
        raise ValueError("int8 graph kernel wants a program lowered "
                         "with quantized=True (flat weight offsets use "
                         "the natural grouped layout)")
    h0, kl = gkp.nodes[0].kp, gkp.out_kp
    B = xq.shape[0]
    (rows, b_max), geos = stacked_shapes(gkp)
    for spec in gkp.nodes:
        kp = spec.kp
        g = kp.wave.program
        l = g.layer
        if l.groups > 1 and (kp.n_chain != 1 or g.out_c_pad != l.out_c):
            raise ValueError(
                f"{l.name}: grouped int8 kernel expects a single-step "
                f"chain over the full out_c (got n_chain={kp.n_chain}, "
                f"out_c_pad={g.out_c_pad})")
    if xq.dtype != jnp.int8 or wf.dtype != jnp.int8:
        raise ValueError(f"int8 graph kernel operands must be int8 "
                         f"(got x {xq.dtype}, w {wf.dtype})")
    if xq.shape != (B, h0.pad_h, h0.pad_w, h0.in_c_kpad):
        raise ValueError(
            f"int8 graph kernel input {xq.shape} != padded "
            f"({B}, {h0.pad_h}, {h0.pad_w}, {h0.in_c_kpad})")
    if wf.shape != (gkp.w_total, rows, gkp.b_max):
        raise ValueError(f"stacked weights {wf.shape} != "
                         f"({gkp.w_total}, {rows}, {gkp.b_max})")
    for name, arr in (("bias_q", bf), ("m", mf), ("shift", sf)):
        if arr.shape != (gkp.b_total, 1, b_max) or arr.dtype != jnp.int32:
            raise ValueError(f"{name} must be int32 ({gkp.b_total}, 1, "
                             f"{b_max}), got {arr.dtype} {arr.shape}")
    if table.shape != (gkp.total_steps, GRAPH_OP_COLS):
        raise ValueError(
            f"graph table {table.shape} != "
            f"({gkp.total_steps}, {GRAPH_OP_COLS})")
    if len(pre_shifts) != len(gkp.nodes) \
            or len(fan_chunks) != len(gkp.nodes):
        raise ValueError("pre_shifts/fan_chunks must have one entry "
                         "per chain node")

    quants = []
    for spec, ps, fc in zip(gkp.nodes, pre_shifts, fan_chunks):
        l = spec.kp.wave.program.layer
        step_in_c = l.in_c // l.groups if l.groups > 1 \
            else spec.kp.c_width
        quants.append((int(ps), exact_channel_chunk(l.kernel) if fc is None
                       else max(1, min(int(fc), step_in_c))))

    # batch as the outermost grid axis (ISSUE 8): ragged batches are
    # zero-padded to whole blocks — int8 zero images quantize and
    # accumulate to exact integer zeros, so real rows are untouched —
    # and cropped on return
    n_bb, bb = batch_grid(B, gkp.batch_block)
    if n_bb * bb != B:
        xq = jnp.pad(xq, ((0, n_bb * bb - B), (0, 0), (0, 0), (0, 0)))
    xq = space_to_depth(xq, geos[0].s2d)
    vec_spec = pl.BlockSpec((1, 1, b_max),
                            lambda bi, t, tbl: (tbl[t, GOP_BOFF], 0, 0))
    out_block = (bb, kl.blk_h, kl.out_w_pad, kl.out_c_pad)
    vmem = graph_kernel_vmem(gkp, bb)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_bb, gkp.total_steps),
        in_specs=[x_block_spec(gkp, bb),
                  pl.BlockSpec((1, rows, gkp.b_max),
                               lambda bi, t, tbl: (tbl[t, GOP_WOFF], 0, 0)),
                  vec_spec, vec_spec, vec_spec],
        out_specs=pl.BlockSpec(
            out_block, lambda bi, t, tbl: (bi, tbl[t, GOP_OY], 0, 0)),
        # int8-valued arena + the shared int32 psum bank and staging
        scratch_shapes=[pltpu.VMEM(sh, dt) for sh, dt in vmem.scratch],
    )
    yq = pl.pallas_call(
        functools.partial(graph_replay_kernel, gkp=gkp, geos=geos,
                          quants=tuple(quants)),
        out_shape=jax.ShapeDtypeStruct(
            (n_bb * bb, kl.out_h_pad, kl.out_w_pad, kl.out_c_pad),
            jnp.int8),
        grid_spec=grid_spec,
        compiler_params=vmem.compiler_params(gkp.nodes[0].name, interpret),
        interpret=interpret,
    )(table, xq, wf, bf, mf, sf)
    return yq[:B] if n_bb * bb != B else yq


def pack_graph_operands_q(gkp: GraphKernelProgram, qops):
    """(wq, bq, m, shift) per chain node -> stacked int8/int32 buffers.

    Weights keep the per-layer kernel's layout: natural per-group fan
    for grouped nodes (whole tensor = the single step's row), chain
    chunk fan slices for ungrouped ones. Padded output channels carry
    m=0 / shift=31 so their requantized lanes are exact zeros — same as
    ``pad_operands_q``.
    """
    if len(qops) != len(gkp.nodes):
        raise ValueError(f"{len(qops)} quantized operand tuples for "
                         f"{len(gkp.nodes)} chain nodes")
    bvecs, mvecs, svecs = [], [], []
    for spec, (_, bq, m, shift) in zip(gkp.nodes, qops):
        pad_c = spec.kp.out_c_pad - spec.kp.wave.program.layer.out_c
        bvecs.append(jnp.pad(bq.astype(jnp.int32), (0, pad_c)))
        mvecs.append(jnp.pad(m.astype(jnp.int32), (0, pad_c)))
        svecs.append(jnp.pad(shift.astype(jnp.int32), (0, pad_c),
                             constant_values=31))
    return (stack_weight_rows(gkp, [q[0] for q in qops], jnp.int8),
            stack_vector_rows(gkp, bvecs), stack_vector_rows(gkp, mvecs),
            stack_vector_rows(gkp, svecs, fill=31))


def wave_replay_graph_q(gkp: GraphKernelProgram, xq: jax.Array, qops,
                        *, pre_shifts, fan_chunks,
                        table: jax.Array | None = None,
                        interpret: bool | None = None) -> jax.Array:
    """Execute a fused int8 conv chain as ONE persistent pallas_call.

    ``xq`` (B, in_h, in_w, in_c) int8 at the head's calibrated input
    scale; ``qops`` one (wq, bq, m, shift) tuple per chain node;
    ``pre_shifts``/``fan_chunks`` the matching ``LayerQuant`` statics.
    Returns the final node's valid int8 output — bit-identical to the
    per-layer int8 megakernel run node by node.
    """
    # one launch for the whole chain, attributed to the head node
    with _ops.launches.record(gkp.nodes[0].name, "graphkernel"):
        if table is None:
            table = jnp.asarray(gkp.operand_table())
        xp = pad_input(gkp.nodes[0].kp, xq)
        wf, bf, mf, sf = pack_graph_operands_q(gkp, qops)
        y = wave_replay_graph_q_raw(gkp, xp, wf, bf, mf, sf, table,
                                    pre_shifts=pre_shifts,
                                    fan_chunks=fan_chunks,
                                    interpret=interpret)
    kl = gkp.out_kp
    return y[:, :kl.out_h, :kl.out_w, :gkp.out_layer.out_c]
