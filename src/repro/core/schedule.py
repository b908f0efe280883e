"""Static tile schedule — the command-decoder instruction stream in software.

The paper's accelerator (§3) owes its throughput to a *static* schedule:
the command decoder replays a fixed list of DMA + compute instructions
per layer, so the CU array never waits on control flow. This module is
the JAX analogue: it lowers a decomposition ``Plan`` (paper §5) into a
flat, array-encoded ``TileProgram`` whose per-step operands (input-window
offsets, output offsets, channel-group offsets) can be scanned by a
``lax.scan`` executor under ``jax.jit`` — one trace, zero per-tile Python.

Regularisation: ``lax.dynamic_slice`` needs static slice *sizes*, so the
program pads the (conv-padded) input and the output to a uniform tile
grid and pads channels up to whole groups. Every step then moves blocks
of identical shape — exactly the property that lets the paper's DMA
engine double-buffer (DESIGN.md §2). Padding is zeros, which contribute
exact 0.0 to every accumulation, so results match the ragged-tile
interpreter bit for bit; the executor crops the padding off at the end.

Instruction encoding (one row of ``operands()`` per step, int32):
  [iy, ix,  oy, ox,  c0, wc0, f0]
   input win  out tile  in-ch  weight-in-ch  out-ch offsets
Steps are ordered tile-major, feature-group middle, in-channel-group
innermost — the same walk as the interpreted executor, so partial-sum
accumulation order (and therefore rounding) is identical.
"""
from __future__ import annotations

import dataclasses
import math
from typing import List, Optional, Sequence, Tuple

import numpy as np

from repro.core.decomposition import ConvLayer, Plan, _ceil_div
# the lowering/validation sites raise the runtime's typed taxonomy
# (each a ValueError subclass — pre-taxonomy callers are unaffected) so
# the fallback chain can attribute failures to a pipeline stage
from repro.runtime.errors import LoweringError, PlanError


@dataclasses.dataclass(frozen=True)
class TileProgram:
    """A lowered, fully static schedule for one CONV layer.

    All geometry fields are Python ints (shape-static under jit); the
    per-step operand arrays live in ``steps`` as a host-side numpy array
    and are fed to the executor as a traced ``(n_steps, 7)`` int32 input,
    so one compiled executable can in principle replay any schedule of
    identical geometry.
    """
    layer: ConvLayer
    plan: Plan
    # padded-buffer geometry (static under jit)
    pad_h: int              # padded input height (conv pad + tile pad)
    pad_w: int
    in_c_pad: int           # input channels incl. group-rounding zeros
    w_in_pad: int           # weight fan-in dim incl. rounding zeros
    out_h_pad: int          # uniform-tile output height
    out_w_pad: int
    out_c_pad: int
    # per-step block shapes (static under jit)
    ih: int                 # input window rows (halo-inclusive)
    iw: int
    cg: int                 # input channels read per step
    fan: int                # weight fan-in per step
    fg: int                 # output channels written per step
    oh: int                 # output tile rows
    ow: int
    gcount: int             # feature_group_count of the per-step conv
    # the instruction stream
    steps: Tuple[Tuple[int, int, int, int, int, int, int], ...]

    @property
    def n_steps(self) -> int:
        return len(self.steps)

    def operands(self) -> np.ndarray:
        """(n_steps, 7) int32 operand table for the scan executor."""
        return np.asarray(self.steps, np.int32)

    @property
    def geometry(self):
        """Hashable key of everything baked into the compiled executable."""
        return (self.layer, self.plan.tiles_h, self.plan.tiles_w,
                self.plan.feat_splits, self.plan.in_splits,
                self.pad_h, self.pad_w, self.in_c_pad, self.w_in_pad,
                self.out_h_pad, self.out_w_pad, self.out_c_pad,
                self.ih, self.iw, self.cg, self.fan, self.fg,
                self.oh, self.ow, self.gcount, self.n_steps)

    def describe(self) -> str:
        l = self.layer
        return (f"{l.name}: {self.n_steps} steps, "
                f"in-win {self.ih}x{self.iw}x{self.cg}, "
                f"out-tile {self.oh}x{self.ow}x{self.fg}, "
                f"weights {l.kernel}x{l.kernel}x{self.fan}x{self.fg}")


def compile_layer(layer: ConvLayer, plan: Plan) -> TileProgram:
    """Lower a Plan to a TileProgram (the §3 instruction stream).

    Mirrors the interpreted executor's channel-group rules exactly:
      * groups == 1: input channels split into ``in_splits`` groups of
        ``cg`` (partial sums), features into ``feat_splits`` groups;
      * groups > 1, feat_splits > 1: each feature group lies inside one
        conv group (planner-aligned) and reads only that group's inputs;
      * groups > 1, feat_splits == 1: one grouped conv per tile
        (``gcount = groups``), no channel slicing.
    """
    l = layer
    oth = _ceil_div(l.out_h, plan.tiles_h)
    otw = _ceil_div(l.out_w, plan.tiles_w)
    out_h_pad = plan.tiles_h * oth
    out_w_pad = plan.tiles_w * otw
    ih = (oth - 1) * l.stride + l.kernel
    iw = (otw - 1) * l.stride + l.kernel
    pad_h = (out_h_pad - 1) * l.stride + l.kernel
    pad_w = (out_w_pad - 1) * l.stride + l.kernel

    in_per_group = l.in_c // l.groups
    out_per_group = l.out_c // l.groups
    if l.groups == 1:
        cg = _ceil_div(l.in_c, plan.in_splits)
        fg = _ceil_div(l.out_c, plan.feat_splits)
        in_c_pad = plan.in_splits * cg
        out_c_pad = plan.feat_splits * fg
        w_in_pad = in_c_pad
        fan, gcount = cg, 1
        chan_steps = [(c * cg, c * cg) for c in range(plan.in_splits)]
    elif plan.feat_splits > 1:
        # planner guarantees in_splits == 1 and feat alignment with groups
        if l.out_c % plan.feat_splits or plan.feat_splits % l.groups:
            raise PlanError(
                f"{l.name}: feat_splits={plan.feat_splits} does not align "
                f"with groups={l.groups}")
        cg = fan = in_per_group
        fg = l.out_c // plan.feat_splits
        in_c_pad, out_c_pad, w_in_pad = l.in_c, l.out_c, in_per_group
        gcount = 1
        chan_steps = None  # c0 depends on the feature group, filled below
    else:
        cg, fan, fg = l.in_c, in_per_group, l.out_c
        in_c_pad, out_c_pad, w_in_pad = l.in_c, l.out_c, in_per_group
        gcount = l.groups
        chan_steps = [(0, 0)]

    steps = []
    for ty in range(plan.tiles_h):
        for tx in range(plan.tiles_w):
            oy, ox = ty * oth, tx * otw
            iy, ix = oy * l.stride, ox * l.stride
            for f in range(plan.feat_splits):
                f0 = f * fg
                if chan_steps is not None:
                    groups_of_f = chan_steps
                else:
                    g = f0 // out_per_group
                    groups_of_f = [(g * in_per_group, 0)]
                for c0, wc0 in groups_of_f:
                    steps.append((iy, ix, oy, ox, c0, wc0, f0))

    return TileProgram(
        layer=l, plan=plan, pad_h=pad_h, pad_w=pad_w,
        in_c_pad=in_c_pad, w_in_pad=w_in_pad,
        out_h_pad=out_h_pad, out_w_pad=out_w_pad, out_c_pad=out_c_pad,
        ih=ih, iw=iw, cg=cg, fan=fan, fg=fg, oh=oth, ow=otw,
        gcount=gcount, steps=tuple(steps))


def compile_network(layers: Sequence[ConvLayer],
                    plans: Sequence[Plan]) -> List[TileProgram]:
    """Lower a whole conv stack — one instruction stream per layer."""
    if len(layers) != len(plans):
        raise ValueError("layers and plans must pair up")
    return [compile_layer(l, p) for l, p in zip(layers, plans)]


# ---------------------------------------------------------------------------
# Wave partitioning — dependency-free dispatch groups (ISSUE 2 tentpole)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class WaveProgram:
    """A TileProgram re-cut into dependency-free *waves*.

    Two steps of a TileProgram depend on each other only when they write
    the same output block (a partial-sum chain over in-channel groups);
    steps with distinct ``(oy, ox, f0)`` are independent — the paper's
    observation that independent tiles can keep every CU busy while DMA
    double-buffers (§3). Wave ``k`` holds the ``k``-th step of every
    chain, so within a wave all output blocks are distinct and the wave
    can be dispatched as ONE batched conv; chains still accumulate in
    their original order across waves, so rounding matches the serial
    replay bit for bit.

    ``compile_layer`` orders steps tile-major / feature-middle /
    in-channel-innermost with equal-length chains, which makes every
    wave (a) the same size, (b) an exact raster tiling of the padded
    output, and (c) single-sourced per wave: every step of a wave reads
    the same input-channel group, so the wave's feature axis collapses
    into the conv's output-channel width and its tile axis into the
    batch axis — ONE ordinary (or ``groups``-grouped) conv per wave,
    encoded by ``tile_operands()``. ``partition_waves`` verifies all
    three; the wave executor's static reassembly (transpose instead of
    scatter) relies on them.
    """
    program: TileProgram
    n_waves: int            # == chain length (in_splits for ungrouped)
    wave_size: int          # steps per wave (tiles * feature groups)
    waves: Tuple[Tuple[Tuple[int, int, int, int, int, int, int], ...], ...]
    # per-wave, per-tile dispatch rows [iy, ix, oy, ox, c0, wc0]; the
    # feature axis is folded into the conv's output-channel width
    tile_waves: Tuple[Tuple[Tuple[int, int, int, int, int, int], ...], ...]
    # channel geometry of one wave dispatch (static under jit)
    c_width: int            # input channels read per dispatch
    fan_width: int          # weight fan-in sliced per dispatch
    dispatch_groups: int    # feature_group_count of the wave conv

    @property
    def n_tiles(self) -> int:
        return len(self.tile_waves[0])

    def operands(self) -> np.ndarray:
        """(n_waves, wave_size, 7) int32 step table (analysis/tests)."""
        return np.asarray(self.waves, np.int32)

    def tile_operands(self) -> np.ndarray:
        """(n_waves, n_tiles, 6) int32 dispatch table for the executor."""
        return np.asarray(self.tile_waves, np.int32)

    @property
    def geometry(self):
        return self.program.geometry + ("wave", self.n_waves,
                                        self.wave_size, self.c_width,
                                        self.fan_width, self.dispatch_groups)

    def describe(self) -> str:
        return (f"{self.program.layer.name}: {self.n_waves} wave(s) x "
                f"{self.n_tiles} tiles "
                f"({self.program.n_steps} serial steps fused)")


def partition_waves(program: TileProgram) -> WaveProgram:
    """Cut a TileProgram's step stream into dependency-free waves.

    A step's wave index is its position within its output-block chain
    (the number of earlier steps writing the same ``(oy, ox, f0)``), so
    by construction no wave contains two writers of one block and
    cross-wave order preserves every chain's accumulation order.
    """
    chain_pos: dict = {}
    waves: List[List[tuple]] = []
    for s in program.steps:
        key = (s[2], s[3], s[6])            # (oy, ox, f0)
        k = chain_pos.get(key, 0)
        chain_pos[key] = k + 1
        if k == len(waves):
            waves.append([])
        waves[k].append(s)

    sizes = {len(w) for w in waves}
    if len(sizes) > 1:
        raise LoweringError(
            f"{program.layer.name}: ragged waves {sorted(sizes)} — "
            f"chains of unequal length cannot batch into one dispatch")

    l = program.layer
    grouped = l.groups > 1
    tile_waves = []
    for k, wave in enumerate(waves):
        rows, seen = [], set()
        for s in wave:
            tile = (s[0], s[1], s[2], s[3])     # (iy, ix, oy, ox)
            if tile in seen:
                continue
            seen.add(tile)
            # grouped layers read the full channel width per dispatch
            # (the conv group structure routes each feature to its
            # inputs); ungrouped layers read this wave's channel group
            rows.append(tile + ((0, 0) if grouped else (s[4], s[5])))
        tile_waves.append(tuple(rows))

    wp = WaveProgram(
        program=program, n_waves=len(waves), wave_size=len(waves[0]),
        waves=tuple(tuple(w) for w in waves),
        tile_waves=tuple(tile_waves),
        c_width=program.in_c_pad if grouped else program.cg,
        fan_width=program.w_in_pad if grouped else program.cg,
        dispatch_groups=l.groups)
    validate_waves(wp)
    return wp


def validate_waves(wp: WaveProgram) -> None:
    """Check the invariants the wave executor's fused dispatch bakes in.

    1. No wave co-schedules two steps writing the same output block
       (independence — the property tests exercise this directly).
    2. Every wave lists blocks in raster order (tile-major, feature
       innermost) and exactly tiles the padded output, so stacked conv
       results reassemble by reshape/transpose with no scatter.
    3. Ungrouped layers: all steps of a wave read one input-channel
       group, so the feature axis can fold into the conv's output
       channels (grouped layers instead read the full width and let
       ``feature_group_count`` route features to their inputs).
    4. Tile windows are wave-invariant: wave ``k``'s dispatch rows name
       the same ``(iy, ix, oy, ox)`` windows (in the same order) as wave
       0 — only the channel offsets change along a chain. The wave
       executor's hoisted gather (slice each unique window once, then
       slice channels per wave) and the megakernel's per-tile operand
       columns both bake this in.
    """
    g, plan = wp.program, wp.program.plan
    expect = [(ty * g.oh, tx * g.ow, f * g.fg)
              for ty in range(plan.tiles_h)
              for tx in range(plan.tiles_w)
              for f in range(plan.feat_splits)]
    for k, wave in enumerate(wp.waves):
        blocks = [(s[2], s[3], s[6]) for s in wave]
        if len(set(blocks)) != len(blocks):
            dupes = {b for b in blocks if blocks.count(b) > 1}
            raise LoweringError(
                f"{g.layer.name} wave {k}: output blocks written twice "
                f"within one wave: {sorted(dupes)}")
        if blocks != expect:
            raise LoweringError(
                f"{g.layer.name} wave {k}: blocks deviate from the "
                f"raster tiling the batched reassembly assumes")
        if g.layer.groups == 1:
            chans = {(s[4], s[5]) for s in wave}
            if len(chans) != 1:
                raise LoweringError(
                    f"{g.layer.name} wave {k}: mixed input-channel "
                    f"groups {sorted(chans)} cannot fuse into one "
                    f"dispatch")
        tiles = [r[:4] for r in wp.tile_waves[k]]
        if tiles != [r[:4] for r in wp.tile_waves[0]]:
            raise LoweringError(
                f"{g.layer.name} wave {k}: tile windows differ from "
                f"wave 0 — the once-per-window gather and the "
                f"megakernel operand tables assume wave-invariant "
                f"windows")


def compile_layer_waves(layer: ConvLayer, plan: Plan) -> WaveProgram:
    """Lower straight to the wave-parallel form."""
    return partition_waves(compile_layer(layer, plan))


# ---------------------------------------------------------------------------
# Megakernel lowering — WaveProgram -> KernelProgram (ISSUE 3 tentpole)
# ---------------------------------------------------------------------------

# operand-table column layout (one row per (chain step, tile), int32):
#   IY, IX   input-window origin, elements into the padded input buffer
#   TY, TX   output block index (blocked: multiplied by the block shape)
#   C0, WC0  input-channel / weight fan-in offsets of the step's chunk
#   VR, VC   write mask: valid rows/cols of this tile's output block
KERNEL_OP_COLS = 8
(OP_IY, OP_IX, OP_TY, OP_TX, OP_C0, OP_WC0, OP_VR, OP_VC) = range(8)

# Default VMEM budget for chain coarsening and megakernel re-planning,
# in element bytes as the planner counts them (``plan_bytes``). It is a
# planning choice, not a device size: each launch states its own
# scoped-VMEM limit from what it holds at the tiled layout
# (``vmem_bytes``).
DEFAULT_VMEM_BUDGET = 8 * 2 ** 20
# lanes of a TPU vector register: the last dim of a kernel block is a
# multiple of this or the whole array dim
LANE_TILE = 128


@dataclasses.dataclass(frozen=True)
class KernelProgram:
    """A WaveProgram lowered for the persistent Pallas megakernel.

    The whole layer becomes ONE ``pallas_call`` whose grid iterates
    (tile, wave): the wave (in-channel-group) axis is innermost, so a
    VMEM scratch accumulator plays the paper's partial-sum SRAM bank —
    it is zeroed when a tile's chain starts (wave 0) and carried across
    the chain with **zero HBM round-trips**; the epilogue (bias, then an
    optional residual add, channel LayerNorm, activation and fused
    max-pool, then a masked write) runs on the last wave
    (kernels/wave_replay). The operand ``table`` is the §3 command
    stream: a static int32 array prefetched to SMEM whose rows steer
    every DMA (window origin, channel-group offsets, output block index,
    write mask) — index maps read it, never the tensor data.

    The grid is rectangular by construction: ``partition_waves``
    guarantees equal-size waves with wave-invariant tile windows
    (``validate_waves`` invariant 4), so the table is a dense
    ``(n_chain, n_tiles, 8)`` block with no ragged padding rows.

    Chain coarsening: the plan's ``in_splits`` was sized for the paper's
    128 KB SRAM, but the megakernel's scratch is real VMEM (~16 MB), so
    the lowering re-runs the planner's budget math at the kernel's
    budget point (DESIGN.md §6) and folds ``chain_chunk`` consecutive
    schedule waves into each grid step — the CU array's Tn-wide
    input-channel parallelism, in software. Chunks accumulate in chain
    order; within a chunk the reduction happens inside one im2col
    matmul, so coarsened outputs match the serial replay to fp32
    tolerance rather than bit-exactly (``vmem_budget=None`` disables
    coarsening for 1:1 replays).

    With ``fuse_pool`` the tile geometry is re-derived over the *pooled*
    output (the fused_conv_pool trick): each tile's accumulator covers
    exactly the conv rows its pooled rows need (``acc = (blk-1)*ps +
    pool``), re-computing the (pool - stride)-row overlap between
    adjacent tiles instead of exchanging it — the conv->pool
    intermediate never exists outside VMEM.
    """
    wave: WaveProgram
    act: Optional[str]      # epilogue activation: "relu" | "gelu" | None
    fuse_pool: bool
    # residual epilogue (ISSUE 5): the kernel takes one extra operand —
    # a pre-computed activation of the layer's OWN output geometry —
    # and adds it to the accumulator right after bias, before ReLU: the
    # paper's accumulation-SRAM add. Mutually exclusive with fuse_pool
    # (pooling a pre-add activation would change shapes under the add).
    residual: bool
    # padded input-buffer geometry (static under jit)
    pad_h: int
    pad_w: int
    in_c_kpad: int          # input channels incl. chain-chunk rounding
    w_in_kpad: int          # weight fan-in incl. chain-chunk rounding
    # per-grid-step block geometry
    ih: int                 # input-window rows (halo-inclusive)
    iw: int
    acc_h: int              # conv rows accumulated per tile (VMEM scratch)
    acc_w: int
    blk_h: int              # output block per tile (pooled if fuse_pool)
    blk_w: int
    c_width: int            # input channels read per step
    fan_width: int          # weight fan-in sliced per step
    out_c_pad: int
    groups: int             # conv groups executed inside the kernel body
    pool: int               # epilogue pool window (1 = bias/ReLU only)
    pool_stride: int
    # valid (cropped) output dims
    out_h: int
    out_w: int
    chain_chunk: int        # schedule waves folded per grid step
    n_chain: int            # grid steps per tile chain
    n_tiles: int
    table: Tuple[Tuple[Tuple[int, ...], ...], ...]
    # batch axis as a first-class grid dimension (ISSUE 8): images
    # processed per grid step. The kernel grid iterates (batch-block,
    # tile, chain); a runtime batch B launches ceil(B / batch_block)
    # batch blocks (``batch_grid``). The default 1 keeps per-image
    # working sets; batch-aware lowering raises it until the per-step
    # VMEM working set fills the budget.
    batch_block: int = 1
    # channel LayerNorm in the epilogue, after the residual add and
    # before the activation: every output channel of a pixel lives in
    # the step's accumulator (the feature axis folds into the matmul
    # width), so the mean and variance are taken in VMEM, in fp32
    norm: bool = False

    @property
    def relu(self) -> bool:
        return self.act == "relu"

    def operand_table(self) -> np.ndarray:
        """(n_chain, n_tiles, 8) int32 SMEM operand table."""
        return np.asarray(self.table, np.int32)

    @property
    def tiles_h(self) -> int:
        return self.wave.program.plan.tiles_h

    @property
    def tiles_w(self) -> int:
        return self.wave.program.plan.tiles_w

    @property
    def out_h_pad(self) -> int:
        return self.tiles_h * self.blk_h

    @property
    def out_w_pad(self) -> int:
        return self.tiles_w * self.blk_w

    @property
    def vmem_bytes(self) -> int:
        """VMEM the fp32 launch holds, every buffer at its tiled layout
        (``kernels/common.py::megakernel_vmem``, which also sets the
        launch's scoped-VMEM limit)."""
        from repro.kernels.common import megakernel_vmem
        return megakernel_vmem(self).bytes

    @property
    def plan_bytes(self) -> int:
        """The planner's per-grid-step working-set model, in element
        bytes: ``batch_block`` images' accumulators + input-window
        chunks (+ residual blocks when the epilogue adds them) plus the
        batch-shared weight chunk, and where the fp32 launch folds the
        taps (``kernels/common.py::tap_fold_factor``) the batch-shared
        row operand of q * q * c_eff channels — what ``vmem_budget``
        bounds. It leaves out the lane padding and double buffering that
        ``vmem_bytes`` counts."""
        from repro.kernels.common import tap_fold_factor
        l = self.wave.program.layer
        q = tap_fold_factor(l, self.c_width, self.n_chain)
        row = (self.acc_w * q * q * self.c_width * l.stride ** 2
               if q > 1 else 0)
        return 4 * (self.batch_block
                    * (self.acc_h * self.acc_w * self.out_c_pad
                       + self.ih * self.iw * self.c_width
                       + (self.blk_h * self.blk_w * self.out_c_pad
                          if self.residual else 0))
                    + l.kernel * l.kernel * self.fan_width
                    * self.out_c_pad + row)

    @property
    def geometry(self):
        """The table is a pure function of these, so they key the cache."""
        return self.wave.geometry + (
            "megakernel", self.act, self.norm, self.fuse_pool,
            self.residual,
            self.pad_h, self.pad_w,
            self.in_c_kpad, self.w_in_kpad,
            self.ih, self.iw, self.acc_h, self.acc_w, self.blk_h, self.blk_w,
            self.c_width, self.fan_width, self.out_c_pad, self.groups,
            self.pool, self.pool_stride, self.out_h, self.out_w,
            self.chain_chunk, self.n_chain, self.batch_block)

    def describe(self) -> str:
        l = self.wave.program.layer
        fused = f"+pool{self.pool}/{self.pool_stride}" if self.fuse_pool \
            else ""
        fused += "+residual" if self.residual else ""
        fused += "+norm" if self.norm else ""
        fused += f"+{self.act}" if self.act else ""
        chunk = f" (x{self.chain_chunk} waves/step)" \
            if self.chain_chunk > 1 else ""
        chunk += f" x{self.batch_block} imgs/step" \
            if self.batch_block > 1 else ""
        return (f"{l.name}: 1 pallas_call, grid {self.n_tiles}x"
                f"{self.n_chain} (tile x chain{chunk}), acc {self.acc_h}x"
                f"{self.acc_w}x{self.out_c_pad} VMEM"
                f"{fused}, table {self.n_chain}x{self.n_tiles}x"
                f"{KERNEL_OP_COLS} SMEM")


def batch_grid(batch: int, batch_block: int) -> Tuple[int, int]:
    """Split a runtime batch into ``(n_blocks, block)`` grid factors.

    The kernels iterate the batch axis as their outermost grid
    dimension in blocks of ``block = min(batch_block, batch)`` images;
    ragged batches are zero-padded up to ``n_blocks * block`` by the
    launchers (zero images convolve to exact zeros) and cropped on
    return. Per-image independence of the im2col matmul rows makes the
    split invisible numerically — only VMEM footprint and launch count
    change.
    """
    if batch < 1:
        raise ValueError(f"batch {batch} < 1")
    bb = max(1, min(int(batch_block), batch))
    return _ceil_div(batch, bb), bb


def lower_kernel_program(
        wprog: WaveProgram, *, act: Optional[str] = None,
        fuse_pool: bool = False, residual: bool = False,
        norm: bool = False,
        vmem_budget: "int | None" = DEFAULT_VMEM_BUDGET,
        batch_block: int = 1) -> KernelProgram:
    """Lower a WaveProgram to the megakernel's static operand tables.

    ``act`` ("relu", "gelu" or None) bakes the activation into the
    epilogue; ``norm`` a channel LayerNorm before it (after any
    residual add; incompatible with ``fuse_pool``); ``fuse_pool`` additionally
    max-pools the accumulator in VMEM (requires ``layer.pool > 1``) and
    re-derives the tile grid over the pooled output. ``residual`` adds
    an extra same-geometry operand to the accumulator after bias and
    before ReLU (the residual accumulation-buffer add; incompatible
    with ``fuse_pool``). ``vmem_budget`` bounds the per-step VMEM
    working set (accumulator + input-window chunk + weight chunk, fp32)
    used to coarsen long partial-sum chains; ``None`` keeps the
    schedule's 1:1 wave chain (bit-faithful replay). ``batch_block``
    asks for that many images per grid step (ISSUE 8); it is clamped so
    a single-wave step still fits the budget — the batch-scaled terms
    (accumulator, input window, residual block) are per image, the
    weight chunk is shared.
    """
    g = wprog.program
    l, plan = g.layer, g.plan
    if fuse_pool and l.pool <= 1:
        raise LoweringError(f"{l.name}: fuse_pool on a layer without a pool")
    if residual and fuse_pool:
        raise LoweringError(
            f"{l.name}: residual add cannot fuse with the pool epilogue "
            f"— the add runs on the conv-geometry accumulator")
    if act not in (None, "relu", "gelu"):
        raise LoweringError(f"{l.name}: unknown epilogue activation "
                            f"{act!r}")
    if norm and (fuse_pool or g.out_c_pad < l.out_c):
        # the kernel's grid has no feature axis, so a step always holds
        # out_c_pad >= out_c channels; a lowering that split them could
        # not take a pixel's mean in one epilogue
        raise LoweringError(
            f"{l.name}: the channel norm needs every output channel of a "
            f"pixel in one step, unpooled")

    if fuse_pool:
        ps = l.pool_stride or l.pool
        if l.pooled_h < 1 or l.pooled_w < 1:
            raise LoweringError(
                f"{l.name}: conv output {l.out_h}x{l.out_w} smaller than "
                f"pool {l.pool}")
        blk_h = _ceil_div(l.pooled_h, plan.tiles_h)
        blk_w = _ceil_div(l.pooled_w, plan.tiles_w)
        acc_h = (blk_h - 1) * ps + l.pool
        acc_w = (blk_w - 1) * ps + l.pool
        ih = (acc_h - 1) * l.stride + l.kernel
        iw = (acc_w - 1) * l.stride + l.kernel
        pad_h = (plan.tiles_h - 1) * blk_h * ps * l.stride + ih
        pad_w = (plan.tiles_w - 1) * blk_w * ps * l.stride + iw
        out_h, out_w = l.pooled_h, l.pooled_w
        pool = l.pool
    else:
        ps, pool = 1, 1
        blk_h = acc_h = g.oh
        blk_w = acc_w = g.ow
        ih, iw = g.ih, g.iw
        pad_h, pad_w = g.pad_h, g.pad_w
        out_h, out_w = l.out_h, l.out_w

    # batch-block clamp: bb images per grid step must fit the budget
    # even at chunk = 1 — the weight chunk is batch-shared, everything
    # else (accumulator, input window, residual block) scales per image
    bb = max(1, int(batch_block))
    if bb > 1 and vmem_budget is not None:
        w1 = l.kernel * l.kernel * wprog.fan_width * g.out_c_pad * 4
        per_img = 4 * (acc_h * acc_w * g.out_c_pad
                       + ih * iw * wprog.c_width
                       + (blk_h * blk_w * g.out_c_pad if residual else 0))
        fit = (vmem_budget - w1) // per_img if vmem_budget > w1 else 1
        bb = max(1, min(bb, fit))

    # chain coarsening: fold `chunk` consecutive waves per grid step so
    # the per-step working set fills (but stays under) the kernel's VMEM
    # budget — the planner's feasibility math re-run at the VMEM budget
    # point. Grouped layers have single-step chains; nothing to fold.
    chunk = 1
    if wprog.n_waves > 1 and vmem_budget is not None:
        acc_bytes = bb * acc_h * acc_w * g.out_c_pad * 4
        per_wave = (bb * ih * iw * wprog.c_width * 4
                    + l.kernel * l.kernel * wprog.fan_width
                    * g.out_c_pad * 4)
        if vmem_budget > acc_bytes + per_wave:
            chunk = min(wprog.n_waves,
                        (vmem_budget - acc_bytes) // per_wave)
        chunk = max(1, chunk)
        # a step's channel block must be whole 128-lane tiles or the
        # whole padded axis (one step): a chunk a lane tile or wider
        # rounds down to whole tiles (768 one-channel waves: 257 -> 256)
        span = wprog.c_width * chunk
        if chunk < wprog.n_waves and span >= LANE_TILE \
                and span % LANE_TILE:
            step = LANE_TILE // math.gcd(LANE_TILE, wprog.c_width)
            chunk = min(wprog.n_waves, max(step, chunk // step * step))
    n_chain = _ceil_div(wprog.n_waves, chunk)
    c_width = wprog.c_width * chunk
    # ungrouped layers run one dense matmul per step, so the weight fan
    # equals the input-channel width; grouped layers keep the natural
    # per-group fan (``in_c // groups`` — the wave program's fan_width):
    # the kernel body accumulates each group's Cin/g x Cout/g slice (or
    # the depthwise MAC epilogue) without materialising the
    # block-diagonal zeros (ISSUE 10)
    fan_width = c_width if l.groups == 1 else wprog.fan_width
    # round the channel axes up to whole chunks (zeros accumulate 0.0)
    in_c_kpad = max(g.in_c_pad, n_chain * c_width) if chunk > 1 \
        else g.in_c_pad
    w_in_kpad = in_c_kpad if l.groups == 1 else wprog.fan_width

    table = []
    for j in range(n_chain):
        rows = wprog.tile_waves[j * chunk]
        c0, wc0 = rows[0][4], rows[0][5]
        step_rows = []
        i = 0
        for ty in range(plan.tiles_h):
            for tx in range(plan.tiles_w):
                if fuse_pool:
                    iy = ty * blk_h * ps * l.stride
                    ix = tx * blk_w * ps * l.stride
                else:
                    # reuse the wave rows (raster order per invariant 2/4)
                    iy, ix = rows[i][0], rows[i][1]
                    if (rows[i][2], rows[i][3]) != (ty * blk_h, tx * blk_w):
                        raise LoweringError(
                            f"{l.name}: wave {j * chunk} tile {i} out of "
                            f"raster order — cannot index a rectangular "
                            f"grid")
                vr = max(0, min(blk_h, out_h - ty * blk_h))
                vc = max(0, min(blk_w, out_w - tx * blk_w))
                step_rows.append((iy, ix, ty, tx, c0, wc0, vr, vc))
                i += 1
        table.append(tuple(step_rows))

    kp = KernelProgram(
        wave=wprog, act=act, norm=norm, fuse_pool=fuse_pool,
        residual=residual,
        pad_h=pad_h, pad_w=pad_w,
        in_c_kpad=in_c_kpad, w_in_kpad=w_in_kpad,
        ih=ih, iw=iw,
        acc_h=acc_h, acc_w=acc_w, blk_h=blk_h, blk_w=blk_w,
        c_width=c_width, fan_width=fan_width,
        out_c_pad=g.out_c_pad, groups=l.groups,
        pool=pool, pool_stride=ps, out_h=out_h, out_w=out_w,
        chain_chunk=chunk, n_chain=n_chain, n_tiles=wprog.n_tiles,
        table=tuple(table), batch_block=bb)
    validate_kernel_program(kp)
    return kp


def validate_kernel_program(kp: KernelProgram) -> None:
    """Check the invariants the persistent kernel's grid bakes in.

    1. The table is a dense rectangular (n_chain, n_tiles, 8) block and
       the chain covers every schedule wave exactly once
       (``n_chain * chain_chunk >= n_waves``, no overlap).
    2. Every input window, channel chunk, and weight slice lies inside
       the padded buffers — a stale offset would make the kernel's
       element-indexed DMA read out of bounds.
    3. Output block indices raster-tile the padded output exactly once
       per chain step, and the write masks cover the valid output
       exactly: per tile column the VR masks sum to out_h, per row VC
       to out_w.
    4. Channel offsets are constant within a step and walk the chain in
       order (step j reads chunk j — the VMEM accumulator assumes grid
       step j holds chain position j of every tile).
    """
    g = kp.wave.program
    l, plan = g.layer, g.plan
    tab = kp.operand_table()
    if tab.shape != (kp.n_chain, kp.n_tiles, KERNEL_OP_COLS):
        raise LoweringError(
            f"{l.name}: operand table {tab.shape} is not the dense "
            f"({kp.n_chain}, {kp.n_tiles}, {KERNEL_OP_COLS}) grid")
    if kp.n_chain * kp.chain_chunk < kp.wave.n_waves:
        raise LoweringError(
            f"{l.name}: {kp.n_chain} steps x chunk {kp.chain_chunk} "
            f"drop waves of the {kp.wave.n_waves}-long chain")
    expect_blocks = [(ty, tx) for ty in range(plan.tiles_h)
                     for tx in range(plan.tiles_w)]
    for j in range(kp.n_chain):
        rows = tab[j]
        if [(r[OP_TY], r[OP_TX]) for r in rows] != expect_blocks:
            raise LoweringError(
                f"{l.name} step {j}: output blocks deviate from the "
                f"raster tiling")
        c0s = {(r[OP_C0], r[OP_WC0]) for r in rows}
        if len(c0s) != 1:
            raise LoweringError(
                f"{l.name} step {j}: mixed channel offsets {sorted(c0s)}")
        if l.groups == 1 and c0s != {(j * kp.c_width, j * kp.fan_width)}:
            raise LoweringError(
                f"{l.name} step {j}: channel offsets {sorted(c0s)} break "
                f"chain order (expected chunk {j} at {j * kp.c_width})")
        if l.groups > 1 and c0s != {(0, 0)}:
            raise LoweringError(
                f"{l.name} step {j}: grouped layers read the full "
                f"channel width at offset 0, got {sorted(c0s)}")
        for r in rows:
            if not (0 <= r[OP_IY] and r[OP_IY] + kp.ih <= kp.pad_h
                    and 0 <= r[OP_IX] and r[OP_IX] + kp.iw <= kp.pad_w):
                raise LoweringError(
                    f"{l.name} step {j}: input window ({r[OP_IY]}, "
                    f"{r[OP_IX]})+({kp.ih}, {kp.iw}) outside the padded "
                    f"({kp.pad_h}, {kp.pad_w}) buffer")
            if r[OP_C0] + kp.c_width > kp.in_c_kpad:
                raise LoweringError(
                    f"{l.name} step {j}: channel offset {r[OP_C0]} + "
                    f"width {kp.c_width} exceeds {kp.in_c_kpad}")
            if r[OP_WC0] + kp.fan_width > kp.w_in_kpad:
                raise LoweringError(
                    f"{l.name} step {j}: weight fan offset {r[OP_WC0]} "
                    f"+ {kp.fan_width} exceeds {kp.w_in_kpad}")
    # masks tile the valid output exactly (step 0 suffices: masks are
    # chain-invariant by construction)
    vr_sum = sum(int(tab[0][ty * plan.tiles_w][OP_VR])
                 for ty in range(plan.tiles_h))
    vc_sum = sum(int(tab[0][tx][OP_VC]) for tx in range(plan.tiles_w))
    if vr_sum != kp.out_h or vc_sum != kp.out_w:
        raise LoweringError(
            f"{l.name}: write masks cover {vr_sum}x{vc_sum}, valid "
            f"output is {kp.out_h}x{kp.out_w}")


def compile_network_waves(layers: Sequence[ConvLayer],
                          plans: Sequence[Plan]) -> List[WaveProgram]:
    """Wave-partitioned instruction streams for a whole conv stack."""
    return [partition_waves(p) for p in compile_network(layers, plans)]


# ---------------------------------------------------------------------------
# Whole-graph persistent kernel lowering (ISSUE 6 tentpole)
# ---------------------------------------------------------------------------

# graph operand-table column layout: the per-layer 8 columns, then the
# cross-layer steering the fused kernel needs — one FLAT row per
# (node, tile, chain step), int32, prefetched to SMEM:
#   NODE, K      which chain node this step belongs to + its chain pos
#   WOFF, BOFF   rows of this step's weights / this node's bias (and
#                requant) vectors in the stacked per-step buffers
#   OY, OX       output block index for the kernel OUTPUT operand —
#                (ty, tx) on the final node's rows, pinned to (0, 0)
#                elsewhere so non-final steps touch one fixed block
GRAPH_OP_COLS = 14
(GOP_IY, GOP_IX, GOP_TY, GOP_TX, GOP_C0, GOP_WC0, GOP_VR, GOP_VC,
 GOP_NODE, GOP_K, GOP_WOFF, GOP_BOFF, GOP_OY, GOP_OX) = range(14)


@dataclasses.dataclass(frozen=True)
class ChainNodeSpec:
    """One conv node of a fused chain, as plain lowering data.

    ``kp`` is the node's ordinary per-layer KernelProgram — the graph
    kernel replays exactly its table/geometry so fused output matches
    the per-layer megakernel. ``in_value``/``out_value`` name the
    activation edges (a fused residual add's output name when the add
    rides this conv's epilogue); ``residual_value`` names the extra
    epilogue operand, or None. Value names only wire up the arena —
    they never reach the kernel body.
    """
    name: str
    kp: KernelProgram
    in_value: str
    out_value: str
    residual_value: Optional[str] = None


@dataclasses.dataclass(frozen=True)
class ArenaValue:
    """Lifetime + layout of one activation held in the VMEM arena.

    ``birth`` is the producing chain-node index (-1 = the chain input,
    written by the prologue copy), ``death`` the last node that reads
    it. ``shape`` is the (rows, cols, channels) extent the value needs
    in its slot; ``pad`` is the (row, col) origin of the valid region —
    the max conv-reader halo, so every reader finds its zero-padding
    in place instead of re-padding between layers.
    """
    name: str
    birth: int
    death: int
    shape: Tuple[int, int, int]
    pad: Tuple[int, int]


@dataclasses.dataclass(frozen=True)
class ArenaPlan:
    """First-fit slot assignment for the chain's live activations."""
    values: Tuple[ArenaValue, ...]
    slots: Tuple[int, ...]                       # values[i] -> slot id
    slot_shapes: Tuple[Tuple[int, int, int], ...]

    def value(self, name: str) -> ArenaValue:
        for v in self.values:
            if v.name == name:
                return v
        raise KeyError(name)

    def slot_of(self, name: str) -> int:
        for v, s in zip(self.values, self.slots):
            if v.name == name:
                return s
        raise KeyError(name)

    @property
    def slot_bytes_f32(self) -> int:
        return 4 * sum(h * w * c for h, w, c in self.slot_shapes)


def plan_arena(values: Sequence[ArenaValue]) -> ArenaPlan:
    """Assign arena slots first-fit over the liveness intervals.

    ``values`` must arrive in birth order. A slot is reusable only when
    its occupant's death is STRICTLY before the new value's birth: the
    producing node zeroes its output slot while it is still reading its
    own inputs, so a value that dies AT the producing node must keep
    its slot through that node. Slot shapes grow to the elementwise max
    of everything assigned to them.
    """
    order = [v.birth for v in values]
    if order != sorted(order):
        raise LoweringError(f"arena values out of birth order: {order}")
    slot_death: List[int] = []
    shapes: List[List[int]] = []
    assign: List[int] = []
    for v in values:
        if v.death < v.birth:
            raise LoweringError(f"{v.name}: dies ({v.death}) before "
                             f"birth ({v.birth})")
        si = next((i for i, d in enumerate(slot_death) if d < v.birth),
                  None)
        if si is None:
            si = len(slot_death)
            slot_death.append(v.death)
            shapes.append(list(v.shape))
        else:
            slot_death[si] = v.death
            shapes[si] = [max(a, b) for a, b in zip(shapes[si], v.shape)]
        assign.append(si)
    return ArenaPlan(tuple(values), tuple(assign),
                     tuple(tuple(s) for s in shapes))


def _graph_weight_chunk(kp: KernelProgram, quantized: bool) -> int:
    """Elements of flat weight one grid step consumes for this node.

    Both precisions pack weights in their natural layout: grouped
    layers' ``fan_width`` is the per-group fan (``in_c // groups``),
    and the whole tensor rides in the node's single grid step.
    """
    del quantized               # layouts agree since ISSUE 10
    l = kp.wave.program.layer
    return l.kernel * l.kernel * kp.fan_width * kp.out_c_pad


def _chain_layout(specs: Sequence[ChainNodeSpec], quantized: bool):
    """Shared arena/offset layout for lowering and cost estimation.

    Tolerates chains whose non-final values leak to outside consumers
    (the greedy partitioner costs such prefixes while growing them);
    ``lower_graph_kernel`` layers the strict checks on top.
    """
    if not specs:
        raise LoweringError("empty chain")
    input_value = specs[0].in_value
    names = [s.out_value for s in specs]
    if len(set(names)) != len(names) or input_value in names:
        raise LoweringError(f"chain value names collide: {names}")

    conv_readers: dict = {}
    res_readers: dict = {}
    for i, s in enumerate(specs):
        conv_readers.setdefault(s.in_value, []).append(i)
        if s.residual_value is not None:
            res_readers.setdefault(s.residual_value, []).append(i)

    input_in_arena = (conv_readers.get(input_value, []) != [0]
                      or input_value in res_readers)

    def _extent(name: str, birth: int) -> ArenaValue:
        convs = conv_readers.get(name, [])
        resis = res_readers.get(name, [])
        pad = max((specs[i].kp.wave.program.layer.pad for i in convs),
                  default=0)
        hs, ws, cs = [], [], []
        if birth >= 0:
            pkp = specs[birth].kp
            hs.append(pad + pkp.out_h_pad)
            ws.append(pad + pkp.out_w_pad)
            cs.append(specs[birth].kp.wave.program.layer.out_c)
        else:                       # the chain input, copied in whole
            hkp = specs[0].kp
            hpad = specs[0].kp.wave.program.layer.pad
            hs.append(pad - hpad + hkp.pad_h)
            ws.append(pad - hpad + hkp.pad_w)
            cs.append(hkp.in_c_kpad)
        for i in convs:
            rkp = specs[i].kp
            rpad = specs[i].kp.wave.program.layer.pad
            hs.append(pad - rpad + rkp.pad_h)
            ws.append(pad - rpad + rkp.pad_w)
            cs.append(rkp.in_c_kpad)
        for i in resis:
            rkp = specs[i].kp
            hs.append(pad + rkp.out_h_pad)
            ws.append(pad + rkp.out_w_pad)
            cs.append(rkp.out_c_pad)
        death = max(convs + resis, default=max(birth, 0))
        return ArenaValue(name, birth, death,
                          (max(hs), max(ws), max(cs)), (pad, pad))

    vals: List[ArenaValue] = []
    if input_in_arena:
        vals.append(_extent(input_value, -1))
    for i, s in enumerate(specs[:-1]):      # final value goes to o_ref
        vals.append(_extent(s.out_value, i))
    arena = plan_arena(vals)

    # the weights stack one row per (node, chain step) and the bias /
    # requant vectors one row per node: WOFF/BOFF are row indices, so
    # every per-step fetch is one whole row of a stacked buffer
    w_chunks = tuple(_graph_weight_chunk(s.kp, quantized) for s in specs)
    w_offsets, off = [], 0
    for s in specs:
        w_offsets.append(off)
        off += s.kp.n_chain
    w_max = max(w_chunks)
    w_total = off
    b_offsets = tuple(range(len(specs)))
    b_max = max(s.kp.out_c_pad for s in specs)
    b_total = len(specs)

    steps, lo = [], 0
    for s in specs:
        steps.append(lo)
        lo += s.kp.n_tiles * s.kp.n_chain
    return (input_value, input_in_arena, arena,
            w_chunks, tuple(w_offsets), w_max, w_total,
            tuple(b_offsets), b_max, b_total, tuple(steps), lo)


@dataclasses.dataclass(frozen=True)
class GraphKernelProgram:
    """A fused chain of KernelPrograms lowered for ONE pallas_call.

    The per-layer megakernel already keeps each layer's partial-sum
    chain in VMEM; this is the next rung of the paper's streaming
    hierarchy — Du et al.'s layer-sequencing controller in software.
    The grid becomes the concatenation of every node's (tile, chain)
    steps (chain innermost per tile, preserving each node's
    accumulation order bit-for-bit), the operand table grows NODE/K
    dispatch and flat-buffer offset columns, and inter-layer
    activations never leave VMEM: each liveness interval from the
    chain is assigned a scratch-arena slot (`plan_arena`), producers
    write their masked epilogue blocks into their slot at the value's
    layout pad, and consumers window it back out — residual operands
    included, replacing the per-layer path's pad_residual round-trip.

    Weights/bias/requant vectors for the whole chain ride in stacked
    operands, one row per (node, chain step) / per node; each grid step
    DMAs only its own row (the table's WOFF/BOFF), so per-step VMEM
    stays bounded by the largest single step, not the whole chain.
    """
    nodes: Tuple[ChainNodeSpec, ...]
    input_value: str
    input_in_arena: bool
    quantized: bool
    arena: ArenaPlan
    node_steps: Tuple[int, ...]         # first flat step of each node
    total_steps: int
    w_chunks: Tuple[int, ...]           # per-step weight elems, per node
    w_offsets: Tuple[int, ...]          # first weight row of each node
    w_max: int                          # elems of the largest weight row
    w_total: int                        # weight rows (node, chain step)
    b_offsets: Tuple[int, ...]          # bias row of each node
    b_max: int                          # widest bias row
    b_total: int                        # bias rows (one per node)
    table: Tuple[Tuple[int, ...], ...]
    # images per grid step (ISSUE 8): the fused kernel's grid becomes
    # (batch-block, flat step) — each batch block replays the whole
    # chain through its own arena/accumulator slice
    batch_block: int = 1

    def operand_table(self) -> np.ndarray:
        """(total_steps, 14) int32 SMEM operand table."""
        return np.asarray(self.table, np.int32)

    @property
    def out_kp(self) -> KernelProgram:
        return self.nodes[-1].kp

    @property
    def out_layer(self) -> ConvLayer:
        return self.nodes[-1].kp.wave.program.layer

    def acc_shape(self, multi_only: bool = False) -> Tuple[int, int, int]:
        """Shared accumulator extent ((1, 1, 1) token when unused)."""
        kps = [s.kp for s in self.nodes
               if not multi_only or s.kp.n_chain > 1]
        if not kps:
            return (1, 1, 1)
        return (max(k.acc_h for k in kps), max(k.acc_w for k in kps),
                max(k.out_c_pad for k in kps))

    @property
    def vmem_bytes(self) -> int:
        """VMEM the launch holds, every buffer at its tiled layout
        (``kernels/wave_replay/graph.py::graph_kernel_vmem``, which also
        sets the launch's scoped-VMEM limit)."""
        from repro.kernels.wave_replay.graph import graph_kernel_vmem
        return graph_kernel_vmem(self).bytes

    @property
    def plan_bytes(self) -> int:
        """The partitioner's per-step working-set model, in element
        bytes: arena slots, shared accumulator, input window and output
        block scale per image (``batch_block``); the flat weight/bias
        windows are batch-shared. Deliberately precision-independent
        (4 B/elem) so fp32 and int8 partition a graph identically."""
        h0 = self.nodes[0].kp
        x_elems = (h0.pad_h * h0.pad_w * h0.in_c_kpad
                   if self.input_in_arena
                   else h0.ih * h0.iw * h0.c_width)
        kl = self.out_kp
        ah, aw, ac = self.acc_shape()
        bb = self.batch_block
        return (bb * self.arena.slot_bytes_f32
                + 4 * (bb * (ah * aw * ac + x_elems
                             + kl.blk_h * kl.blk_w * kl.out_c_pad)
                       + self.w_max + self.b_max))

    @property
    def geometry(self):
        """Everything the compiled kernel closure bakes in."""
        return (("graphkernel", self.quantized, self.input_in_arena,
                 self.batch_block,
                 self.arena.slots, self.arena.slot_shapes,
                 tuple((v.birth, v.death, v.shape, v.pad)
                       for v in self.arena.values),
                 self.node_steps, self.total_steps,
                 self.w_chunks, self.w_offsets, self.w_max, self.w_total,
                 self.b_offsets, self.b_max, self.b_total)
                + tuple(s.kp.geometry + (s.residual_value is not None,)
                        for s in self.nodes))

    def describe(self) -> str:
        names = "+".join(s.name for s in self.nodes)
        return (f"{names}: 1 pallas_call, {self.total_steps} grid steps, "
                f"{len(self.arena.slot_shapes)}-slot arena "
                f"({self.arena.slot_bytes_f32 // 1024} KiB f32), "
                f"table {self.total_steps}x{GRAPH_OP_COLS} SMEM")


def chain_plan_bytes(specs: Sequence[ChainNodeSpec],
                     quantized: bool = False,
                     batch_block: int = 1) -> int:
    """Working-set estimate of a (possibly still-growing) chain.

    The greedy partitioner calls this on prefixes whose values may
    still leak to later nodes, so it skips ``lower_graph_kernel``'s
    strict consumption checks but shares its exact layout math.
    ``batch_block`` scales the per-image terms (arena, accumulator,
    input window, output block) like ``GraphKernelProgram.plan_bytes``.
    """
    (_, input_in_arena, arena, _, _, w_max, _, _, b_max, _, _, _) = \
        _chain_layout(specs, quantized)
    h0 = specs[0].kp
    x_elems = (h0.pad_h * h0.pad_w * h0.in_c_kpad if input_in_arena
               else h0.ih * h0.iw * h0.c_width)
    kl = specs[-1].kp
    accs = [s.kp for s in specs]
    acc = (max(k.acc_h for k in accs) * max(k.acc_w for k in accs)
           * max(k.out_c_pad for k in accs))
    bb = max(1, int(batch_block))
    return (bb * arena.slot_bytes_f32
            + 4 * (bb * (acc + x_elems
                         + kl.blk_h * kl.blk_w * kl.out_c_pad)
                   + w_max + b_max))


def lower_graph_kernel(specs: Sequence[ChainNodeSpec], *,
                       quantized: bool = False,
                       batch_block: int = 1) -> GraphKernelProgram:
    """Lower a fused chain of per-layer KernelPrograms to one program.

    Each node's rows replay its own table verbatim (same IY/IX/C0/VR/VC,
    chain innermost per tile), extended with NODE/K dispatch, flat
    weight/bias offsets, and the output-block steering; head-node rows
    keep their input-window origins only when the chain input stays a
    kernel operand (windowed mode) — when later nodes also read it
    (e.g. a residual off the chain input) it is copied into the arena
    once by the ``t == 0`` prologue and the columns are zeroed.
    """
    (input_value, input_in_arena, arena, w_chunks, w_offsets, w_max,
     w_total, b_offsets, b_max, b_total, node_steps, total_steps) = \
        _chain_layout(specs, quantized)

    visible = {input_value}
    for i, s in enumerate(specs):
        l = s.kp.wave.program.layer
        if s.kp.norm or s.kp.act == "gelu":
            raise LoweringError(
                f"{s.name}: the graph kernel's epilogue has no norm or "
                f"gelu — run this node on the per-layer megakernel")
        if s.in_value not in visible:
            raise LoweringError(
                f"{s.name}: input {s.in_value!r} not produced earlier "
                f"in the chain")
        if s.residual_value is not None and s.residual_value not in visible:
            raise LoweringError(
                f"{s.name}: residual {s.residual_value!r} not produced "
                f"earlier in the chain")
        if s.kp.residual != (s.residual_value is not None):
            raise LoweringError(
                f"{s.name}: KernelProgram residual={s.kp.residual} "
                f"disagrees with residual_value={s.residual_value!r}")
        visible.add(s.out_value)
    # every internal value is fully consumed inside the chain (the cut
    # validity the partitioner guarantees), and wiring geometry agrees
    producer = {s.out_value: i for i, s in enumerate(specs)}
    for i, s in enumerate(specs):
        for val, kind in ((s.in_value, "conv"),
                          (s.residual_value, "residual")):
            if val is None or val == input_value:
                continue
            p = specs[producer[val]]
            pl_, rl = p.kp.wave.program.layer, s.kp.wave.program.layer
            if kind == "conv":
                ok = (rl.in_h == p.kp.out_h and rl.in_w == p.kp.out_w
                      and rl.in_c == pl_.out_c)
            else:
                ok = (s.kp.out_h == p.kp.out_h and s.kp.out_w == p.kp.out_w
                      and rl.out_c == pl_.out_c)
            if not ok:
                raise LoweringError(
                    f"{s.name}: {kind} input {val!r} geometry "
                    f"mismatch with producer {p.name}")
    for i, s in enumerate(specs[:-1]):
        if not any(t.in_value == s.out_value
                   or t.residual_value == s.out_value
                   for t in specs[i + 1:]):
            raise LoweringError(
                f"{s.name}: internal value {s.out_value!r} has no "
                f"reader inside the chain — invalid cut")

    last = len(specs) - 1
    rows: List[Tuple[int, ...]] = []
    for ni, s in enumerate(specs):
        kp = s.kp
        windowed_head = ni == 0 and not input_in_arena
        for t in range(kp.n_tiles):
            for k in range(kp.n_chain):
                iy, ix, ty, tx, c0, _, vr, vc = kp.table[k][t]
                sy, sx, sc0 = (iy, ix, c0) if windowed_head else (0, 0, 0)
                oy, ox = (ty, tx) if ni == last else (0, 0)
                rows.append((sy, sx, ty, tx, sc0, 0, vr, vc,
                             ni, k, w_offsets[ni] + k,
                             b_offsets[ni], oy, ox))

    gkp = GraphKernelProgram(
        nodes=tuple(specs), input_value=input_value,
        input_in_arena=input_in_arena, quantized=quantized, arena=arena,
        node_steps=node_steps, total_steps=total_steps,
        w_chunks=w_chunks, w_offsets=w_offsets, w_max=w_max,
        w_total=w_total, b_offsets=b_offsets, b_max=b_max,
        b_total=b_total, table=tuple(rows),
        batch_block=max(1, int(batch_block)))
    validate_graph_kernel(gkp)
    return gkp


def validate_graph_kernel(gkp: GraphKernelProgram) -> None:
    """Invariants the fused kernel's grid + arena bake in.

    1. The flat table is dense (total_steps, 14); each node's rows are
       contiguous at node_steps[ni], tile-major with its chain
       innermost, and replay its per-layer table's TY/TX/VR/VC.
    2. Arena safety: values sharing a slot have disjoint lifetimes
       (previous occupant dies strictly before the next is born) and
       every slot is at least as large as each value assigned to it;
       reader/producer extents fit inside the slot.
    3. WOFF/BOFF name each step's own row of the stacked weight and
       bias buffers.
    4. Output steering: final-node rows raster-tile the output, all
       other rows pin the output block to (0, 0).
    """
    tab = gkp.operand_table()
    if tab.shape != (gkp.total_steps, GRAPH_OP_COLS):
        raise LoweringError(
            f"graph table {tab.shape} != ({gkp.total_steps}, "
            f"{GRAPH_OP_COLS})")
    last = len(gkp.nodes) - 1
    for ni, s in enumerate(gkp.nodes):
        kp = s.kp
        lo = gkp.node_steps[ni]
        n = kp.n_tiles * kp.n_chain
        hi = gkp.node_steps[ni + 1] if ni + 1 < len(gkp.nodes) \
            else gkp.total_steps
        if hi - lo != n:
            raise LoweringError(f"{s.name}: rows [{lo}, {hi}) != {n} steps")
        r = 0
        for t in range(kp.n_tiles):
            for k in range(kp.n_chain):
                row = tab[lo + r]
                src = kp.table[k][t]
                if (row[GOP_NODE], row[GOP_K]) != (ni, k):
                    raise LoweringError(
                        f"{s.name} row {r}: dispatch "
                        f"({row[GOP_NODE]}, {row[GOP_K]}) != ({ni}, {k})")
                if (row[GOP_TY], row[GOP_TX], row[GOP_VR],
                        row[GOP_VC]) != (src[2], src[3], src[6], src[7]):
                    raise LoweringError(
                        f"{s.name} row {r}: tile/mask columns deviate "
                        f"from the per-layer table")
                want_oyx = (src[2], src[3]) if ni == last else (0, 0)
                if (row[GOP_OY], row[GOP_OX]) != want_oyx:
                    raise LoweringError(
                        f"{s.name} row {r}: output steering "
                        f"({row[GOP_OY]}, {row[GOP_OX]}) != {want_oyx}")
                if row[GOP_WOFF] != gkp.w_offsets[ni] + k:
                    raise LoweringError(
                        f"{s.name} row {r}: weight row {row[GOP_WOFF]} "
                        f"!= {gkp.w_offsets[ni] + k} of {gkp.w_total}")
                if row[GOP_BOFF] != gkp.b_offsets[ni]:
                    raise LoweringError(
                        f"{s.name} row {r}: bias row {row[GOP_BOFF]} "
                        f"!= {gkp.b_offsets[ni]} of {gkp.b_total}")
                r += 1
    occupants: dict = {}
    for v, si in zip(gkp.arena.values, gkp.arena.slots):
        shape = gkp.arena.slot_shapes[si]
        if any(a > b for a, b in zip(v.shape, shape)):
            raise LoweringError(
                f"arena: {v.name} extent {v.shape} overflows slot "
                f"{si} {shape}")
        for u in occupants.get(si, []):
            if not (u.death < v.birth or v.death < u.birth):
                raise LoweringError(
                    f"arena: {u.name} [{u.birth}, {u.death}] and "
                    f"{v.name} [{v.birth}, {v.death}] alias slot {si} "
                    f"while both live")
        occupants.setdefault(si, []).append(v)
