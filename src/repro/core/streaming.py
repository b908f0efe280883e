"""Streaming tiled executor (paper §3 + §5 operationally combined).

Plays the role of the paper's command decoder + DMA schedule: walks a conv
layer tile-by-tile according to a decomposition Plan — image tiles (with
halo), feature groups, input-channel groups with on-chip partial sums —
and never touches more than the planned working set per pass. Numerically
identical to the direct convolution (asserted in tests), demonstrating
that decomposition trades passes for buffer size without changing results.

Four executors share the schedule (DESIGN.md §2):

  * ``mode="interpret"`` — the original Python triple loop over
    ``tile_grid``. One conv dispatch per pass, full-output
    re-materialisation per tile. Faithful to the hardware walk, slow.
  * ``mode="jit"`` — lowers the Plan to a static ``TileProgram``
    (core/schedule.py) and replays it with ``lax.scan``
    + ``lax.dynamic_slice`` / ``dynamic_update_slice`` under ``jax.jit``.
    The schedule is traced once per (geometry, batch shape, conv
    backend) and cached, like the paper's command decoder replaying a
    fixed instruction stream. Outputs are bit-identical to the
    interpreter whenever the channel splits divide evenly (all AlexNet
    planner plans); ragged splits are zero-padded to keep scan shapes
    static, which can let the conv backend reassociate sums by a few ULP.
  * ``mode="wave"`` (default) — partitions the step stream into
    dependency-free *waves* (core/schedule.py ``partition_waves``):
    every step of a wave writes a distinct output block, so the whole
    wave's input windows are gathered with one vmapped
    ``dynamic_slice``, convolved by ONE batched dispatch, and
    reassembled into the padded output by a static transpose. Only
    in-channel partial-sum chains serialise — across waves — so a layer
    costs O(in_splits) big dispatches instead of O(n_steps) small ones
    (the paper's §3 point that independent tiles keep the CU array
    saturated). Accumulation order per output element is unchanged
    (wave k is always chain position k), so outputs stay bit-identical
    to the interpreter on evenly-split plans.
  * ``mode="megakernel"`` — the whole layer inside ONE persistent
    Pallas kernel (kernels/wave_replay): the grid walks (tile, wave)
    with the chain axis innermost, a VMEM scratch accumulator carries
    partial sums across each tile's in-channel-group chain (the paper's
    128 KB SRAM bank), halo windows are indexed via a scalar-prefetched
    SMEM operand table instead of gathered into fresh copies, and
    bias+ReLU+max-pool run in the kernel epilogue on the last chain
    step — zero HBM round-trips for partials, one launch per layer.
    In-tile reductions run as im2col matmuls, so outputs match the
    interpreter to fp32 tolerance (not bit-exactly).

``precision="int8"`` swaps the megakernel's datapath for the paper's
fixed-point pipeline (kernels/wave_replay_q, DESIGN.md §2.3): int8
operands, int32 VMEM accumulators, requantize+ReLU+pool fused into the
kernel epilogue — over the SAME KernelProgram schedules and operand
tables, bit-exact against the int32 reference model.

The per-tile compute is pluggable: the XLA conv (default) or the Pallas
streaming kernel (kernels/conv_stream) via ``conv_fn=pallas_tile_conv_fn``
or ``conv_backend="pallas"`` — tile windows arrive halo-inclusive and
pre-padded, which is exactly the VALID layout ``conv2d_stream_raw``
expects, so the planner's tile coordinates hand off to the kernel's
row-block grid with no extra padding.
"""
from __future__ import annotations

import functools
import itertools
import weakref
from collections import OrderedDict
from typing import Callable, List, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.decomposition import (ConvLayer, Plan, evaluate,
                                      plan_decomposition, tile_grid)
from repro.core.graph import (INPUT, NORM_EPS, NetworkGraph, chain_graph,
                              check_graph_input, conv_keyed,
                              fusible_chains, graph_params, norm_fusion,
                              plan_buffers, refuse_norm_gelu,
                              residual_fusion, topological_schedule)
from repro.core.schedule import (DEFAULT_VMEM_BUDGET as _VMEM_DEFAULT,
                                 ChainNodeSpec, KernelProgram, TileProgram,
                                 WaveProgram, batch_grid, compile_layer,
                                 lower_graph_kernel, lower_kernel_program,
                                 partition_waves)
from repro.obs import metrics as _metrics
from repro.obs import trace as _trace
from repro.runtime.errors import PlanError


def conv2d_direct(x: jax.Array, w: jax.Array, stride: int = 1,
                  pad: int = 0, groups: int = 1) -> jax.Array:
    """x (B,H,W,Cin), w (K,K,Cin/groups,Cout) -> (B,Ho,Wo,Cout).

    HIGHEST precision: on a TPU the default fp32 conv is one bf16 pass.
    """
    return lax.conv_general_dilated(
        x, w, window_strides=(stride, stride),
        padding=[(pad, pad), (pad, pad)],
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        feature_group_count=groups, precision=lax.Precision.HIGHEST)


def maxpool_direct(x: jax.Array, window: int, stride: int = 0) -> jax.Array:
    stride = stride or window
    return lax.reduce_window(
        x, -jnp.inf, lax.max, (1, window, window, 1),
        (1, stride, stride, 1), "VALID")


def activation_direct(y: jax.Array, act) -> jax.Array:
    """A graph node's activation kind as plain XLA ops."""
    if act == "relu":
        return jnp.maximum(y, 0)
    if act == "gelu":
        return jax.nn.gelu(y, approximate=False)
    return y


def channel_norm_direct(y: jax.Array, gamma, beta) -> jax.Array:
    """A ``norm`` node as plain XLA ops: LayerNorm over the channel
    axis (``NORM_EPS``), then the per-channel affine."""
    mean = jnp.mean(y, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(y - mean), axis=-1, keepdims=True)
    return ((y - mean) * lax.rsqrt(var + NORM_EPS) * gamma.astype(y.dtype)
            + beta.astype(y.dtype))


# ---------------------------------------------------------------------------
# Pluggable tile-conv backends
# ---------------------------------------------------------------------------

# single policy point for all Pallas launches (kernels import it too)
from repro.kernels.common import (megakernel_fold,  # noqa: E402
                                  pallas_interpret_default)


# partition_waves is pure on a hashable frozen TileProgram; memoizing it
# means a session's forward builder, its operand tables, and benchmarks
# re-partitioning the same program all share one lowering + validation
_partition_waves_cached = functools.lru_cache(maxsize=128)(partition_waves)

# same deal for the megakernel lowering: pure on (WaveProgram, flags)
_lower_kernel_cached = functools.lru_cache(maxsize=128)(lower_kernel_program)


def _normalize_mode(mode: str) -> str:
    """One executor vocabulary across layer- and network-level APIs:
    ``jit`` and ``scan`` name the same serial scan replay."""
    if mode in ("jit", "scan"):
        return "scan"
    if mode in ("wave", "interpret", "megakernel", "graphkernel"):
        return mode
    raise ValueError(f"unknown executor mode {mode!r} "
                     f"(expected graphkernel | megakernel | wave | "
                     f"scan/jit | interpret)")


def xla_tile_conv_fn(stride: int) -> Callable:
    """Default backend: one XLA VALID conv per (halo-inclusive) tile."""
    return lambda xt, wt: conv2d_direct(xt, wt, stride, 0)


def pallas_tile_conv_fn(stride: int, row_block: int = 8,
                        interpret: Optional[bool] = None) -> Callable:
    """Pallas streaming-kernel backend for the executor.

    The executor hands over tiles that already carry their stride-aware
    halo (``ih = (oh-1)*stride + K``), i.e. exactly the pre-padded VALID
    input ``conv2d_stream_raw`` wants; the kernel's own row-block grid
    pads/trims internally, and its ``H_out`` recomputed from the tile
    equals the planner's ``oh`` — so no coordinate fix-up is needed at
    the boundary.

    ``interpret=None`` auto-detects: compiled on TPU, interpreter
    elsewhere (``pallas_interpret_default``).
    """
    from repro.kernels.conv_stream.kernel import conv2d_stream_raw

    if interpret is None:
        interpret = pallas_interpret_default()

    def fn(xt, wt):
        rb = min(row_block, (xt.shape[1] - wt.shape[0]) // stride + 1)
        return conv2d_stream_raw(xt, wt, stride=stride, row_block=rb,
                                 interpret=interpret)
    return fn


# Stable identities for custom conv_fn callables: id() can be recycled
# after a GC'd callable, which would silently serve an executable traced
# for the *wrong* conv function. Tokens from a monotonic counter held in
# a WeakKeyDictionary are never reused, and die with the callable.
_CONV_FN_TOKENS: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()
_TOKEN_COUNTER = itertools.count()


def _conv_fn_token(fn: Callable) -> str:
    try:
        tok = _CONV_FN_TOKENS.get(fn)
        if tok is None:
            tok = f"custom:{next(_TOKEN_COUNTER)}"
            _CONV_FN_TOKENS[fn] = tok
        return tok
    except TypeError:
        # unhashable / non-weakrefable callable: unique token per call —
        # always retraces, never aliases
        return f"custom-uncacheable:{next(_TOKEN_COUNTER)}"


def _resolve_conv_fn(conv_fn, conv_backend, stride,
                     conv_fn_name: Optional[str] = None):
    """Pick the tile-conv callable and a *stable* cache key for it.

    A caller-supplied ``conv_fn_name`` keys the executable cache
    directly (the caller asserts two same-named callables trace
    identically); otherwise custom callables get a weakref-backed token
    that is never recycled.
    """
    if conv_fn is not None:
        return conv_fn, (f"named:{conv_fn_name}" if conv_fn_name
                         else _conv_fn_token(conv_fn))
    if conv_backend == "pallas":
        return pallas_tile_conv_fn(stride), "pallas"
    return xla_tile_conv_fn(stride), "xla"


# ---------------------------------------------------------------------------
# Interpreted executor (the original Python walk — kept as reference)
# ---------------------------------------------------------------------------

def run_layer_interpreted(layer: ConvLayer, plan: Plan, x: jax.Array,
                          w: jax.Array, b: Optional[jax.Array] = None,
                          conv_fn: Optional[Callable] = None) -> jax.Array:
    """Execute one CONV layer via the planned tile schedule, in Python.

    x: (B, in_h, in_w, in_c); w: (K, K, in_c, out_c). Returns the full
    (B, out_h, out_w, out_c) output, assembled tile by tile."""
    l = layer
    if x.shape[1:] != (l.in_h, l.in_w, l.in_c):
        raise ValueError(
            f"{l.name}: input {x.shape[1:]} != declared "
            f"({l.in_h}, {l.in_w}, {l.in_c})")
    conv_fn = conv_fn or xla_tile_conv_fn(l.stride)
    B = x.shape[0]
    xp = jnp.pad(x, ((0, 0), (l.pad, l.pad), (l.pad, l.pad), (0, 0)))
    out = jnp.zeros((B, l.out_h, l.out_w, l.out_c), x.dtype)

    cg = -(-l.in_c // plan.in_splits)
    fg = -(-l.out_c // plan.feat_splits)
    out_per_group = l.out_c // l.groups
    in_per_group = l.in_c // l.groups
    for t in tile_grid(l, plan):
        xin_full = xp[:, t["iy"]:t["iy"] + t["ih"],
                      t["ix"]:t["ix"] + t["iw"], :]
        for f in range(plan.feat_splits):
            f0, f1 = f * fg, min((f + 1) * fg, l.out_c)
            if f0 >= l.out_c:
                continue
            acc = jnp.zeros((B, t["oh"], t["ow"], f1 - f0), jnp.float32)
            for c in range(plan.in_splits):
                if l.groups == 1:
                    c0, c1 = c * cg, min((c + 1) * cg, l.in_c)
                elif plan.feat_splits > 1:
                    # feature group lies inside one conv group (planner
                    # guarantees alignment): read only that group's inputs
                    g = f0 // out_per_group
                    c0, c1 = g * in_per_group, (g + 1) * in_per_group
                else:
                    c0, c1 = 0, l.in_c
                if c0 >= l.in_c:
                    continue
                gcount = (l.groups if (l.groups > 1 and plan.feat_splits == 1)
                          else 1)
                wt = w[:, :, :, f0:f1] if l.groups > 1 else \
                    w[:, :, c0:c1, f0:f1]
                if gcount > 1:
                    part = conv2d_direct(xin_full[..., c0:c1], wt, l.stride,
                                         0, groups=gcount)
                else:
                    part = conv_fn(xin_full[..., c0:c1], wt)
                acc = acc + part.astype(jnp.float32)  # on-chip psum (32-bit)
            if b is not None:
                acc = acc + b[f0:f1].astype(jnp.float32)
            out = out.at[:, t["oy"]:t["oy"] + t["oh"],
                         t["ox"]:t["ox"] + t["ow"], f0:f1].set(
                             acc.astype(x.dtype))
    return out


# ---------------------------------------------------------------------------
# Compiled executor: replay the TileProgram with lax.scan under jit
# ---------------------------------------------------------------------------

def _pad_to_grid(g: TileProgram, x, w):
    """Pad input/weights up to the program's uniform tile grid.

    When the conv window never reaches the last input rows/cols
    ((in - K) % stride != 0), pad_h/pad_w is *smaller* than the
    conv-padded input, hence the trailing trim."""
    l = g.layer
    xp = jnp.pad(x, ((0, 0),
                     (l.pad, max(0, g.pad_h - l.in_h - l.pad)),
                     (l.pad, max(0, g.pad_w - l.in_w - l.pad)),
                     (0, g.in_c_pad - l.in_c)))[:, :g.pad_h, :g.pad_w]
    wp = jnp.pad(w, ((0, 0), (0, 0),
                     (0, g.w_in_pad - w.shape[2]),
                     (0, g.out_c_pad - l.out_c)))
    return xp, wp


def _traced_execute(kind: str, layer_of: Callable):
    """Wrap an executor body in a trace-time ``cat="execute"`` span.

    Like the megakernel launch counters, the span fires once per jax
    *trace* (the executor body runs at trace time inside jit), so span
    counts line up with dispatch counts, not call counts. The disabled
    path is one global read — no span objects, no context manager."""
    def deco(fn):
        @functools.wraps(fn)
        def wrapper(prog, *a, **k):
            t = _trace.current_tracer()
            if t is None:
                return fn(prog, *a, **k)
            name = layer_of(prog).name
            with t.span(f"{kind}:{name}", cat="execute", node=name,
                        kind=kind):
                return fn(prog, *a, **k)
        return wrapper
    return deco


@_traced_execute("scan", lambda p: p.layer)
def _scan_executor(program: TileProgram, conv_fn: Callable, has_bias: bool,
                   x, w, b, ops):
    """Trace-time body shared by all compiled executables."""
    g, l = program, program.layer
    B = x.shape[0]
    xp, wp = _pad_to_grid(g, x, w)
    out0 = jnp.zeros((B, g.out_h_pad, g.out_w_pad, g.out_c_pad), jnp.float32)

    def step(out, op):
        iy, ix, oy, ox, c0, wc0, f0 = (op[i] for i in range(7))
        xt = lax.dynamic_slice(xp, (0, iy, ix, c0), (B, g.ih, g.iw, g.cg))
        wt = lax.dynamic_slice(wp, (0, 0, wc0, f0),
                               (l.kernel, l.kernel, g.fan, g.fg))
        if g.gcount > 1:
            part = conv2d_direct(xt, wt, l.stride, 0, groups=g.gcount)
        else:
            part = conv_fn(xt, wt)
        cur = lax.dynamic_slice(out, (0, oy, ox, f0), (B, g.oh, g.ow, g.fg))
        out = lax.dynamic_update_slice(
            out, cur + part.astype(jnp.float32), (0, oy, ox, f0))
        return out, None

    out, _ = lax.scan(step, out0, ops)
    out = out[:, :l.out_h, :l.out_w, :l.out_c]
    if has_bias:
        out = out + b.astype(jnp.float32)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Wave executor: one fused dispatch per dependency-free wave (ISSUE 2)
# ---------------------------------------------------------------------------

@_traced_execute("wave", lambda p: p.program.layer)
def _wave_executor(wprog: WaveProgram, conv_fn: Callable, has_bias: bool,
                   x, w, b, wave_ops):
    """Replay a WaveProgram: ONE fused conv dispatch per wave.

    Per wave: every tile's halo-inclusive input window is gathered with
    a vmapped ``dynamic_slice`` (the DMA engine fetching all of a wave's
    tiles at once) and stacked along the batch axis; the wave's feature
    groups all read the same input-channel group, so they collapse into
    the conv's output-channel width. The whole wave is then one ordinary
    ``(n_tiles·B, ih, iw, c)`` conv over the wave's weight slice — the
    software analogue of the paper's saturated CU array. Because
    ``validate_waves`` pinned the wave's blocks to the raster tiling of
    the padded output, the stacked results reassemble with a static
    transpose — no scatter, no serial update chain.

    Waves accumulate in chain order onto a zero-initialised fp32 buffer,
    reproducing the interpreter's per-element partial-sum order exactly
    (0 + p_0 + p_1 + ... + bias), hence bit-identical outputs on
    evenly-split plans.

    Multi-wave chains gather each *unique* tile window exactly once:
    tile windows are wave-invariant (``validate_waves`` invariant 4 —
    only the channel offset walks along a chain), so the spatial gather
    is hoisted out of the wave scan at full channel width and each wave
    takes a cheap channel slice of the pre-gathered stack, instead of
    re-materialising identical halo windows once per (tile,
    channel-group) as the original executor did.
    """
    g = wprog.program
    l, plan = g.layer, g.plan
    B = x.shape[0]
    T = wprog.n_tiles
    xp, wp = _pad_to_grid(g, x, w)

    if wprog.dispatch_groups > 1:
        conv = lambda xt, wt: conv2d_direct(xt, wt, l.stride, 0,
                                            groups=wprog.dispatch_groups)
    else:
        conv = conv_fn

    def conv_wave(wins, wc0):
        # wins (T, B, ih, iw, c_width); wc0 the wave's weight fan offset
        wt = lax.dynamic_slice(
            wp, (0, 0, wc0, 0),
            (l.kernel, l.kernel, wprog.fan_width, g.out_c_pad))
        part = conv(wins.reshape(T * B, g.ih, g.iw, wprog.c_width), wt)
        part = part.astype(jnp.float32)     # (T*B, oh, ow, out_c_pad)
        img = part.reshape(plan.tiles_h, plan.tiles_w, B, g.oh, g.ow,
                           g.out_c_pad)
        img = img.transpose(2, 0, 3, 1, 4, 5)
        return img.reshape(B, g.out_h_pad, g.out_w_pad, g.out_c_pad)

    def gather(ops, c0, width):
        # ops (n_tiles, 6): [iy, ix, oy, ox, c0, wc0]
        return jax.vmap(lambda op: lax.dynamic_slice(
            xp, (0, op[0], op[1], c0), (B, g.ih, g.iw, width)))(ops)

    out0 = jnp.zeros((B, g.out_h_pad, g.out_w_pad, g.out_c_pad),
                     jnp.float32)
    if wprog.n_waves == 1:
        ops = wave_ops[0]
        out = out0 + conv_wave(gather(ops, ops[0, 4], wprog.c_width),
                               ops[0, 5])
    else:
        # gather once per unique window (full channel width), then scan
        # the chain: each wave slices its channel group from the stack —
        # O(T) gathers total instead of O(T * n_waves)
        wins_all = gather(wave_ops[0], 0, g.in_c_pad)

        def step(acc, ops):
            wins = lax.dynamic_slice(
                wins_all, (0, 0, 0, 0, ops[0, 4]),
                (T, B, g.ih, g.iw, wprog.c_width))
            return acc + conv_wave(wins, ops[0, 5]), None

        # partial-sum chains serialise across waves (and only there);
        # scanning the wave axis keeps the traced graph O(1) in n_waves
        out, _ = lax.scan(step, out0, wave_ops)
    out = out[:, :l.out_h, :l.out_w, :l.out_c]
    if has_bias:
        out = out + b.astype(jnp.float32)
    return out.astype(x.dtype)


def run_layer_wave(wprog: WaveProgram, x: jax.Array, w: jax.Array,
                   b: Optional[jax.Array] = None,
                   conv_fn: Optional[Callable] = None,
                   conv_backend: str = "xla",
                   conv_fn_name: Optional[str] = None) -> jax.Array:
    """Execute a pre-partitioned WaveProgram under the wave executor."""
    l = wprog.program.layer
    _check_input(l, x)
    conv_fn, conv_key = _resolve_conv_fn(conv_fn, conv_backend, l.stride,
                                         conv_fn_name)
    key = (wprog.geometry, "wave", conv_key, "fp32", b is not None,
           x.shape[0], str(x.dtype))
    ops = jnp.asarray(wprog.tile_operands())
    bias = b if b is not None else jnp.zeros((0,), x.dtype)
    return _call_cached(key, lambda: jax.jit(
        functools.partial(_wave_executor, wprog, conv_fn, b is not None)),
        x, w, bias, ops)


# ---------------------------------------------------------------------------
# Megakernel executor: ONE persistent pallas_call per layer (ISSUE 3)
# ---------------------------------------------------------------------------

def _megakernel_executor(kprog: KernelProgram, has_bias: bool,
                         x, w, b, table):
    """Replay a whole layer inside one persistent Pallas kernel.

    The grid walks (tile, wave) with the chain axis innermost; a VMEM
    scratch accumulator carries each tile's partial sums across its
    in-channel-group chain (zeroed at wave 0, finished in the epilogue on
    the last wave), so — unlike the wave executor, whose per-wave conv
    results accumulate into an HBM-resident fp32 buffer — partials never
    round-trip off-chip, and halo windows are *indexed* via the SMEM
    operand table instead of materialised by a gather. Bias, and (when
    the program was lowered with ``relu``/``fuse_pool``) ReLU + max-pool,
    run in the same epilogue; cropping happens here.
    """
    from repro.kernels.wave_replay.ops import wave_replay_layer
    y = wave_replay_layer(kprog, x, w, b if has_bias else None,
                          table=table)
    return y.astype(x.dtype)


def run_layer_megakernel(wprog: WaveProgram, x: jax.Array, w: jax.Array,
                         b: Optional[jax.Array] = None,
                         relu: bool = False,
                         fuse_pool: bool = False,
                         vmem_budget: Optional[int] = _VMEM_DEFAULT
                         ) -> jax.Array:
    """Execute a WaveProgram as ONE persistent Pallas megakernel launch.

    Parity with the other ``run_layer_*`` entry points: by default the
    epilogue applies bias only (no ReLU, no pool), so outputs compare
    against ``run_layer_interpreted`` within fp32 tolerance (the in-tile
    reduction runs on the MXU as an im2col matmul, so per-partial
    rounding can differ by a few ULP from the XLA conv). The per-tile
    conv backend is *not* pluggable here — the megakernel IS the
    backend. ``vmem_budget`` mirrors ``lower_kernel_program``: the
    working-set bound for coarsening long partial-sum chains
    (``None`` = keep the schedule's 1:1 wave chain). The batch rides
    the kernel grid (ISSUE 8): the lowering requests
    ``batch_block=x.shape[0]`` and the VMEM clamp sizes the per-step
    image block to whatever fits the budget alongside the weights.
    """
    l = wprog.program.layer
    _check_input(l, x)
    batch = x.shape[0]
    wprog = _coarsen_single_wave(wprog, fuse_pool, vmem_budget, batch)
    kprog = _lower_kernel_cached(wprog, act="relu" if relu else None,
                                 fuse_pool=fuse_pool,
                                 vmem_budget=vmem_budget,
                                 batch_block=batch)
    return _run_kernel_program(kprog, x, w, b)


def _coarsen_single_wave(wprog: WaveProgram, fuse_pool: bool,
                         vmem_budget: Optional[int],
                         batch: int = 1) -> WaveProgram:
    """Wave-equivalent coarsening for tiny chains (BENCH regression fix).

    Chain coarsening folds waves per grid step, but a single-wave
    schedule (``n_waves == 1`` — e.g. AlexNet conv1's 7-tile plan at
    the 128 KB SRAM point) has nothing to fold, so the megakernel
    replays every tiny tile as its own grid step and fixed per-step
    dispatch dominates (megakernel 0.6x of the one-dispatch wave
    executor). Re-plan the tile grid at the kernel's VMEM budget
    instead — conv1 becomes a single 1x1-tile grid step, the same
    one-dispatch shape the wave executor runs — and keep the coarser
    plan only when it strictly reduces grid steps. Grouped layers keep
    their schedule (their plans carry group-alignment constraints).
    """
    if vmem_budget is None or wprog.n_waves > 1 \
            or wprog.program.layer.groups > 1:
        return wprog
    l = wprog.program.layer
    plan = plan_for_vmem(l, vmem_budget, fuse_pool, residual=False,
                         batch=batch)
    coarse = _partition_waves_cached(compile_layer(l, plan))
    if coarse.n_tiles * coarse.n_waves < wprog.n_tiles * wprog.n_waves:
        return coarse
    return wprog


def _run_kernel_program(kprog: KernelProgram, x, w, b):
    key = (kprog.geometry, "megakernel", "fp32", b is not None,
           x.shape[0], str(x.dtype))
    table = jnp.asarray(kprog.operand_table())
    bias = b if b is not None else jnp.zeros((0,), x.dtype)
    return _call_cached(key, lambda: jax.jit(
        functools.partial(_megakernel_executor, kprog, b is not None)),
        x, w, bias, table)


# ---------------------------------------------------------------------------
# Quantized (int8) megakernel executor — precision="int8" (ISSUE 4)
# ---------------------------------------------------------------------------

def _megakernel_q_executor(kprog: KernelProgram, pre_shift: int,
                           fan_chunk: int, in_scale: float,
                           out_scale: float, dequantize: bool,
                           x, wq, bq, m, shift, table):
    """Replay a layer through the int8 megakernel.

    fp32 inputs are quantized at entry (symmetric, the calibrated
    ``in_scale``); int8 inputs pass straight through — that is how the
    network path chains layers without dequant round-trips. The kernel
    epilogue requantizes into the layer's calibrated output scale;
    ``dequantize`` converts back to fp32 for float callers.
    """
    from repro.core.quantization import dequantize_int8, quantize_int8_sym
    from repro.kernels.wave_replay_q.ops import wave_replay_q_layer
    xq = x if x.dtype == jnp.int8 else quantize_int8_sym(x, in_scale)
    yq = wave_replay_q_layer(kprog, xq, wq, bq, m, shift,
                             pre_shift=pre_shift, fan_chunk=fan_chunk,
                             table=table)
    return dequantize_int8(yq, out_scale) if dequantize else yq


def run_layer_megakernel_q(wprog: WaveProgram, x: jax.Array, quant,
                           relu: bool = False, fuse_pool: bool = False,
                           dequantize: bool = True,
                           vmem_budget: Optional[int] = _VMEM_DEFAULT
                           ) -> jax.Array:
    """Execute a WaveProgram as ONE int8 Pallas megakernel launch.

    ``quant`` is the layer's ``LayerQuant`` (quant/calibrate.py). The
    KernelProgram lowering is byte-identical to the fp32 megakernel's —
    same grid, same SMEM operand table, same ``vmem_budget`` chain
    coarsening — only the datapath (int8 operands, int32 VMEM
    accumulator, requantize-on-writeback epilogue) changes; quantization
    never perturbs the planner. Output is bit-exact against
    ``kernels/wave_replay_q/ref.py`` (integer arithmetic end to end).
    """
    l = wprog.program.layer
    _check_input(l, x)
    batch = x.shape[0]
    wprog = _coarsen_single_wave(wprog, fuse_pool, vmem_budget, batch)
    kprog = _lower_kernel_cached(wprog, act="relu" if relu else None,
                                 fuse_pool=fuse_pool,
                                 vmem_budget=vmem_budget,
                                 batch_block=batch)
    # precision is an explicit key component: the int8 path accepts the
    # SAME fp32 inputs over the SAME geometry as the fp32 megakernel,
    # so without it the two executables would collide
    key = (kprog.geometry, "megakernel", "int8", quant.pre_shift,
           quant.fan_chunk, float(quant.in_scale),
           float(quant.out_scale), dequantize, x.shape[0], str(x.dtype))
    table = jnp.asarray(kprog.operand_table())
    wq, bq, m, shift = quant.device_arrays()
    return _call_cached(key, lambda: jax.jit(functools.partial(
        _megakernel_q_executor, kprog, quant.pre_shift, quant.fan_chunk,
        float(quant.in_scale), float(quant.out_scale), dequantize)),
        x, wq, bq, m, shift, table)


# One jitted executable per (schedule geometry, backend, batch shape).
# The operand table is a traced input, so replays with the same geometry
# hit this cache — the software command-decoder replaying its stream.
# LRU-bounded: long-lived servers cycling through many geometries or
# custom conv_fns evict the coldest executable instead of growing
# without bound.
_EXECUTOR_CACHE: "OrderedDict[tuple, Callable]" = OrderedDict()
_EXECUTOR_CACHE_LIMIT = 64


def clear_executor_cache() -> None:
    """Drop every cached executable (tests; long-lived server hygiene)."""
    _EXECUTOR_CACHE.clear()


def executor_cache_size() -> int:
    return len(_EXECUTOR_CACHE)


def set_executor_cache_limit(limit: int) -> None:
    """Bound the executable cache; evicts least-recently-used over it."""
    global _EXECUTOR_CACHE_LIMIT
    if limit < 1:
        raise ValueError("executor cache limit must be >= 1")
    _EXECUTOR_CACHE_LIMIT = limit
    while len(_EXECUTOR_CACHE) > _EXECUTOR_CACHE_LIMIT:
        _EXECUTOR_CACHE.popitem(last=False)
        _metrics.registry().counter("executor_cache.evictions").inc()


def _cached_executable(key: tuple, build: Callable) -> Callable:
    reg = _metrics.registry()
    fn = _EXECUTOR_CACHE.get(key)
    if fn is None:
        reg.counter("executor_cache.misses").inc()
        fn = _EXECUTOR_CACHE[key] = build()
    else:
        reg.counter("executor_cache.hits").inc()
        _EXECUTOR_CACHE.move_to_end(key)
    while len(_EXECUTOR_CACHE) > _EXECUTOR_CACHE_LIMIT:
        _EXECUTOR_CACHE.popitem(last=False)
        reg.counter("executor_cache.evictions").inc()
    return fn


def _call_cached(key: tuple, build: Callable, *args):
    """Get-or-build the executable for ``key`` and invoke it.

    ``jax.jit`` is lazy — the trace/compile happens on the *first call*,
    after ``_cached_executable`` has already inserted the entry — so a
    failing compile used to leave a poisoned entry behind under a
    healthy-looking key. Evict on any failure: the cache only ever
    holds executables whose most recent call succeeded, and a later
    retry (or a fallback-mode rebuild under a different key) starts
    from a clean slot."""
    fresh = key not in _EXECUTOR_CACHE
    fn = _cached_executable(key, build)
    try:
        if _trace.current_tracer() is None:     # disabled fast path
            return fn(*args)
        # first call traces + compiles (jit is lazy); later calls just
        # dispatch — split the span categories so the bench breakdown
        # attributes time to the right phase
        if fresh:
            with _trace.span("compile", cat="compile"):
                return fn(*args)
        with _trace.span("executor_call", cat="run"):
            return fn(*args)
    except Exception:
        _EXECUTOR_CACHE.pop(key, None)
        _metrics.registry().counter("executor_cache.poisoned").inc()
        raise


def _check_input(l: ConvLayer, x: jax.Array) -> None:
    if x.shape[1:] != (l.in_h, l.in_w, l.in_c):
        raise ValueError(
            f"{l.name}: input {x.shape[1:]} != declared "
            f"({l.in_h}, {l.in_w}, {l.in_c}) — schedule offsets would "
            f"silently address the wrong pixels")


def run_layer_scheduled(program: TileProgram, x: jax.Array, w: jax.Array,
                        b: Optional[jax.Array] = None,
                        conv_fn: Optional[Callable] = None,
                        conv_backend: str = "xla",
                        conv_fn_name: Optional[str] = None) -> jax.Array:
    """Execute a pre-lowered TileProgram under the compiled scan executor.

    A custom ``conv_fn`` caches by a stable weakref-backed token (or by
    ``conv_fn_name`` when given): pass a *stable* callable or a name,
    not a fresh per-call lambda, or every call pays a full trace +
    compile. The named ``conv_backend`` strings cache by name and never
    have this problem."""
    l = program.layer
    _check_input(l, x)
    conv_fn, conv_key = _resolve_conv_fn(conv_fn, conv_backend, l.stride,
                                         conv_fn_name)
    key = (program.geometry, "scan", conv_key, "fp32", b is not None,
           x.shape[0], str(x.dtype))
    ops = jnp.asarray(program.operands())
    bias = b if b is not None else jnp.zeros((0,), x.dtype)
    return _call_cached(key, lambda: jax.jit(
        functools.partial(_scan_executor, program, conv_fn, b is not None)),
        x, w, bias, ops)


def run_layer_streamed(layer: ConvLayer, plan: Plan, x: jax.Array,
                       w: jax.Array, b: Optional[jax.Array] = None,
                       conv_fn: Optional[Callable] = None,
                       mode: str = "wave",
                       conv_backend: str = "xla",
                       conv_fn_name: Optional[str] = None,
                       precision: str = "fp32",
                       quant=None) -> jax.Array:
    """Execute one CONV layer via the planned tile schedule.

    ``mode="wave"`` (default) batches each dependency-free wave into one
    fused dispatch; ``mode="megakernel"`` replays the whole layer inside
    ONE persistent Pallas kernel (partial sums live in VMEM scratch; the
    pluggable conv backend is ignored — the kernel is the backend);
    ``mode="jit"`` (alias ``"scan"``) compiles the serial scan replay;
    ``mode="interpret"`` runs the original per-tile Python loop.

    ``precision="int8"`` (megakernel mode only) runs the fixed-point
    datapath: int8 operands, int32 VMEM accumulation, requantize fused
    into the epilogue. Pass the layer's calibrated ``quant``
    (``quant.calibrate.LayerQuant``); omitting it calibrates absmax
    scales on the fly from this call's ``x``/``w``/``b`` (fine for
    experiments — real serving should calibrate once over a set). The
    fp32 input is quantized at entry and the int8 output dequantized,
    so signatures and return types match the float executors.
    """
    mode = _normalize_mode(mode)
    if mode == "graphkernel":
        # a single layer is a one-node chain: the per-layer launch IS
        # the graph kernel's fallback for it
        mode = "megakernel"
    if precision not in ("fp32", "int8"):
        raise ValueError(f"unknown precision {precision!r} "
                         f"(expected fp32 | int8)")
    if precision == "int8":
        if mode != "megakernel":
            raise ValueError(
                "precision='int8' runs on the quantized megakernel only "
                "— pass mode='megakernel' (the scan/wave executors have "
                "no integer datapath)")
        if quant is None:
            from repro.quant.calibrate import calibrate_layer
            quant = calibrate_layer(layer, w, b, x)
        wprog = _partition_waves_cached(compile_layer(layer, plan))
        return run_layer_megakernel_q(wprog, x, quant)
    if mode == "interpret":
        return run_layer_interpreted(layer, plan, x, w, b, conv_fn)
    if mode == "megakernel":
        wprog = _partition_waves_cached(compile_layer(layer, plan))
        return run_layer_megakernel(wprog, x, w, b)
    if mode == "wave":
        wprog = _partition_waves_cached(compile_layer(layer, plan))
        return run_layer_wave(wprog, x, w, b, conv_fn=conv_fn,
                              conv_backend=conv_backend,
                              conv_fn_name=conv_fn_name)
    program = compile_layer(layer, plan)
    return run_layer_scheduled(program, x, w, b, conv_fn=conv_fn,
                               conv_backend=conv_backend,
                               conv_fn_name=conv_fn_name)


# ---------------------------------------------------------------------------
# NetworkGraph executors (ISSUE 5 tentpole): the topology-aware program
# IR (core/graph.py) replaces the positional layer lists — every
# network-level entry point walks a validated topological schedule,
# keys weights/plans/operand tables by *node name*, and frees
# inter-layer activation buffers per the graph's liveness plan.
# ---------------------------------------------------------------------------

def plan_graph(graph: NetworkGraph,
               sram_budget: int = 128 * 1024) -> "OrderedDict[str, Plan]":
    """Plan every conv node's decomposition under one buffer budget."""
    with _trace.span(f"plan:{graph.name}", cat="plan",
                     sram_budget=sram_budget) as sp:
        plans = OrderedDict((n.name,
                             plan_decomposition(n.layer, sram_budget))
                            for n in graph.conv_nodes())
        traffic = sum(p.dram_traffic for p in plans.values())
        _metrics.registry().counter(
            "modelled_dram_traffic_bytes").inc(traffic)
        if sp is not None:
            sp.attrs.update(nodes=len(plans), dram_traffic_bytes=traffic)
    return plans


# the shared per-conv-node calling convention lives in core/graph.py
_conv_keyed = conv_keyed


def compile_graph(graph: NetworkGraph,
                  plans) -> "OrderedDict[str, TileProgram]":
    """Lower every conv node's Plan to its TileProgram, keyed by node."""
    plans = _conv_keyed(graph, plans, "plans")
    with _trace.span(f"lower:{graph.name}", cat="lower",
                     nodes=len(plans)):
        return OrderedDict((name, compile_layer(graph.node(name).layer, p))
                           for name, p in plans.items())


class Epilogue(NamedTuple):
    """What one conv node's megakernel epilogue runs, in order: bias,
    the add of ``residual`` (a value name) if any, the LayerNorm of
    ``norm`` (a norm node name, whose ``(gamma, beta)`` the kernel
    takes) if any, then ``act``; the launch produces value ``out``."""
    act: Optional[str]
    residual: Optional[str]
    out: str
    norm: Optional[str] = None

    def parts(self, pool: bool) -> List[str]:
        """The epilogue's ops by name, as the kernel span lists them."""
        return (["bias"] + ["residual"] * (self.residual is not None)
                + ["norm"] * (self.norm is not None)
                + [self.act] * (self.act is not None)
                + ["pool"] * pool)


def _graph_epilogues(graph: NetworkGraph) -> "dict[str, Epilogue]":
    """Per conv node, its ``Epilogue``.

    Residual-fused convs produce the ADD's value (the add node itself
    is skipped) and take the add's activation; a norm fused after
    either (``norm_fusion``) produces the NORM's value and its
    activation is the epilogue's; all other convs keep their own. Used
    by the megakernel paths — the paper's accumulation-SRAM add lives
    in the kernel epilogue.
    """
    rf = residual_fusion(graph)
    conv_res = rf.conv_residual()
    add_of = rf.add_of_conv()
    norm_of = norm_fusion(graph).norm_of_conv()
    by_name = {n.name: n for n in graph.nodes}
    out = {}
    for n in graph.conv_nodes():
        last = by_name[add_of[n.name]] if n.name in conv_res else n
        if n.name in norm_of:
            last = by_name[norm_of[n.name]]
        out[n.name] = Epilogue(last.act, conv_res.get(n.name), last.name,
                               norm_of.get(n.name))
    return out


def _graph_kernel_program(program: TileProgram, act: Optional[str],
                          residual: bool,
                          vmem_budget: Optional[int],
                          batch: int = 1,
                          norm: bool = False) -> KernelProgram:
    """Megakernel lowering for one graph conv node: the node's
    activation (or its fused add's or norm's) in the epilogue, the
    layer's pool fused when it has one, the residual operand when an
    add folds in, the channel norm when a norm does, and the schedule
    re-planned at the kernel's VMEM budget point (``plan_for_vmem``;
    ``None`` replays the given program 1:1). ``batch`` requests that
    many images per grid step (clamped to the budget by the
    lowering)."""
    l = program.layer
    fuse = l.pool > 1
    if vmem_budget is None:
        return _lower_kernel_cached(_partition_waves_cached(program),
                                    act=act, fuse_pool=fuse,
                                    residual=residual, norm=norm,
                                    vmem_budget=None, batch_block=batch)
    plan = plan_for_vmem(l, vmem_budget, fuse, residual=residual,
                         batch=batch)
    return _lower_kernel_cached(
        _partition_waves_cached(compile_layer(l, plan)),
        act=act, fuse_pool=fuse, residual=residual, norm=norm,
        vmem_budget=vmem_budget, batch_block=batch)


def _epilogue_nodes(graph: NetworkGraph) -> set:
    """The add and norm nodes that run inside a conv's epilogue."""
    return (set(residual_fusion(graph).as_dict())
            | set(norm_fusion(graph).as_dict()))


def _outside_epilogues(graph: NetworkGraph) -> int:
    """Norm and GELU ops the megakernel forward runs as XLA ops, outside
    every conv epilogue: unfused norms, and GELUs on unfused adds and
    norms."""
    inside = _epilogue_nodes(graph)
    return sum((n.op == "norm") + (n.act == "gelu")
               for n in graph.nodes
               if n.op != "conv" and n.name not in inside)


def graph_kernel_programs(
        graph: NetworkGraph, programs,
        vmem_budget: Optional[int] = _VMEM_DEFAULT,
        batch: int = 1,
        quantized: bool = False) -> "OrderedDict[str, KernelProgram]":
    """The megakernel lowering of a whole graph, exactly as the graph
    forward replays it (per-node epilogue activation, fused residual
    adds and norms, fused pools, VMEM re-planning) — public so weight
    packers and accuracy harnesses lower the same programs the forward
    replays.

    With a tracer active, each node's lowering is a ``cat="kernel"``
    span (attrs ``node``, ``epilogue``, ``fold``, ``vmem_bytes``,
    ``grid_steps``) inside this call's ``lower`` span, whose
    ``norm_gelu_outside`` attr counts the norm and GELU ops left outside
    every epilogue. ``fold`` is how the node's per-layer launch folds
    its input (``megakernel_fold``): the int8 launch (``quantized``)
    never folds taps."""
    programs = _conv_keyed(graph, programs, "programs")
    epi = _graph_epilogues(graph)
    out = OrderedDict()
    with _trace.span(f"lower_kernels:{graph.name}", cat="lower",
                     nodes=len(programs), batch=batch) as top:
        for name, p in programs.items():
            e = epi[name]
            with _trace.span(f"kernel:{name}", cat="kernel", node=name,
                             epilogue=e.parts(p.layer.pool > 1)) as sp:
                kp = out[name] = _graph_kernel_program(
                    p, e.act, e.residual is not None, vmem_budget, batch,
                    norm=e.norm is not None)
                if sp is not None:
                    n_bb, _ = batch_grid(batch, kp.batch_block)
                    sp.attrs.update(fold=megakernel_fold(kp, quantized),
                                    vmem_bytes=kp.vmem_bytes,
                                    grid_steps=n_bb * kp.n_tiles
                                    * kp.n_chain)
        if top is not None:
            top.attrs.update(norm_gelu_outside=_outside_epilogues(graph))
    return out


def graph_chain_programs(graph: NetworkGraph, programs,
                         vmem_budget: Optional[int] = _VMEM_DEFAULT,
                         quantized: bool = False,
                         batch: int = 1):
    """Partition a graph into fused chains and lower each multi-node
    chain to its whole-chain ``GraphKernelProgram``.

    Returns ``(chains, kprogs, gkps)``: the ``FusedChain`` partition in
    schedule order, the per-node ``KernelProgram`` map (single-node
    chains fall back to these per-layer launches), and the
    ``GraphKernelProgram`` per multi-node chain keyed by its HEAD conv
    name. Deterministic for a (graph, programs, budget, precision,
    batch) tuple, so operand tables and the forward fn derive the
    identical partition independently.

    ``batch`` (ISSUE 8): chain MEMBERSHIP is still decided at the
    per-image footprint (a chain valid at one image per step stays
    fusible at any batch), but each chain's kernel is lowered with the
    largest per-step image block whose whole-chain arena + accumulator
    footprint fits the budget."""
    programs = _conv_keyed(graph, programs, "programs")
    with _trace.span(f"lower_chains:{graph.name}", cat="lower",
                     batch=batch, quantized=quantized) as sp:
        kprogs = graph_kernel_programs(graph, programs, vmem_budget, batch,
                                       quantized)
        chains = fusible_chains(graph, kprogs, vmem_budget=vmem_budget,
                                quantized=quantized)
        epi = _graph_epilogues(graph)
        by_name = {n.name: n for n in graph.nodes}
        gkps = {}
        for c in chains:
            if len(c.convs) < 2:
                continue
            specs = [ChainNodeSpec(name=name, kp=kprogs[name],
                                   in_value=by_name[name].inputs[0],
                                   out_value=epi[name].out,
                                   residual_value=epi[name].residual)
                     for name in c.convs]
            gkps[c.convs[0]] = lower_graph_kernel(
                specs, quantized=quantized,
                batch_block=_chain_batch_block(specs, quantized,
                                               vmem_budget, batch))
        if sp is not None:
            sp.attrs.update(chains=len(chains), fused=len(gkps))
    return chains, kprogs, gkps


def _chain_batch_block(specs, quantized: bool,
                       vmem_budget: Optional[int], batch: int) -> int:
    """Largest images-per-step block whose whole-chain VMEM footprint
    (arena slots + accumulator + input/output blocks, all per-image)
    fits ``vmem_budget``. ``chain_plan_bytes`` is affine in the block
    size — weights and bias are batch-shared — so the bound solves in
    two evaluations. ``None`` budget takes the full batch."""
    bb = max(1, int(batch))
    if vmem_budget is None or bb == 1:
        return bb
    from repro.core.schedule import chain_plan_bytes
    b1 = chain_plan_bytes(specs, quantized=quantized, batch_block=1)
    per = chain_plan_bytes(specs, quantized=quantized, batch_block=2) - b1
    if per <= 0:
        return bb
    fit = (vmem_budget - (b1 - per)) // per
    return max(1, min(bb, int(fit)))


def graph_operands(graph: NetworkGraph, programs, mode: str = "wave",
                   vmem_budget: Optional[int] = _VMEM_DEFAULT,
                   precision: str = "fp32",
                   batch: int = 1) -> "OrderedDict[str, jax.Array]":
    """Per-conv-node operand tables matching ``graph_forward_fn``,
    keyed by node name (wave dispatch tables, megakernel SMEM tables,
    whole-chain graphkernel tables keyed by chain head, or flat scan
    step tables). Pass the same ``batch`` as the forward builder — the
    batch-aware chain coarsening can change table shapes."""
    mode = _normalize_mode(mode)
    if mode == "interpret":
        raise ValueError("interpret mode has no operand tables")
    if mode != "megakernel" or precision != "fp32":
        refuse_norm_gelu(graph, f"mode={mode!r} precision={precision!r}")
    programs = _conv_keyed(graph, programs, "programs")
    if mode == "graphkernel":
        chains, kprogs, gkps = graph_chain_programs(
            graph, programs, vmem_budget,
            quantized=precision == "int8", batch=batch)
        return OrderedDict(
            (c.convs[0],
             jnp.asarray(gkps[c.convs[0]].operand_table()
                         if c.convs[0] in gkps
                         else kprogs[c.convs[0]].operand_table()))
            for c in chains)
    if mode == "megakernel":
        return OrderedDict(
            (name, jnp.asarray(kp.operand_table()))
            for name, kp in graph_kernel_programs(
                graph, programs, vmem_budget, batch).items())
    if mode == "wave":
        return OrderedDict(
            (name, jnp.asarray(
                _partition_waves_cached(p).tile_operands()))
            for name, p in programs.items())
    return OrderedDict((name, jnp.asarray(p.operands()))
                       for name, p in programs.items())


def graph_forward_fn(graph: NetworkGraph, programs,
                     conv_fn: Optional[Callable] = None,
                     conv_backend: str = "xla",
                     mode: str = "wave",
                     pool_backend: str = "xla",
                     vmem_budget: Optional[int] = _VMEM_DEFAULT,
                     precision: str = "fp32",
                     qgraph=None,
                     dequantize: bool = True,
                     batch: int = 1) -> Callable:
    """Whole-graph forward over pre-lowered programs, built for one jit.

    Returns ``f(x, weights, ops) -> y`` where ``weights`` maps conv
    node name -> (w, b) (or the int8 weight tuples) and ``ops`` maps
    node name -> operand table (``graph_operands(graph, programs,
    mode)``). The walk follows the graph's validated topological
    schedule; residual ``add`` nodes execute as explicit elementwise
    ops in wave/scan modes and fold into the producing conv's kernel
    epilogue in the megakernel modes (``residual_fusion``, the paper's
    accumulation-SRAM add); activation references are dropped per the
    graph's buffer-liveness plan the moment their last consumer fired,
    so XLA reuses the HBM buffers instead of holding every edge alive
    to the end of the pass.

    ``precision="int8"`` (megakernel only) walks the same schedule on
    the fixed-point datapath over a calibrated ``qgraph``
    (``quant.calibrate.QuantizedGraph``): raw int8 activations flow
    along every edge (calibration unified the scales at add nodes, so
    shortcut adds are plain integer adds + clip), residual adds run in
    the int8 kernel epilogue, and ``weights`` are
    ``qgraph.device_weights()``. ``dequantize=False`` returns raw int8.
    """
    mode = _normalize_mode(mode)
    if mode == "interpret":
        raise ValueError("the compiled network path has no interpret "
                         "mode — use run_network_streamed for that")
    if pool_backend not in ("xla", "fused"):
        raise ValueError(f"unknown pool backend {pool_backend!r} "
                         f"(expected xla | fused)")
    if precision not in ("fp32", "int8"):
        raise ValueError(f"unknown precision {precision!r} "
                         f"(expected fp32 | int8)")
    if mode != "megakernel" or precision != "fp32":
        refuse_norm_gelu(graph, f"mode={mode!r} precision={precision!r}")
    programs = _conv_keyed(graph, programs, "programs")
    sched = topological_schedule(graph)
    bplan = plan_buffers(graph)

    if precision == "int8":
        if mode not in ("megakernel", "graphkernel"):
            raise ValueError(
                "precision='int8' runs on the quantized megakernel only "
                "— pass mode='megakernel' or mode='graphkernel'")
        if qgraph is None:
            raise ValueError(
                "precision='int8' needs a calibrated QuantizedGraph — "
                "run repro.quant.calibrate_graph (or calibrate_network "
                "for a linear stack) over a few batches first")
        from repro.core.quantization import (dequantize_int8,
                                             quantize_int8_sym)
        from repro.kernels.wave_replay_q.graph import wave_replay_graph_q
        from repro.kernels.wave_replay_q.kernel import residual_add_i8
        from repro.kernels.wave_replay_q.ops import wave_replay_q_layer
        epi = _graph_epilogues(graph)
        if mode == "graphkernel":
            chains, kprogs, gkps = graph_chain_programs(
                graph, programs, vmem_budget, quantized=True,
                batch=batch)
            chain_of = {c.convs[0]: c for c in chains}
            members = {name for c in chains for name in c.convs[1:]}
        else:
            kprogs = graph_kernel_programs(graph, programs, vmem_budget,
                                           batch, quantized=True)
            chain_of, members, gkps = {}, set(), {}
        statics = {name: (qgraph.quants[name].pre_shift,
                          qgraph.quants[name].fan_chunk)
                   for name in kprogs}
        in_scale = float(qgraph.scales[INPUT])
        out_scale = float(qgraph.scales[graph.output])
        fused_adds = {e.out for e in epi.values()
                      if e.residual is not None}

        def forward_q(x, weights, ops):
            check_graph_input(graph, x)       # trace-time, per shape
            env = {INPUT: x if x.dtype == jnp.int8
                   else quantize_int8_sym(x, in_scale)}
            for i, n in enumerate(sched):
                if n.op == "conv":
                    if n.name in members:
                        pass                  # runs inside its chain head
                    elif n.name in gkps:      # multi-node fused chain
                        c = chain_of[n.name]
                        env[c.output_value] = wave_replay_graph_q(
                            gkps[n.name], env[c.input_value],
                            [weights[m] for m in c.convs],
                            pre_shifts=[statics[m][0] for m in c.convs],
                            fan_chunks=[statics[m][1] for m in c.convs],
                            table=ops[n.name])
                    else:
                        e = epi[n.name]
                        wq, bq, m, s = weights[n.name]
                        ps, fc = statics[n.name]
                        env[e.out] = wave_replay_q_layer(
                            kprogs[n.name], env[n.inputs[0]],
                            wq, bq, m, s,
                            pre_shift=ps, fan_chunk=fc,
                            table=ops[n.name],
                            residual=env[e.residual]
                            if e.residual is not None else None)
                elif n.name not in fused_adds:
                    env[n.name] = residual_add_i8(
                        env[n.inputs[0]], env[n.inputs[1]], n.relu)
                for v in bplan.frees[i]:        # liveness: drop dead refs
                    env.pop(v, None)
            y = env[graph.output]
            return dequantize_int8(y, out_scale) if dequantize else y

        return forward_q

    if mode in ("megakernel", "graphkernel"):
        from repro.kernels.wave_replay.graph import wave_replay_graph
        from repro.kernels.wave_replay.ops import wave_replay_layer
        epi = _graph_epilogues(graph)
        if mode == "graphkernel":
            chains, kprogs, gkps = graph_chain_programs(
                graph, programs, vmem_budget, quantized=False,
                batch=batch)
            chain_of = {c.convs[0]: c for c in chains}
            members = {name for c in chains for name in c.convs[1:]}
        else:
            kprogs = graph_kernel_programs(graph, programs, vmem_budget,
                                           batch)
            chain_of, members, gkps = {}, set(), {}
        fused = _epilogue_nodes(graph)
        n_norms = sum(n.op == "norm" for n in graph.nodes)
        n_fused = len(norm_fusion(graph).fused)
        reg = _metrics.registry()
        reg.counter("graph.norms_fused").inc(n_fused)
        reg.counter("graph.norms_unfused").inc(n_norms - n_fused)
        reg.counter("graph.taps_folded").inc(sum(
            megakernel_fold(kp) == "taps" for name, kp in kprogs.items()
            if name not in members and name not in gkps))

        def forward_mega(x, weights, ops):
            check_graph_input(graph, x)       # trace-time, per shape
            env = {INPUT: x}
            for i, n in enumerate(sched):
                if n.op == "conv":
                    if n.name in members:
                        pass                  # runs inside its chain head
                    elif n.name in gkps:      # multi-node fused chain
                        c = chain_of[n.name]
                        env[c.output_value] = wave_replay_graph(
                            gkps[n.name], env[c.input_value],
                            [weights[m] for m in c.convs],
                            table=ops[n.name]).astype(x.dtype)
                    else:
                        e = epi[n.name]
                        w, b = weights[n.name]
                        env[e.out] = wave_replay_layer(
                            kprogs[n.name], env[n.inputs[0]], w, b,
                            table=ops[n.name],
                            residual=env[e.residual]
                            if e.residual is not None else None,
                            norm=weights[e.norm] if e.norm is not None
                            else None).astype(x.dtype)
                elif n.name not in fused:
                    if n.op == "norm":
                        y = channel_norm_direct(env[n.inputs[0]],
                                                *weights[n.name])
                    else:
                        y = env[n.inputs[0]] + env[n.inputs[1]]
                    env[n.name] = activation_direct(y, n.act)
                for v in bplan.frees[i]:        # liveness: drop dead refs
                    env.pop(v, None)
            return env[graph.output]

        return forward_mega

    conv_fns = {name: _resolve_conv_fn(conv_fn, conv_backend,
                                       p.layer.stride)[0]
                for name, p in programs.items()}
    wprogs = {name: _partition_waves_cached(p) if mode == "wave" else None
              for name, p in programs.items()}
    if pool_backend == "fused":
        from repro.kernels.fused_conv_pool.ops import fused_conv_pool

    def forward(x, weights, ops):
        check_graph_input(graph, x)           # trace-time, per shape
        env = {INPUT: x}
        for i, n in enumerate(sched):
            if n.op == "conv":
                l = n.layer
                xin = env[n.inputs[0]]
                w, b = weights[n.name]
                if pool_backend == "fused" and l.pool > 1 and n.relu:
                    env[n.name] = fused_conv_pool(
                        xin, w, b, stride=l.stride, pad=l.pad,
                        pool=l.pool, pool_stride=l.pool_stride or l.pool,
                        relu=True, groups=l.groups).astype(x.dtype)
                else:
                    wprog = wprogs[n.name]
                    if wprog is not None:
                        y = _wave_executor(wprog, conv_fns[n.name],
                                           b is not None, xin, w, b,
                                           ops[n.name])
                    else:
                        y = _scan_executor(programs[n.name],
                                           conv_fns[n.name],
                                           b is not None, xin, w, b,
                                           ops[n.name])
                    if n.relu:
                        y = jnp.maximum(y, 0)
                    if l.pool > 1:
                        y = maxpool_direct(y, l.pool,
                                           l.pool_stride or l.pool)
                    env[n.name] = y
            else:
                y = env[n.inputs[0]] + env[n.inputs[1]]
                env[n.name] = jnp.maximum(y, 0) if n.relu else y
            for v in bplan.frees[i]:            # liveness: drop dead refs
                env.pop(v, None)
        return env[graph.output]

    return forward


def run_graph_reference(graph: NetworkGraph, weights,
                        x: jax.Array) -> "OrderedDict[str, jax.Array]":
    """Direct (undecomposed) reference forward over the graph schedule,
    returning EVERY value (``"input"`` included): each conv value is
    post-bias/activation/pool, each add and norm value
    post-activation; every op runs unfused. The single oracle the
    streamed executors are tested against AND the tensor set PTQ
    calibration observes (quant/calibrate.py) — one walk, so the two
    can never drift apart. ``weights`` as ``graph_params`` reads
    them."""
    check_graph_input(graph, x)
    weights = graph_params(graph, weights)
    env = OrderedDict({INPUT: x})
    for n in topological_schedule(graph):
        if n.op == "conv":
            l = n.layer
            w, b = weights[n.name]
            y = conv2d_direct(env[n.inputs[0]], w.astype(x.dtype),
                              l.stride, l.pad, groups=l.groups)
            if b is not None:
                y = y + b.astype(x.dtype)
            y = activation_direct(y, n.act)
            if l.pool > 1:
                y = maxpool_direct(y, l.pool, l.pool_stride or l.pool)
        elif n.op == "norm":
            y = activation_direct(
                channel_norm_direct(env[n.inputs[0]], *weights[n.name]),
                n.act)
        else:
            y = activation_direct(env[n.inputs[0]] + env[n.inputs[1]],
                                  n.act)
        env[n.name] = y
    return env


def run_graph_streamed(graph: NetworkGraph, plans, x: jax.Array, weights,
                       conv_fn: Optional[Callable] = None,
                       mode: str = "wave", conv_backend: str = "xla",
                       precision: str = "fp32", qgraph=None,
                       liveness: bool = True,
                       track_peak: Optional[list] = None) -> jax.Array:
    """Run a NetworkGraph end to end through the streaming executors.

    ``plans``/``weights`` map conv node name -> Plan / (w, b), or are
    sequences in schedule order. ``mode="interpret"`` walks the graph
    eagerly with the per-tile Python executor (adds as explicit
    elementwise ops); the compiled modes build one whole-graph
    executable, cached by the graph's **topology key** plus per-node
    schedule geometry — two graphs sharing a layer geometry but wired
    differently can never collide. ``precision="int8"`` (megakernel /
    graphkernel) needs a calibrated ``qgraph`` and ignores ``weights``.

    ``mode="graphkernel"`` partitions the graph into fused chains
    (``fusible_chains``) and runs each multi-node chain as ONE
    persistent pallas_call with a VMEM activation arena carrying every
    inter-layer tensor — zero HBM round-trips inside a chain,
    O(#chains) launches per forward.

    ``liveness=False`` disables the buffer-liveness pass on the eager
    walk (every activation held to the end — the naive per-edge
    allocation, kept for A/B measurement). ``track_peak``, a list,
    receives the measured peak of summed live activation bytes across
    the eager walk (interpret mode only — the compiled modes manage
    buffers inside XLA).
    """
    mode = _normalize_mode(mode)
    check_graph_input(graph, x)
    plans = _conv_keyed(graph, plans, "plans")
    if precision != "int8":
        weights = graph_params(graph, weights)
    if mode == "interpret":
        if precision != "fp32":
            raise ValueError("interpret mode is fp32-only — the int8 "
                             "datapath runs on the megakernel")
        sched = topological_schedule(graph)
        bplan = plan_buffers(graph) if liveness else None
        env = {INPUT: x}
        peak = x.nbytes
        for i, n in enumerate(sched):
            if n.op == "conv":
                l = n.layer
                w, b = weights[n.name]
                y = run_layer_interpreted(l, plans[n.name],
                                          env[n.inputs[0]], w, b, conv_fn)
                y = activation_direct(y, n.act)
                if l.pool > 1:
                    y = maxpool_direct(y, l.pool, l.pool_stride or l.pool)
                env[n.name] = y
            elif n.op == "norm":
                env[n.name] = activation_direct(channel_norm_direct(
                    env[n.inputs[0]], *weights[n.name]), n.act)
            else:
                y = env[n.inputs[0]] + env[n.inputs[1]]
                env[n.name] = activation_direct(y, n.act)
            peak = max(peak, sum(int(v.nbytes) for v in env.values()))
            if bplan is not None:
                for v in bplan.frees[i]:
                    env.pop(v, None)
        if track_peak is not None:
            track_peak.append(peak)
        return env[graph.output]

    programs = compile_graph(graph, plans)
    conv_key = _resolve_conv_fn(
        conv_fn, conv_backend,
        next(iter(programs.values())).layer.stride)[1]
    # the int8 forward bakes the calibration statics in as Python
    # constants (entry/exit scales, per-node pre_shift/fan_chunk), so
    # they must key the executable — a recalibrated graph over the same
    # geometry must never reuse a stale executable (the per-layer int8
    # path keys the same values)
    qsig = ()
    if precision == "int8":
        qsig = (float(qgraph.scales[INPUT]),
                float(qgraph.scales[graph.output]),
                tuple((name, q.pre_shift, q.fan_chunk)
                      for name, q in sorted(qgraph.quants.items())))
    key = (graph.topology_key,
           tuple(p.geometry for p in programs.values()),
           mode, precision, conv_key, qsig, x.shape[0], str(x.dtype))
    build = lambda: jax.jit(graph_forward_fn(
        graph, programs, conv_fn=conv_fn, conv_backend=conv_backend,
        mode=mode, precision=precision, qgraph=qgraph,
        batch=x.shape[0]))
    ops = graph_operands(graph, programs, mode, precision=precision,
                         batch=x.shape[0])
    if precision == "int8":
        return _call_cached(key, build, x, qgraph.device_weights(), ops)
    return _call_cached(key, build, x, weights, ops)


# ---------------------------------------------------------------------------
# Linear-stack wrappers: the old positional-list entry points, now thin
# shims over the graph IR (a chain graph IS the old implicit contract)
# ---------------------------------------------------------------------------

def run_network_streamed(layers, plans, x, weights, conv_fn=None,
                         mode: str = "wave", conv_backend: str = "xla"):
    """Run a linear CONV(+POOL) stack through the streaming executor —
    ``run_graph_streamed`` over the stack's chain graph."""
    g = chain_graph(tuple(layers))
    return run_graph_streamed(g, list(plans), x, list(weights),
                              conv_fn=conv_fn, mode=mode,
                              conv_backend=conv_backend)


def network_forward_fn(programs: Sequence[TileProgram],
                       conv_fn: Optional[Callable] = None,
                       conv_backend: str = "xla",
                       mode: str = "wave",
                       pool_backend: str = "xla",
                       vmem_budget: Optional[int] = _VMEM_DEFAULT,
                       precision: str = "fp32",
                       qnet=None,
                       dequantize: bool = True,
                       batch: int = 1) -> Callable:
    """Whole-network forward over pre-lowered programs, built for one jit.

    The linear-stack shim over ``graph_forward_fn``: the positional
    ``programs`` list becomes a chain graph, and the returned
    ``f(x, weights, ops_list)`` keeps the historical list-based calling
    convention — one (w, b) pair and one operand table per layer, in
    stack order (build the tables with ``network_operands``; pass the
    SAME ``vmem_budget`` to both). All executor semantics — wave/scan/
    megakernel modes, fused pools, VMEM re-planning, buffer liveness —
    live in ``graph_forward_fn``.

    ``precision="int8"`` (megakernel only) builds the fixed-point
    forward over a calibrated ``qnet``
    (``quant.calibrate.QuantizedNetwork``, adapted to the chain graph's
    ``QuantizedGraph``): the input batch is quantized once at entry,
    every layer runs the int8 megakernel, and raw int8 activations flow
    between layers with **zero** dequant round-trips. ``weights`` must
    then be the per-layer ``(wq, bias_q, m, shift)`` tuples from
    ``qnet.device_weights()``. ``dequantize=False`` returns raw int8.
    """
    programs = list(programs)
    g = chain_graph(tuple(p.layer for p in programs))
    progs = {p.layer.name: p for p in programs}
    qgraph = qnet
    if precision == "int8":
        if _normalize_mode(mode) != "megakernel":
            raise ValueError(
                "precision='int8' runs on the quantized megakernel only "
                "— pass mode='megakernel'")
        if qnet is None:
            raise ValueError(
                "precision='int8' needs a calibrated QuantizedNetwork — "
                "run repro.quant.calibrate_network over a few batches "
                "first and pass it as qnet=")
        if not hasattr(qnet, "scales"):
            from repro.quant.calibrate import quantized_graph_from_network
            qgraph = quantized_graph_from_network(qnet, g)
    f_graph = graph_forward_fn(g, progs, conv_fn=conv_fn,
                               conv_backend=conv_backend, mode=mode,
                               pool_backend=pool_backend,
                               vmem_budget=vmem_budget,
                               precision=precision, qgraph=qgraph,
                               dequantize=dequantize, batch=batch)
    names = [n.name for n in g.conv_nodes()]

    def forward(x, weights, ops_list):
        return f_graph(x, dict(zip(names, weights)),
                       dict(zip(names, ops_list)))

    return forward


@functools.lru_cache(maxsize=128)
def plan_for_vmem(layer: ConvLayer,
                  vmem_budget: int = _VMEM_DEFAULT,
                  fuse_pool: bool = False,
                  max_tiles: int = 8,
                  residual: bool = False,
                  batch: int = 1) -> Plan:
    """Re-plan a layer's decomposition at the megakernel's VMEM budget.

    DESIGN.md §6's point made literal: the decomposition planner serves
    any buffer budget, and the megakernel's scratch is real VMEM (MBs),
    not the paper's 128 KB SRAM — so the kernel replays the schedule the
    planner produces *for its own budget point*: the fewest (tile x
    chain) grid steps whose fp32 working set (``KernelProgram.
    plan_bytes``) fits, ties broken toward the smaller working set.
    Feature splits stay at 1 — the kernel folds the feature axis into
    its matmul width. When nothing fits the budget (working sets shrink
    with more tiles/splits only down to the halo/weight floor), the
    over-budget candidate with the fewest steps wins — an oversubscribed
    scratch beats a grid that explodes the step count. ``residual``
    (graph convs with a fused add) counts the residual block in each
    candidate's working set.

    ``batch`` (ISSUE 8) makes the scoring batch-aware: each candidate
    is lowered with ``batch_block=batch`` so the budget clamp sizes the
    per-step image block, and the step count becomes the TOTAL grid
    steps for the whole batch — ``ceil(batch / batch_block) * tiles *
    chain`` — so a plan whose accumulator leaves room for more images
    per step beats one that wins per-image but serialises the batch.
    ``batch=1`` reproduces the historical per-image scoring exactly.
    """
    best = None          # ((over_budget, grid_steps, ws), plan)
    in_choices = sorted({1, 2, 4, 8, 16, 32, 64, 128, layer.in_c})
    for th in range(1, max_tiles + 1):
        for tw in range(1, max_tiles + 1):
            for cs in in_choices:
                if cs > layer.in_c:
                    continue
                p = evaluate(layer, th, tw, 1, cs)
                if p is None:
                    continue
                kp = _lower_kernel_cached(
                    _partition_waves_cached(compile_layer(layer, p)),
                    act="relu", fuse_pool=fuse_pool, residual=residual,
                    vmem_budget=None if batch == 1 else vmem_budget,
                    batch_block=batch)
                ws = kp.plan_bytes
                n_bb = -(-batch // kp.batch_block)
                key = (ws > vmem_budget,
                       n_bb * kp.n_tiles * kp.n_chain, ws)
                if best is None or key < best[0]:
                    best = (key, p)
    if best is None:
        raise PlanError(f"{layer.name}: no feasible megakernel plan")
    return best[1]


def network_kernel_programs(
        programs: Sequence[TileProgram],
        vmem_budget: Optional[int] = _VMEM_DEFAULT,
        batch: int = 1) -> List["KernelProgram"]:
    """The megakernel lowering of a whole linear stack, as the network
    path builds it (ReLU fused, pools fused, VMEM re-planning) — public
    so the int8 weight packers and the accuracy harness lower the exact
    same programs the forward fn replays. Graph callers use
    ``graph_kernel_programs`` (which also wires residual epilogues)."""
    return [_network_kernel_program(p, vmem_budget, batch)
            for p in programs]


def _network_kernel_program(
        program: TileProgram,
        vmem_budget: Optional[int] = _VMEM_DEFAULT,
        batch: int = 1) -> KernelProgram:
    """The linear-stack megakernel lowering: ReLU always fused, the
    layer's max-pool fused whenever it has one, no residual operand —
    ``_graph_kernel_program`` with a chain node's flags."""
    return _graph_kernel_program(program, act="relu", residual=False,
                                 vmem_budget=vmem_budget, batch=batch)


def network_operands(programs: Sequence[TileProgram], mode: str = "wave",
                     vmem_budget: Optional[int] = _VMEM_DEFAULT,
                     batch: int = 1):
    """Per-layer operand tables matching ``network_forward_fn(mode=...)``
    in stack order: wave-encoded ``(n_waves, n_tiles, 6)`` dispatch
    tables for wave mode, SMEM ``(n_chain, n_tiles, 8)`` megakernel
    tables for megakernel (pass the same ``vmem_budget`` as the forward
    builder), flat ``(n_steps, 7)`` step tables for scan. The list
    shim over ``graph_operands``."""
    programs = list(programs)
    g = chain_graph(tuple(p.layer for p in programs))
    ops = graph_operands(g, {p.layer.name: p for p in programs}, mode,
                         vmem_budget, batch=batch)
    return [ops[n.name] for n in g.conv_nodes()]
