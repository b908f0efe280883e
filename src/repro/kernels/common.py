"""Shared kernel-launch policy helpers and in-kernel building blocks."""
from __future__ import annotations

import contextlib
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.obs import metrics as _metrics
from repro.obs import trace as _trace
from repro.runtime.errors import BudgetExceeded


class LaunchCounter:
    """Trace-time kernel-launch counter shared by both megakernel
    families (fp32 ``wave_replay``, int8 ``wave_replay_q``).

    A launch increments at jax *trace* time — once per pallas_call
    built, not per execution — which is exactly the dispatch count the
    paper's launch-overhead argument cares about. ``record(...)``
    counts one launch (per-family local count + ``kernel_launches`` /
    ``kernel_launches.<family>`` in the current metrics registry) and
    returns a context to wrap the kernel build: a ``cat="execute"``
    span, so the execute-phase span count in a trace equals the launch
    counter by construction, inside ``jax.named_scope("<kind>:<node>")``,
    so every op built for the node (its pads, crops and the
    ``pallas_call``) carries the node in its HLO ``op_name`` and a
    device trace can charge its time to the node. The local count backs
    the historical ``launch_count()`` / ``reset_launch_count()``
    per-family API.
    """

    def __init__(self, family: str):
        self.family = family
        self._count = 0

    def record(self, node: str, kind: str):
        """Count one launch; returns the named scope and span context
        (the span a no-op when tracing is disabled) to wrap the kernel
        construction."""
        self._count += 1
        reg = _metrics.registry()
        reg.counter("kernel_launches").inc()
        reg.counter(f"kernel_launches.{self.family}").inc()
        return self._scope(node, kind)

    @contextlib.contextmanager
    def _scope(self, node: str, kind: str):
        name = f"{kind}:{node}"
        with jax.named_scope(name), \
                _trace.span(name, cat="execute", family=self.family,
                            node=node, kind=kind):
            yield

    def count(self) -> int:
        return self._count

    def reset(self) -> None:
        self._count = 0


def pallas_interpret_default() -> bool:
    """Pallas interpret mode unless a real TPU backs the computation.

    Compiled Pallas lowering needs Mosaic/TPU; everywhere else (CPU CI,
    GPU hosts) the kernels run under the interpreter. Callers pass
    ``interpret=None`` to defer to this single policy point.
    """
    return jax.default_backend() != "tpu"


def element_block(shape) -> tuple:
    """Block shape whose every dim is indexed by element offset.

    Halo windows overlap, so their index maps return element offsets
    (``pl.Element``) rather than block indices on every dimension.
    """
    return tuple(pl.Element(int(n)) for n in shape)


# The TPU vector register is (8 sublanes x 128 lanes) of 32-bit words;
# VMEM buffers are laid out in such tiles over their last two dims.
LANES = 128
SUBLANES = 8


def s2d_factor(layer, c_width: int) -> int:
    """Stride folded into channels at the kernel boundary (1 = none).

    Strided taps are loaded from a lane-tiled copy of the window
    (``stage_lanes``), which pads every pixel to a 128-lane row: over a
    narrow input (AlexNet conv1: 3 channels) a 227x227x3 window becomes
    27 MB of VMEM. Space-to-depth by the stride turns an ungrouped
    strided conv into a stride-1 conv over ``stride**2`` times the
    channels (57x57x48 for conv1, 1.9 MB) with plain loads. The fold
    applies only where the folded ``c_width * stride**2`` channels of a
    step still fit one lane tile, so it costs no VMEM lanes; wider and
    grouped strided layers take the staged path, whose taps carry no
    zero weights.
    """
    s = layer.stride
    if s > 1 and layer.groups == 1 and c_width * s * s <= LANES:
        return s
    return 1


def space_to_depth(x: jax.Array, s: int) -> jax.Array:
    """(B, H, W, C) -> (B, ceil(H/s), ceil(W/s), C*s*s), channel order
    (c, py, px): input pixel (s*i + py, s*j + px, c) lands at
    (i, j, (c*s + py)*s + px), zero-padded to whole s x s cells. The
    channel-major order keeps every original channel range contiguous,
    so chain chunks and exact int8 sub-gemms still slice one range."""
    if s == 1:
        return x
    B, H, W, C = x.shape
    hs, ws = -(-H // s), -(-W // s)
    x = jnp.pad(x, ((0, 0), (0, hs * s - H), (0, ws * s - W), (0, 0)))
    x = x.reshape(B, hs, s, ws, s, C).transpose(0, 1, 3, 5, 2, 4)
    return x.reshape(B, hs, ws, C * s * s)


def space_to_depth_weights(w: jax.Array, s: int) -> jax.Array:
    """(K, K, C, O) -> (ceil(K/s), ceil(K/s), C*s*s, O) matching
    ``space_to_depth``: tap (qy, qx) of the folded conv carries the
    original taps (s*qy + py, s*qx + px), zeros past the kernel edge."""
    if s == 1:
        return w
    K, _, C, O = w.shape
    ks = -(-K // s)
    w = jnp.pad(w, ((0, ks * s - K), (0, ks * s - K), (0, 0), (0, 0)))
    w = w.reshape(ks, s, ks, s, C, O).transpose(0, 2, 4, 1, 3, 5)
    return w.reshape(ks, ks, C * s * s, O)


def conv_rows(acc_ref, b, load, wtap, *, K: int, stride: int, acc_h: int,
              acc_w: int, cin: int, out_c: int, groups: int,
              exact_chunk: "int | None" = None) -> None:
    """Accumulate one grid step's convolution into ``acc_ref[b]``.

    The kernel body shared by every wave-replay kernel. Output row ``i``
    is the sum over the K*K taps of a (acc_w, channels) x (channels,
    out) matmul: ``load(r, kx, c0, cw)`` returns input row ``r``'s
    columns ``kx, kx + stride, ...`` over channels ``[c0, c0 + cw)`` (a
    strided ref load), ``wtap(ky, kx, c0, cw, o0, ow)`` the tap's
    (cw, ow) weights. Rows keep every operand two-dimensional, so no
    value reshape or lane concatenation is needed at any width.

    Grouped layers run one gemm per group over its natural fan slice;
    depthwise layers (one channel per group) a K*K-tap elementwise MAC.
    ``exact_chunk=None`` is the fp32 datapath (HIGHEST-precision dots
    summed in fp32). An int ``exact_chunk`` marks int8-valued operands:
    each dot covers at most that many channels, so every fp32 partial
    sum is an exact integer, and the parts sum in int32 — bit-exact
    against the int32 reference.
    """
    fan = cin // groups
    opg = out_c // groups
    exact = exact_chunk is not None
    step = min(fan, exact_chunk) if exact else fan

    def dot(a, w):
        y = jax.lax.dot_general(a, w, (((1,), (0,)), ((), ())),
                                precision=jax.lax.Precision.HIGHEST,
                                preferred_element_type=jnp.float32)
        return y.astype(jnp.int32) if exact else y

    def row(i, carry):
        r0 = i * stride
        if groups > 1 and fan == 1:
            # depthwise: out channel o reads in channel o // opg, one
            # lane tile of channels at a time. int8 products summed over
            # K*K taps stay far below 2^24, so the fp32 MAC is exact
            # for the int8 datapath too
            for c0, cw in lane_pieces(0, cin, cin):
                a = jnp.zeros((acc_w, cw * opg), jnp.float32)
                for ky in range(K):
                    for kx in range(K):
                        xt = load(r0 + ky, kx, c0, cw)
                        if opg > 1:   # channel-multiplier fan-out
                            xt = jnp.repeat(xt, opg, axis=-1)
                        a = a + xt * wtap(ky, kx, 0, 1, c0 * opg, cw * opg)
                if exact:
                    a = a.astype(jnp.int32)
                acc_ref[b, i, 0:acc_w, c0 * opg:(c0 + cw) * opg] += a
        else:
            for g in range(groups):
                a = None
                for ky in range(K):
                    for kx in range(K):
                        for c0, cw in lane_pieces(g * fan, (g + 1) * fan,
                                                  step):
                            part = dot(load(r0 + ky, kx, c0, cw),
                                       wtap(ky, kx, c0 - g * fan, cw,
                                            g * opg, opg))
                            a = part if a is None else a + part
                acc_ref[b, i, 0:acc_w, g * opg:(g + 1) * opg] += a
        return carry

    jax.lax.fori_loop(0, acc_h, row, 0)


def lane_pieces(lo: int, hi: int, step: int):
    """Split channels ``[lo, hi)`` into ``(start, width)`` pieces of at
    most ``step`` that never cross a lane-tile boundary."""
    out, c = [], lo
    while c < hi:
        end = min(hi, c + step, (c // LANES + 1) * LANES)
        out.append((c, end - c))
        c = end
    return out


def lane_tiles(c: int):
    """(count, width) of the lane tiles holding ``c`` channels."""
    return -(-c // LANES), min(c, LANES)


def stage_lanes(dst_ref, v: jax.Array, lead=()) -> None:
    """Park a (..., C) value in a lane-tiled scratch ``dst_ref[lead +
    (tile,)]`` of shape (..., n_tiles, H, W, min(C, 128)) or larger —
    the layout strided loads need, since Mosaic strides only over
    buffers at most one lane tile wide."""
    n, lw = lane_tiles(v.shape[-1])
    for j in range(n):
        w = min(lw, v.shape[-1] - j * lw)
        idx = tuple(lead) + (j,) + tuple(slice(0, d) for d in v.shape[:-1])
        dst_ref[idx + (slice(0, w),)] = v[..., j * lw:j * lw + w]


def lane_load(ref, lead, r, cols, c0: int, cw: int):
    """Rows ``r``, columns ``cols`` of channels ``[c0, c0 + cw)`` from a
    ``stage_lanes`` buffer (the piece lies in one lane tile)."""
    j, o = divmod(c0, LANES)
    return ref[tuple(lead) + (j, r, cols, slice(o, o + cw))]


def strided(start, size: int, stride: int):
    """``pl.ds`` over every ``stride``-th element (plain slice at 1)."""
    if stride == 1:
        return pl.ds(start, size)
    return pl.ds(start, size, stride=stride)


def pool_tile(pool_ref, a: jax.Array, *, pool: int, ps: int,
              blk_h: int, blk_w: int) -> jax.Array:
    """Max-pool an activated accumulator tile ``a`` (acc_h, acc_w, C).

    The tile is parked in the lane-tiled ``pool_ref`` (``stage_lanes``)
    and each of the ``pool * pool`` window taps becomes one strided load
    over every pool window at once — overlapping pools (AlexNet's 3/2)
    re-derive their shared rows for free.
    """
    ah, aw, c = a.shape
    stage_lanes(pool_ref, a)
    n, lw = lane_tiles(c)
    outs = []
    for j in range(n):
        w = min(lw, c - j * lw)
        m = None
        for dy in range(pool):
            for dx in range(pool):
                v = pool_ref[j, strided(dy, blk_h, ps),
                             strided(dx, blk_w, ps), 0:w]
                m = v if m is None else jnp.maximum(m, v)
        outs.append(m)
    return outs[0] if n == 1 else jnp.concatenate(outs, -1)


def pool_scratch(acc_h: int, acc_w: int, c: int, dtype):
    """Shape/dtype of the ``pool_tile`` scratch for a C-channel tile."""
    n, lw = lane_tiles(c)
    return (n, acc_h, acc_w, lw), dtype


def mask_tile(v: jax.Array, valid_rows, valid_cols) -> jax.Array:
    """Zero the rows/cols of a (H, W, C) tile past the valid output —
    the uniform tile grid's padding lanes (full-rank iotas)."""
    rows = jax.lax.broadcasted_iota(jnp.int32, v.shape, 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, v.shape, 1)
    return jnp.where((rows < valid_rows) & (cols < valid_cols), v,
                     jnp.zeros_like(v))


def at_tile_col(tx, tiles_w: int, fn) -> None:
    """Run ``fn(j)`` for the static tile column ``j`` equal to the
    dynamic ``tx``. Output blocks span every tile column (a block of
    ``blk_w`` columns is rarely a multiple of 8 sublanes), and Mosaic
    stores only at column offsets it can prove, so each column gets
    its own statically addressed branch."""
    if tiles_w == 1:
        fn(0)
        return
    for j in range(tiles_w):
        def branch(j=j):
            fn(j)
        pl.when(tx == j)(branch)


def _tiled_bytes(shape, dtype) -> int:
    """VMEM bytes of one buffer laid out in (sublane, lane) tiles."""
    itemsize = jnp.dtype(dtype).itemsize
    dims = [int(d) for d in shape] or [1]
    if len(dims) == 1:
        dims = [1] + dims
    sub = SUBLANES * max(1, 4 // itemsize)
    dims[-1] = -(-dims[-1] // LANES) * LANES
    dims[-2] = -(-dims[-2] // sub) * sub
    return int(np.prod(dims)) * itemsize


# VMEM of one TPU v5e TensorCore, the chip the kernels are built for
VMEM_CAPACITY = 128 * 2 ** 20


@dataclasses.dataclass(frozen=True)
class LaunchVmem:
    """The VMEM one kernel launch holds: the (shape, dtype) of its
    pipelined in/out ``blocks`` (double-buffered), of its ``scratch``
    buffers (in the order the kernel takes them) and of the largest
    values its body holds at once (``temps``)."""
    blocks: tuple
    scratch: tuple
    temps: tuple = ()

    @property
    def bytes(self) -> int:
        """Every buffer counted at its (sublane, lane)-tiled size."""
        return (2 * sum(_tiled_bytes(s, d) for s, d in self.blocks)
                + sum(_tiled_bytes(s, d) for s, d in self.scratch)
                + sum(_tiled_bytes(s, d) for s, d in self.temps))

    def compiler_params(self, name: str, interpret: bool):
        """Compiler parameters whose scoped-VMEM limit fits the launch
        (the default scoped limit is far below what a whole AlexNet
        layer holds). A compiled launch that needs more than the chip
        has raises ``BudgetExceeded``; interpret mode has no VMEM."""
        need = self.bytes
        if not interpret and need > VMEM_CAPACITY:
            raise BudgetExceeded(
                f"{name}: the kernel holds {need / 2 ** 20:.1f} MiB of "
                f"VMEM, more than the {VMEM_CAPACITY // 2 ** 20} MiB of "
                f"a TPU v5e core")
        # headroom for what the count leaves out (Mosaic's own
        # temporaries, each row's dot operands), capped at the chip
        limit = min(VMEM_CAPACITY,
                    max(32 * 2 ** 20, need + need // 2 + 4 * 2 ** 20))
        return pltpu.CompilerParams(vmem_limit_bytes=int(limit))


def megakernel_geometry(kp):
    """``(s, K, stride, ih, full_w, c, fan)`` of a per-layer launch once
    the stride fold (``s2d_factor``) is applied: window rows and buffer
    width, taps, and the channels / weight fan one grid step reads."""
    l = kp.wave.program.layer
    s = s2d_factor(l, kp.c_width)
    return (s, -(-l.kernel // s), l.stride // s, -(-kp.ih // s),
            -(-kp.pad_w // s), kp.c_width * s * s, kp.fan_width * s * s)


def megakernel_vmem(kp, *, quantized: bool = False,
                    bb: "int | None" = None) -> LaunchVmem:
    """The VMEM a per-layer megakernel launch of ``kp`` holds, at
    ``bb`` images per grid step (default ``kp.batch_block``): fp32
    (``kernels/wave_replay``) or int8 (``kernels/wave_replay_q``, whose
    window and weights are staged as fp32 and whose psum bank is
    int32). The launchers take their scratch shapes from here."""
    bb = kp.batch_block if bb is None else bb
    _, k, stride, ih, full_w, c, fan = megakernel_geometry(kp)
    oc = kp.out_c_pad
    io = jnp.int8 if quantized else jnp.float32
    acc_dt = jnp.int32 if quantized else jnp.float32
    out = ((bb, kp.blk_h, kp.out_w_pad, oc), io)
    blocks = [((bb, ih, full_w, c), io), ((k, k, fan, oc), io)]
    blocks += [((1, oc), acc_dt)] * (3 if quantized else 1) + [out]
    if kp.residual:
        blocks.append(out)
    acc = (bb, kp.acc_h, kp.acc_w, oc)
    scratch = [(acc, acc_dt)]
    staged = ((lane_tiles(c)[0], ih, full_w, lane_tiles(c)[1]), jnp.float32)
    if quantized:
        scratch += [staged, ((k, k, fan, oc), jnp.float32)]
    if kp.fuse_pool:
        scratch.append(pool_scratch(kp.acc_h, kp.acc_w, oc, acc_dt))
    if not quantized and stride > 1:
        scratch.append(staged)
    return LaunchVmem(tuple(blocks), tuple(scratch),
                      ((acc[1:], acc_dt),) * 3)


def pool_max_subsampled(a: jax.Array, *, pool: int, stride: int,
                        out_h: int, out_w: int) -> jax.Array:
    """In-VMEM max-pool over the trailing (H, W, C) dims of ``a``.

    The subsampled-slice trick shared by the fused conv+pool kernel and
    the wave-replay megakernel epilogue: the max over ``pool*pool``
    strided slices handles overlapping pools (stride < pool, e.g.
    AlexNet's 3/2) without any window primitive — each candidate slice
    is one (ky, kx) tap of every pool window at once. Leading dims
    (e.g. batch) pass through untouched.
    """
    lead = a.ndim - 3
    cands = []
    for dy in range(pool):
        for dx in range(pool):
            cands.append(jax.lax.slice(
                a,
                (0,) * lead + (dy, dx, 0),
                a.shape[:lead] + (dy + (out_h - 1) * stride + 1,
                                  dx + (out_w - 1) * stride + 1,
                                  a.shape[-1]),
                (1,) * lead + (stride, stride, 1)))
    return functools.reduce(jnp.maximum, cands)
