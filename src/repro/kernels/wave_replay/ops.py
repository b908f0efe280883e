"""Public wrapper for the persistent wave-replay megakernel.

``wave_replay_layer`` takes a layer's *natural* tensors (unpadded input,
per-group weights, optional bias), pads them to the KernelProgram's
buffer geometry, launches the ONE ``pallas_call``, and crops the valid
output — the whole streamed layer in a single kernel launch.

``launch_count()`` counts megakernel launches at trace time (each
``jax.jit`` trace of a network forward launches exactly one per layer) —
the dispatch-counting hook the ISSUE 3 acceptance gate verifies.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.schedule import KernelProgram
from repro.distributed.fault import fault_point
from repro.kernels.common import LaunchCounter
from repro.kernels.wave_replay.kernel import wave_replay_raw

# shared trace-time counter (kernels/common.py): local per-family count
# behind the launch_count() shims below, plus kernel_launches.* metrics
# and a cat="execute" span per launch
launches = LaunchCounter("wave_replay")


def launch_count() -> int:
    """Megakernel launches since ``reset_launch_count`` (trace-time)."""
    return launches.count()


def reset_launch_count() -> None:
    launches.reset()


def expand_grouped(w: jax.Array, groups: int) -> jax.Array:
    """(K, K, Cin/groups, Cout) -> block-diagonal dense (K, K, Cin, Cout).

    Cross-group blocks are zeros. The streaming executors no longer use
    this (ISSUE 10: the kernels accumulate each group's natural fan
    slice directly); it survives as the reference construction for the
    block-diagonal baseline the grouped-speedup bench rows compare
    against, and for tests asserting the two layouts agree.
    """
    if groups == 1:
        return w
    oc = w.shape[-1]
    opg = oc // groups
    rows = [jnp.pad(w[:, :, :, g * opg:(g + 1) * opg],
                    ((0, 0), (0, 0), (0, 0),
                     (g * opg, oc - (g + 1) * opg)))
            for g in range(groups)]
    return jnp.concatenate(rows, axis=2)


def pad_input(kp: KernelProgram, x: jax.Array) -> jax.Array:
    """Pad an input activation to the program's buffer geometry.

    Conv padding goes top/left; the tile grid's trailing zeros (or trim,
    when the conv window never reaches the last rows) complete ``pad_h``
    x ``pad_w``; channels round up to whole chunks. Shared by the
    per-layer launch and the graph kernel's chain-input staging so both
    see bit-identical buffers.
    """
    l = kp.wave.program.layer
    return jnp.pad(x, ((0, 0),
                       (l.pad, max(0, kp.pad_h - l.in_h - l.pad)),
                       (l.pad, max(0, kp.pad_w - l.in_w - l.pad)),
                       (0, kp.in_c_kpad - x.shape[-1])
                       ))[:, :kp.pad_h, :kp.pad_w]


def pad_operands(kp: KernelProgram, x: jax.Array, w: jax.Array,
                 b: jax.Array | None):
    """Pad (x, w, b) to the megakernel's static buffer geometry.

    Input via ``pad_input``; weights keep their natural per-group
    layout (``w_in_kpad`` is the per-group fan for grouped layers —
    ISSUE 10 killed the block-diagonal expansion). All padding is
    zeros, which add exact 0.0 into every accumulation.
    """
    g = kp.wave.program
    l = g.layer
    xp = pad_input(kp, x)
    wp = jnp.pad(w.astype(jnp.float32),
                 ((0, 0), (0, 0),
                  (0, kp.w_in_kpad - w.shape[2]),
                  (0, g.out_c_pad - l.out_c)))
    bias = jnp.zeros((1, g.out_c_pad), jnp.float32)
    if b is not None:
        bias = bias.at[0, :l.out_c].set(b.astype(jnp.float32))
    return xp, wp, bias


def pad_residual(kp: KernelProgram, r: jax.Array) -> jax.Array:
    """Pad a residual activation (B, out_h, out_w, out_c) to the
    kernel's padded output geometry (zeros land in the masked lanes)."""
    g = kp.wave.program
    return jnp.pad(r.astype(jnp.float32),
                   ((0, 0), (0, kp.out_h_pad - kp.out_h),
                    (0, kp.out_w_pad - kp.out_w),
                    (0, g.out_c_pad - g.layer.out_c)))


def pad_norm(kp: KernelProgram, gamma: jax.Array,
             beta: jax.Array) -> jax.Array:
    """A channel norm's affine as the kernel's (2, out_c_pad) operand:
    gamma over beta, zeros in the padding channels."""
    c = kp.wave.program.layer.out_c
    ab = jnp.stack([gamma, beta]).astype(jnp.float32)
    return jnp.pad(ab, ((0, 0), (0, kp.out_c_pad - c)))


def wave_replay_layer(kp: KernelProgram, x: jax.Array, w: jax.Array,
                      b: jax.Array | None = None,
                      table: jax.Array | None = None,
                      residual: jax.Array | None = None,
                      norm=None,
                      interpret: bool | None = None) -> jax.Array:
    """Execute one streamed CONV layer as ONE persistent pallas_call.

    ``x`` (B, in_h, in_w, in_c); ``w`` (K, K, in_c/groups, out_c);
    ``table`` defaults to the program's own operand table (pass it
    pre-uploaded to keep it a traced argument under an outer jit).
    Programs lowered with ``residual=True`` take the residual
    activation (B, out_h, out_w, out_c) — added to the accumulator
    after bias, before ReLU (the paper's accumulation-SRAM add).
    Programs lowered with ``norm=True`` take ``norm``, the norm's
    ``(gamma, beta)``, each of shape (out_c,). Returns the valid
    (B, out_h, out_w, out_c) output — pooled dims when the program
    fuses its pool — as fp32.
    """
    l = kp.wave.program.layer
    with launches.record(l.name, "megakernel"):
        # launch-stage fault hook (trace time, before the pallas_call is
        # built): lets the FaultInjector exercise the fallback runtime's
        # KernelLaunchError path in CPU CI (distributed/fault.py)
        fault_point("launch", l.name, "megakernel")
        if table is None:
            table = jnp.asarray(kp.operand_table())
        if kp.residual and residual is None:
            raise ValueError(f"{l.name}: program lowered with "
                             f"residual=True needs the residual operand")
        if kp.norm and norm is None:
            raise ValueError(f"{l.name}: program lowered with norm=True "
                             f"needs the norm's (gamma, beta)")
        xp, wp, bias = pad_operands(kp, x, w, b)
        rp = pad_residual(kp, residual) if kp.residual else None
        y = wave_replay_raw(kp, xp, wp, bias, table, residual=rp,
                            norm=pad_norm(kp, *norm) if kp.norm else None,
                            interpret=interpret)
    return y[:, :kp.out_h, :kp.out_w, :l.out_c]
