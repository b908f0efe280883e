"""Post-execution numeric guards: quarantine + reference re-run.

The fallback chain (runtime/fallback.py) catches failures the pipeline
*reports* — a guard catches the ones it doesn't: a kernel that launches
fine but emits NaN/Inf (fp32) or an int8 datapath whose activations
saturate wholesale because the serving distribution drifted off the
calibration set. Guards run on the final output of a (possibly
degraded) graph executable; a trip quarantines the batch and re-runs it
through the reference path, walking node-by-node to *attribute* the
corruption:

* **fp32** — each node re-executes at its resolved mode eagerly; the
  first node whose output goes non-finite is recomputed with the direct
  (undecomposed) ``conv2d_direct`` reference and the walk continues
  from the corrected value. One ``DegradationEvent`` per quarantined
  node (``stage="guard"``, ``to_mode="reference"``).
* **int8** — saturation is a *model-level* property (every downstream
  layer sees clipped inputs), so the whole batch re-runs through the
  int32 reference model (``quant_graph_reference_acts``) — bit-exact by
  construction — under one event on the graph output.

Guards are OPTIONAL (off by default): every check is an extra device
round-trip, the price of serving with a safety net.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.graph import INPUT, plan_buffers, topological_schedule
from repro.core.streaming import conv2d_direct, maxpool_direct
from repro.distributed import fault
from repro.runtime.errors import NumericGuardTripped
from repro.runtime.fallback import (DegradationEvent, ResolvedGraph,
                                    record_event)

INT8_QMAX = 127


@dataclasses.dataclass(frozen=True)
class GuardConfig:
    """What the post-execution guards check.

    ``nonfinite`` trips on any NaN/Inf in a floating output;
    ``int8_saturation`` trips when at least that fraction of int8
    output lanes sit at +-127 (None disables). ``repair=False`` raises
    ``NumericGuardTripped`` instead of re-running the reference path —
    for callers that would rather shed the request than pay for the
    re-run.
    """
    nonfinite: bool = True
    int8_saturation: Optional[float] = 0.5
    repair: bool = True


def check_fp32(y: jax.Array, cfg: GuardConfig) -> Optional[str]:
    """Cause string if the fp32 guard trips, else None."""
    if not cfg.nonfinite:
        return None
    if not bool(jnp.isfinite(y).all()):
        bad = int(jnp.sum(~jnp.isfinite(y)))
        return (f"non-finite output: {bad}/{y.size} lanes NaN/Inf")
    return None


def check_int8(y: jax.Array, cfg: GuardConfig) -> Optional[str]:
    """Cause string if the int8 saturation guard trips, else None."""
    if cfg.int8_saturation is None:
        return None
    rate = float(jnp.mean(jnp.abs(y.astype(jnp.int32)) >= INT8_QMAX))
    if rate >= cfg.int8_saturation:
        return (f"int8 saturation {rate:.2f} >= threshold "
                f"{cfg.int8_saturation:.2f} — input distribution off "
                f"the calibration set")
    return None


def _reference_node(node, x, weights):
    """Direct (undecomposed) reference for one conv node — the same op
    sequence as ``run_graph_reference``."""
    l = node.layer
    w, b = weights[node.name]
    y = conv2d_direct(x, w.astype(x.dtype), l.stride, l.pad,
                      groups=l.groups)
    if b is not None:
        y = y + b.astype(x.dtype)
    if node.relu:
        y = jnp.maximum(y, 0)
    if l.pool > 1:
        y = maxpool_direct(y, l.pool, l.pool_stride or l.pool)
    return y


def repair_fp32(resolved: ResolvedGraph, x: jax.Array, weights,
                cfg: GuardConfig, cause: str,
                conv_fn=None, conv_backend: str = "xla") -> jax.Array:
    """Quarantined fp32 batch: eager node-by-node diagnosis + repair.

    Re-executes each node at its resolved mode (graphkernel members
    diagnose per-layer as megakernels — the chain's designed
    decomposition); a node whose output trips the guard is recomputed
    on the reference path and the walk continues from the corrected
    value, so one poisoned node doesn't condemn its whole downstream
    cone. Poison arms (``FaultInjector``) still apply during diagnosis
    — that's what lets CPU CI attribute a fault to the node that was
    actually armed.
    """
    from repro.core.streaming import (_partition_waves_cached,
                                      _resolve_conv_fn, _scan_executor,
                                      _wave_executor)
    from repro.kernels.wave_replay.ops import wave_replay_layer
    graph, modes = resolved.graph, resolved.node_modes
    if not bool(jnp.isfinite(x).all()):
        # a non-finite INPUT is not a kernel fault — every executor
        # (reference included) propagates it, so a diagnosis walk would
        # "attribute" the first conv and repair into the same garbage
        raise NumericGuardTripped(
            f"{graph.name}: guard tripped ({cause}) but no node "
            f"attributed — the input batch itself is non-finite")
    bplan = plan_buffers(graph)
    sched = topological_schedule(graph)
    env = {INPUT: x}
    repaired = []
    for i, n in enumerate(sched):
        if n.op == "conv":
            m = modes[n.name]
            xin = env[n.inputs[0]]
            w, b = weights[n.name]
            if m in ("graphkernel", "megakernel"):
                # members diagnose per-layer; epilogue adds run below
                # explicitly so attribution stays per-node
                kp = resolved.kprogs[n.name]
                if kp.residual:
                    # re-lower without the fused add for diagnosis
                    from repro.core.streaming import _graph_kernel_program
                    kp = _graph_kernel_program(
                        resolved.programs[n.name], n.act, False,
                        resolved.vmem_budget)
                y = wave_replay_layer(kp, xin, w, b).astype(x.dtype)
            else:
                l = n.layer
                fn, _ = _resolve_conv_fn(conv_fn, conv_backend, l.stride)
                if m == "wave":
                    wp = _partition_waves_cached(resolved.programs[n.name])
                    y = _wave_executor(wp, fn, b is not None, xin, w, b,
                                       wp.tile_operands())
                else:
                    p = resolved.programs[n.name]
                    y = _scan_executor(p, fn, b is not None, xin, w, b,
                                       p.operands())
                if n.relu:
                    y = jnp.maximum(y, 0)
                if n.layer.pool > 1:
                    y = maxpool_direct(y, n.layer.pool,
                                       n.layer.pool_stride or n.layer.pool)
            y = fault.apply_poison(n.name, y)
            if check_fp32(y, cfg) is not None:
                y = _reference_node(n, xin, weights)
                repaired.append(n.name)
                record_event(resolved.events, DegradationEvent(
                    node=n.name, from_mode=m, to_mode="reference",
                    stage="guard", cause=cause, retry=0))
        else:
            y = env[n.inputs[0]] + env[n.inputs[1]]
            y = jnp.maximum(y, 0) if n.relu else y
            y = fault.apply_poison(n.name, y)
            if check_fp32(y, cfg) is not None:
                a, bv = env[n.inputs[0]], env[n.inputs[1]]
                y = a + bv
                y = jnp.maximum(y, 0) if n.relu else y
                repaired.append(n.name)
                record_event(resolved.events, DegradationEvent(
                    node=n.name, from_mode=modes.get(n.name, "add"),
                    to_mode="reference", stage="guard", cause=cause,
                    retry=0))
        env[n.name] = y
        for v in bplan.frees[i]:
            env.pop(v, None)
    if not repaired:
        # nothing attributed node-by-node (e.g. non-finite *input*):
        # surface the trip rather than silently returning the same bad
        # output
        raise NumericGuardTripped(
            f"{graph.name}: guard tripped ({cause}) but no node "
            f"attributed — input itself may be non-finite")
    return env[graph.output]


def repair_int8(resolved: ResolvedGraph, x: jax.Array,
                cfg: GuardConfig, cause: str) -> jax.Array:
    """Quarantined int8 batch: whole-graph int32 reference re-run.

    Saturation poisons every downstream layer's inputs, so per-node
    attribution is meaningless — one event on the graph output, one
    deterministic re-run (returns the raw int8 output value)."""
    from repro.quant.accuracy import quant_graph_reference_acts
    graph = resolved.graph
    record_event(resolved.events, DegradationEvent(
        node=graph.output, from_mode="int8-kernels",
        to_mode="reference", stage="guard", cause=cause, retry=0))
    return quant_graph_reference_acts(resolved.qgraph, x)[graph.output]


def guarded_output(resolved: ResolvedGraph, y: jax.Array, x: jax.Array,
                   weights, cfg: GuardConfig, *, raw_int8: bool = False,
                   conv_fn=None, conv_backend: str = "xla"):
    """Check a graph output; quarantine + repair on trip.

    Returns ``(y, cause | None)``. ``raw_int8`` marks ``y`` as the
    un-dequantized int8 output value (the guard must see raw codes —
    saturation is invisible after dequantize). ``cfg.repair=False``
    raises ``NumericGuardTripped`` instead of re-running.
    """
    if raw_int8:
        cause = check_int8(y, cfg)
        if cause is None:
            return y, None
        if not cfg.repair:
            raise NumericGuardTripped(
                f"{resolved.graph.name}: {cause}")
        return repair_int8(resolved, x, cfg, cause), cause
    cause = check_fp32(y, cfg)
    if cause is None:
        return y, None
    if not cfg.repair:
        raise NumericGuardTripped(f"{resolved.graph.name}: {cause}")
    return repair_fp32(resolved, x, weights, cfg, cause,
                       conv_fn, conv_backend), cause
