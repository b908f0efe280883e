"""Post-training calibration: a float CNN -> a ``QuantizedNetwork``.

The paper's accelerator is a fixed-point machine (Table 2: 16-bit
operands, 32-bit accumulators); its quoted throughput/efficiency live in
that datapath, not in fp32. This module is the *offline* half of the
repo's int8 streaming path (DESIGN.md §7): run a handful of batches
through the existing float executors, observe per-tensor activation
ranges and per-output-channel weight ranges, and freeze everything the
integer datapath needs — int8 weights, int32 biases, and the
fixed-point requantize multipliers — into host-side numpy arrays.

Scale scheme (all symmetric, zero-point 0, so padding zeros stay exact
integer zeros through every schedule):

  * weights: per-output-channel absmax over (K, K, fan) — the classic
    PTQ choice; channel dynamic ranges differ by orders of magnitude
    and the requantize multiplier absorbs the per-channel scale for
    free (``core/quantization.py::requant_params``).
  * activations: per-tensor, absmax or percentile of |x| over the
    calibration set. Percentile (default 99.9) clips rare outliers —
    values beyond the clip saturate at ±127 at runtime, trading a few
    clipped pixels for a finer LSB everywhere else.

The layer boundaries chain: layer i's output scale IS layer i+1's input
scale, so between layers activations flow as raw int8 with no
dequant/requant round-trip — the requantize folded into each kernel
epilogue lands directly in the next layer's operand format, exactly the
paper's write-back-at-operand-precision datapath.
"""
from __future__ import annotations

import dataclasses
from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.decomposition import ConvLayer
from repro.core.graph import (INPUT, NetworkGraph, chain_graph,
                              conv_keyed, refuse_norm_gelu,
                              topological_schedule)
from repro.core.quantization import INT8_QMAX, requant_params

# bias magnitudes are clipped here when a pathological scale pair would
# blow them up; the requantized output saturates at ±127 anyway long
# before a bias of 2^30 acc-LSBs matters
_BIAS_CLIP = 1 << 30


@dataclasses.dataclass(frozen=True)
class LayerQuant:
    """Everything the int8 datapath needs for ONE conv layer (host numpy).

    ``wq`` keeps the layer's natural per-group weight layout
    (K, K, in_c/groups, out_c) — the quantized megakernel runs true
    per-group gemms instead of the fp32 path's block-diagonal dense
    expansion. ``m``/``shift``/``pre_shift`` encode the requantize
    multiplier ``in_scale * w_scale[c] / out_scale ~= m * 2^-shift``
    (see ``requant_params``); ``acc_bound`` is the |accumulator + bias|
    bound the ``pre_shift`` headroom was derived from.
    """
    wq: np.ndarray            # (K, K, in_c/groups, out_c) int8
    w_scale: np.ndarray       # (out_c,) float32
    in_scale: float
    out_scale: float
    bias_q: np.ndarray        # (out_c,) int32
    m: np.ndarray             # (out_c,) int32 — 7-bit requant mantissa
    shift: np.ndarray         # (out_c,) int32
    pre_shift: int
    acc_bound: int
    # max input channels per exact-fp32 sub-gemm, derived from the
    # ACTUAL quantized weights: any partial sum of an int8 x wq gemm is
    # bounded by 127 * max-column sum(|wq|), so when that bound clears
    # 2^24 the whole (per-group) fan runs as ONE gemm (fan_chunk =
    # in_c/groups, the common case) — the worst-case
    # EXACT_FP32_FAN chunking only kicks in for pathological weights.
    fan_chunk: int

    def device_arrays(self) -> Tuple[jax.Array, jax.Array, jax.Array,
                                     jax.Array]:
        """(wq, bias_q, m, shift) as jnp arrays — the traced per-layer
        weight tuple of the int8 network forward."""
        return (jnp.asarray(self.wq), jnp.asarray(self.bias_q),
                jnp.asarray(self.m), jnp.asarray(self.shift))


@dataclasses.dataclass(frozen=True)
class QuantizedNetwork:
    """A calibrated conv stack: layers + per-layer ``LayerQuant``.

    Scales chain by construction (``quants[i].out_scale ==
    quants[i+1].in_scale``, validated) so the int8 executors pass raw
    int8 activations between layers.
    """
    layers: Tuple[ConvLayer, ...]
    quants: Tuple[LayerQuant, ...]
    method: str = "percentile"

    def __post_init__(self):
        if len(self.layers) != len(self.quants):
            raise ValueError("layers and quants must pair up")
        for i, (a, b) in enumerate(zip(self.quants[:-1], self.quants[1:])):
            if a.out_scale != b.in_scale:
                raise ValueError(
                    f"layer {i}->{i + 1}: out_scale {a.out_scale} != next "
                    f"in_scale {b.in_scale} — int8 activations could not "
                    f"flow between layers unconverted")

    @property
    def in_scale(self) -> float:
        return self.quants[0].in_scale

    @property
    def out_scale(self) -> float:
        return self.quants[-1].out_scale

    def device_weights(self) -> List[Tuple[jax.Array, ...]]:
        """Per-layer traced weight tuples for the int8 network forward."""
        return [q.device_arrays() for q in self.quants]

    def describe(self) -> str:
        lines = [f"QuantizedNetwork: {len(self.layers)} layers, "
                 f"method={self.method}, in_scale={self.in_scale:.3g}"]
        for l, q in zip(self.layers, self.quants):
            lines.append(
                f"  {l.name}: w_scale [{q.w_scale.min():.3g}, "
                f"{q.w_scale.max():.3g}], out_scale {q.out_scale:.3g}, "
                f"pre_shift {q.pre_shift}")
        return "\n".join(lines)


# ---------------------------------------------------------------------------
# Observers
# ---------------------------------------------------------------------------

def activation_scale(values, method: str = "percentile",
                     percentile: float = 99.9) -> float:
    """Per-tensor symmetric scale from observed activation values.

    ``absmax`` uses the largest |x| seen (no saturation on the
    calibration set); ``percentile`` clips to the given percentile of
    |x| (outliers beyond it saturate at runtime). All-zero observations
    (dead layers, zero calibration images) fall back to scale 1.0 so
    downstream integer math stays finite.
    """
    a = np.abs(np.asarray(values, np.float32).ravel())
    if method == "absmax":
        amax = float(a.max()) if a.size else 0.0
    elif method == "percentile":
        amax = float(np.percentile(a, percentile)) if a.size else 0.0
    else:
        raise ValueError(f"unknown calibration method {method!r} "
                         f"(expected absmax | percentile)")
    if amax <= 0.0:
        return 1.0
    return amax / INT8_QMAX


def quantize_weights_per_channel(w) -> Tuple[np.ndarray, np.ndarray]:
    """(K, K, fan, out_c) float -> per-output-channel symmetric int8.

    All-zero channels get scale 1.0 (their int weights are zeros, so any
    positive scale reproduces them exactly)."""
    w = np.asarray(w, np.float32)
    amax = np.max(np.abs(w), axis=(0, 1, 2))
    w_scale = np.where(amax > 0.0, amax / INT8_QMAX, 1.0).astype(np.float32)
    wq = np.clip(np.rint(w / w_scale), -INT8_QMAX, INT8_QMAX)
    return wq.astype(np.int8), w_scale


def quantize_layer(layer: ConvLayer, w, b,
                   in_scale: float, out_scale: float) -> LayerQuant:
    """Freeze one layer's integer datapath from float weights + scales."""
    wq, w_scale = quantize_weights_per_channel(w)
    if wq.shape != (layer.kernel, layer.kernel,
                    layer.in_c // layer.groups, layer.out_c):
        raise ValueError(
            f"{layer.name}: weights {wq.shape} != declared "
            f"({layer.kernel}, {layer.kernel}, "
            f"{layer.in_c // layer.groups}, {layer.out_c})")
    acc_scale = in_scale * w_scale.astype(np.float64)
    bias = np.zeros((layer.out_c,), np.float64) if b is None \
        else np.asarray(b, np.float64)
    bias_q = np.clip(np.rint(bias / acc_scale),
                     -_BIAS_CLIP, _BIAS_CLIP).astype(np.int32)
    fan = layer.kernel * layer.kernel * (layer.in_c // layer.groups)
    acc_bound = fan * INT8_QMAX * INT8_QMAX + int(np.abs(bias_q).max())
    m, shift, pre_shift = requant_params(acc_scale / out_scale, acc_bound)
    # weight-aware exact-fp32 gemm bound: every partial sum of an
    # int8 activation x wq gemm is <= 127 * (worst column's sum |wq|);
    # under 2^24 the kernel can run each (per-group) fan as one gemm
    col_sums = np.abs(wq.astype(np.int64)).sum(axis=(0, 1, 2))
    if int(col_sums.max()) * INT8_QMAX < 1 << 24:
        fan_chunk = layer.in_c // layer.groups      # unchunked
    else:
        from repro.kernels.wave_replay_q.kernel import exact_channel_chunk
        fan_chunk = exact_channel_chunk(layer.kernel)
    return LayerQuant(wq=wq, w_scale=w_scale, in_scale=float(in_scale),
                      out_scale=float(out_scale), bias_q=bias_q, m=m,
                      shift=shift, pre_shift=pre_shift,
                      acc_bound=acc_bound, fan_chunk=fan_chunk)


# ---------------------------------------------------------------------------
# Calibration: observe the float network, freeze the integer one
# ---------------------------------------------------------------------------

def float_network_acts(layers: Sequence[ConvLayer], weights,
                       x: jax.Array) -> List[jax.Array]:
    """Reference float forward returning every layer boundary:
    ``[x, act_1, ..., act_N]`` where ``act_i`` is layer i's post-ReLU,
    post-pool output — exactly the tensors the int8 path carries as
    int8, which makes these both the calibration observations and the
    accuracy-harness reference points."""
    from repro.core.streaming import conv2d_direct, maxpool_direct
    acts = [x]
    y = x
    for l, (w, b) in zip(layers, weights):
        y = conv2d_direct(y, w, l.stride, l.pad, groups=l.groups)
        if b is not None:
            y = y + b
        y = jnp.maximum(y, 0.0)
        if l.pool > 1:
            y = maxpool_direct(y, l.pool, l.pool_stride or l.pool)
        acts.append(y)
    return acts


# ---------------------------------------------------------------------------
# Graph-aware calibration (ISSUE 5): observe graph VALUES, not list
# indices — residual add operands are forced onto one shared scale so
# the int8 accumulation-buffer add is a plain integer add.
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class QuantizedGraph:
    """A calibrated NetworkGraph: per-conv-node ``LayerQuant`` (keyed by
    node name) + per-VALUE activation scales (keyed by value name,
    ``"input"`` included).

    Scale invariants (validated): every conv's in/out scale equals its
    input/output value's scale, and both operands of every ``add`` node
    share the add output's scale — which is what lets raw int8
    activations flow along every edge and shortcut adds run as plain
    integer adds (kernel epilogue or explicit, bit-identically).
    """
    graph: NetworkGraph
    quants: "dict[str, LayerQuant]"
    scales: "dict[str, float]"
    method: str = "percentile"

    def __post_init__(self):
        conv_names = {n.name for n in self.graph.conv_nodes()}
        if set(self.quants) != conv_names:
            raise ValueError(
                f"{self.graph.name}: quants keyed {sorted(self.quants)} "
                f"!= conv nodes {sorted(conv_names)}")
        for n in topological_schedule(self.graph):
            if n.op == "conv":
                q = self.quants[n.name]
                if q.in_scale != self.scales[n.inputs[0]] \
                        or q.out_scale != self.scales[n.name]:
                    raise ValueError(
                        f"{self.graph.name}: {n.name} scales "
                        f"({q.in_scale}, {q.out_scale}) disagree with "
                        f"edge scales — int8 activations could not flow "
                        f"unconverted")
            else:
                a, b = n.inputs
                if not (self.scales[a] == self.scales[b]
                        == self.scales[n.name]):
                    raise ValueError(
                        f"{self.graph.name}: add {n.name} operands/"
                        f"output must share one scale "
                        f"({self.scales[a]}, {self.scales[b]}, "
                        f"{self.scales[n.name]})")

    def device_weights(self) -> "dict[str, Tuple[jax.Array, ...]]":
        """Per-conv-node traced weight tuples for the int8 graph
        forward (``core/streaming.py::graph_forward_fn``)."""
        return {name: q.device_arrays() for name, q in self.quants.items()}

    def describe(self) -> str:
        lines = [f"QuantizedGraph {self.graph.name}: "
                 f"{len(self.quants)} conv nodes, method={self.method}, "
                 f"in_scale={self.scales[INPUT]:.3g}"]
        for n in self.graph.conv_nodes():
            q = self.quants[n.name]
            lines.append(f"  {n.name}: out_scale {q.out_scale:.3g}, "
                         f"pre_shift {q.pre_shift}")
        return "\n".join(lines)


def float_graph_acts(graph: NetworkGraph, weights,
                     x: jax.Array) -> "dict[str, jax.Array]":
    """Reference float forward over the graph schedule returning every
    VALUE (``"input"`` included): each conv value is post-ReLU/post-pool,
    each add value post-ReLU — exactly the tensors the int8 path carries
    as int8, making these both the calibration observations and the
    accuracy-harness reference points. Delegates to the one shared walk
    (``core/streaming.py::run_graph_reference``), so calibration can
    never observe different tensors than the executors produce."""
    from repro.core.streaming import run_graph_reference
    return run_graph_reference(graph, weights, x)


def _unify_add_scales(graph: NetworkGraph,
                      base: "dict[str, float]") -> "dict[str, float]":
    """Union-find over values: each add node's operands and output land
    in one scale group (identity shortcuts chain groups transitively);
    a group's scale is the max of its members' base scales, so no
    member saturates harder than its own calibration said it would."""
    parent = {v: v for v in base}

    def find(v):
        while parent[v] != v:
            parent[v] = parent[parent[v]]
            v = parent[v]
        return v

    def union(a, b):
        ra, rb = find(a), find(b)
        if ra != rb:
            parent[ra] = rb

    for n in graph.nodes:
        if n.op == "add":
            union(n.inputs[0], n.name)
            union(n.inputs[1], n.name)
    groups: "dict[str, float]" = {}
    for v in base:
        r = find(v)
        groups[r] = max(groups.get(r, 0.0), base[v])
    return {v: groups[find(v)] for v in base}


def calibrate_graph(graph: NetworkGraph, weights, calib,
                    method: str = "percentile",
                    percentile: float = 99.9) -> QuantizedGraph:
    """PTQ calibration over a NetworkGraph: run ``calib`` through the
    float graph walk, observe every VALUE, freeze the integer datapath.

    ``calib`` is one (N, H, W, C) array or an iterable of such batches.
    Observations pool per value; add-operand scales are unified
    (``_unify_add_scales``) so the residual add needs no requantize;
    each conv node freezes with its input value's scale in and its own
    value's scale out.
    """
    refuse_norm_gelu(graph, "calibrate_graph (int8)")
    weights = conv_keyed(graph, weights, "weights")
    if hasattr(calib, "ndim"):
        calib = [calib]
    fwd = jax.jit(lambda xb: float_graph_acts(graph, weights, xb))
    samples: "dict[str, List[np.ndarray]]" = {}
    n_batches = 0
    for batch in calib:
        n_batches += 1
        for v, act in fwd(batch).items():
            samples.setdefault(v, []).append(
                np.asarray(act, np.float32).ravel())
    if n_batches == 0:
        raise ValueError("calibration needs at least one batch")
    base = {v: activation_scale(np.concatenate(s), method, percentile)
            for v, s in samples.items()}
    scales = _unify_add_scales(graph, base)
    quants = {
        n.name: quantize_layer(n.layer, *weights[n.name],
                               scales[n.inputs[0]], scales[n.name])
        for n in graph.conv_nodes()}
    return QuantizedGraph(graph=graph, quants=quants, scales=scales,
                          method=method)


def quantized_graph_from_network(qnet: QuantizedNetwork,
                                 graph: NetworkGraph) -> QuantizedGraph:
    """Adapt a linear-stack ``QuantizedNetwork`` to its chain graph's
    ``QuantizedGraph`` (same quants, scales keyed by value name)."""
    convs = graph.conv_nodes()
    if tuple(n.layer for n in convs) != tuple(qnet.layers) \
            or any(n.op != "conv" for n in graph.nodes):
        raise ValueError(
            f"{graph.name}: not the chain graph of this "
            f"QuantizedNetwork")
    quants = {n.name: q for n, q in zip(convs, qnet.quants)}
    scales = {INPUT: qnet.in_scale}
    for n, q in zip(convs, qnet.quants):
        scales[n.name] = q.out_scale
    return QuantizedGraph(graph=graph, quants=quants, scales=scales,
                          method=qnet.method)


def calibrate_network(layers: Sequence[ConvLayer], weights, calib,
                      method: str = "percentile",
                      percentile: float = 99.9) -> QuantizedNetwork:
    """PTQ calibration of a linear stack: ``calibrate_graph`` over the
    stack's chain graph, repackaged as a ``QuantizedNetwork``.

    ``calib`` is one (N, H, W, C) array or an iterable of such batches
    (a single image works — (1, H, W, C)). Activation observations from
    every batch pool into one per-boundary (= per graph value) scale;
    weights quantize per-output-channel independent of the data.
    """
    layers = tuple(layers)
    g = chain_graph(layers)
    weights = list(weights)
    qg = calibrate_graph(g, weights, calib, method, percentile)
    quants = tuple(qg.quants[l.name] for l in layers)
    return QuantizedNetwork(layers=layers, quants=quants, method=method)


def calibrate_layer(layer: ConvLayer, w, b, x: jax.Array,
                    method: str = "absmax",
                    percentile: float = 99.9) -> LayerQuant:
    """Single-layer on-the-fly calibration (no ReLU/pool — parity with
    the layer-level ``run_layer_*`` entry points, whose reference is the
    raw conv + bias output)."""
    from repro.core.streaming import conv2d_direct
    y = conv2d_direct(x, jnp.asarray(w, jnp.float32), layer.stride,
                      layer.pad, groups=layer.groups)
    if b is not None:
        y = y + b
    return quantize_layer(layer, w, b,
                          activation_scale(x, method, percentile),
                          activation_scale(y, method, percentile))
