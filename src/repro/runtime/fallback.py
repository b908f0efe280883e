"""Executor fallback chains: per-node graceful degradation (ISSUE 7).

The paper's companion IoT accelerator (Du et al., "A Reconfigurable
Streaming Deep CNN Accelerator for Internet of Things") survives
resource pressure by *reconfiguring to a cheaper dataflow* instead of
failing the inference. This module is that story for the executor
stack: an ordered ``FallbackChain`` over the executor modes

    graphkernel  ->  megakernel  ->  wave  ->  scan

resolved **per node**. ``resolve_graph`` walks every conv node through
its mode's pipeline stages (plan -> lower -> budget -> launch-probe);
when a stage raises the typed taxonomy (runtime/errors.py — real
validation failures and ``FaultInjector``-armed ones look identical),
ONLY that node degrades to the next mode and retries — the rest of the
graph keeps its plan. Chains are re-partitioned over the surviving
graphkernel nodes (``fusible_chains(only=...)``); a fused chain that
fails to lower degrades *as a unit* to per-layer megakernels. Every
degradation is a structured ``DegradationEvent`` (node id, from/to
mode, stage, cause, per-node retry count), bumps the registry-scoped
``degradation_events[.<stage>]`` counters (repro.obs.metrics — swap a
fresh registry in and nothing bleeds across tests; an autouse conftest
fixture resets it), and mirrors as a tracer instant event. The bench
harness snapshots the per-run ``resolved.events`` list — a clean run
reports zero events, and the regression gate enforces that.

The resolved plan compiles to ONE mixed-mode whole-graph executable
(``ResolvedGraph.forward_fn``): fused chains launch their graph
kernel, megakernel nodes their per-layer persistent kernel (residual
adds still ride the epilogues), degraded nodes fall back to the wave /
scan executors with explicit ReLU/pool/add — all inside a single jit,
sharing the graph's buffer-liveness frees.

``precision="int8"`` degrades along ``graphkernel -> megakernel`` only
(the scan/wave executors have no integer datapath); below that the
int32 reference model is the terminal fallback, reached via the
numeric guards (runtime/guard.py).
"""
from __future__ import annotations

import dataclasses
import functools
from collections import OrderedDict
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.graph import (INPUT, NetworkGraph, check_graph_input,
                              conv_keyed, fusible_chains, plan_buffers,
                              refuse_norm_gelu, topological_schedule)
from repro.core.schedule import (DEFAULT_VMEM_BUDGET, ChainNodeSpec,
                                 lower_graph_kernel)
from repro.core.streaming import (_call_cached, _chain_batch_block,
                                  _graph_epilogues,
                                  _graph_kernel_program,
                                  _normalize_mode,
                                  _partition_waves_cached,
                                  _resolve_conv_fn, _scan_executor,
                                  _wave_executor, compile_graph,
                                  maxpool_direct)
from repro.distributed import fault
from repro.obs import metrics as _metrics
from repro.obs import trace as _trace
from repro.runtime.errors import (BudgetExceeded, ExecutorError,
                                  FallbackExhausted, KernelLaunchError,
                                  LoweringError, PlanError)

MODE_ORDER = ("graphkernel", "megakernel", "wave", "scan")
INT8_MODE_ORDER = ("graphkernel", "megakernel")

_STAGE_OF = {PlanError: "plan", LoweringError: "lower",
             BudgetExceeded: "budget", KernelLaunchError: "launch"}


def _stage_of(err: Exception) -> str:
    for cls, stage in _STAGE_OF.items():
        if isinstance(err, cls):
            return stage
    return "validate"


@dataclasses.dataclass(frozen=True)
class FallbackChain:
    """An ordered subset of executor modes, most- to least-aggressive.

    ``next_mode`` gives the degradation target; ``from_mode`` the
    sub-chain a session starting at ``mode`` walks. Modes must appear
    in ``MODE_ORDER`` order — degrading may only get cheaper.
    """
    modes: Tuple[str, ...] = MODE_ORDER

    def __post_init__(self):
        modes = tuple(_normalize_mode(m) for m in self.modes)
        object.__setattr__(self, "modes", modes)
        if not modes:
            raise ValueError("empty fallback chain")
        ranks = []
        for m in modes:
            if m not in MODE_ORDER:
                raise ValueError(f"unknown fallback mode {m!r} "
                                 f"(expected one of {MODE_ORDER})")
            ranks.append(MODE_ORDER.index(m))
        if ranks != sorted(ranks) or len(set(ranks)) != len(ranks):
            raise ValueError(
                f"fallback chain {modes} must follow {MODE_ORDER} order "
                f"— degradation only moves toward cheaper executors")

    def from_mode(self, mode: str) -> Tuple[str, ...]:
        mode = _normalize_mode(mode)
        if mode not in self.modes:
            raise ValueError(f"mode {mode!r} not in fallback chain "
                             f"{self.modes}")
        return self.modes[self.modes.index(mode):]

    def next_mode(self, mode: str) -> Optional[str]:
        i = self.modes.index(_normalize_mode(mode))
        return self.modes[i + 1] if i + 1 < len(self.modes) else None


# ---------------------------------------------------------------------------
# Structured degradation events + registry-scoped counters (clean runs
# must report zero; regression_gate.py enforces it on the bench rows)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class DegradationEvent:
    """One node (or fused chain) falling one mode down the chain."""
    node: str           # conv node name (chain events: the chain head)
    from_mode: str
    to_mode: str        # next executor mode, or "reference" (guard)
    stage: str          # plan | lower | budget | launch | chain | guard
    cause: str          # "<ErrorType>: <message>"
    retry: int          # how many times this node has degraded so far

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)


def record_event(events: List[DegradationEvent],
                 ev: DegradationEvent) -> None:
    """Append ``ev``, bump the registry-scoped degradation counters
    (``degradation_events`` + per-stage dimension), and mirror it as a
    tracer instant event so degradations land on the timeline."""
    events.append(ev)
    reg = _metrics.registry()
    reg.counter("degradation_events").inc()
    reg.counter(f"degradation_events.{ev.stage}").inc()
    _trace.event(f"degrade:{ev.node}", cat="degrade", **ev.as_dict())


def degradation_event_count() -> int:
    """Degradation events in the current metrics registry since its
    last reset. Historically a process-global int — registry scoping
    (plus the autouse conftest reset) is what stops one test's
    degradations from leaking into the next."""
    return _metrics.registry().counter("degradation_events").value


def reset_degradation_events() -> None:
    reg = _metrics.registry()
    for kind, name, inst in reg.instruments():
        if kind == "counter" and (name == "degradation_events"
                                  or name.startswith("degradation_events.")):
            inst.reset()


# ---------------------------------------------------------------------------
# Resolution: walk each node down the chain until its stages pass
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class ResolvedGraph:
    """A graph resolved to per-node executor modes + lowered programs.

    ``node_modes`` maps every conv node to its final mode; a node is
    ``"graphkernel"`` iff it sits inside a multi-node fused chain
    (``chains``/``gkps``) — standalone survivors run as per-layer
    megakernels, the chain partitioner's pre-existing cut-point
    fallback. ``events`` records every degradation in resolution
    order.
    """
    graph: NetworkGraph
    programs: "OrderedDict"
    node_modes: "OrderedDict[str, str]"
    chains: tuple                       # multi-node FusedChains, active
    kprogs: Dict[str, object]           # per-layer KernelPrograms
    gkps: Dict[str, object]             # chain head -> GraphKernelProgram
    events: List[DegradationEvent]
    precision: str = "fp32"
    qgraph: object = None
    vmem_budget: Optional[int] = DEFAULT_VMEM_BUDGET

    def signature(self) -> tuple:
        """Cache-key component: the mixed-mode shape of the executable
        (per-node modes + chain partition) plus any armed NaN poisons —
        a degraded or poisoned trace can never collide with a clean
        one."""
        return (tuple(self.node_modes.items()),
                tuple(c.convs for c in self.chains),
                fault.poison_signature())

    def mode_counts(self) -> Dict[str, int]:
        out: Dict[str, int] = {}
        for m in self.node_modes.values():
            out[m] = out.get(m, 0) + 1
        return out

    # -- operand tables -------------------------------------------------
    def operands(self) -> "OrderedDict[str, jax.Array]":
        members = {m for c in self.chains for m in c.convs[1:]}
        ops: "OrderedDict[str, jax.Array]" = OrderedDict()
        for name, m in self.node_modes.items():
            if name in members:
                continue
            if name in self.gkps:
                ops[name] = jnp.asarray(self.gkps[name].operand_table())
            elif m in ("graphkernel", "megakernel"):
                ops[name] = jnp.asarray(self.kprogs[name].operand_table())
            elif m == "wave":
                ops[name] = jnp.asarray(
                    _partition_waves_cached(
                        self.programs[name]).tile_operands())
            else:
                ops[name] = jnp.asarray(self.programs[name].operands())
        return ops

    # -- mixed-mode forward ---------------------------------------------
    def forward_fn(self, conv_fn: Optional[Callable] = None,
                   conv_backend: str = "xla",
                   dequantize: bool = True) -> Callable:
        """One whole-graph forward mixing per-node executors.

        Same calling convention as ``graph_forward_fn``:
        ``f(x, weights, ops)`` with ``ops = self.operands()``. Fused
        residual adds ride megakernel/graphkernel epilogues; a conv
        degraded to wave/scan runs its add explicitly. Armed NaN
        poisons (``FaultInjector.arm_nan``) are stamped at trace time —
        ``signature()`` keys them, so poisoned executables never leak
        into clean runs.
        """
        graph, modes = self.graph, self.node_modes
        sched = topological_schedule(graph)
        bplan = plan_buffers(graph)
        epi = _graph_epilogues(graph)
        chain_of = {c.convs[0]: c for c in self.chains}
        members = {m for c in self.chains for m in c.convs[1:]}
        # adds fused into an epilogue only where the conv still runs a
        # kernel mode; degraded convs hand the add back to the walk
        fused_adds = {epi[n].out for n, m in modes.items()
                      if epi[n].residual is not None
                      and m in ("graphkernel", "megakernel")}

        if self.precision == "int8":
            return self._forward_int8(sched, bplan, epi, chain_of,
                                      members, fused_adds, dequantize)

        conv_fns = {name: _resolve_conv_fn(conv_fn, conv_backend,
                                           p.layer.stride)[0]
                    for name, p in self.programs.items()}
        wprogs = {name: _partition_waves_cached(self.programs[name])
                  for name, m in modes.items() if m == "wave"}
        from repro.kernels.wave_replay.graph import wave_replay_graph
        from repro.kernels.wave_replay.ops import wave_replay_layer
        kprogs, programs = self.kprogs, self.programs

        def forward(x, weights, ops):
            check_graph_input(graph, x)       # trace-time, per shape
            env = {INPUT: x}
            for i, n in enumerate(sched):
                if n.op == "conv":
                    m = modes[n.name]
                    if n.name in members:
                        pass                  # runs inside its chain head
                    elif n.name in chain_of:  # multi-node fused chain
                        c = chain_of[n.name]
                        y = wave_replay_graph(
                            self.gkps[n.name], env[c.input_value],
                            [weights[k] for k in c.convs],
                            table=ops[n.name]).astype(x.dtype)
                        for k in c.convs:
                            y = fault.apply_poison(k, y)
                        env[c.output_value] = y
                    elif m == "megakernel":
                        resv, outv = epi[n.name].residual, epi[n.name].out
                        w, b = weights[n.name]
                        y = wave_replay_layer(
                            kprogs[n.name], env[n.inputs[0]], w, b,
                            table=ops[n.name],
                            residual=env[resv] if resv is not None
                            else None).astype(x.dtype)
                        env[outv] = fault.apply_poison(n.name, y)
                    else:                     # wave | scan, degraded
                        l = n.layer
                        w, b = weights[n.name]
                        xin = env[n.inputs[0]]
                        if m == "wave":
                            y = _wave_executor(wprogs[n.name],
                                               conv_fns[n.name],
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
                        env[n.name] = fault.apply_poison(n.name, y)
                elif n.name not in fused_adds:
                    y = env[n.inputs[0]] + env[n.inputs[1]]
                    y = jnp.maximum(y, 0) if n.relu else y
                    env[n.name] = fault.apply_poison(n.name, y)
                for v in bplan.frees[i]:        # liveness: drop dead refs
                    env.pop(v, None)
            return env[graph.output]

        return forward

    def _forward_int8(self, sched, bplan, epi, chain_of, members,
                      fused_adds, dequantize):
        from repro.core.quantization import (dequantize_int8,
                                             quantize_int8_sym)
        from repro.kernels.wave_replay_q.graph import wave_replay_graph_q
        from repro.kernels.wave_replay_q.kernel import residual_add_i8
        from repro.kernels.wave_replay_q.ops import wave_replay_q_layer
        graph, modes, qgraph = self.graph, self.node_modes, self.qgraph
        statics = {name: (qgraph.quants[name].pre_shift,
                          qgraph.quants[name].fan_chunk)
                   for name in self.kprogs}
        in_scale = float(qgraph.scales[INPUT])
        out_scale = float(qgraph.scales[graph.output])

        def forward_q(x, weights, ops):
            check_graph_input(graph, x)       # trace-time, per shape
            env = {INPUT: x if x.dtype == jnp.int8
                   else quantize_int8_sym(x, in_scale)}
            for i, n in enumerate(sched):
                if n.op == "conv":
                    if n.name in members:
                        pass                  # runs inside its chain head
                    elif n.name in chain_of:
                        c = chain_of[n.name]
                        env[c.output_value] = wave_replay_graph_q(
                            self.gkps[n.name], env[c.input_value],
                            [weights[k] for k in c.convs],
                            pre_shifts=[statics[k][0] for k in c.convs],
                            fan_chunks=[statics[k][1] for k in c.convs],
                            table=ops[n.name])
                    else:                     # megakernel (int8 floor)
                        resv, outv = epi[n.name].residual, epi[n.name].out
                        wq, bq, m, s = weights[n.name]
                        ps, fc = statics[n.name]
                        env[outv] = wave_replay_q_layer(
                            self.kprogs[n.name], env[n.inputs[0]],
                            wq, bq, m, s, pre_shift=ps, fan_chunk=fc,
                            table=ops[n.name],
                            residual=env[resv] if resv is not None
                            else None)
                elif n.name not in fused_adds:
                    env[n.name] = residual_add_i8(
                        env[n.inputs[0]], env[n.inputs[1]], n.relu)
                for v in bplan.frees[i]:        # liveness: drop dead refs
                    env.pop(v, None)
            y = env[graph.output]
            return dequantize_int8(y, out_scale) if dequantize else y

        return forward_q


def resolve_graph(graph: NetworkGraph, programs, *,
                  mode: str = "graphkernel",
                  chain: Optional[FallbackChain] = None,
                  vmem_budget: Optional[int] = DEFAULT_VMEM_BUDGET,
                  precision: str = "fp32",
                  qgraph=None,
                  batch: int = 1) -> ResolvedGraph:
    """Resolve per-node executor modes by walking the fallback chain.

    Each conv node starts at ``mode`` and attempts its pipeline stages;
    a typed failure (``ExecutorError`` — real or injected) degrades
    only that node and retries at the next mode, recording a
    ``DegradationEvent``. Then the fused-chain partition re-forms over
    the surviving graphkernel nodes; a chain whose whole-chain lowering
    fails degrades as a unit to per-layer megakernels (one ``chain``
    event on its head), and standalone graphkernel survivors settle as
    megakernels (the partitioner's designed cut-point fallback — no
    event). A node failing at the chain's terminal mode raises
    ``FallbackExhausted``.
    """
    mode = _normalize_mode(mode)
    quantized = precision == "int8"
    if chain is None:
        chain = FallbackChain(INT8_MODE_ORDER if quantized else MODE_ORDER)
    start = chain.from_mode(mode)[0]
    # the chain ends in executors that have no norm or gelu
    refuse_norm_gelu(graph, "the fallback runtime")
    programs = conv_keyed(graph, programs, "programs")
    epi = _graph_epilogues(graph)
    modes: "OrderedDict[str, str]" = OrderedDict(
        (n.name, start) for n in graph.conv_nodes())
    retries = {name: 0 for name in modes}
    events: List[DegradationEvent] = []
    kprogs: Dict[str, object] = {}

    def degrade(name: str, stage: str, err: Exception,
                to: Optional[str] = None) -> None:
        cur = modes[name]
        nxt = chain.next_mode(cur) if to is None else to
        if nxt is None:
            raise FallbackExhausted(
                f"{name}: failed at terminal mode {cur!r} "
                f"({stage}: {err})") from err
        retries[name] += 1
        record_event(events, DegradationEvent(
            node=name, from_mode=cur, to_mode=nxt, stage=stage,
            cause=f"{type(err).__name__}: {err}", retry=retries[name]))
        modes[name] = nxt

    def attempt(name: str) -> None:
        """Walk ``name`` down the chain until a mode's stages pass."""
        while True:
            m = modes[name]
            budget = fault.effective_vmem(vmem_budget, name)
            try:
                if m in ("graphkernel", "megakernel"):
                    fault.fault_point("plan", name, m)
                    kp = _graph_kernel_program(
                        programs[name], epi[name].act,
                        epi[name].residual is not None, vmem_budget, batch)
                    fault.fault_point("lower", name, m)
                    if budget is not None and kp.plan_bytes > budget:
                        raise BudgetExceeded(
                            f"{name}: working set {kp.plan_bytes} B "
                            f"exceeds the {budget} B VMEM budget at "
                            f"mode {m!r}")
                    if m == "megakernel":
                        fault.fault_point("launch", name, m)
                    kprogs[name] = kp
                elif m == "wave":
                    fault.fault_point("plan", name, m)
                    _partition_waves_cached(programs[name])
                    fault.fault_point("lower", name, m)
                else:                           # scan — terminal
                    fault.fault_point("plan", name, m)
                    fault.fault_point("lower", name, m)
                return
            except ExecutorError as e:
                degrade(name, _stage_of(e), e)

    for name in modes:
        attempt(name)

    # chain partition over the graphkernel survivors; excluded nodes
    # break runs (fusible_chains(only=...))
    gk = frozenset(n for n, m in modes.items() if m == "graphkernel")
    chains_all = fusible_chains(graph, kprogs, vmem_budget=vmem_budget,
                                quantized=quantized, only=gk or None) \
        if gk else ()
    active, gkps = [], {}
    demoted: List[str] = []
    by_name = {n.name: n for n in graph.nodes}
    for c in chains_all:
        if c.convs[0] not in gk:
            continue
        if len(c.convs) < 2:
            # standalone survivor: the per-layer megakernel IS the
            # graph kernel's designed fallback at cut points — not a
            # degradation, no event
            modes[c.convs[0]] = "megakernel"
            continue
        head = c.convs[0]
        try:
            specs = [ChainNodeSpec(name=k, kp=kprogs[k],
                                   in_value=by_name[k].inputs[0],
                                   out_value=epi[k].out,
                                   residual_value=epi[k].residual)
                     for k in c.convs]
            gkp = lower_graph_kernel(
                specs, quantized=quantized,
                batch_block=_chain_batch_block(specs, quantized,
                                               vmem_budget, batch))
            # chain-unit launch probe: the whole fused chain is the
            # failure unit here (arm("launch", head, "graphkernel"))
            fault.fault_point("launch", head, "graphkernel")
        except ExecutorError as e:
            retries[head] += 1
            record_event(events, DegradationEvent(
                node=head, from_mode="graphkernel", to_mode="megakernel",
                stage="chain",
                cause=f"{type(e).__name__}: {e} "
                      f"[chain {'+'.join(c.convs)}]",
                retry=retries[head]))
            for k in c.convs:
                modes[k] = "megakernel"
                demoted.append(k)
            continue
        active.append(c)
        gkps[head] = gkp

    # demoted chain members re-attempt at megakernel — they may degrade
    # further (e.g. an armed tiny VMEM budget pushes them to wave)
    for name in demoted:
        attempt(name)

    return ResolvedGraph(graph=graph, programs=programs,
                         node_modes=modes, chains=tuple(active),
                         kprogs=kprogs, gkps=gkps, events=events,
                         precision=precision, qgraph=qgraph,
                         vmem_budget=vmem_budget)


def run_graph_degraded(graph: NetworkGraph, plans, x: jax.Array, weights,
                       *, mode: str = "graphkernel",
                       chain: Optional[FallbackChain] = None,
                       vmem_budget: Optional[int] = DEFAULT_VMEM_BUDGET,
                       precision: str = "fp32", qgraph=None,
                       conv_fn: Optional[Callable] = None,
                       conv_backend: str = "xla",
                       dequantize: bool = True):
    """Resolve + run a graph through the fallback runtime in one call.

    Returns ``(y, resolved)`` — the output plus the ``ResolvedGraph``
    carrying the per-node modes and degradation events. The compiled
    executable caches in the process executor cache, keyed by the
    resolved signature (mixed-mode map + chain partition + poison
    arms), so a degraded trace never collides with a clean one.
    """
    plans = conv_keyed(graph, plans, "plans")
    programs = compile_graph(graph, plans)
    resolved = resolve_graph(graph, programs, mode=mode, chain=chain,
                             vmem_budget=vmem_budget,
                             precision=precision, qgraph=qgraph,
                             batch=x.shape[0])
    qsig = ()
    if precision == "int8":
        qsig = (float(qgraph.scales[INPUT]),
                float(qgraph.scales[graph.output]),
                tuple((name, q.pre_shift, q.fan_chunk)
                      for name, q in sorted(qgraph.quants.items())))
    key = ("degraded", graph.topology_key,
           tuple(p.geometry for p in programs.values()),
           resolved.signature(), precision, qsig, dequantize,
           x.shape[0], str(x.dtype))
    build = lambda: jax.jit(resolved.forward_fn(
        conv_fn, conv_backend, dequantize=dequantize))
    ops = resolved.operands()
    if precision == "int8":
        y = _call_cached(key, build, x, qgraph.device_weights(), ops)
    else:
        weights = conv_keyed(graph, weights, "weights")
        y = _call_cached(key, build, x, weights, ops)
    return y, resolved
