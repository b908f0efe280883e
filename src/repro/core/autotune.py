"""Measured execution-plan autotuner (ISSUE 8).

The paper's image/feature decomposition is a *parameter search*: §4
picks tile heights, feature-group widths and channel splits per layer
by evaluating the candidate set against the SRAM budget and DRAM
traffic model. The repo's planner reproduces that analytically — but
the bench shows the model does not rank *executors*: AlexNet conv1's
one-dispatch wave replay beats its megakernel on CPU while every other
layer prefers the persistent kernel, and the graphkernel wins launches
and DRAM traffic yet can trail wall-clock. So the executor choice is
measured, not modelled: ``tune_graph`` times candidate plans per graph
node — wave vs megakernel per conv, graphkernel chain membership for
megakernel-shaped nodes, over one or more VMEM-budget points — then
races the assembled mixed-mode plan against every fixed mode end to
end and keeps whichever wins. The winner is a ``TunedPlan``: a
per-node mode map realised through the fallback runtime's
``ResolvedGraph`` (one jit mixing executors), cached under
``topology_key + batch + precision`` and JSON-persistable so CI and
serving reuse measurements instead of repeating them
(``AutotuneCache``).

Timing goes through an injectable ``timer(label, fn) -> seconds`` so
tests tune deterministically with fake clocks and CI's smoke lane can
shrink the candidate set; the default timer is min-of-reps wall clock
(robust to scheduler noise, same estimator as the bench).
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import time
from collections import OrderedDict
from typing import Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.core.graph import (INPUT, NetworkGraph, conv_keyed,
                              refuse_norm_gelu)
from repro.core.schedule import DEFAULT_VMEM_BUDGET
from repro.obs import metrics as _metrics
from repro.obs import trace as _trace

# per-conv-node executor candidates (fp32); int8 has no wave datapath
NODE_MODES_F32 = ("wave", "megakernel")
FIXED_MODES_F32 = ("wave", "megakernel", "graphkernel")
FIXED_MODES_INT8 = ("megakernel", "graphkernel")


def default_timer(reps: int = 3) -> Callable:
    """min-of-``reps`` wall-clock seconds, after one warm-up call (the
    warm-up absorbs trace+compile). Same estimator as the bench, so
    tuned decisions and bench rows rank candidates identically."""
    def timer(label, fn):
        del label
        jax.block_until_ready(fn())
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            jax.block_until_ready(fn())
            best = min(best, time.perf_counter() - t0)
        return best
    return timer


# ---------------------------------------------------------------------------
# TunedPlan: the JSON-stable winner record
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class TunedPlan:
    """One tuning decision: per-node executor modes + the budget point.

    ``node_modes`` is (conv name, mode) in schedule order — the full
    prescription; chains re-derive deterministically from the
    ``graphkernel`` members (``fusible_chains(only=...)``), so the plan
    stays valid JSON without serialising lowered programs. ``batch``
    and ``precision`` echo the cache-key components the measurement is
    only valid for; ``us_per_batch`` is the winner's measured
    wall-clock and ``candidates_us`` every raced candidate's, for
    provenance (the bench's ``auto`` row and the regression gate's
    ratchet read them).
    """
    node_modes: Tuple[Tuple[str, str], ...]
    vmem_budget: int
    batch: int
    precision: str
    us_per_batch: float
    candidates_us: Tuple[Tuple[str, float], ...] = ()

    def modes_dict(self) -> "OrderedDict[str, str]":
        return OrderedDict(self.node_modes)

    def as_dict(self) -> dict:
        return {"node_modes": [list(nm) for nm in self.node_modes],
                "vmem_budget": self.vmem_budget,
                "batch": self.batch,
                "precision": self.precision,
                "us_per_batch": self.us_per_batch,
                "candidates_us": [[n, u] for n, u in self.candidates_us]}

    @classmethod
    def from_dict(cls, d: dict) -> "TunedPlan":
        return cls(
            node_modes=tuple((str(n), str(m)) for n, m in d["node_modes"]),
            vmem_budget=int(d["vmem_budget"]),
            batch=int(d["batch"]),
            precision=str(d["precision"]),
            us_per_batch=float(d["us_per_batch"]),
            candidates_us=tuple((str(n), float(u))
                                for n, u in d.get("candidates_us", ())))


class AutotuneCache:
    """JSON-persistable winner store keyed by (topology, batch shape,
    precision).

    The key hashes the graph's ``topology_key`` — wiring + per-node
    layer geometry — NOT just the layer shapes, so two graphs sharing
    every conv geometry but wired differently can never exchange plans
    (the same collision rule the executor cache enforces). ``load`` on
    a missing path returns an empty cache (first CI run, cold server).
    """

    def __init__(self, entries: Optional[Dict[str, dict]] = None):
        self.entries: Dict[str, dict] = dict(entries or {})

    @staticmethod
    def key(graph: NetworkGraph, batch: int, precision: str) -> str:
        blob = json.dumps([repr(graph.topology_key), int(batch),
                           str(precision)], sort_keys=True)
        return hashlib.sha256(blob.encode()).hexdigest()[:24]

    def get(self, graph: NetworkGraph, batch: int,
            precision: str) -> Optional[TunedPlan]:
        d = self.entries.get(self.key(graph, batch, precision))
        return TunedPlan.from_dict(d) if d is not None else None

    def put(self, graph: NetworkGraph, plan: TunedPlan) -> str:
        k = self.key(graph, plan.batch, plan.precision)
        self.entries[k] = plan.as_dict()
        return k

    def __len__(self) -> int:
        return len(self.entries)

    def to_json(self) -> str:
        return json.dumps({"version": 1, "entries": self.entries},
                          indent=1, sort_keys=True)

    @classmethod
    def from_json(cls, text: str) -> "AutotuneCache":
        d = json.loads(text)
        if d.get("version") != 1:
            raise ValueError(
                f"unknown autotune cache version {d.get('version')!r}")
        return cls(d["entries"])

    def save(self, path: str) -> None:
        with open(path, "w") as f:
            f.write(self.to_json() + "\n")

    @classmethod
    def load(cls, path: str) -> "AutotuneCache":
        if not os.path.exists(path):
            return cls()
        with open(path) as f:
            return cls.from_json(f.read())


# ---------------------------------------------------------------------------
# Plan realisation: a forced-mode ResolvedGraph (no fault walking)
# ---------------------------------------------------------------------------

def resolve_plan(graph: NetworkGraph, programs, node_modes,
                 *, vmem_budget: Optional[int] = DEFAULT_VMEM_BUDGET,
                 precision: str = "fp32", qgraph=None, batch: int = 1):
    """Realise an explicit per-node mode map as a ``ResolvedGraph``.

    The autotuner's counterpart to ``runtime.fallback.resolve_graph``:
    modes are *prescribed* (a tuned winner, or a uniform fixed-mode
    candidate during the race) rather than discovered by walking the
    degradation chain, and no events are recorded. ``graphkernel``
    nodes re-form chains with ``fusible_chains(only=...)``; standalone
    survivors settle as per-layer megakernels exactly as the fallback
    runtime does, so a cached plan replayed later lowers to the same
    executable shape that was measured.
    """
    from repro.core.graph import fusible_chains
    from repro.core.schedule import ChainNodeSpec, lower_graph_kernel
    from repro.core.streaming import (_chain_batch_block,
                                      _graph_epilogues,
                                      _graph_kernel_program,
                                      _normalize_mode)
    from repro.runtime.fallback import ResolvedGraph

    refuse_norm_gelu(graph, "the autotuner")
    programs = conv_keyed(graph, programs, "programs")
    node_modes = OrderedDict(node_modes)
    quantized = precision == "int8"
    epi = _graph_epilogues(graph)
    modes: "OrderedDict[str, str]" = OrderedDict()
    for n in graph.conv_nodes():
        if n.name not in node_modes:
            raise ValueError(f"tuned plan has no mode for conv node "
                             f"{n.name!r}")
        m = _normalize_mode(node_modes[n.name])
        if quantized and m not in ("graphkernel", "megakernel"):
            raise ValueError(f"{n.name}: int8 has no {m!r} datapath")
        modes[n.name] = m
    kprogs = {name: _graph_kernel_program(programs[name], epi[name].act,
                                          epi[name].residual is not None,
                                          vmem_budget, batch)
              for name, m in modes.items()
              if m in ("graphkernel", "megakernel")}
    gk = frozenset(n for n, m in modes.items() if m == "graphkernel")
    chains_all = fusible_chains(graph, kprogs, vmem_budget=vmem_budget,
                                quantized=quantized, only=gk or None) \
        if gk else ()
    by_name = {n.name: n for n in graph.nodes}
    active, gkps = [], {}
    for c in chains_all:
        if c.convs[0] not in gk:
            continue
        if len(c.convs) < 2:
            modes[c.convs[0]] = "megakernel"
            continue
        specs = [ChainNodeSpec(name=k, kp=kprogs[k],
                               in_value=by_name[k].inputs[0],
                               out_value=epi[k].out,
                               residual_value=epi[k].residual)
                 for k in c.convs]
        gkps[c.convs[0]] = lower_graph_kernel(
            specs, quantized=quantized,
            batch_block=_chain_batch_block(specs, quantized,
                                           vmem_budget, batch))
        active.append(c)
    return ResolvedGraph(graph=graph, programs=programs,
                         node_modes=modes, chains=tuple(active),
                         kprogs=kprogs, gkps=gkps, events=[],
                         precision=precision, qgraph=qgraph,
                         vmem_budget=vmem_budget)


# ---------------------------------------------------------------------------
# The measured search
# ---------------------------------------------------------------------------

def _uniform(graph: NetworkGraph, mode: str):
    return tuple((n.name, mode) for n in graph.conv_nodes())


def _time_plan(graph, programs, node_modes, x, weights, *, vmem_budget,
               precision, qgraph, timer, label,
               conv_fn=None, conv_backend="xla"):
    """End-to-end seconds for one candidate mode map (fresh jit — the
    candidates race as the executables serving would actually run)."""
    resolved = resolve_plan(graph, programs, node_modes,
                            vmem_budget=vmem_budget, precision=precision,
                            qgraph=qgraph, batch=x.shape[0])
    fwd = jax.jit(resolved.forward_fn(conv_fn, conv_backend))
    ops = resolved.operands()
    w = qgraph.device_weights() if precision == "int8" else weights
    return timer(label, lambda: fwd(x, w, ops)), resolved


def tune_graph(graph: NetworkGraph, programs, weights, x: jax.Array,
               *, precision: str = "fp32", qgraph=None,
               vmem_budgets: Sequence[int] = (DEFAULT_VMEM_BUDGET,),
               timer: Optional[Callable] = None,
               cache: Optional[AutotuneCache] = None,
               conv_fn: Optional[Callable] = None,
               conv_backend: str = "xla",
               per_node: bool = True) -> TunedPlan:
    """Measure candidate execution plans for ``graph`` and pick one.

    The search, per VMEM-budget point:

    1. **fixed modes** — every uniform mode map (wave / megakernel /
       graphkernel; int8 drops wave) timed end to end;
    2. **per-node** (fp32, ``per_node=True``) — each conv node timed in
       isolation on its *actual* input activation (from the reference
       walk — the paper's §4 per-layer parameter choice, measured) under
       wave vs megakernel; the winners assemble a mixed map, raced once
       plainly and once with its megakernel nodes offered to the chain
       partitioner (``graphkernel`` membership — fused chains keep only
       the nodes ``fusible_chains`` accepts).

    The overall argmin becomes the ``TunedPlan``. Because every fixed
    mode is itself a candidate, the tuned plan can never measure worse
    than the best fixed mode on the machine that tuned it — the
    regression-gate ratchet's invariant. ``cache`` short-circuits the
    whole search on a hit and records the winner on a miss.

    ``weights`` maps conv node name -> (w, b) (fp32); int8 tuning takes
    the calibrated ``qgraph`` and ignores ``weights``. ``x`` fixes the
    batch shape the measurement is valid for (= the cache key's batch).
    """
    refuse_norm_gelu(graph, "the autotuner")
    programs = conv_keyed(graph, programs, "programs")
    batch = int(x.shape[0])
    if cache is not None:
        hit = cache.get(graph, batch, precision)
        if hit is not None:
            _metrics.registry().counter("autotune_cache.hits").inc()
            _trace.event(f"autotune_hit:{graph.name}", cat="autotune",
                         batch=batch, precision=precision)
            return hit
        _metrics.registry().counter("autotune_cache.misses").inc()
    if timer is None:
        timer = default_timer()
    if precision == "int8" and qgraph is None:
        raise ValueError("int8 tuning needs a calibrated qgraph")
    if precision == "fp32":
        weights = conv_keyed(graph, weights, "weights")

    fixed = FIXED_MODES_INT8 if precision == "int8" else FIXED_MODES_F32
    candidates: "OrderedDict[str, tuple]" = OrderedDict()
    for budget in vmem_budgets:
        for mode in fixed:
            candidates[f"{mode}@{budget}"] = (_uniform(graph, mode),
                                              budget)
        if per_node and precision == "fp32":
            mixed = _per_node_modes(graph, programs, weights, x,
                                    vmem_budget=budget, timer=timer,
                                    conv_fn=conv_fn,
                                    conv_backend=conv_backend)
            candidates[f"mixed@{budget}"] = (tuple(mixed.items()), budget)
            if any(m == "megakernel" for m in mixed.values()):
                chained = OrderedDict(
                    (n, "graphkernel" if m == "megakernel" else m)
                    for n, m in mixed.items())
                candidates[f"mixed+chains@{budget}"] = (
                    tuple(chained.items()), budget)

    results: "OrderedDict[str, float]" = OrderedDict()
    best = None          # (seconds, label, node_modes, budget)
    for label, (node_modes, budget) in candidates.items():
        with _trace.span(f"candidate:{label}", cat="autotune",
                         batch=batch, precision=precision) as sp:
            secs, resolved = _time_plan(
                graph, programs, node_modes, x, weights,
                vmem_budget=budget, precision=precision, qgraph=qgraph,
                timer=timer, label=("plan", label),
                conv_fn=conv_fn, conv_backend=conv_backend)
            if sp is not None:
                sp.attrs["us"] = round(secs * 1e6, 1)
        results[label] = secs
        # record the modes the resolution actually settled on
        # (standalone graphkernel nodes demote to megakernel)
        settled = tuple(resolved.node_modes.items())
        if best is None or secs < best[0]:
            best = (secs, label, settled, budget)

    plan = TunedPlan(
        node_modes=best[2], vmem_budget=best[3], batch=batch,
        precision=precision, us_per_batch=round(best[0] * 1e6, 1),
        candidates_us=tuple((lbl, round(s * 1e6, 1))
                            for lbl, s in results.items()))
    if cache is not None:
        cache.put(graph, plan)
    return plan


def _per_node_modes(graph, programs, weights, x, *, vmem_budget, timer,
                    conv_fn=None, conv_backend="xla"):
    """wave-vs-megakernel per conv node, timed on the node's actual
    input activation (reference walk). Pure cost proxy: the per-layer
    entry points skip epilogue ReLU/pool/residual, which are identical
    work across the two candidates."""
    from repro.core.streaming import (_partition_waves_cached,
                                      run_graph_reference,
                                      run_layer_megakernel,
                                      run_layer_wave)
    env = run_graph_reference(graph, weights, x)
    out = OrderedDict()
    for n in graph.conv_nodes():
        xin = env[n.inputs[0]]
        w, b = weights[n.name]
        wprog = _partition_waves_cached(programs[n.name])
        with _trace.span(f"probe:{n.name}:wave", cat="autotune") as sp:
            t_wave = timer(
                ("node", n.name, "wave"),
                lambda: run_layer_wave(wprog, xin, w, b, conv_fn=conv_fn,
                                       conv_backend=conv_backend))
            if sp is not None:
                sp.attrs["us"] = round(t_wave * 1e6, 1)
        with _trace.span(f"probe:{n.name}:megakernel",
                         cat="autotune") as sp:
            t_mega = timer(
                ("node", n.name, "megakernel"),
                lambda: run_layer_megakernel(wprog, xin, w, b,
                                             vmem_budget=vmem_budget))
            if sp is not None:
                sp.attrs["us"] = round(t_mega * 1e6, 1)
        out[n.name] = "wave" if t_wave < t_mega else "megakernel"
    return out
