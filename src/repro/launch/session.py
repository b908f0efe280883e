"""Compiled streaming sessions: batched multi-image CNN serving.

The paper's deployment story (§7, the FPGA face-detection demo) is a
fixed network whose tile schedule is burned into the command decoder
once, then replayed per frame. ``StreamingSession`` is that story for
the JAX executor, now over the **NetworkGraph IR** (core/graph.py): the
session takes a graph — a linear conv stack is just a chain graph —
lowers every conv node to a static ``TileProgram`` at construction,
compiles ONE whole-graph executable per batch shape (walking the
graph's validated topological schedule: residual adds fold into
megakernel epilogues, shortcut projections stream like any 1x1 conv,
and activation buffers free per the graph's liveness plan), and
replays it for every request — weights and operand tables are traced
arguments, so weight updates and schedule replays never retrigger
compilation.

Serving modes:

  * ``run_batch(x)`` — synchronous batched inference; the executable
    cache is keyed on (shape, dtype, mode, precision, resolved
    fallback signature), so steady-state traffic of a fixed batch
    shape compiles exactly once (``compile_count`` exposes this) and a
    degraded resolution never aliases a clean one.
  * ``submit(img)`` / ``result(ticket)`` — micro-batching queue: many
    independent single-image requests are coalesced into one
    ``max_batch``-sized compiled call (partial batches are zero-padded
    to keep the batch shape — and therefore the executable — stable).

DESIGN.md §2 maps this onto the paper's control path in detail.
"""
from __future__ import annotations

import time
import warnings
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.core.decomposition import ConvLayer, plan_decomposition
from repro.core.graph import NetworkGraph, chain_graph, conv_keyed
from repro.core.schedule import TileProgram
from repro.core.streaming import (compile_graph, graph_forward_fn,
                                  graph_operands, plan_graph)
from repro.obs import metrics as _metrics
from repro.obs import trace as _trace
from repro.runtime.errors import DeadlineExceeded, Overloaded


class StreamingSession:
    """One compiled (graph, plan-set, batch-shape) serving session.

    ``graph`` is a ``NetworkGraph`` (or a plain layer sequence, wrapped
    into its chain graph). ``mode`` picks the per-conv-node executor
    the session compiles: ``"wave"`` (default — each dependency-free
    wave of the schedule is one fused dispatch), ``"megakernel"`` (one
    persistent Pallas kernel per conv node; bias+ReLU+pool AND residual
    adds fused in the kernel epilogue, so ``pool_backend`` is ignored),
    ``"graphkernel"`` (fused chains of conv nodes share ONE persistent
    kernel and a VMEM activation arena — O(#chains) launches),
    ``"scan"`` (serial step replay), or ``"auto"`` — the measured
    autotuner (core/autotune.py) times candidate plans per conv node at
    construction and serves the winning mixed-mode plan; pass
    ``autotune_cache`` (an ``AutotuneCache`` or a JSON path) to reuse
    cached measurements across sessions, ``autotune_timer`` /
    ``autotune_budgets`` to control the search (CI smoke lanes shrink
    both). ``pool_backend="fused"`` serves CONV+POOL nodes through the
    Pallas fused conv+ReLU+pool kernel.

    Kernel programs are lowered batch-aware at ``max_batch`` (ISSUE 8):
    the batch axis rides the megakernel/graphkernel grids as the
    outermost dimension (``batch_block`` clamped to the VMEM budget),
    so batched calls amortise launch + weight traffic instead of
    replaying a per-image schedule B times. Smaller batches still serve
    through the same programs (the launch clamps the block to the
    actual batch).

    ``donate`` (default True) donates the input batch buffer to the
    compiled executable, so XLA reuses it for the inter-layer
    activations in place instead of doubling peak HBM — callers must
    treat the array passed to ``run_batch`` as consumed (the
    micro-batch queue always builds a fresh batch, so ``submit`` /
    ``flush`` are unaffected).

    ``precision="int8"`` (megakernel mode only) serves the fixed-point
    datapath: pass a calibrated ``qnet`` — a ``QuantizedGraph``
    (``repro.quant.calibrate_graph``) or, for chain graphs, a
    ``QuantizedNetwork``; the session packs its int8 weights / int32
    requant vectors as the traced weight tuples, fp32 requests are
    quantized at entry and dequantized at exit, and raw int8
    activations flow along every edge. The tile schedules and operand
    tables are byte-identical to the fp32 megakernel session's.
    """

    def __init__(self, graph, plans,
                 weights,
                 conv_fn: Optional[Callable] = None,
                 conv_backend: str = "xla", max_batch: int = 8,
                 mode: str = "wave", pool_backend: str = "xla",
                 donate: bool = True, precision: str = "fp32",
                 qnet=None,
                 fallback=None, guard=None,
                 autotune_cache=None,
                 autotune_timer: Optional[Callable] = None,
                 autotune_budgets: Optional[Sequence[int]] = None,
                 max_pending: Optional[int] = None,
                 compile_retries: int = 2,
                 backoff_base: float = 0.05,
                 sleep_fn: Callable[[float], None] = time.sleep,
                 validate_inputs: bool = True,
                 clock: Callable[[], float] = time.monotonic,
                 tracer: Optional["_trace.Tracer"] = None):
        if not isinstance(graph, NetworkGraph):
            graph = chain_graph(tuple(graph))
        self.graph = graph
        # opt-in observability: with a Tracer, the session activates it
        # around construction (plan/lower/compile spans) and every
        # serving entry point (request lifecycle + trace-time kernel
        # launch spans); None costs nothing (no-op fast path)
        self.tracer = tracer
        self.layers = tuple(n.layer for n in graph.conv_nodes())
        self._plans = self._conv_dict(plans, "plans")
        self.plans = tuple(self._plans.values())
        self.max_batch = int(max_batch)
        self.mode = mode
        self.pool_backend = pool_backend
        self.donate = bool(donate)
        self.precision = precision
        with _trace.use_tracer(tracer):
            self._progs = compile_graph(graph, self._plans)
        # schedule-ordered program list (chain sessions: stack order)
        self.programs: List[TileProgram] = list(self._progs.values())
        qgraph = None
        if precision == "int8":
            if qnet is None:
                raise ValueError(
                    "precision='int8' needs a calibrated qnet — run "
                    "repro.quant.calibrate_graph (or calibrate_network "
                    "for a linear stack) first")
            if not hasattr(qnet, "scales"):      # QuantizedNetwork
                from repro.quant.calibrate import \
                    quantized_graph_from_network
                if tuple(qnet.layers) != self.layers:
                    raise ValueError(
                        "qnet was calibrated for a different layer stack")
                qnet = quantized_graph_from_network(qnet, graph)
            if qnet.graph != graph:
                raise ValueError(
                    "qnet was calibrated for a different graph")
            # the traced per-node weight tuples (wq, bias_q, m, shift);
            # float weights are not needed at serving time
            self.weights = qnet.device_weights()
            qgraph = qnet
        else:
            if weights is None:
                raise ValueError(
                    "weights=None is only valid with precision='int8' "
                    "(where the calibrated qnet supplies them) — pass "
                    "the float (w, b) pairs")
            self.weights = self._conv_dict(weights, "weights")
        self.qnet = qnet
        self._qgraph = qgraph
        self._conv_fn, self._conv_backend = conv_fn, conv_backend
        # -- graceful degradation (runtime/fallback.py, runtime/guard.py)
        if guard is not None and guard is not False and fallback is None \
                and mode != "auto":
            fallback = True             # repair needs the resolved plan
            # (mode="auto" already serves through a resolved plan)
        self.guard = None
        if guard is not None and guard is not False:
            from repro.runtime.guard import GuardConfig
            self.guard = guard if isinstance(guard, GuardConfig) \
                else GuardConfig()
            # the repair path re-reads the input batch — incompatible
            # with donating its buffer to the compiled executable
            self.donate = False
        self.resolved = None
        self.tuned = None
        self.autotune_cache = None
        # int8 + guard: the guard must see raw int8 codes (saturation
        # is invisible after dequantize) — the session dequantizes
        # after the check
        self._guard_raw = (self.guard is not None and precision == "int8")
        if mode == "auto":
            if fallback is not None and fallback is not False:
                raise ValueError(
                    "mode='auto' builds its own resolved plan — it "
                    "cannot combine with fallback= (the tuner, not the "
                    "degradation walk, decides per-node modes)")
            from repro.core.autotune import (AutotuneCache, resolve_plan,
                                             tune_graph)
            cache_path = None
            if isinstance(autotune_cache, str):
                cache_path = autotune_cache
                autotune_cache = AutotuneCache.load(autotune_cache)
            self.autotune_cache = autotune_cache \
                if autotune_cache is not None else AutotuneCache()
            # tune at the serving batch shape: the winner is only valid
            # for the batch it was measured at (= the cache key's batch)
            xt = jax.random.normal(jax.random.key(0),
                                   (self.max_batch,) + graph.in_shape)
            with _trace.use_tracer(tracer):
                self.tuned = tune_graph(
                    graph, self._progs,
                    None if precision == "int8" else self.weights, xt,
                    precision=precision, qgraph=qgraph,
                    timer=autotune_timer, cache=self.autotune_cache,
                    conv_fn=conv_fn, conv_backend=conv_backend,
                    **({"vmem_budgets": tuple(autotune_budgets)}
                       if autotune_budgets is not None else {}))
                if cache_path is not None:
                    self.autotune_cache.save(cache_path)
                self.resolved = resolve_plan(
                    graph, self._progs, self.tuned.modes_dict(),
                    vmem_budget=self.tuned.vmem_budget,
                    precision=precision,
                    qgraph=qgraph, batch=self.max_batch)
                self._ops = self.resolved.operands()
                self._forward = self.resolved.forward_fn(
                    conv_fn, conv_backend,
                    dequantize=not self._guard_raw)
        elif fallback is not None and fallback is not False:
            from repro.runtime.fallback import (FallbackChain,
                                                resolve_graph)
            chain = fallback if isinstance(fallback, FallbackChain) \
                else None
            with _trace.use_tracer(tracer):
                self.resolved = resolve_graph(graph, self._progs,
                                              mode=mode,
                                              chain=chain,
                                              precision=precision,
                                              qgraph=qgraph,
                                              batch=self.max_batch)
                self._ops = self.resolved.operands()
                self._forward = self.resolved.forward_fn(
                    conv_fn, conv_backend,
                    dequantize=not self._guard_raw)
        else:
            self._guard_raw = False
            with _trace.use_tracer(tracer):
                self._ops = graph_operands(graph, self._progs, mode,
                                           precision=precision,
                                           batch=self.max_batch)
                self._forward = graph_forward_fn(graph, self._progs,
                                                 conv_fn,
                                                 conv_backend, mode=mode,
                                                 pool_backend=pool_backend,
                                                 precision=precision,
                                                 qgraph=qgraph,
                                                 batch=self.max_batch)
        # -- serving guardrails
        self.max_pending = max_pending
        self.compile_retries = int(compile_retries)
        self.backoff_base = float(backoff_base)
        self._sleep = sleep_fn
        self._clock = clock
        self.validate_inputs = bool(validate_inputs)
        self.shed = 0                   # requests rejected (queue full)
        self.deadline_expired = 0       # requests dropped past deadline
        self.guard_trips = 0            # batches quarantined + repaired
        self.compile_retries_used = 0   # transient-failure retries taken
        self._executables: Dict[tuple, Callable] = {}
        self.compile_count = 0          # traces performed (the spy)
        self.calls = 0                  # compiled-executable invocations
        # micro-batch queue state:
        # (ticket, image, expiry | None, submitted_at)
        self._pending: List[
            Tuple[int, jax.Array, Optional[float], float]] = []
        self._results: Dict[int, jax.Array] = {}
        self._expired: set = set()
        self._next_ticket = 0

    def _conv_dict(self, items, what: str):
        return conv_keyed(self.graph, items, what)

    @classmethod
    def for_network(cls, layers: Sequence[ConvLayer],
                    weights,
                    sram_budget: int = 128 * 1024,
                    **kw) -> "StreamingSession":
        """Plan every layer under one buffer budget, then build a
        session over the stack's chain graph."""
        plans = [plan_decomposition(l, sram_budget) for l in layers]
        return cls(tuple(layers), plans, weights, **kw)

    @classmethod
    def for_graph(cls, graph: NetworkGraph, weights,
                  sram_budget: int = 128 * 1024,
                  **kw) -> "StreamingSession":
        """Plan every conv node under one buffer budget, then build the
        session (VGG-16 / ResNet-18 graphs from ``core.model_zoo``)."""
        # planning runs before __init__ installs the session tracer, so
        # activate it here too — the plan span belongs to this session
        with _trace.use_tracer(kw.get("tracer")):
            plans = plan_graph(graph, sram_budget)
        return cls(graph, plans, weights, **kw)

    # ------------------------------------------------------------------
    # compiled batched path
    # ------------------------------------------------------------------
    def _exec_key(self, shape, dtype) -> tuple:
        # mode + precision + the resolved mixed-mode signature: a
        # degraded executable must never collide with a clean one (nor
        # fp32 with int8 on the same geometry)
        sig = self.resolved.signature() if self.resolved is not None \
            else ()
        return (tuple(shape), str(dtype), self.mode, self.precision, sig)

    def _executable(self, key: tuple) -> Callable:
        if key not in self._executables:
            def traced(x, weights, ops):
                # runs only while jax traces: counts (re)compilations
                self.compile_count += 1
                _metrics.registry().counter("session.compiles").inc()
                return self._forward(x, weights, ops)
            # donate the input batch: XLA reuses its buffer for the
            # inter-layer activations instead of doubling peak HBM.
            # Weights and operand tables are NOT donated — they serve
            # every subsequent call of the cached executable.
            raw = jax.jit(
                traced, donate_argnums=(0,) if self.donate else ())
            jitted = raw
            if self.donate:
                # backends without donation support (CPU) warn on every
                # compile; suppress just that, just here — not with a
                # process-global filter
                def jitted(*args, _fn=raw):
                    with warnings.catch_warnings():
                        warnings.filterwarnings(
                            "ignore",
                            message="Some donated buffers were not usable")
                        return _fn(*args)
                # keep the jit's inspection surface: the donation audit
                # (tests/test_donation.py) lowers the serving executable
                # and checks the input-output aliasing annotation
                jitted.lower = raw.lower
            self._executables[key] = jitted
        return self._executables[key]

    def check_input(self, x, batched: bool = True) -> None:
        """Reject a request whose shape/dtype/content can't be served.

        The error names the expected spec — a serving boundary that
        answers garbage shapes with XLA trace errors (or worse, a
        silently mis-addressed schedule) is not a boundary. Recorded as
        a ``check_input`` span; its finiteness test waits for the device
        (one ``host_sync``)."""
        with _trace.span("check_input", cat="request", batched=batched):
            self._check_input(x, batched)

    @staticmethod
    def _host_sync():
        """Count one wait of the host for the device's answer in
        ``session.host_syncs``; returns its ``host_sync`` span context
        to wrap the wait."""
        _metrics.registry().counter("session.host_syncs").inc()
        return _trace.span("host_sync", cat="request")

    def _check_input(self, x, batched: bool) -> None:
        H, W, C = self.graph.in_shape
        spec = (f"(B, {H}, {W}, {C})" if batched else f"({H}, {W}, {C})")
        what = "run_batch" if batched else "submit"
        want_nd = 4 if batched else 3
        if getattr(x, "ndim", None) != want_nd \
                or tuple(x.shape[-3:]) != (H, W, C):
            raise ValueError(
                f"{self.graph.name}.{what}: expected {spec} "
                f"{self.graph.dtype} input, got shape "
                f"{tuple(getattr(x, 'shape', ()))}")
        dt = jnp.asarray(x).dtype
        ok = (jnp.issubdtype(dt, jnp.floating)
              or (self.precision == "int8" and dt == jnp.int8))
        if not ok:
            raise ValueError(
                f"{self.graph.name}.{what}: expected {spec} "
                f"{self.graph.dtype} input, got dtype {dt}")
        if not jnp.issubdtype(dt, jnp.floating):
            return
        with self._host_sync():
            finite = bool(jnp.isfinite(x).all())
        if not finite:
            raise ValueError(
                f"{self.graph.name}.{what}: input contains NaN/Inf — "
                f"refusing to serve (expected finite {spec} "
                f"{self.graph.dtype})")

    def _dequant_out(self, y: jax.Array) -> jax.Array:
        from repro.core.quantization import dequantize_int8
        return dequantize_int8(y, self._qgraph.scales[self.graph.output])

    def run_batch(self, x: jax.Array) -> jax.Array:
        """(B, H, W, C) -> network output, through the cached executable.

        With ``donate=True`` (default) ``x``'s buffer is donated — treat
        it as consumed after this call. Transient compile/launch
        failures retry up to ``compile_retries`` times with exponential
        backoff; a failed compile is evicted from the executable cache
        immediately, so it can never poison later calls. With
        ``guard=`` set, the output is checked post-execution and a
        tripped batch re-runs on the reference path."""
        reg = _metrics.registry()
        attempts = 0
        with _trace.use_tracer(self.tracer), \
                _trace.span("run_batch", cat="run", mode=self.mode,
                            graph=self.graph.name) as sp:
            if self.validate_inputs:
                self.check_input(x, batched=True)
            if sp is not None:
                sp.attrs["batch"] = int(x.shape[0])
            key = self._exec_key(x.shape, x.dtype)
            while True:
                fresh = key not in self._executables
                fn = self._executable(key)
                try:
                    self.calls += 1
                    reg.counter("session.calls").inc()
                    # the first call of a fresh executable traces +
                    # compiles (jit is lazy) — attribute it to the
                    # compile phase; a steady-state call returns once
                    # the batch is enqueued on the device (dispatch)
                    with _trace.span("compile" if fresh else "dispatch",
                                     cat="compile" if fresh else "run"):
                        y = fn(x, self.weights, self._ops)
                    break
                except Exception as e:
                    # evict FIRST: a half-built executable must not serve
                    # the next request (cache-poisoning fix, ISSUE 7)
                    self._executables.pop(key, None)
                    attempts += 1
                    if attempts > self.compile_retries:
                        raise
                    self.compile_retries_used += 1
                    reg.counter("session.compile_retries").inc()
                    _trace.event("compile_retry", cat="request",
                                 attempt=attempts,
                                 cause=f"{type(e).__name__}: {e}")
                    self._sleep(min(self.backoff_base
                                    * 2 ** (attempts - 1), 1.0))
            if self.guard is not None:
                from repro.runtime.guard import guarded_output
                weights = self.weights if self.precision == "fp32" \
                    else None
                with self._host_sync():
                    y, cause = guarded_output(
                        self.resolved, y, x, weights, self.guard,
                        raw_int8=self._guard_raw, conv_fn=self._conv_fn,
                        conv_backend=self._conv_backend)
                if cause is not None:
                    self.guard_trips += 1
                    reg.counter("session.guard_trips").inc()
                    _trace.event("guard_trip", cat="request", cause=cause)
            if self._guard_raw:
                y = self._dequant_out(y)
        return y

    # ------------------------------------------------------------------
    # micro-batching queue: single-image requests share one compiled call
    # ------------------------------------------------------------------
    def submit(self, image: jax.Array,
               deadline: Optional[float] = None) -> int:
        """Enqueue one (H, W, C) image; returns a ticket for result().

        Auto-flushes whenever a full ``max_batch`` accumulates, so a
        steady stream of submits turns into back-to-back full batches.
        With ``max_pending`` set, a full queue rejects the request with
        ``Overloaded`` (explicit load-shedding — the alternative is an
        unbounded queue whose latency grows without limit). ``deadline``
        is a per-request budget in seconds: a request still queued when
        it expires is dropped at the next flush and its ``result()``
        raises ``DeadlineExceeded``."""
        with _trace.use_tracer(self.tracer), \
                _trace.span("submit", cat="request") as sp:
            if self.validate_inputs:
                self.check_input(image, batched=False)
            elif getattr(image, "ndim", None) != 3:
                raise ValueError(
                    f"submit() wants (H, W, C), got {image.shape}")
            reg = _metrics.registry()
            if self.max_pending is not None \
                    and len(self._pending) >= self.max_pending:
                self.shed += 1
                reg.counter("session.shed").inc()
                _trace.event("shed", cat="request",
                             pending=len(self._pending),
                             max_pending=self.max_pending)
                raise Overloaded(
                    f"{self.graph.name}: pending queue full "
                    f"({len(self._pending)}/{self.max_pending}) — request "
                    f"shed; retry after a flush")
            ticket = self._next_ticket
            self._next_ticket += 1
            expiry = None if deadline is None else self._clock() + deadline
            self._pending.append((ticket, image, expiry, self._clock()))
            reg.gauge("session.queue_depth").set(len(self._pending))
            if sp is not None:
                sp.attrs.update(ticket=ticket,
                                queue_depth=len(self._pending))
            if len(self._pending) >= self.max_batch:
                self.flush()
        return ticket

    def flush(self) -> None:
        """Run all pending requests as one (padded) compiled batch.

        Requests whose deadline already passed are dropped here —
        spending a batch slot on an answer nobody is waiting for only
        delays the live requests behind it. Each live request's time in
        the queue (submit to the start of this flush) goes to the
        ``session.queue_wait_s`` histogram, and its sum and count to
        the ``flush`` span (``wait_s_sum``, ``n``)."""
        if not self._pending:
            return
        reg = _metrics.registry()
        with _trace.use_tracer(self.tracer), \
                _trace.span("flush", cat="request",
                            pending=len(self._pending)) as sp:
            now = self._clock()
            live = []
            for t, im, exp, sub in self._pending:
                if exp is not None and now > exp:
                    self._expired.add(t)
                    self.deadline_expired += 1
                    reg.counter("session.deadline_expired").inc()
                    _trace.event("deadline_expired", cat="request",
                                 ticket=t)
                else:
                    live.append((t, im, sub))
            self._pending.clear()
            reg.gauge("session.queue_depth").set(0)
            waits = [max(0.0, now - sub) for _, _, sub in live]
            wait_hist = reg.histogram("session.queue_wait_s")
            for w in waits:
                wait_hist.observe(w)
            if sp is not None:
                sp.attrs.update(wait_s_sum=sum(waits), n=len(waits))
            if not live:
                return
            with _trace.span("stack", cat="request", n=len(live)):
                imgs = jnp.stack([im for _, im, _ in live])
                n = imgs.shape[0]
                if n < self.max_batch:
                    # zero-pad to the session batch so the same
                    # executable serves partial flushes; padded rows are
                    # discarded below
                    fill = jnp.zeros(
                        (self.max_batch - n,) + imgs.shape[1:], imgs.dtype)
                    imgs = jnp.concatenate([imgs, fill])
            reg.histogram("session.batch_fill_ratio") \
               .observe(n / self.max_batch)
            out = self.run_batch(imgs)
            with _trace.span("split", cat="request", n=len(live)):
                for i, (t, _, _) in enumerate(live):
                    self._results[t] = out[i]

    def result(self, ticket: int) -> jax.Array:
        """Fetch (and forget) one request's output; flushes if pending.

        Results are held until fetched or discarded — a server dropping
        clients mid-flight must ``discard()`` abandoned tickets or the
        result map grows without bound. A ticket dropped past its
        deadline raises ``DeadlineExceeded``."""
        with _trace.use_tracer(self.tracer), \
                _trace.span("result", cat="request", ticket=ticket):
            if ticket not in self._results:
                self.flush()
            if ticket in self._expired:
                self._expired.discard(ticket)
                raise DeadlineExceeded(
                    f"ticket {ticket}: dropped — its deadline passed while "
                    f"queued")
            if ticket not in self._results:
                raise KeyError(
                    f"ticket {ticket}: unknown, already fetched, or "
                    f"discarded")
            return self._results.pop(ticket)

    def discard(self, ticket: int) -> None:
        """Drop a pending or completed request without fetching it."""
        self._pending = [(t, im, e, s) for t, im, e, s in self._pending
                         if t != ticket]
        self._results.pop(ticket, None)
        self._expired.discard(ticket)

    @property
    def pending(self) -> int:
        return len(self._pending)

    def health(self) -> dict:
        """Machine-readable serving health: per-node executor modes,
        degradation events, and the guardrail counters (``serve
        --health`` prints this)."""
        h = {
            "graph": self.graph.name,
            "mode": self.mode,
            "precision": self.precision,
            "fallback": self.resolved is not None,
            "guard": self.guard is not None,
            "degradation_events": [],
            "node_modes": {},
            "counters": {
                "shed": self.shed,
                "deadline_expired": self.deadline_expired,
                "guard_trips": self.guard_trips,
                "compile_retries_used": self.compile_retries_used,
                "compiles": self.compile_count,
                "calls": self.calls,
            },
            "pending": len(self._pending),
            "executables": len(self._executables),
        }
        h["node_modes"] = self.node_modes()
        if self.resolved is not None:
            h["degradation_events"] = [e.as_dict()
                                       for e in self.resolved.events]
        if self.tuned is not None:
            h["autotune"] = self.tuned.as_dict()
        h["metrics"] = _metrics.registry().snapshot()
        return h

    def node_modes(self) -> Dict[str, str]:
        """The executor each conv node actually runs: the resolved plan's
        modes under fallback or autotuning; otherwise the session mode,
        except that a graphkernel node left alone in its chain runs the
        per-layer megakernel."""
        if self.resolved is not None:
            return dict(self.resolved.node_modes)
        names = [n.name for n in self.graph.conv_nodes()]
        if self.mode != "graphkernel":
            return {n: self.mode for n in names}
        from repro.core.streaming import graph_chain_programs
        chains, _, _ = graph_chain_programs(
            self.graph, self._progs, quantized=self.precision == "int8",
            batch=self.max_batch)
        return {n: "graphkernel" if len(c.convs) > 1 else "megakernel"
                for c in chains for n in c.convs}

    def describe(self) -> str:
        lines = [f"StreamingSession[{self.graph.name}]: "
                 f"{len(self.graph.nodes)} nodes "
                 f"({len(self.programs)} conv), "
                 f"mode={self.mode}, precision={self.precision}, "
                 f"pool_backend={self.pool_backend}, "
                 f"max_batch={self.max_batch}, "
                 f"executables={len(self._executables)}, "
                 f"compiles={self.compile_count}, calls={self.calls}"]
        if self.resolved is not None:
            counts = self.resolved.mode_counts()
            lines.append(
                "  fallback: " +
                ", ".join(f"{m}={n}" for m, n in sorted(counts.items())) +
                f", degradations={len(self.resolved.events)}, "
                f"guard={'on' if self.guard is not None else 'off'}, "
                f"shed={self.shed}, expired={self.deadline_expired}, "
                f"guard_trips={self.guard_trips}, "
                f"retries={self.compile_retries_used}")
        lines += ["  " + p.describe() for p in self.programs]
        return "\n".join(lines)
