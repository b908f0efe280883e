"""Observability subsystem (ISSUE 9): span tracer, metrics registry,
Chrome/Perfetto export, and the execute-span == launch-count invariant.

The acceptance artifact: per-layer megakernel execute spans (and
per-chain graphkernel spans) are recorded by the SAME code path as the
trace-time launch counters (kernels/common.py LaunchCounter), so the
span count equals launch_count() by construction — verified here on
the real AlexNet stack.
"""
import json
import threading

import jax
import jax.numpy as jnp
import pytest

import repro.kernels.wave_replay.ops as wr
import repro.kernels.wave_replay_q.ops as wrq
from repro.core.decomposition import ALEXNET_STACK, plan_decomposition
from repro.core.graph import chain_graph
from repro.core.streaming import (compile_graph, graph_forward_fn,
                                  graph_operands, plan_graph)
from repro.models.cnn import init_graph_weights
from repro.obs import (MetricsRegistry, Tracer, chrome_trace_events,
                       current_tracer, render_metrics, reset_metrics,
                       set_tracer, use_registry, use_tracer,
                       write_chrome_trace)
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace


# ---------------------------------------------------------------------------
# Tracer core
# ---------------------------------------------------------------------------

def test_spans_nest_and_carry_attrs():
    t = Tracer()
    with t.span("outer", cat="plan", graph="g") as outer:
        with t.span("inner", cat="lower") as inner:
            pass
    assert outer.parent_id is None
    assert inner.parent_id == outer.id
    assert outer.attrs["graph"] == "g"
    assert outer.end_ns is not None and inner.end_ns is not None
    # child lies within the parent interval
    assert outer.start_ns <= inner.start_ns <= inner.end_ns <= outer.end_ns


def test_span_is_recorded_when_entered():
    """``Tracer.span`` hands back the span; entering it records it and
    gives it its parent and start, leaving it gives it its end."""
    t = Tracer()
    with t.span("outer") as outer:
        inner = t.span("inner", cat="run", n=2)
        assert t.spans() == [outer] and inner.end_ns is None
        with inner as got:
            assert got is inner and inner.parent_id == outer.id
            assert inner.end_ns is None
    assert t.spans() == [outer, inner]
    assert inner.attrs == {"n": 2} and inner.dur_ns >= 0


def test_span_closes_with_error_attribute_on_exception():
    """A failing node still closes its span — with an ``error``
    attribute — so traces of failing runs are complete."""
    t = Tracer()
    with pytest.raises(ValueError):
        with t.span("outer", cat="run"):
            with t.span("boom", cat="execute"):
                raise ValueError("tile does not fit")
    outer, boom = t.spans()
    assert boom.attrs["error"] == "ValueError: tile does not fit"
    assert boom.end_ns is not None
    # the parent also closed (and recorded the propagating error)
    assert outer.end_ns is not None
    assert "error" in outer.attrs
    # nesting stack unwound: a new span is again a root
    with t.span("after"):
        pass
    assert t.spans()[-1].parent_id is None


def test_disabled_helpers_are_noops():
    assert current_tracer() is None
    cm = obs_trace.span("anything", cat="plan")   # shared nullcontext
    with cm:
        pass
    obs_trace.event("nothing")                    # must not raise
    t = Tracer()
    with use_tracer(t):
        with obs_trace.span("live", cat="plan"):
            pass
        # use_tracer(None) must NOT mask the outer tracer
        with use_tracer(None):
            with obs_trace.span("still_live", cat="plan"):
                pass
    assert current_tracer() is None
    assert [s.name for s in t.spans("plan")] == ["live", "still_live"]


def test_tracer_thread_local_stacks():
    t = Tracer()
    done = threading.Event()

    def worker():
        with t.span("w", cat="run"):
            done.wait(2.0)

    with use_tracer(t):
        th = threading.Thread(target=worker)
        th.start()
        # main-thread span must not become a child of the worker's span
        with t.span("m", cat="run") as m:
            pass
        done.set()
        th.join()
    assert m.parent_id is None
    w = [s for s in t.spans() if s.name == "w"][0]
    assert w.parent_id is None
    assert w.tid != m.tid


def test_tracer_bounded_and_truncation_reported():
    t = Tracer(max_spans=2)
    for i in range(4):
        with t.span(f"s{i}"):
            pass
    assert len(t.spans()) == 2
    assert t.dropped == 2
    payload = chrome_trace_events(t)
    assert payload["metadata"]["dropped"] == 2


# ---------------------------------------------------------------------------
# Metrics registry
# ---------------------------------------------------------------------------

def test_registry_isolation_and_snapshot():
    reg = MetricsRegistry()
    with use_registry(reg):
        obs_metrics.registry().counter("kernel_launches").inc(3)
        obs_metrics.registry().gauge("train.loss").set(1.5)
        obs_metrics.registry().histogram("lat").observe(0.01)
    # nothing leaked into the default registry
    assert obs_metrics.registry().counter("kernel_launches").value == 0
    snap = reg.snapshot()
    assert snap["counters"]["kernel_launches"] == 3
    assert snap["gauges"]["train.loss"] == 1.5
    assert snap["histograms"]["lat"]["count"] == 1
    reg.reset()
    assert reg.counter("kernel_launches").value == 0
    assert reg.histogram("lat").count == 0


def test_histogram_buckets_and_stats():
    reg = MetricsRegistry()
    h = reg.histogram("t", buckets=(0.1, 1.0))
    for v in (0.05, 0.5, 2.0):
        h.observe(v)
    snap = h.snapshot()
    assert snap["buckets"] == {"0.1": 1, "1.0": 1, "+inf": 1}
    assert snap["count"] == 3
    assert snap["min"] == 0.05 and snap["max"] == 2.0
    assert abs(h.mean - (0.05 + 0.5 + 2.0) / 3) < 1e-12


def test_render_metrics_plain_text():
    reg = MetricsRegistry()
    reg.counter("kernel_launches.wave_replay").inc(7)
    reg.counter("session.host_syncs").inc(9)
    reg.histogram("session.queue_wait_s").observe(0.002)
    text = render_metrics(reg)
    assert "kernel_launches.wave_replay 7" in text
    assert "session.host_syncs 9" in text
    assert "session.queue_wait_s count=1" in text


def test_launch_counter_shims_and_registry_feed():
    """The deduplicated LaunchCounter keeps the launch_count()/
    reset_launch_count() shims AND mirrors into the metrics registry."""
    reg = MetricsRegistry()
    with use_registry(reg):
        wr.reset_launch_count()
        with wr.launches.record("c1", "megakernel"):
            pass
        with wr.launches.record("c2", "megakernel"):
            pass
        assert wr.launch_count() == 2
        assert reg.counter("kernel_launches").value == 2
        assert reg.counter("kernel_launches.wave_replay").value == 2
        wr.reset_launch_count()
        assert wr.launch_count() == 0


def test_degradation_counter_is_registry_scoped():
    from repro.runtime.fallback import (DegradationEvent,
                                        degradation_event_count,
                                        record_event,
                                        reset_degradation_events)
    ev = DegradationEvent(node="c1", from_mode="megakernel",
                          to_mode="wave", stage="plan",
                          cause="ValueError: boom", retry=0)
    with use_registry(MetricsRegistry()):
        events = []
        record_event(events, ev)
        assert degradation_event_count() == 1
        assert obs_metrics.registry() \
            .counter("degradation_events.plan").value == 1
    # the fresh-registry increments never touched the default registry
    assert degradation_event_count() == 0
    record_event([], ev)
    assert degradation_event_count() == 1
    reset_degradation_events()
    assert degradation_event_count() == 0


# ---------------------------------------------------------------------------
# Export round-trip
# ---------------------------------------------------------------------------

def test_chrome_trace_roundtrip_and_child_containment(tmp_path):
    t = Tracer()
    with t.span("parent", cat="run", mode="megakernel"):
        with t.span("child_a", cat="execute", node="c1"):
            pass
        with t.span("child_b", cat="execute", node="c2"):
            pass
        t.event("marker", cat="request", ticket=1)
    path = tmp_path / "trace.json"
    n = write_chrome_trace(str(path), t)
    payload = json.loads(path.read_text())      # round-trips
    assert len(payload["traceEvents"]) == n == 4
    ev = {e["name"]: e for e in payload["traceEvents"]}
    parent, a, b = ev["parent"], ev["child_a"], ev["child_b"]
    for e in (parent, a, b):
        assert e["ph"] == "X" and e["dur"] >= 0
    assert ev["marker"]["ph"] == "i"
    # children fit inside the parent, and siblings do not overlap:
    # monotonic, a closes before b opens
    assert parent["ts"] <= a["ts"]
    assert a["ts"] + a["dur"] <= b["ts"]
    assert b["ts"] + b["dur"] <= parent["ts"] + parent["dur"]
    assert a["args"]["node"] == "c1"
    # ts list is sorted (Perfetto wants ordered events)
    ts = [e["ts"] for e in payload["traceEvents"]]
    assert ts == sorted(ts)


def test_chrome_trace_serializes_arbitrary_attrs():
    t = Tracer()
    with t.span("s", cat="plan", shape=(1, 2, 3), plan=object()):
        pass
    json.dumps(chrome_trace_events(t))   # must not raise


# ---------------------------------------------------------------------------
# Execute spans == launch counters (the acceptance criterion), AlexNet
# ---------------------------------------------------------------------------

def _alexnet_setup(mode):
    g = chain_graph(tuple(ALEXNET_STACK), name="alexnet_obs")
    plans = plan_graph(g, 128 * 1024)
    progs = compile_graph(g, plans)
    ws = init_graph_weights(g, jax.random.key(0))
    x = jnp.zeros((1,) + g.in_shape)
    fn = graph_forward_fn(g, progs, mode=mode)
    ops = graph_operands(g, progs, mode=mode)
    return fn, x, ws, ops


@pytest.mark.parametrize("mode", ["megakernel", "graphkernel"])
def test_execute_span_count_matches_launch_count_alexnet(mode):
    """Tracing one AlexNet forward records exactly one ``execute`` span
    per kernel launch — per conv layer in megakernel mode, per fused
    chain in graphkernel mode — and the span count equals the
    trace-time launch counter."""
    fn, x, ws, ops = _alexnet_setup(mode)
    t = Tracer()
    with use_tracer(t):
        wr.reset_launch_count()
        wrq.reset_launch_count()
        jax.eval_shape(fn, x, ws, ops)     # one trace, no execution
    launches = wr.launch_count() + wrq.launch_count()
    assert launches > 0
    ex = t.spans("execute")
    assert len(ex) == launches
    if mode == "megakernel":
        assert launches == len(ALEXNET_STACK)
        assert sorted(s.attrs["node"] for s in ex) \
            == sorted(l.name for l in ALEXNET_STACK)
        assert all(s.attrs["kind"] == "megakernel" for s in ex)
    else:
        # fused chains record kind=graphkernel; a single-node chain
        # executes through the per-layer megakernel path
        assert {s.attrs["kind"] for s in ex} \
            <= {"graphkernel", "megakernel"}
        assert any(s.attrs["kind"] == "graphkernel" for s in ex)
    # registry mirror agrees with the shim counters
    # (default registry: the autouse conftest fixture resets it)
    assert obs_metrics.registry().counter("kernel_launches").value \
        == launches


def test_plan_and_lower_spans_emitted():
    g = chain_graph(tuple(ALEXNET_STACK[:2]), name="alexnet_obs2")
    t = Tracer()
    with use_tracer(t):
        plans = plan_graph(g, 128 * 1024)
        compile_graph(g, plans)
    plan_spans = t.spans("plan")
    assert [s.name for s in plan_spans] == ["plan:alexnet_obs2"]
    assert plan_spans[0].attrs["dram_traffic_bytes"] > 0
    assert [s.name for s in t.spans("lower")] == ["lower:alexnet_obs2"]
    # modelled traffic also landed in the metrics registry
    assert obs_metrics.registry() \
        .counter("modelled_dram_traffic_bytes").value \
        == plan_spans[0].attrs["dram_traffic_bytes"]


# ---------------------------------------------------------------------------
# Session lifecycle + health merge
# ---------------------------------------------------------------------------

def _tiny_graph():
    from repro.core.decomposition import ConvLayer
    layers = (ConvLayer("t1", 8, 8, 3, 4, 3, stride=1, pad=1),
              ConvLayer("t2", 8, 8, 4, 4, 3, stride=1, pad=1))
    return chain_graph(layers, name="tiny_obs")


def test_session_lifecycle_spans_and_health_metrics():
    from repro.launch.session import StreamingSession
    g = _tiny_graph()
    ws = init_graph_weights(g, jax.random.key(1))
    t = Tracer()
    with use_registry(MetricsRegistry()) as reg:
        sess = StreamingSession.for_graph(g, ws, sram_budget=64 * 1024,
                                          max_batch=2, mode="scan",
                                          tracer=t)
        imgs = jax.random.normal(jax.random.key(2), (3,) + g.in_shape)
        tk0 = sess.submit(imgs[0])
        tk1 = sess.submit(imgs[1])        # fills the batch -> auto flush
        jax.block_until_ready(sess.result(tk0))
        sess.result(tk1)
        tk2 = sess.submit(imgs[2])
        sess.flush()
        sess.result(tk2)
        h = sess.health()
        snap = reg.snapshot()
    # plan/lower spans from construction, run_batch + flush spans from
    # serving, a submit and a result span per request — all on one
    # tracer, and no instants
    assert t.span_count("plan") >= 1
    assert t.span_count("lower") >= 1
    runs = [s.name for s in t.spans("run")]
    assert runs.count("run_batch") == 2
    assert [s.name for s in t.spans("request")
            if s.name == "flush"] == ["flush", "flush"]
    subs = [s for s in t.spans("request") if s.name == "submit"]
    assert [s.attrs["ticket"] for s in subs] == [tk0, tk1, tk2]
    assert [s.attrs["queue_depth"] for s in subs] == [1, 2, 1]
    replies = [s for s in t.spans("request") if s.name == "result"]
    assert [s.attrs["ticket"] for s in replies] == [tk0, tk1, tk2]
    assert t.events("request") == []
    # first run_batch compiled, second hit the session executable cache
    kinds = [s.name for s in t.spans("compile")]
    assert kinds.count("compile") >= 1
    # metrics: health() merges the registry snapshot
    assert h["metrics"]["counters"]["session.calls"] == 2
    assert snap["counters"]["session.compiles"] == 1
    fill = snap["histograms"]["session.batch_fill_ratio"]
    assert fill["count"] == 2
    assert fill["min"] == 0.5 and fill["max"] == 1.0
    assert snap["histograms"]["session.queue_wait_s"]["count"] == 3
    assert "session.request_latency_s" not in snap["histograms"]
    # one isfinite sync per submit and per batch
    assert h["metrics"]["counters"]["session.host_syncs"] == 3 + 2
    assert snap["gauges"]["session.queue_depth"] == 0


def _served(max_batch, batches, tracer=None, **kw):
    """A tiny scan-mode session that served ``batches`` full batches of
    single-image requests, every result fetched."""
    from repro.launch.session import StreamingSession
    g = _tiny_graph()
    ws = init_graph_weights(g, jax.random.key(1))
    sess = StreamingSession.for_graph(g, ws, sram_budget=64 * 1024,
                                      max_batch=max_batch, mode="scan",
                                      tracer=tracer, **kw)
    imgs = jax.random.normal(jax.random.key(2),
                             (max_batch * batches,) + g.in_shape)
    tickets = [sess.submit(im) for im in imgs]
    jax.block_until_ready([sess.result(tk) for tk in tickets])
    return sess


def _path(span, by_id):
    names = []
    while span is not None:
        names.append(span.name)
        span = by_id.get(span.parent_id)
    return "/".join(reversed(names))


@pytest.mark.parametrize("max_batch,syncs", [(8, 9), (1, 2)])
def test_served_session_records_the_span_tree(max_batch, syncs):
    """Serving records submit > check_input > host_sync per request and
    flush > stack, run_batch > check_input > host_sync, dispatch, split
    per batch: max_batch + 1 host syncs per batch, also in the
    counter."""
    t = Tracer()
    with use_registry(MetricsRegistry()) as reg:
        _served(max_batch, 3, tracer=t)
    serving = [s for s in t.spans()
               if s.cat in ("request", "run", "compile")]
    by_id = {s.id: s for s in t.spans()}
    paths = [_path(s, by_id) for s in serving]
    per_request = ["submit", "submit/check_input",
                   "submit/check_input/host_sync", "result"]
    per_batch = ["submit/flush", "submit/flush/stack",
                 "submit/flush/split", "submit/flush/run_batch",
                 "submit/flush/run_batch/check_input",
                 "submit/flush/run_batch/check_input/host_sync"]
    for p in per_request:
        assert paths.count(p) == 3 * max_batch, p
    for p in per_batch:
        assert paths.count(p) == 3, p
    # the first batch compiles, the two after it dispatch
    assert paths.count("submit/flush/run_batch/compile") == 1
    assert paths.count("submit/flush/run_batch/dispatch") == 2
    assert "execute" not in {s.name for s in serving}
    assert set(paths) == set(per_request + per_batch) | {
        "submit/flush/run_batch/compile", "submit/flush/run_batch/dispatch"}
    syncs_seen = paths.count("submit/check_input/host_sync") \
        + paths.count("submit/flush/run_batch/check_input/host_sync")
    assert syncs_seen == 3 * syncs
    assert reg.counter("session.host_syncs").value == 3 * syncs
    # every span closed inside its parent
    for s in serving:
        parent = by_id.get(s.parent_id)
        if parent is not None:
            assert parent.start_ns <= s.start_ns <= s.end_ns \
                <= parent.end_ns


def test_untraced_serving_records_nothing(monkeypatch):
    """With no tracer on the session or the process, serving opens no
    span: ``span()`` hands back the one shared null context."""
    def refuse(*a, **kw):
        raise AssertionError("a span was opened with tracing off")

    monkeypatch.setattr(obs_trace.Tracer, "span", refuse)
    monkeypatch.setattr(obs_trace.Tracer, "event", refuse)
    assert current_tracer() is None
    sess = _served(2, 2)
    assert sess.tracer is None and current_tracer() is None
    assert obs_trace.span("submit", cat="request") is obs_trace._NULL_CM
    assert obs_trace.span("host_sync") is obs_trace.span("dispatch")
    # the always-on counters still count
    assert obs_metrics.registry().counter("session.host_syncs").value \
        == 2 * 3


def test_queue_wait_counts_each_live_request_once():
    """``session.queue_wait_s`` observes submit -> flush start for each
    live request (not the expired one); the flush span carries the
    same sum and count."""
    now = [100.0]
    t = Tracer()
    with use_registry(MetricsRegistry()) as reg:
        from repro.launch.session import StreamingSession
        g = _tiny_graph()
        sess = StreamingSession.for_graph(
            g, init_graph_weights(g, jax.random.key(1)),
            sram_budget=64 * 1024, max_batch=4, mode="scan", tracer=t,
            clock=lambda: now[0])
        imgs = jax.random.normal(jax.random.key(2), (3,) + g.in_shape)
        sess.submit(imgs[0])                       # waits 0.5 s
        now[0] += 0.25
        sess.submit(imgs[1], deadline=0.1)         # expires
        sess.submit(imgs[2])                       # waits 0.25 s
        now[0] += 0.25
        sess.flush()
        hist = reg.histogram("session.queue_wait_s")
        assert hist.count == 2
        assert hist.sum == pytest.approx(0.75)
        assert hist.snapshot()["max"] == pytest.approx(0.5)
    flush, = [s for s in t.spans("request") if s.name == "flush"]
    assert flush.attrs["n"] == 2
    assert flush.attrs["wait_s_sum"] == pytest.approx(0.75)
    assert sess.deadline_expired == 1


def test_guard_check_is_a_host_sync():
    """With ``guard`` set, the post-execution check waits on the device:
    one more host_sync per batch, inside run_batch."""
    t = Tracer()
    with use_registry(MetricsRegistry()) as reg:
        _served(2, 1, tracer=t, guard=True)
    by_id = {s.id: s for s in t.spans()}
    syncs = [_path(s, by_id) for s in t.spans() if s.name == "host_sync"]
    assert syncs.count("submit/flush/run_batch/host_sync") == 1
    assert len(syncs) == 2 + 1 + 1
    assert reg.counter("session.host_syncs").value == 4


def test_serving_hlo_names_each_conv_node():
    """Every megakernel launch is built under ``named_scope("megakernel:
    <node>")``: the serving executable's HLO carries each node in its
    ``op_name`` metadata, which a device trace can read."""
    from repro.core.decomposition import ConvLayer
    from repro.launch.session import StreamingSession
    layers = (ConvLayer("conv1", 8, 8, 3, 4, 3, stride=1, pad=1),
              ConvLayer("conv2", 8, 8, 4, 4, 3, stride=1, pad=1))
    g = chain_graph(layers, name="tiny_named")
    sess = StreamingSession.for_graph(
        g, init_graph_weights(g, jax.random.key(1)),
        sram_budget=64 * 1024, max_batch=2, mode="megakernel")
    x = jax.ShapeDtypeStruct((2,) + g.in_shape, jnp.float32)
    key = sess._exec_key(x.shape, x.dtype)
    lowered = sess._executable(key).lower(x, sess.weights, sess._ops)
    text = lowered.as_text(debug_info=True)
    assert "megakernel:conv1" in text and "megakernel:conv2" in text
    hlo = lowered.compile().as_text()
    assert 'op_name="jit(traced)/megakernel:conv2/' in hlo


def test_executor_cache_metrics():
    from repro.core import streaming as S
    reg = MetricsRegistry()
    with use_registry(reg):
        S._EXECUTOR_CACHE.clear()
        calls = []
        S._call_cached(("obs_test", 1), lambda: calls.append(1) or
                       (lambda: 42), )
        S._call_cached(("obs_test", 1), lambda: calls.append(1) or
                       (lambda: 42), )
        S._EXECUTOR_CACHE.pop(("obs_test", 1), None)
    assert len(calls) == 1
    assert reg.counter("executor_cache.misses").value == 1
    assert reg.counter("executor_cache.hits").value == 1
