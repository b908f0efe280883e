"""Graceful-degradation runtime (ISSUE 7): every fallback edge
(graphkernel -> megakernel -> wave -> scan, chain-unit demotion, int8's
graphkernel -> megakernel floor) exercised via injected faults with the
degraded output checked against the interpreter / int32 reference, plus
the hardened serving session (input validation, deadlines,
load-shedding, compile retry without cache poisoning)."""
import jax
import jax.numpy as jnp
import pytest

from repro.core.decomposition import ConvLayer
from repro.core.graph import INPUT, GraphNode, NetworkGraph, conv_keyed
from repro.core.streaming import (plan_graph, run_graph_reference,
                                  run_graph_streamed)
from repro.distributed.fault import FaultInjector
from repro.launch.session import StreamingSession
from repro.models.cnn import init_graph_weights
from repro.quant.accuracy import quant_graph_reference_acts
from repro.quant.calibrate import calibrate_graph
from repro.runtime import (DeadlineExceeded, FallbackChain,
                           FallbackExhausted, Overloaded,
                           degradation_event_count,
                           reset_degradation_events, resolve_graph,
                           run_graph_degraded)

BUDGET = 64 * 1024


def _conv(name, h, c_in, c_out, inputs, relu=True, pool=1):
    return GraphNode(name, "conv", inputs,
                     layer=ConvLayer(name, h, h, c_in, c_out, 3,
                                     stride=1, pad=1, pool=pool),
                     act="relu" if relu else None)


def _identity_block():
    nodes = (
        _conv("stem", 8, 3, 8, (INPUT,)),
        _conv("c1", 8, 8, 8, ("stem",)),
        _conv("c2", 8, 8, 8, ("c1",), relu=False),
        GraphNode("add", "add", ("c2", "stem"), act="relu"),
    )
    return NetworkGraph("identity_block", (8, 8, 3), nodes, "add")


@pytest.fixture
def block():
    g = _identity_block()
    plans = plan_graph(g, BUDGET)
    ws = init_graph_weights(g, jax.random.key(0))
    x = jax.random.normal(jax.random.key(1), (2,) + g.in_shape)
    ref = run_graph_reference(g, ws, x)[g.output]
    return g, plans, ws, x, ref


# ---------------------------------------------------------------------------
# FallbackChain semantics
# ---------------------------------------------------------------------------

def test_chain_order_enforced():
    FallbackChain(("graphkernel", "wave"))          # subset OK
    with pytest.raises(ValueError, match="order"):
        FallbackChain(("wave", "megakernel"))
    with pytest.raises(ValueError, match="unknown fallback mode"):
        FallbackChain(("interpret",))
    assert FallbackChain().next_mode("scan") is None
    assert FallbackChain().from_mode("wave") == ("wave", "scan")


# ---------------------------------------------------------------------------
# Every fallback edge, with output parity vs the interpreter reference
# ---------------------------------------------------------------------------

def test_clean_run_full_fidelity_zero_events(block):
    g, plans, ws, x, ref = block
    reset_degradation_events()
    y, res = run_graph_degraded(g, plans, x, ws)
    assert set(res.node_modes.values()) == {"graphkernel"}
    assert res.events == [] and degradation_event_count() == 0
    assert jnp.allclose(y, ref, atol=1e-4)


def test_graphkernel_to_megakernel_only_faulted_node_degrades(block):
    g, plans, ws, x, ref = block
    with FaultInjector() as fi:
        fi.arm("plan", node="c1", mode="graphkernel")
        y, res = run_graph_degraded(g, plans, x, ws)
    assert res.node_modes["c1"] == "megakernel"
    # the rest of the graph keeps kernel-mode plans (chains can't span
    # the degraded node, so survivors settle as megakernels — but
    # nothing falls to wave/scan)
    assert all(m in ("graphkernel", "megakernel")
               for m in res.node_modes.values())
    # exactly ONE structured event, naming node / edge / stage / cause
    (ev,) = res.events
    assert ev.node == "c1" and ev.stage == "plan" and ev.retry == 1
    assert (ev.from_mode, ev.to_mode) == ("graphkernel", "megakernel")
    assert "PlanError" in ev.cause and "injected" in ev.cause
    assert jnp.allclose(y, ref, atol=1e-4)


def test_megakernel_to_wave_edge(block):
    g, plans, ws, x, ref = block
    with FaultInjector() as fi:
        fi.arm("plan", node="c1", mode="graphkernel")
        fi.arm("launch", node="c1", mode="megakernel")
        y, res = run_graph_degraded(g, plans, x, ws)
    assert res.node_modes["c1"] == "wave"
    assert [(e.from_mode, e.to_mode, e.retry) for e in res.events] == \
        [("graphkernel", "megakernel", 1), ("megakernel", "wave", 2)]
    assert res.events[1].stage == "launch"
    assert jnp.allclose(y, ref, atol=1e-4)


def test_wave_to_scan_edge(block):
    g, plans, ws, x, ref = block
    with FaultInjector() as fi:
        fi.arm("plan", node="c2", mode="graphkernel")
        fi.arm("plan", node="c2", mode="megakernel")
        fi.arm("lower", node="c2", mode="wave")
        y, res = run_graph_degraded(g, plans, x, ws)
    assert res.node_modes["c2"] == "scan"
    assert [e.to_mode for e in res.events] == \
        ["megakernel", "wave", "scan"]
    assert jnp.allclose(y, ref, atol=1e-4)


def test_chain_unit_fault_demotes_all_members_with_one_event(block):
    g, plans, ws, x, ref = block
    with FaultInjector() as fi:
        # launch@graphkernel on the chain HEAD = the fused chain's own
        # launch failing — the chain degrades as a unit
        fi.arm("launch", node="stem", mode="graphkernel")
        y, res = run_graph_degraded(g, plans, x, ws)
    assert set(res.node_modes.values()) == {"megakernel"}
    assert res.chains == ()
    (ev,) = res.events
    assert ev.stage == "chain" and ev.node == "stem"
    assert "stem+c1+c2" in ev.cause       # names the demoted members
    assert jnp.allclose(y, ref, atol=1e-4)


def test_vmem_budget_fault_forces_budget_exceeded_edge(block):
    g, plans, ws, x, ref = block
    with FaultInjector() as fi:
        fi.arm_vmem(128, node="c2")       # nothing lowers into 128 bytes
        y, res = run_graph_degraded(g, plans, x, ws)
    assert res.node_modes["c2"] == "wave"
    assert [e.stage for e in res.events] == ["budget", "budget"]
    assert jnp.allclose(y, ref, atol=1e-4)


def test_exhaustion_at_terminal_mode_raises_chained(block):
    g, plans, ws, x, _ = block
    with FaultInjector() as fi:
        for mode in ("graphkernel", "megakernel", "wave", "scan"):
            fi.arm("plan", node="c1", mode=mode)
        with pytest.raises(FallbackExhausted, match="terminal mode"):
            run_graph_degraded(g, plans, x, ws)


def test_mode_argument_starts_partway_down_the_chain(block):
    g, plans, ws, x, ref = block
    y, res = run_graph_degraded(g, plans, x, ws, mode="wave")
    assert set(res.node_modes.values()) == {"wave"}
    assert res.events == []
    assert jnp.allclose(y, ref, atol=1e-4)


def test_degraded_output_matches_undegraded_wave_exactly(block):
    """A node degraded to wave runs the SAME executor the all-wave
    session runs — bitwise, not approximately."""
    g, plans, ws, x, _ = block
    y_wave = run_graph_streamed(g, plans, x, ws, mode="wave")
    with FaultInjector() as fi:
        fi.arm("plan", node="c1", mode="graphkernel")
        fi.arm("plan", node="c1", mode="megakernel")
        y, res = run_graph_degraded(g, plans, x, ws)
    assert res.node_modes["c1"] == "wave"
    assert jnp.allclose(y, y_wave, atol=1e-5)


# ---------------------------------------------------------------------------
# int8: graphkernel -> megakernel only, bit-exact vs the int32 reference
# ---------------------------------------------------------------------------

def test_int8_edge_bit_exact_vs_int32_reference(block):
    g, plans, ws, x, _ = block
    qg = calibrate_graph(g, ws, x)
    ref_q = quant_graph_reference_acts(qg, x)[g.output]
    with FaultInjector() as fi:
        fi.arm("lower", node="c1", mode="graphkernel")
        y, res = run_graph_degraded(g, plans, x, ws, precision="int8",
                                    qgraph=qg, dequantize=False)
    assert res.node_modes["c1"] == "megakernel"
    (ev,) = res.events
    assert ev.stage == "lower" and "LoweringError" in ev.cause
    assert jnp.array_equal(y, ref_q)      # bit-exact, no tolerance


def test_int8_has_no_wave_floor(block):
    g, plans, ws, x, _ = block
    qg = calibrate_graph(g, ws, x)
    with FaultInjector() as fi:
        fi.arm("plan", node="c1", mode="graphkernel")
        fi.arm("plan", node="c1", mode="megakernel")
        with pytest.raises(FallbackExhausted):
            run_graph_degraded(g, plans, x, ws, precision="int8",
                               qgraph=qg)


# ---------------------------------------------------------------------------
# Executable-cache hygiene: degraded signatures never collide with clean
# ---------------------------------------------------------------------------

def test_resolved_signature_distinguishes_degradation(block):
    g, plans, ws, x, _ = block
    from repro.core.streaming import compile_graph
    programs = compile_graph(g, plan_graph(g, BUDGET))
    clean = resolve_graph(g, programs)
    with FaultInjector() as fi:
        fi.arm("plan", node="c1", mode="graphkernel")
        degraded = resolve_graph(g, programs)
    assert clean.signature() != degraded.signature()
    # the signature also keys armed poisons (it reads the LIVE arms, so
    # the key is computed at call time): a poisoned trace must not
    # serve clean traffic
    clean_sig = clean.signature()
    with FaultInjector() as fi:
        fi.arm_nan("c1")
        poisoned = resolve_graph(g, programs)
        assert poisoned.signature() != clean_sig


def test_degraded_then_clean_run_does_not_reuse_degraded_executable(block):
    g, plans, ws, x, ref = block
    with FaultInjector() as fi:
        fi.arm("plan", node="c1", mode="graphkernel")
        y_deg, res_deg = run_graph_degraded(g, plans, x, ws)
    y_clean, res_clean = run_graph_degraded(g, plans, x, ws)
    assert res_deg.node_modes != res_clean.node_modes
    assert set(res_clean.node_modes.values()) == {"graphkernel"}
    assert jnp.allclose(y_clean, ref, atol=1e-4)


# ---------------------------------------------------------------------------
# Hardened serving session
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("network", ["alexnet", "vgg16", "resnet18"])
def test_session_input_validation_names_expected_spec(network):
    from repro.core.model_zoo import network_graph
    g = network_graph(network)
    ws = init_graph_weights(g, jax.random.key(0))
    sess = StreamingSession.for_graph(g, ws, max_batch=2)
    H, W, C = g.in_shape
    with pytest.raises(ValueError) as ei:
        sess.run_batch(jnp.zeros((1, H + 1, W, C)))
    assert f"(B, {H}, {W}, {C})" in str(ei.value)
    with pytest.raises(ValueError, match="dtype int32"):
        sess.run_batch(jnp.zeros((1, H, W, C), jnp.int32))
    with pytest.raises(ValueError, match="NaN/Inf"):
        sess.run_batch(jnp.full((1, H, W, C), jnp.inf))
    with pytest.raises(ValueError, match=f"\\({H}, {W}, {C}\\)"):
        sess.submit(jnp.zeros((H, W, C + 1)))


def _mini_session(**kw):
    nodes = (_conv("stem", 8, 3, 8, (INPUT,)),
             _conv("c1", 8, 8, 8, ("stem",)))
    g = NetworkGraph("mini", (8, 8, 3), nodes, "c1")
    ws = init_graph_weights(g, jax.random.key(0))
    return StreamingSession.for_graph(g, ws, sram_budget=BUDGET, **kw), g, ws


def test_session_load_shedding_bounded_queue():
    sess, g, _ = _mini_session(max_batch=8, max_pending=2)
    img = jnp.zeros(g.in_shape)
    t1, t2 = sess.submit(img), sess.submit(img)
    with pytest.raises(Overloaded, match="queue full"):
        sess.submit(img)
    assert sess.shed == 1
    sess.flush()                           # draining reopens the queue
    t3 = sess.submit(img)
    assert sess.result(t1).shape == sess.result(t3).shape


def test_session_deadline_expiry_sheds_stale_requests():
    now = [0.0]
    sess, g, _ = _mini_session(max_batch=8, clock=lambda: now[0])
    img = jnp.zeros(g.in_shape)
    stale = sess.submit(img, deadline=1.0)
    live = sess.submit(img)
    now[0] = 5.0
    sess.flush()
    with pytest.raises(DeadlineExceeded, match="deadline passed"):
        sess.result(stale)
    assert sess.deadline_expired == 1
    assert sess.result(live).shape == (8, 8, 8)   # live one still served


def test_session_compile_retry_evicts_failed_executable():
    sleeps = []
    sess, g, _ = _mini_session(max_batch=2, compile_retries=2,
                               backoff_base=0.05,
                               sleep_fn=sleeps.append)
    x = jax.random.normal(jax.random.key(1), (2,) + g.in_shape)
    good = sess._forward
    fails = [1]

    def flaky(xx, w, o):
        if fails[0] > 0:
            fails[0] -= 1
            raise RuntimeError("transient compile blowup")
        return good(xx, w, o)

    sess._forward = flaky
    y = sess.run_batch(x)
    assert y.shape == (2, 8, 8, 8)
    assert sleeps == [0.05]                # deterministic backoff
    assert sess.compile_retries_used == 1
    # the failed executable was evicted BEFORE the retry — the cache
    # holds exactly the one good executable, never the poisoned one
    assert len(sess._executables) == 1
    assert sess.run_batch(x).shape == (2, 8, 8, 8)


def test_session_compile_failure_exhausts_retries_and_raises():
    sess, g, _ = _mini_session(max_batch=2, compile_retries=1,
                               sleep_fn=lambda _: None)
    x = jnp.zeros((2,) + g.in_shape)

    def always_bad(xx, w, o):
        raise RuntimeError("permanent lowering bug")

    sess._forward = always_bad
    with pytest.raises(RuntimeError, match="permanent lowering bug"):
        sess.run_batch(x)
    assert sess._executables == {}         # nothing poisoned the cache


def test_session_fallback_reports_modes_and_health():
    with FaultInjector() as fi:
        fi.arm("plan", node="c1", mode="graphkernel")
        sess, g, _ = _mini_session(max_batch=2, mode="graphkernel",
                                   fallback=True)
    assert sess.resolved.node_modes["c1"] == "megakernel"
    x = jax.random.normal(jax.random.key(1), (2,) + g.in_shape)
    ws = init_graph_weights(g, jax.random.key(0))
    ref = run_graph_reference(g, ws, x)[g.output]
    assert jnp.allclose(sess.run_batch(x), ref, atol=1e-4)
    h = sess.health()
    assert h["node_modes"]["c1"] == "megakernel"
    assert len(h["degradation_events"]) == 1
    assert h["degradation_events"][0]["node"] == "c1"
    assert "fallback: " in sess.describe()


def test_session_executable_key_carries_mode_precision_signature():
    sess, g, _ = _mini_session(max_batch=2, mode="graphkernel",
                               fallback=True)
    key = sess._exec_key((2, 8, 8, 3), "float32")
    assert "graphkernel" in key and "fp32" in key
    assert sess.resolved.signature() in key
