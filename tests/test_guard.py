"""Numeric guards (ISSUE 7): NaN/Inf quarantine with per-node
attribution + reference repair on fp32, int8 saturation-rate detection
with the int32-reference re-run, and the guarded serving session."""
import jax
import jax.numpy as jnp
import pytest

from repro.core.decomposition import ConvLayer
from repro.core.graph import INPUT, GraphNode, NetworkGraph, conv_keyed
from repro.core.streaming import plan_graph, run_graph_reference
from repro.distributed.fault import FaultInjector
from repro.launch.session import StreamingSession
from repro.models.cnn import init_graph_weights
from repro.quant.accuracy import quant_graph_reference_acts
from repro.quant.calibrate import calibrate_graph
from repro.runtime import (GuardConfig, NumericGuardTripped, check_fp32,
                           check_int8, guarded_output, run_graph_degraded)

BUDGET = 64 * 1024


def _conv(name, h, c_in, c_out, inputs, relu=True):
    return GraphNode(name, "conv", inputs,
                     layer=ConvLayer(name, h, h, c_in, c_out, 3,
                                     stride=1, pad=1),
                     act="relu" if relu else None)


def _block():
    nodes = (
        _conv("stem", 8, 3, 8, (INPUT,)),
        _conv("c1", 8, 8, 8, ("stem",)),
        _conv("c2", 8, 8, 8, ("c1",), relu=False),
        GraphNode("add", "add", ("c2", "stem"), act="relu"),
    )
    g = NetworkGraph("identity_block", (8, 8, 3), nodes, "add")
    plans = plan_graph(g, BUDGET)
    ws = init_graph_weights(g, jax.random.key(0))
    x = jax.random.normal(jax.random.key(1), (2,) + g.in_shape)
    return g, plans, ws, x


# ---------------------------------------------------------------------------
# Checks in isolation
# ---------------------------------------------------------------------------

def test_check_fp32_detects_nonfinite():
    cfg = GuardConfig()
    assert check_fp32(jnp.ones((4,)), cfg) is None
    assert "non-finite" in check_fp32(jnp.array([1.0, jnp.nan]), cfg)
    assert "non-finite" in check_fp32(jnp.array([jnp.inf, 0.0]), cfg)
    assert check_fp32(jnp.array([jnp.nan]),
                      GuardConfig(nonfinite=False)) is None


def test_check_int8_saturation_threshold():
    cfg = GuardConfig(int8_saturation=0.5)
    ok = jnp.zeros((8,), jnp.int8)
    sat = jnp.full((8,), 127, jnp.int8)
    assert check_int8(ok, cfg) is None
    assert "saturation" in check_int8(sat, cfg)
    half = jnp.array([127, -127, 0, 0], jnp.int8)
    assert check_int8(half, cfg) is not None       # exactly at threshold
    assert check_int8(half, GuardConfig(int8_saturation=0.6)) is None
    assert check_int8(sat, GuardConfig(int8_saturation=None)) is None


# ---------------------------------------------------------------------------
# fp32: poisoned node -> attributed, repaired on the reference path
# ---------------------------------------------------------------------------

def test_fp32_guard_attributes_and_repairs_poisoned_node():
    g, plans, ws, x = _block()
    ref = run_graph_reference(g, ws, x)[g.output]
    wsd = conv_keyed(g, ws, "weights")
    with FaultInjector() as fi:
        fi.arm_nan("c1")
        y, res = run_graph_degraded(g, plans, x, ws)
        assert not bool(jnp.isfinite(y).all())     # kernel output poisoned
        y2, cause = guarded_output(res, y, x, wsd, GuardConfig())
    assert "non-finite" in cause
    # exactly the poisoned node was quarantined, as a structured event
    guard_events = [e for e in res.events if e.stage == "guard"]
    assert [(e.node, e.to_mode) for e in guard_events] == \
        [("c1", "reference")]
    # the repaired output matches the clean interpreter reference
    assert jnp.allclose(y2, ref, atol=1e-4)


def test_fp32_guard_clean_output_untouched_zero_events():
    g, plans, ws, x = _block()
    wsd = conv_keyed(g, ws, "weights")
    y, res = run_graph_degraded(g, plans, x, ws)
    y2, cause = guarded_output(res, y, x, wsd, GuardConfig())
    assert cause is None and y2 is y
    assert [e for e in res.events if e.stage == "guard"] == []


def test_fp32_guard_repair_false_raises_instead():
    g, plans, ws, x = _block()
    wsd = conv_keyed(g, ws, "weights")
    with FaultInjector() as fi:
        fi.arm_nan("c2")
        y, res = run_graph_degraded(g, plans, x, ws)
        with pytest.raises(NumericGuardTripped, match="non-finite"):
            guarded_output(res, y, x, wsd, GuardConfig(repair=False))


def test_fp32_guard_nonfinite_input_surfaces_instead_of_looping():
    """Garbage input (not a kernel fault) must raise, not silently
    return the same garbage after a futile diagnosis walk."""
    g, plans, ws, x = _block()
    wsd = conv_keyed(g, ws, "weights")
    y, res = run_graph_degraded(g, plans, x, ws)
    xbad = x.at[0, 0, 0, 0].set(jnp.nan)
    ybad = jnp.full_like(y, jnp.nan)
    with pytest.raises(NumericGuardTripped, match="no node attributed"):
        guarded_output(res, ybad, xbad, wsd, GuardConfig())


# ---------------------------------------------------------------------------
# int8: calibration drift -> saturation -> int32 reference re-run
# ---------------------------------------------------------------------------

def test_int8_guard_saturation_reruns_int32_reference():
    nodes = (_conv("stem", 8, 3, 8, (INPUT,)),
             _conv("c1", 8, 8, 8, ("stem",)))
    g = NetworkGraph("mini", (8, 8, 3), nodes, "c1")
    plans = plan_graph(g, BUDGET)
    ws = init_graph_weights(g, jax.random.key(0))
    x = jax.random.normal(jax.random.key(1), (2,) + g.in_shape)
    # calibrate on a far quieter distribution than the serving traffic:
    # the serving batch drives activations past the calibrated range
    qg = calibrate_graph(g, ws, x * 0.01)
    y, res = run_graph_degraded(g, plans, x, ws, precision="int8",
                                qgraph=qg, dequantize=False)
    cfg = GuardConfig(int8_saturation=0.05)
    y2, cause = guarded_output(res, y, x, None, cfg, raw_int8=True)
    assert "saturation" in cause and "calibration" in cause
    (ev,) = [e for e in res.events if e.stage == "guard"]
    assert ev.to_mode == "reference"
    # the re-run is the int32 reference model — bit-exact by definition
    ref_q = quant_graph_reference_acts(qg, x)[g.output]
    assert jnp.array_equal(y2, ref_q)


def test_int8_guard_calibrated_traffic_passes():
    nodes = (_conv("stem", 8, 3, 8, (INPUT,)),
             _conv("c1", 8, 8, 8, ("stem",)))
    g = NetworkGraph("mini", (8, 8, 3), nodes, "c1")
    plans = plan_graph(g, BUDGET)
    ws = init_graph_weights(g, jax.random.key(0))
    x = jax.random.normal(jax.random.key(1), (2,) + g.in_shape)
    qg = calibrate_graph(g, ws, x)         # calibrated on the real traffic
    y, res = run_graph_degraded(g, plans, x, ws, precision="int8",
                                qgraph=qg, dequantize=False)
    y2, cause = guarded_output(res, y, x, None,
                               GuardConfig(int8_saturation=0.5),
                               raw_int8=True)
    assert cause is None and y2 is y and res.events == []


# ---------------------------------------------------------------------------
# Guarded serving session end-to-end
# ---------------------------------------------------------------------------

def test_session_guard_quarantines_and_repairs():
    g, plans, ws, x = _block()
    ref = run_graph_reference(g, ws, x)[g.output]
    with FaultInjector() as fi:
        fi.arm_nan("c1")
        sess = StreamingSession(g, plans, ws, max_batch=2,
                                mode="megakernel", guard=True)
        y = sess.run_batch(x)
        assert sess.guard_trips == 1
        assert jnp.allclose(y, ref, atol=1e-4)
        h = sess.health()
        assert h["counters"]["guard_trips"] == 1
        assert any(e["stage"] == "guard" for e in h["degradation_events"])


def test_session_guard_clean_traffic_zero_trips():
    g, plans, ws, x = _block()
    ref = run_graph_reference(g, ws, x)[g.output]
    sess = StreamingSession(g, plans, ws, max_batch=2,
                            mode="megakernel", guard=True)
    y = sess.run_batch(x)
    assert sess.guard_trips == 0
    assert sess.health()["degradation_events"] == []
    assert jnp.allclose(y, ref, atol=1e-4)
