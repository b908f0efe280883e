"""VGG-16 and ResNet-18 end to end through every executor (ISSUE 5
acceptance): cross-mode output parity on the full topologies (reduced
CPU-friendly scale), int8 bit-exactness against the int32 graph
reference with residual adds fused in the megakernel epilogue, the
topology-aware executor cache, measured peak-activation savings from
the buffer-liveness pass, and graph serving sessions."""
import jax
import jax.numpy as jnp
import pytest

from repro.core.decomposition import ConvLayer
from repro.core.graph import (INPUT, GraphNode, NetworkGraph,
                              residual_fusion)
from repro.core.model_zoo import resnet18_graph, vgg16_graph
from repro.core.quantization import dequantize_int8
from repro.core.streaming import (clear_executor_cache,
                                  executor_cache_size, graph_forward_fn,
                                  graph_operands, compile_graph,
                                  plan_graph, run_graph_streamed)
from repro.launch.session import StreamingSession
from repro.models.cnn import apply_graph, init_graph_weights
from repro.quant.accuracy import quant_graph_reference_acts, snr_db
from repro.quant.calibrate import calibrate_graph

BUDGET = 64 * 1024


@pytest.fixture(scope="module")
def tiny_resnet():
    g = resnet18_graph(in_hw=32, width=8, name="r18t")
    ws = init_graph_weights(g, jax.random.key(0))
    x = jax.random.normal(jax.random.key(42), (2,) + g.in_shape)
    return g, plan_graph(g, BUDGET), ws, x


@pytest.fixture(scope="module")
def tiny_vgg():
    g = vgg16_graph(in_hw=32, width=8, name="vggt")
    ws = init_graph_weights(g, jax.random.key(1))
    x = jax.random.normal(jax.random.key(43), (2,) + g.in_shape)
    return g, plan_graph(g, BUDGET), ws, x


def _rel_err(got, ref):
    return float(jnp.max(jnp.abs(got - ref))) \
        / (float(jnp.max(jnp.abs(ref))) + 1e-12)


# ---------------------------------------------------------------------------
# Cross-mode parity: all five executor modes, both networks
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", ["interpret", "scan", "wave",
                                  "megakernel", "graphkernel"])
def test_resnet18_all_modes_match_direct(tiny_resnet, mode):
    g, plans, ws, x = tiny_resnet
    ref = apply_graph(g, ws, x)
    got = run_graph_streamed(g, plans, x, ws, mode=mode)
    assert got.shape == ref.shape
    assert _rel_err(got, ref) < 1e-4, mode


@pytest.mark.parametrize("mode", ["interpret", "scan", "wave",
                                  "megakernel", "graphkernel"])
def test_vgg16_all_modes_match_direct(tiny_vgg, mode):
    g, plans, ws, x = tiny_vgg
    ref = apply_graph(g, ws, x)
    got = run_graph_streamed(g, plans, x, ws, mode=mode)
    assert got.shape == ref.shape
    assert _rel_err(got, ref) < 1e-4, mode


def test_resnet18_int8_bit_exact_and_residual_fused(tiny_resnet):
    """The fifth executor mode: int8 megakernel, bit-exact against the
    int32 graph reference, with every residual add fused into a conv
    epilogue (one kernel launch per conv node, none per add)."""
    from repro.kernels.wave_replay_q import (launch_count,
                                             reset_launch_count)
    g, plans, ws, x = tiny_resnet
    assert len(residual_fusion(g).fused) == 8     # all adds fold in
    qg = calibrate_graph(g, ws, x)
    clear_executor_cache()
    reset_launch_count()
    got = run_graph_streamed(g, plans, x, None, mode="megakernel",
                             precision="int8", qgraph=qg)
    # one int8 kernel launch per conv node — the adds ride the epilogues
    assert launch_count() == len(g.conv_nodes())
    ref_q = quant_graph_reference_acts(qg, x)[g.output]
    ref = dequantize_int8(ref_q, qg.scales[g.output])
    assert jnp.array_equal(got, ref), "int8 graph path != int32 reference"
    # and the quantized pipeline still tracks the float network
    assert snr_db(apply_graph(g, ws, x), got) > 20.0


def test_vgg16_int8_bit_exact(tiny_vgg):
    g, plans, ws, x = tiny_vgg
    qg = calibrate_graph(g, ws, x)
    got = run_graph_streamed(g, plans, x, None, mode="megakernel",
                             precision="int8", qgraph=qg)
    ref_q = quant_graph_reference_acts(qg, x)[g.output]
    ref = dequantize_int8(ref_q, qg.scales[g.output])
    assert jnp.array_equal(got, ref)
    assert snr_db(apply_graph(g, ws, x), got) > 20.0


def test_projection_shortcuts_stream_as_ordinary_convs(tiny_resnet):
    """The 1x1 stride-2 projections are plain conv nodes: they carry
    plans/programs/weights like every other conv node."""
    g, plans, ws, x = tiny_resnet
    projs = [n for n in g.conv_nodes() if n.name.endswith("_proj")]
    assert len(projs) == 3
    for n in projs:
        assert n.layer.kernel == 1 and n.layer.stride == 2
        assert n.name in plans and plans[n.name].sram_needed <= BUDGET


# ---------------------------------------------------------------------------
# Topology-aware executor cache (ISSUE 5 satellite): same layer
# geometry, different wiring -> distinct executables
# ---------------------------------------------------------------------------

def test_graph_cache_no_collision_on_shared_layer_geometry():
    l1 = ConvLayer("c1", 12, 12, 4, 4, 3, pad=1)
    l2 = ConvLayer("c2", 12, 12, 4, 4, 3, pad=1)
    chain = NetworkGraph("g", (12, 12, 4), (
        GraphNode("c1", "conv", (INPUT,), layer=l1),
        GraphNode("c2", "conv", ("c1",), layer=l2, act=None)), "c2")
    resid = NetworkGraph("g", (12, 12, 4), (
        GraphNode("c1", "conv", (INPUT,), layer=l1),
        GraphNode("c2", "conv", ("c1",), layer=l2, act=None),
        GraphNode("add", "add", ("c2", INPUT))), "add")
    plans = plan_graph(chain, BUDGET)
    ws = init_graph_weights(chain, jax.random.key(3))
    x = jax.random.normal(jax.random.key(4), (1, 12, 12, 4))
    clear_executor_cache()
    y_chain = run_graph_streamed(chain, plans, x, ws, mode="wave")
    n1 = executor_cache_size()
    y_resid = run_graph_streamed(resid, plans, x, ws, mode="wave")
    assert executor_cache_size() == n1 + 1, \
        "same-geometry graphs must not share an executable"
    # replay hits the cache (no growth) and the outputs really differ
    run_graph_streamed(chain, plans, x, ws, mode="wave")
    assert executor_cache_size() == n1 + 1
    assert not jnp.array_equal(y_chain, y_resid)
    assert jnp.max(jnp.abs(
        y_resid - jnp.maximum(y_chain + x, 0))) < 1e-5


def test_executor_cache_keys_mode_precision_and_degradation():
    """ISSUE 7 satellite: on the SAME graph geometry, each executor
    mode, each precision, and each degraded resolution gets its own
    cache entry — a wave executable must never serve a scan request,
    an fp32 one an int8 request, or a degraded trace a clean run."""
    from repro.distributed.fault import FaultInjector
    from repro.runtime import run_graph_degraded
    l1 = ConvLayer("c1", 12, 12, 4, 4, 3, pad=1)
    l2 = ConvLayer("c2", 12, 12, 4, 4, 3, pad=1)
    g = NetworkGraph("g", (12, 12, 4), (
        GraphNode("c1", "conv", (INPUT,), layer=l1),
        GraphNode("c2", "conv", ("c1",), layer=l2, act=None)), "c2")
    plans = plan_graph(g, BUDGET)
    ws = init_graph_weights(g, jax.random.key(3))
    x = jax.random.normal(jax.random.key(4), (1, 12, 12, 4))
    clear_executor_cache()
    run_graph_streamed(g, plans, x, ws, mode="wave")
    n = executor_cache_size()
    run_graph_streamed(g, plans, x, ws, mode="scan")
    assert executor_cache_size() == n + 1, "mode must be in the key"
    n = executor_cache_size()
    qg = calibrate_graph(g, ws, x)
    run_graph_streamed(g, plans, x, ws, mode="megakernel",
                       precision="int8", qgraph=qg)
    assert executor_cache_size() > n, "precision must be in the key"
    # a clean fallback resolution and a degraded one compile separately
    n = executor_cache_size()
    run_graph_degraded(g, plans, x, ws)
    n_clean = executor_cache_size()
    assert n_clean == n + 1
    with FaultInjector() as fi:
        fi.arm("plan", node="c1", mode="graphkernel")
        run_graph_degraded(g, plans, x, ws)
    assert executor_cache_size() == n_clean + 1, \
        "degraded signature must be in the key"
    # replaying the clean resolution hits the cache (no growth)
    run_graph_degraded(g, plans, x, ws)
    assert executor_cache_size() == n_clean + 1


# ---------------------------------------------------------------------------
# Buffer liveness: measured peak activation bytes drop on ResNet-18
# ---------------------------------------------------------------------------

def test_measured_peak_activation_drops_with_liveness(tiny_resnet):
    g, plans, ws, x = tiny_resnet
    with_pass, without = [], []
    y1 = run_graph_streamed(g, plans, x, ws, mode="interpret",
                            liveness=True, track_peak=with_pass)
    y2 = run_graph_streamed(g, plans, x, ws, mode="interpret",
                            liveness=False, track_peak=without)
    assert jnp.array_equal(y1, y2), "liveness must not change results"
    assert with_pass[0] < without[0], (with_pass, without)


# ---------------------------------------------------------------------------
# Serving sessions over graphs
# ---------------------------------------------------------------------------

def test_session_serves_resnet18_graph(tiny_resnet):
    g, plans, ws, x = tiny_resnet
    sess = StreamingSession.for_graph(g, ws, sram_budget=BUDGET,
                                      max_batch=2, donate=False)
    y1 = sess.run_batch(x)
    y2 = sess.run_batch(x + 0.5)
    assert sess.compile_count == 1, "repeat batches must not retrace"
    assert _rel_err(y1, apply_graph(g, ws, x)) < 1e-4
    assert not jnp.array_equal(y1, y2)


def test_session_microbatches_vgg16_graph(tiny_vgg):
    g, plans, ws, x = tiny_vgg
    sess = StreamingSession.for_graph(g, ws, sram_budget=BUDGET,
                                      max_batch=2)
    imgs = jax.random.normal(jax.random.key(9), (3,) + g.in_shape)
    tickets = [sess.submit(imgs[i]) for i in range(3)]
    outs = [sess.result(t) for t in tickets]
    assert sess.compile_count == 1
    ref = apply_graph(g, ws, imgs)
    for i, o in enumerate(outs):
        assert _rel_err(o, ref[i]) < 1e-4


def test_session_int8_resnet18_graph(tiny_resnet):
    g, plans, ws, x = tiny_resnet
    qg = calibrate_graph(g, ws, x)
    sess = StreamingSession.for_graph(g, None, sram_budget=BUDGET,
                                      max_batch=2, mode="megakernel",
                                      precision="int8", qnet=qg,
                                      donate=False)
    y = sess.run_batch(x)
    ref_q = quant_graph_reference_acts(qg, x)[g.output]
    assert jnp.array_equal(y, dequantize_int8(ref_q,
                                              qg.scales[g.output]))
    assert sess.compile_count == 1


def test_int8_recalibration_never_reuses_stale_executable():
    """Regression: the int8 graph forward bakes calibration statics in
    as Python constants, so a RECALIBRATED QuantizedGraph over the same
    geometry must compile (and use) a fresh executable, not replay the
    old calibration's scales."""
    l1 = ConvLayer("qc1", 8, 8, 4, 4, 3, pad=1)
    g = NetworkGraph("qcache", (8, 8, 4),
                     (GraphNode("qc1", "conv", (INPUT,), layer=l1),),
                     "qc1")
    plans = plan_graph(g, BUDGET)
    ws = init_graph_weights(g, jax.random.key(5))
    x1 = jax.random.normal(jax.random.key(6), (1, 8, 8, 4))
    x2 = x1 * 37.0                       # very different dynamic range
    qg1 = calibrate_graph(g, ws, x1)
    qg2 = calibrate_graph(g, ws, x2)
    clear_executor_cache()
    run_graph_streamed(g, plans, x2, None, mode="megakernel",
                       precision="int8", qgraph=qg1)
    got = run_graph_streamed(g, plans, x2, None, mode="megakernel",
                             precision="int8", qgraph=qg2)
    ref_q = quant_graph_reference_acts(qg2, x2)[g.output]
    ref = dequantize_int8(ref_q, qg2.scales[g.output])
    assert jnp.array_equal(got, ref), \
        "recalibrated graph must not reuse the stale int8 executable"


def test_compiled_graph_paths_reject_mismatched_input(tiny_resnet):
    """Regression (review): the compiled executors must validate the
    batch against the graph's input edge, like the per-layer paths do —
    a clamped dynamic_slice would otherwise return wrong pixels."""
    from repro.core.graph import GraphValidationError
    g, plans, ws, _ = tiny_resnet
    bad = jax.random.normal(jax.random.key(8), (1, 30, 30, 3))
    for mode in ("wave", "scan", "megakernel", "graphkernel",
                 "interpret"):
        with pytest.raises(GraphValidationError, match="wrong pixels"):
            run_graph_streamed(g, plans, bad, ws, mode=mode)
