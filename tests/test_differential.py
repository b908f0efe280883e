"""Cross-executor differential harness (ISSUE 6): every executor mode
must agree on every graph.

A fixed panel of small-but-feature-complete graphs (pools, grouped
convs, residual identity + projection blocks, no-ReLU tails) runs
through all five executors — interpret / scan / wave / megakernel /
graphkernel — against the eager interpreter as the reference, and the
int8 graphkernel runs bit-exact against the int32 fixed-point
reference walk. When hypothesis is installed, randomly generated
graphs (tests/strategies.py ``streaming_graphs``) fuzz the same
agreement properties."""
import jax
import jax.numpy as jnp
import pytest

from repro.core.decomposition import ConvLayer
from repro.core.graph import INPUT, GraphNode, NetworkGraph
from repro.core.quantization import dequantize_int8
from repro.core.streaming import plan_graph, run_graph_streamed
from repro.models.cnn import init_graph_weights
from repro.quant.accuracy import quant_graph_reference_acts
from repro.quant.calibrate import calibrate_graph

try:
    import hypothesis
    from strategies import streaming_graphs
except ImportError:  # dev-only dependency (requirements.txt)
    hypothesis = None

BUDGET = 64 * 1024
MODES = ("scan", "wave", "megakernel", "graphkernel")


def _conv(name, h, c_in, c_out, inputs, stride=1, relu=True, pool=1,
          kernel=3, pad=1, groups=1):
    return GraphNode(name, "conv", inputs,
                     layer=ConvLayer(name, h, h, c_in, c_out, kernel,
                                     stride=stride, pad=pad, pool=pool,
                                     groups=groups),
                     act="relu" if relu else None)


def _chain_pool_tail():
    """Pooled stem -> widening conv -> no-ReLU 1x1 tail."""
    nodes = (
        _conv("c1", 16, 3, 8, (INPUT,), pool=2),
        _conv("c2", 8, 8, 16, ("c1",)),
        _conv("c3", 8, 16, 16, ("c2",), relu=False, kernel=1, pad=0),
    )
    return NetworkGraph("chain_pool_tail", (16, 16, 3), nodes, "c3")


def _grouped_chain():
    """Grouped conv mid-chain (the natural per-group gemm path)."""
    nodes = (
        _conv("c1", 12, 3, 8, (INPUT,)),
        _conv("c2", 12, 8, 8, ("c1",), groups=2),
        _conv("c3", 12, 8, 8, ("c2",), pool=2),
    )
    return NetworkGraph("grouped_chain", (12, 12, 3), nodes, "c3")


def _depthwise_chain():
    """Depthwise-separable block — 3x3 depthwise (``groups == Cin``)
    with a ragged channel multiplier, then a 1x1 pointwise: the
    MobileNet motif the depthwise MAC path (ISSUE 10) lowers."""
    nodes = (
        _conv("c1", 12, 3, 6, (INPUT,)),
        _conv("dw", 12, 6, 18, ("c1",), groups=6),   # multiplier 3
        _conv("pw", 12, 18, 8, ("dw",), kernel=1, pad=0, pool=2),
    )
    return NetworkGraph("depthwise_chain", (12, 12, 3), nodes, "pw")


def _identity_block():
    """Stem + one identity-shortcut residual block (ReLU on the add)."""
    nodes = (
        _conv("stem", 8, 3, 8, (INPUT,)),
        _conv("c1", 8, 8, 8, ("stem",)),
        _conv("c2", 8, 8, 8, ("c1",), relu=False),
        GraphNode("add", "add", ("c2", "stem"), act="relu"),
    )
    return NetworkGraph("identity_block", (8, 8, 3), nodes, "add")


def _projection_block():
    """Strided residual block with a 1x1 projection shortcut."""
    nodes = (
        _conv("stem", 16, 3, 4, (INPUT,)),
        _conv("c1", 16, 4, 8, ("stem",), stride=2),
        _conv("c2", 8, 8, 8, ("c1",), relu=False),
        GraphNode("proj", "conv", ("stem",),
                  layer=ConvLayer("proj", 16, 16, 4, 8, 1, stride=2),
                  act=None),
        GraphNode("add", "add", ("c2", "proj"), act="relu"),
        _conv("head", 8, 8, 8, ("add",)),
    )
    return NetworkGraph("projection_block", (16, 16, 3), nodes, "head")


def _deep_mixed():
    """Pool, stride, grouped conv and a no-ReLU tail in one graph."""
    nodes = (
        _conv("c1", 16, 2, 4, (INPUT,), pool=2),
        _conv("c2", 8, 4, 8, ("c1",), stride=2),
        _conv("c3", 4, 8, 8, ("c2",), groups=2),
        _conv("c4", 4, 8, 8, ("c3",), relu=False, kernel=1, pad=0),
    )
    return NetworkGraph("deep_mixed", (16, 16, 2), nodes, "c4")


PANEL = (_chain_pool_tail, _grouped_chain, _depthwise_chain,
         _identity_block, _projection_block, _deep_mixed)


def _run_all_modes(g):
    plans = plan_graph(g, BUDGET)
    ws = init_graph_weights(g, jax.random.key(0))
    x = jax.random.normal(jax.random.key(1), (2,) + g.in_shape)
    ref = run_graph_streamed(g, plans, x, ws, mode="interpret")
    for mode in MODES:
        got = run_graph_streamed(g, plans, x, ws, mode=mode)
        assert got.shape == ref.shape, (g.name, mode)
        err = float(jnp.max(jnp.abs(got - ref)))
        assert err <= 1e-4, (g.name, mode, err)


@pytest.mark.parametrize("make", PANEL, ids=[m().name for m in PANEL])
def test_all_executors_agree(make):
    """interpret == scan == wave == megakernel == graphkernel, to fp32
    tolerance, on every panel graph."""
    _run_all_modes(make())


@pytest.mark.parametrize(
    "make", (_chain_pool_tail, _identity_block, _projection_block),
    ids=("chain_pool_tail", "identity_block", "projection_block"))
def test_int8_graphkernel_bit_exact_vs_int32_reference(make):
    """The fused-chain int8 kernel reproduces the int32 fixed-point
    reference walk bit for bit (and so matches the per-layer quantized
    megakernel, which pins the same reference)."""
    g = make()
    plans = plan_graph(g, BUDGET)
    ws = init_graph_weights(g, jax.random.key(0))
    x = jax.random.normal(jax.random.key(1), (2,) + g.in_shape)
    qg = calibrate_graph(g, ws, x)
    for mode in ("megakernel", "graphkernel"):
        got = run_graph_streamed(g, plans, x, None, mode=mode,
                                 precision="int8", qgraph=qg)
        ref_q = quant_graph_reference_acts(qg, x)[g.output]
        ref = dequantize_int8(ref_q, qg.scales[g.output])
        assert jnp.array_equal(got, ref), (g.name, mode)


@pytest.mark.parametrize("make", (_grouped_chain, _depthwise_chain),
                         ids=("grouped_chain", "depthwise_chain"))
def test_graphkernel_int8_matches_megakernel_int8_grouped(make):
    """Grouped/depthwise convs through the fused chain: int8
    graphkernel output is bit-identical to the per-layer quantized
    megakernel's AND to the int32 reference walk."""
    g = make()
    plans = plan_graph(g, BUDGET)
    ws = init_graph_weights(g, jax.random.key(0))
    x = jax.random.normal(jax.random.key(1), (2,) + g.in_shape)
    qg = calibrate_graph(g, ws, x)
    a = run_graph_streamed(g, plans, x, None, mode="megakernel",
                           precision="int8", qgraph=qg)
    b = run_graph_streamed(g, plans, x, None, mode="graphkernel",
                           precision="int8", qgraph=qg)
    assert jnp.array_equal(a, b)
    ref_q = quant_graph_reference_acts(qg, x)[g.output]
    ref = dequantize_int8(ref_q, qg.scales[g.output])
    assert jnp.array_equal(a, ref)


def test_depthwise_single_launch_per_node():
    """Depthwise nodes lower to ONE megakernel launch each (trace
    time): the natural per-group path never falls back to per-group
    dispatch or block-diagonal re-lowering."""
    from repro.core.streaming import clear_executor_cache
    from repro.kernels.wave_replay import launch_count, reset_launch_count
    g = _depthwise_chain()
    plans = plan_graph(g, BUDGET)
    ws = init_graph_weights(g, jax.random.key(0))
    x = jax.random.normal(jax.random.key(1), (2,) + g.in_shape)
    clear_executor_cache()
    reset_launch_count()
    run_graph_streamed(g, plans, x, ws, mode="megakernel")
    assert launch_count() == len(g.conv_nodes())


if hypothesis is not None:
    import hypothesis.strategies as st

    @hypothesis.given(streaming_graphs())
    @hypothesis.settings(max_examples=10, deadline=None)
    def test_random_graphs_all_executors_agree(g):
        _run_all_modes(g)

    # -- fault-injection differential harness (ISSUE 7): one random
    # fault per run, the degraded output must still match the
    # interpreter and every degradation must be a structured event
    @hypothesis.given(g=streaming_graphs(), data=st.data())
    @hypothesis.settings(max_examples=10, deadline=None)
    def test_random_fault_degraded_output_matches_interpreter(g, data):
        from repro.distributed.fault import FaultInjector
        from repro.runtime import run_graph_degraded
        plans = plan_graph(g, BUDGET)
        ws = init_graph_weights(g, jax.random.key(0))
        x = jax.random.normal(jax.random.key(1), (2,) + g.in_shape)
        ref = run_graph_streamed(g, plans, x, ws, mode="interpret")
        node = data.draw(st.sampled_from(
            [n.name for n in g.conv_nodes()]), label="node")
        kind = data.draw(st.sampled_from(
            ["plan", "lower", "launch", "vmem"]), label="fault")
        with FaultInjector() as fi:
            if kind == "vmem":
                fi.arm_vmem(128, node=node)   # nothing lowers into 128 B
            else:
                # mode=None: fire at the first probe of that stage,
                # wherever the node currently sits in the chain
                fi.arm(kind, node=node)
            got, res = run_graph_degraded(g, plans, x, ws)
        err = float(jnp.max(jnp.abs(got - ref)))
        assert err <= 1e-4, (g.name, node, kind, err)
        if fi.fired:
            # the injected fault produced structured degradation events
            # on the faulted node (chain-unit faults land on the head)
            assert res.events, (node, kind, fi.fired)
            assert any(e.node == node or node in e.cause
                       for e in res.events)
            # degradation moved DOWN the chain, one edge per event
            for e in res.events:
                assert e.to_mode in ("megakernel", "wave", "scan")
                assert e.cause and e.retry >= 1

    @hypothesis.given(g=streaming_graphs(allow_groups=False),
                      data=st.data())
    @hypothesis.settings(max_examples=6, deadline=None)
    def test_random_fault_int8_stays_bit_exact(g, data):
        from repro.distributed.fault import FaultInjector
        from repro.runtime import run_graph_degraded
        plans = plan_graph(g, BUDGET)
        ws = init_graph_weights(g, jax.random.key(0))
        x = jax.random.normal(jax.random.key(1), (2,) + g.in_shape)
        qg = calibrate_graph(g, ws, x)
        node = data.draw(st.sampled_from(
            [n.name for n in g.conv_nodes()]), label="node")
        stage = data.draw(st.sampled_from(["plan", "lower"]),
                          label="stage")
        with FaultInjector() as fi:
            fi.arm(stage, node=node, mode="graphkernel")
            got, res = run_graph_degraded(g, plans, x, ws,
                                          precision="int8", qgraph=qg,
                                          dequantize=False)
        ref_q = quant_graph_reference_acts(qg, x)[g.output]
        assert jnp.array_equal(got, ref_q), (g.name, node, stage)
        if fi.fired:
            assert res.node_modes[node] == "megakernel"
            assert any(e.node == node for e in res.events)

    @hypothesis.given(streaming_graphs(allow_groups=False))
    @hypothesis.settings(max_examples=6, deadline=None)
    def test_random_graphs_int8_bit_exact(g):
        plans = plan_graph(g, BUDGET)
        ws = init_graph_weights(g, jax.random.key(0))
        x = jax.random.normal(jax.random.key(1), (2,) + g.in_shape)
        qg = calibrate_graph(g, ws, x)
        got = run_graph_streamed(g, plans, x, None, mode="graphkernel",
                                 precision="int8", qgraph=qg)
        ref_q = quant_graph_reference_acts(qg, x)[g.output]
        ref = dequantize_int8(ref_q, qg.scales[g.output])
        assert jnp.array_equal(got, ref)
else:
    def test_property_cases_need_hypothesis():
        pytest.importorskip("hypothesis")  # skips, visibly
