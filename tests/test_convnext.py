"""ConvNeXt-T on the graph IR: the ``norm`` op, activation kinds, the
norm-fusion analysis, the megakernel's norm and GELU epilogue, the
weight folds, the kernel-lowering spans, and the executors that refuse
norm and GELU nodes.

Numbers are checked at a small size (32 px, dims (8, 16, 32, 64),
depths (1, 1, 2, 1)) on seeded random weights with non-identity norm
affines and layer scales, against a plain float32 reference written
here from the paper's equations, with no program code in it.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.decomposition import ConvLayer, evaluate
from repro.core.graph import (INPUT, GraphNode, GraphValidationError,
                              NetworkGraph, graph_params, norm_fusion,
                              residual_fusion, value_shapes)
from repro.core.model_zoo import convnext_fold, network_graph
from repro.core.schedule import (compile_layer, lower_kernel_program,
                                 partition_waves)
from repro.core.streaming import (compile_graph, graph_forward_fn,
                                  graph_kernel_programs, graph_operands,
                                  plan_graph, run_graph_streamed)
from repro.kernels.common import erf_f32
from repro.launch.session import StreamingSession
from repro.models.cnn import apply_graph
from repro.obs import Tracer, use_tracer
from repro.obs import metrics as obs_metrics
from repro.runtime.errors import LoweringError

SMALL = dict(in_hw=32, dims=(8, 16, 32, 64), depths=(1, 1, 2, 1))
EPS = 1e-6
# Relative to the output's largest magnitude. The megakernel sums each
# 1x1 conv's fan-in in MXU-sized pieces and each depthwise tap in its
# own order, and the folds move the norm affines and layer scales into
# the conv sums: reassociation in fp32 over 19 convs and 6 norms, each
# a few ulp (1.2e-7) of the output scale. 1e-5 is ~100 ulp; the
# readings are near 1e-6.
REL_TOL = 1e-5


def small_graph():
    return network_graph("convnext_t", **SMALL)


def published_params(graph, key):
    """Conv (w, b), norm (gamma, beta) away from the identity, and a
    layer scale per block, as the published model parameterises them."""
    params = {}
    for i, n in enumerate(graph.nodes):
        k1, k2 = jax.random.split(jax.random.fold_in(key, i))
        if n.op == "conv":
            l = n.layer
            fan_in = l.kernel * l.kernel * (l.in_c // l.groups)
            w = jax.random.normal(k1, (l.kernel, l.kernel,
                                       l.in_c // l.groups, l.out_c))
            params[n.name] = (w * (2.0 / fan_in) ** 0.5,
                              0.1 * jax.random.normal(k2, (l.out_c,)))
        elif n.op == "norm":
            c = value_shapes(graph)[n.name][2]
            params[n.name] = (1.0 + 0.3 * jax.random.normal(k1, (c,)),
                              0.2 * jax.random.normal(k2, (c,)))
        elif n.op == "add":
            c = params[n.inputs[0]][1].shape[0]
            params[n.name[:-len("_add")] + "_scale"] = \
                jax.random.uniform(k1, (c,), minval=0.5, maxval=1.5)
    return params


def _conv(x, p, stride=1, pad=0, groups=1):
    w, b = p
    return jax.lax.conv_general_dilated(
        x, w, (stride, stride), [(pad, pad), (pad, pad)],
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        feature_group_count=groups,
        precision=jax.lax.Precision.HIGHEST) + b


def _ln(x, p):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + EPS) * p[0] + p[1]


def plain_convnext(params, x, dims, depths):
    """ConvNeXt's trunk from the paper's equations, unfolded."""
    x = _ln(_conv(x, params["stem"], stride=4), params["stem_norm"])
    for si, (c, depth) in enumerate(zip(dims, depths), start=1):
        if si > 1:
            x = _conv(_ln(x, params[f"ds{si}_norm"]), params[f"ds{si}"],
                      stride=2)
        for bi in range(1, depth + 1):
            t = f"s{si}b{bi}"
            y = _conv(x, params[f"{t}_dw"], pad=3, groups=c)
            y = _ln(y, params[f"{t}_norm"])
            y = jax.nn.gelu(_conv(y, params[f"{t}_pw1"]), approximate=False)
            y = _conv(y, params[f"{t}_pw2"]) * params[f"{t}_scale"]
            x = x + y
    return x


def rel_err(got, want) -> float:
    return float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))


@pytest.fixture(scope="module")
def small():
    g = small_graph()
    params = published_params(g, jax.random.key(11))
    x = jax.random.normal(jax.random.key(12), (2, 32, 32, 3))
    want = plain_convnext(params, x, SMALL["dims"], SMALL["depths"])
    return g, params, convnext_fold(g, params), x, want


# ---------------------------------------------------------------------------
# The published trunk
# ---------------------------------------------------------------------------

def test_published_trunk_counts_and_fusion():
    g = network_graph("convnext_t")
    assert g.in_shape == (224, 224, 3)
    ops = [n.op for n in g.nodes]
    assert (ops.count("conv"), ops.count("add"), ops.count("norm")) \
        == (58, 18, 22)
    assert sum(n.act == "gelu" for n in g.nodes) == 18
    assert sum(n.act == "relu" for n in g.nodes) == 0
    # every norm and every add runs in a conv epilogue
    assert len(norm_fusion(g).fused) == 22
    assert len(residual_fusion(g).fused) == 18
    dw = g.node("s3b9_dw").layer
    assert (dw.kernel, dw.pad, dw.groups, dw.in_h, dw.in_c) \
        == (7, 3, 384, 14, 384)
    assert g.node("s1b1_pw1").layer.out_c == 4 * 96
    # the last block of stage 3 carries the residual add AND ds4's norm
    assert norm_fusion(g).as_dict()["ds4_norm"] == "s3b9_pw2"
    assert residual_fusion(g).as_dict()["s3b9_add"][0] == "s3b9_pw2"


def test_describe_and_topology_key_name_the_activation():
    g = small_graph()
    text = g.describe()
    assert "s1b1_pw1 = conv(s1b1_norm) +gelu -> (8, 8, 32)" in text
    assert "stem_norm = norm(stem) -> (8, 8, 8)" in text
    relu = NetworkGraph(g.name, g.in_shape, tuple(
        GraphNode(n.name, n.op, n.inputs, layer=n.layer, act="relu")
        if n.name == "s1b1_pw1" else n for n in g.nodes), g.output)
    assert relu.topology_key != g.topology_key


# ---------------------------------------------------------------------------
# Numbers against the plain reference
# ---------------------------------------------------------------------------

def test_folded_graph_matches_the_unfolded_reference(small):
    g, params, folded, x, want = small
    assert set(folded) == {n.name for n in g.conv_nodes()} | {"stem_norm"}
    assert rel_err(apply_graph(g, folded, x), want) < REL_TOL


def test_megakernel_session_matches_the_reference(small):
    g, params, folded, x, want = small
    sess = StreamingSession.for_graph(g, folded, mode="megakernel",
                                      max_batch=2)
    got = sess.run_batch(x)
    assert got.shape == want.shape
    assert rel_err(got, want) < REL_TOL
    # the stem norm's affine rides the stem's epilogue: dropping it
    # moves the answer far past the tolerance, so the agreement above
    # shows the kernel applied it
    plain = dict(folded)
    del plain["stem_norm"]
    assert rel_err(apply_graph(g, plain, x), want) > 100 * REL_TOL


def test_interpret_walk_runs_norm_and_gelu(small):
    g, params, folded, x, want = small
    got = run_graph_streamed(g, plan_graph(g), x, folded, mode="interpret")
    assert rel_err(got, want) < REL_TOL


def test_unfused_norm_runs_between_kernels():
    """A norm whose producer's value also feeds an add cannot fold:
    the megakernel forward runs it as an XLA op, then a GELU on the
    add — and still matches the reference walk."""
    c = 8
    nodes = (
        GraphNode("c1", "conv", (INPUT,), act=None,
                  layer=ConvLayer("c1", 8, 8, 3, c, 3, pad=1)),
        GraphNode("n1", "norm", ("c1",), act="gelu"),
        GraphNode("c2", "conv", ("n1",), act="relu",
                  layer=ConvLayer("c2", 8, 8, c, c, 1)),
        GraphNode("add", "add", ("c2", "c1"), act="gelu"),
    )
    g = NetworkGraph("unfused", (8, 8, 3), nodes, "add")
    assert norm_fusion(g).fused == ()
    w = published_params(g, jax.random.key(3))
    x = jax.random.normal(jax.random.key(4), (2, 8, 8, 3))
    want = apply_graph(g, w, x)
    t = Tracer()
    with obs_metrics.use_registry(obs_metrics.MetricsRegistry()) as reg, \
            use_tracer(t):
        got = run_graph_streamed(g, plan_graph(g), x, w, mode="megakernel")
        assert reg.counter("graph.norms_unfused").value == 1
        assert reg.counter("graph.norms_fused").value == 0
    assert rel_err(got, want) < REL_TOL
    tops = [s for s in t.spans("lower")
            if s.name.startswith("lower_kernels")]
    assert tops and all(s.attrs["norm_gelu_outside"] == 3 for s in tops)


def test_kernel_erf_matches_jax_erf():
    """The kernel evaluates XLA's float32 rational erf itself; its fma
    and division rounding may differ from XLA's by a few ulp, most
    near |x| = 3.83 where erf crosses 1.0 and the float32 spacing
    doubles. 8 ulp of the reference bounds it (the measured worst is
    7, at x = -3.831); elsewhere it agrees to 2."""
    x = jnp.linspace(-10.0, 10.0, 400_001, dtype=jnp.float32)
    got = np.asarray(jax.jit(erf_f32)(x))
    want = np.asarray(jax.scipy.special.erf(x))
    ulp = np.spacing(np.abs(want).astype(np.float32))
    err = np.abs(got - want) / ulp
    assert err.max() <= 8
    assert err[np.abs(np.asarray(x)) < 3.5].max() <= 2
    assert np.all(np.abs(got) <= 1.0 + 2 ** -22)


# ---------------------------------------------------------------------------
# Validation, fusion and lowering rules
# ---------------------------------------------------------------------------

def _norm_graph(*extra, norm=None):
    conv = GraphNode("c", "conv", (INPUT,), act=None,
                     layer=ConvLayer("c", 8, 8, 3, 4, 3, pad=1))
    norm = norm or GraphNode("n", "norm", ("c",), act=None)
    return NetworkGraph("v", (8, 8, 3), (conv, norm) + extra, norm.name)


@pytest.mark.parametrize("bad,match", [
    (GraphNode("n", "norm", ("c", INPUT), act=None), "exactly one"),
    (GraphNode("n", "norm", ("c",), act=None,
               layer=ConvLayer("n", 8, 8, 4, 4, 1, pool=2)), "no pool"),
    (GraphNode("n", "norm", ("c",), act="tanh"), "unknown activation"),
], ids=["arity", "pool", "activation"])
def test_norm_validation(bad, match):
    with pytest.raises(GraphValidationError, match=match):
        _norm_graph(norm=bad)


def test_norm_params_shape_checked():
    g = _norm_graph()
    assert [a.tolist() for a in graph_params(g, {"c": (0, 0)})["n"]] \
        == [[1.0] * 4, [0.0] * 4]
    with pytest.raises(GraphValidationError, match="'n'.*shape \\(4,\\)"):
        graph_params(g, {"c": (0, 0), "n": (jnp.ones(3), jnp.zeros(3))})


def test_norm_fuses_only_into_a_bare_producer():
    g = _norm_graph()
    assert norm_fusion(g).as_dict() == {"n": "c"}
    act = NetworkGraph("v", (8, 8, 3), (
        GraphNode("c", "conv", (INPUT,), act="relu",
                  layer=ConvLayer("c", 8, 8, 3, 4, 3, pad=1)),
        GraphNode("n", "norm", ("c",), act=None)), "n")
    assert norm_fusion(act).fused == ()


def test_norm_lowering_refuses_a_pooled_epilogue():
    layer = ConvLayer("p", 8, 8, 4, 4, 3, pad=1, pool=2)
    wprog = partition_waves(compile_layer(layer, evaluate(layer, 1, 1, 1,
                                                          1)))
    with pytest.raises(LoweringError, match="every output channel"):
        lower_kernel_program(wprog, fuse_pool=True, norm=True)
    a = lower_kernel_program(partition_waves(compile_layer(
        ConvLayer("q", 8, 8, 4, 4, 3, pad=1),
        evaluate(ConvLayer("q", 8, 8, 4, 4, 3, pad=1), 1, 1, 1, 1))),
        act="gelu", norm=True)
    b = lower_kernel_program(a.wave, act="gelu")
    assert a.geometry != b.geometry      # the norm keys the caches


def test_chain_chunks_keep_whole_lane_tiles():
    """ConvNeXt-T's stage-4 pw1 (768 -> 3072 at 7 px, batch 8) chains
    its fan-in in steps. Each step's channel block has to be whole
    128-lane tiles on the chip: the budget alone gave 257 of 768."""
    g = network_graph("convnext_t")
    layer = g.node("s4b1_pw1").layer
    from repro.core.streaming import plan_for_vmem
    from repro.core.schedule import DEFAULT_VMEM_BUDGET
    plan = plan_for_vmem(layer, DEFAULT_VMEM_BUDGET, batch=8)
    kp = lower_kernel_program(partition_waves(compile_layer(layer, plan)),
                              act="gelu", batch_block=8)
    assert kp.n_chain > 1
    assert kp.c_width % 128 == 0 and kp.in_c_kpad == kp.n_chain * kp.c_width


@pytest.mark.parametrize("mode,precision", [
    ("wave", "fp32"), ("scan", "fp32"), ("graphkernel", "fp32"),
    ("megakernel", "int8")])
@pytest.mark.parametrize("first", ["stem_norm", "gelu"])
def test_other_executors_refuse_naming_the_node(mode, precision, first):
    g = small_graph()
    if first == "gelu":          # a graph whose only new op is one GELU
        g = NetworkGraph("g", (8, 8, 3), (
            GraphNode("c", "conv", (INPUT,), act="gelu",
                      layer=ConvLayer("c", 8, 8, 3, 4, 3, pad=1)),), "c")
        first = "c"
    progs = compile_graph(g, plan_graph(g))
    with pytest.raises(GraphValidationError, match=repr(first)):
        graph_forward_fn(g, progs, mode=mode, precision=precision,
                         qgraph=object())
    with pytest.raises(GraphValidationError, match=repr(first)):
        graph_operands(g, progs, mode, precision=precision)


@pytest.mark.parametrize("who", ["calibrate_graph", "fallback", "autotune"])
def test_graph_tools_refuse_naming_the_node(who, small):
    from repro.core.autotune import tune_graph
    from repro.quant.calibrate import calibrate_graph
    from repro.runtime.fallback import resolve_graph
    g, _, folded, x, _ = small
    progs = compile_graph(g, plan_graph(g))
    call = {"calibrate_graph": lambda: calibrate_graph(g, folded, x),
            "fallback": lambda: resolve_graph(g, progs, mode="megakernel"),
            "autotune": lambda: tune_graph(g, progs, folded, x)}[who]
    with pytest.raises(GraphValidationError, match="'stem_norm'"):
        call()


# ---------------------------------------------------------------------------
# Kernel-lowering spans
# ---------------------------------------------------------------------------

def test_kernel_spans_list_each_epilogue_inside_the_lower_span():
    g = small_graph()
    progs = compile_graph(g, plan_graph(g))
    t = Tracer()
    with use_tracer(t):
        kps = graph_kernel_programs(g, progs, batch=2)
    (top,) = t.spans("lower")
    kern = {s.attrs["node"]: s for s in t.spans("kernel")}
    assert set(kern) == {n.name for n in g.conv_nodes()}
    assert all(s.parent_id == top.id for s in kern.values())
    assert top.attrs["norm_gelu_outside"] == 0
    assert kern["stem"].attrs["epilogue"] == ["bias", "norm"]
    assert kern["s1b1_dw"].attrs["epilogue"] == ["bias", "norm"]
    assert kern["s1b1_pw1"].attrs["epilogue"] == ["bias", "gelu"]
    assert kern["s1b1_pw2"].attrs["epilogue"] == ["bias", "residual",
                                                  "norm"]
    assert kern["s3b2_pw2"].attrs["epilogue"] == ["bias", "residual",
                                                  "norm"]
    assert kern["s4b1_pw2"].attrs["epilogue"] == ["bias", "residual"]
    for name, s in kern.items():
        kp = kps[name]
        assert s.attrs["vmem_bytes"] == kp.vmem_bytes > 0
        assert s.attrs["grid_steps"] == (-(-2 // kp.batch_block)
                                         * kp.n_tiles * kp.n_chain)
    norms = sum(p == "norm" for s in kern.values()
                for p in s.attrs["epilogue"])
    assert norms == sum(n.op == "norm" for n in g.nodes)
