"""Paper §7 'supports most popular CNNs': VGG-16 / ResNet-18 layer tables
decompose under the 128 KB budget; nameplate op counts check out; the
ResNet-18 planner edge cases (1x1 stride-2 projections, the 7x7/2 stem
with its overlapping 3/2 pool) execute correctly, not just plan
(ISSUE 5 satellite — these shapes used to be smoke-planned only)."""
import jax
import jax.numpy as jnp
import pytest

from repro.core.decomposition import ConvLayer, plan_decomposition
from repro.core.model_zoo import (RESNET18_LAYERS, VGG16_LAYERS,
                                  network_graph, resnet18_graph,
                                  vgg16_graph)
from repro.core.streaming import (conv2d_direct, maxpool_direct,
                                  run_layer_interpreted,
                                  run_layer_streamed)

BUDGET = 128 * 1024


def test_vgg16_all_layers_fit():
    for l in VGG16_LAYERS:
        assert plan_decomposition(l, BUDGET).sram_needed <= BUDGET


def test_resnet18_all_layers_fit():
    for l in RESNET18_LAYERS:
        assert plan_decomposition(l, BUDGET).sram_needed <= BUDGET


# ---------------------------------------------------------------------------
# ResNet-18 planner edge cases: plan AND execute (regression)
# ---------------------------------------------------------------------------

PROJ_LAYERS = [l for l in RESNET18_LAYERS if l.name.startswith("res_proj")]


@pytest.mark.parametrize("layer", PROJ_LAYERS, ids=lambda l: l.name)
def test_projection_conv_plans_under_budget(layer):
    """1x1 stride-2 projections: planned under 128 KB with a positive
    working set and full output coverage."""
    plan = plan_decomposition(layer, BUDGET)
    assert 0 < plan.sram_needed <= BUDGET
    assert plan.tiles_h * plan.tiles_w * plan.feat_splits \
        * plan.in_splits == plan.passes


@pytest.mark.parametrize("mode", ["interpret", "scan", "wave",
                                  "megakernel"])
def test_projection_conv_executes_correctly(mode):
    """The res_proj geometry at test scale: k=1, stride=2, no pad — the
    conv window never reaches the last input row/col ((in - 1) % 2 != 0),
    the trailing-trim path every executor must get right."""
    layer = ConvLayer("proj", 14, 14, 8, 16, 1, stride=2)
    plan = plan_decomposition(layer, 16 * 1024)
    x = jax.random.normal(jax.random.key(0), (2, 14, 14, 8))
    w = jax.random.normal(jax.random.key(1), (1, 1, 8, 16)) * 0.2
    got = run_layer_streamed(layer, plan, x, w, mode=mode)
    ref = conv2d_direct(x, w, 2, 0)
    assert got.shape == ref.shape == (2, 7, 7, 16)
    assert float(jnp.max(jnp.abs(got - ref))) < 1e-4


def test_stem_plans_under_budget_and_executes():
    """The 7x7 stride-2, pad-3 stem with its overlapping 3/2 max-pool:
    plans under 128 KB at nameplate dims; executes correctly (with the
    pool applied) at test scale."""
    stem = RESNET18_LAYERS[0]
    plan = plan_decomposition(stem, BUDGET)
    assert plan.sram_needed <= BUDGET
    small = ConvLayer("stem_s", 32, 32, 3, 8, 7, stride=2, pad=3,
                      pool=3, pool_stride=2)
    plan_s = plan_decomposition(small, 32 * 1024)
    x = jax.random.normal(jax.random.key(2), (1, 32, 32, 3))
    w = jax.random.normal(jax.random.key(3), (7, 7, 3, 8)) * 0.1
    ref = maxpool_direct(conv2d_direct(x, w, 2, 3), 3, 2)
    for mode in ("interpret", "scan", "wave"):
        got = run_layer_streamed(small, plan_s, x, w, mode=mode)
        got = maxpool_direct(got, 3, 2)
        assert float(jnp.max(jnp.abs(got - ref))) < 1e-4, mode


def test_stem_megakernel_fused_pool_matches():
    """The graph megakernel path fuses the stem's 3/2 pool into the
    kernel epilogue — overlapping pool windows on a stride-2 conv."""
    from repro.core.schedule import (compile_layer, lower_kernel_program,
                                     partition_waves)
    from repro.kernels.wave_replay.ops import wave_replay_layer
    small = ConvLayer("stem_s", 32, 32, 3, 8, 7, stride=2, pad=3,
                      pool=3, pool_stride=2)
    plan = plan_decomposition(small, 32 * 1024)
    kp = lower_kernel_program(partition_waves(compile_layer(small, plan)),
                              act="relu", fuse_pool=True, vmem_budget=None)
    x = jax.random.normal(jax.random.key(4), (1, 32, 32, 3))
    w = jax.random.normal(jax.random.key(5), (7, 7, 3, 8)) * 0.1
    got = wave_replay_layer(kp, x, w)
    ref = maxpool_direct(jnp.maximum(conv2d_direct(x, w, 2, 3), 0), 3, 2)
    assert got.shape == ref.shape
    assert float(jnp.max(jnp.abs(got - ref))) < 1e-4


def test_projection_interpreted_matches_scan_bit_exact():
    """Regression guard for the schedule's trailing-trim arithmetic on
    even-input stride-2 1x1 convs (no partial sums -> bit-identical)."""
    layer = ConvLayer("proj", 56, 56, 4, 8, 1, stride=2)
    plan = plan_decomposition(layer, 16 * 1024)
    x = jax.random.normal(jax.random.key(6), (1, 56, 56, 4))
    w = jax.random.normal(jax.random.key(7), (1, 1, 4, 8)) * 0.2
    a = run_layer_interpreted(layer, plan, x, w)
    b = run_layer_streamed(layer, plan, x, w, mode="scan")
    assert jnp.array_equal(a, b)


def test_network_graph_registry():
    assert network_graph("vgg16").name == "vgg16"
    g = network_graph("resnet18")
    assert len([n for n in g.nodes if n.op == "add"]) == 8
    with pytest.raises(ValueError, match="unknown network"):
        network_graph("lenet")


def test_full_size_graphs_plan_under_128k():
    """Every conv node of the nameplate VGG-16 and ResNet-18 graphs
    (projections and stem included) decomposes under the paper budget."""
    from repro.core.streaming import plan_graph
    for g in (vgg16_graph(), resnet18_graph()):
        plans = plan_graph(g, BUDGET)
        assert all(p.sram_needed <= BUDGET for p in plans.values())


def test_vgg16_total_ops_matches_literature():
    # VGG-16 conv ops ~30.7 GFLOPs (2 ops/MAC) at 224x224
    total = sum(l.num_ops for l in VGG16_LAYERS) / 1e9
    assert 29.0 < total < 32.0


def test_alexnet_config_importable():
    from repro.configs import get_config
    cfg = get_config("alexnet")
    assert cfg.name == "alexnet" and len(cfg.layers) == 5
