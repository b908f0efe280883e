"""The ConvNeXt-T configuration and its plain reference against the
program's graph and direct forward, on the CPU."""
import json

import jax
import numpy as np
import pytest

from chipbench import bench, plain, work

CONFIGS = bench.HERE / "configs"


def config() -> dict:
    return json.loads((CONFIGS / "convnext-t-fp32.json").read_text())


def small() -> dict:
    """The configuration at 32 px, dims (8, 16, 32, 64), depths
    (1, 1, 2, 1): every kind of node, CPU-sized."""
    dims, depths = [8, 16, 32, 64], [1, 1, 2, 1]
    return dict(config(), in_shape=[32, 32, 3], dims=dims, depths=depths,
                network_args={"in_hw": 32, "dims": dims,
                              "depths": depths})


def test_config_matches_the_programs_graph():
    cfg = config()
    ref = bench.load_module(CONFIGS / cfg["reference"])
    nodes = ref.conv_nodes(cfg)
    bench.check_graph(bench.build_graph(cfg), nodes)
    assert len(nodes) == 58
    assert sum(n["residual"] for n in nodes) == 18


def test_flops_per_image():
    """8.91 GFLOP (2 x MACs), of which the stem and the three
    downsampling convs are 4.2%."""
    nodes = bench.load_module(CONFIGS / "convnext_ref.py").conv_nodes(
        config())
    total = work.flops_per_image(nodes)
    assert total / 1e9 == pytest.approx(8.91, abs=0.01)
    edge = sum(work.conv_flops(n) for n in nodes
               if n["name"] == "stem" or n["name"].startswith("ds"))
    assert 100 * edge / total == pytest.approx(4.2, abs=0.05)


@pytest.mark.parametrize("affine", [False, True],
                         ids=["conv_params_only", "norm_affine"])
def test_reference_agrees_with_the_programs_forward(affine):
    from repro.models.cnn import apply_graph

    cfg = small()
    ref = bench.load_module(CONFIGS / cfg["reference"])
    nodes = ref.conv_nodes(cfg)
    graph = bench.build_graph(cfg)
    bench.check_graph(graph, nodes)
    params = plain.init_params(nodes, jax.random.key(3))
    if affine:                # the stem norm's affine, which cannot fold
        k1, k2 = jax.random.split(jax.random.key(9))
        params["stem_norm"] = (1 + 0.3 * jax.random.normal(k1, (8,)),
                               0.2 * jax.random.normal(k2, (8,)))
    x = jax.random.normal(jax.random.key(4), (2,) + tuple(cfg["in_shape"]))
    want = apply_graph(graph, params, x)
    got = ref.forward(cfg, params, x)
    assert got.shape == (2, 1, 1, 64)
    # both float32 HIGHEST on the CPU; the norms and GELU are written
    # out differently (mean / rsqrt against sqrt and a division), a few
    # ulp through 19 convs and 6 norms
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_fused_epilogue_share_reads_the_sessions_set_up_spans():
    """100.0 where every norm and GELU rides a conv epilogue; None where
    the spans hold no kernel lowering (as the parent program's do)."""
    from types import SimpleNamespace

    from repro.launch.serve import make_cnn_session
    from repro.obs import Tracer

    cfg = small()
    nodes = bench.load_module(CONFIGS / cfg["reference"]).conv_nodes(cfg)
    tracer = Tracer()
    make_cnn_session(bench.build_graph(cfg),
                     plain.init_params(nodes, jax.random.key(1)),
                     mode="megakernel", precision="fp32", max_batch=8,
                     sram_kb=128, tracer=tracer)
    reader = bench.load_module(bench.HERE / "metrics"
                               / "fused_epilogue_share.py")
    assert reader.read(SimpleNamespace(program_spans=tracer.spans())) \
        == 100.0
    without = [s for s in tracer.spans() if s.cat != "kernel"]
    assert reader.read(SimpleNamespace(program_spans=without)) is None
