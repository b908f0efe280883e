"""The plain references against the program's graphs, on the CPU.

The references import nothing of the program; here they are held to
the program's own graph definitions and its direct forward, so that a
cell's comparison measures the executors and not a disagreement about
what the network is.
"""
import json

import jax
import numpy as np
import pytest

from chipbench import bench, plain

CONFIGS = bench.HERE / "configs"


def config(name: str) -> dict:
    return json.loads((CONFIGS / f"{name}.json").read_text())


@pytest.mark.parametrize("name", ["alexnet-fp32", "resnet18-fp32"])
def test_config_matches_the_programs_graph(name):
    cfg = config(name)
    ref = bench.load_module(CONFIGS / cfg["reference"])
    bench.check_graph(bench.build_graph(cfg), ref.conv_nodes(cfg))


def test_check_graph_refuses_a_different_network():
    cfg = config("resnet18-fp32")
    ref = bench.load_module(CONFIGS / cfg["reference"])
    nodes = ref.conv_nodes(cfg)
    nodes[3] = dict(nodes[3], out_c=nodes[3]["out_c"] * 2)
    with pytest.raises(ValueError, match="differ"):
        bench.check_graph(bench.build_graph(cfg), nodes)


def _small_resnet():
    cfg = config("resnet18-fp32")
    cfg = dict(cfg, in_shape=[32, 32, 3], network_args={"in_hw": 32,
                                                        "width": 4},
               stem=dict(cfg["stem"], out_c=4),
               stages=[[4, 1], [8, 2], [16, 2], [32, 2]])
    return cfg


@pytest.mark.parametrize("make", [_small_resnet], ids=["resnet18"])
def test_reference_agrees_with_the_programs_forward(make):
    from repro.models.cnn import apply_graph

    cfg = make()
    ref = bench.load_module(CONFIGS / cfg["reference"])
    nodes = ref.conv_nodes(cfg)
    graph = bench.build_graph(cfg)
    bench.check_graph(graph, nodes)
    params = plain.init_params(nodes, jax.random.key(3))
    x = jax.random.normal(jax.random.key(4), (2,) + tuple(cfg["in_shape"]))
    want = apply_graph(graph, params, x)
    got = ref.forward(cfg, params, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_alexnet_reference_agrees_with_the_programs_forward():
    from repro.models.cnn import apply_graph

    cfg = config("alexnet-fp32")
    ref = bench.load_module(CONFIGS / cfg["reference"])
    nodes = ref.conv_nodes(cfg)
    graph = bench.build_graph(cfg)
    params = plain.init_params(nodes, jax.random.key(5))
    x = jax.random.normal(jax.random.key(6), (1,) + tuple(cfg["in_shape"]))
    want = apply_graph(graph, params, x)
    got = ref.forward(cfg, params, x)
    assert got.shape == (1, 6, 6, 256)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_control_is_three_bf16_passes():
    x = jax.random.normal(jax.random.key(7), (1, 9, 9, 16))
    w = jax.random.normal(jax.random.key(8), (3, 3, 16, 8))
    exact = plain.conv(x, w, 1, 1, 1)
    ctrl = plain.conv_bf16x3(x, w, 1, 1, 1)
    one = plain.conv(x.astype(jax.numpy.bfloat16).astype(np.float32),
                     w.astype(jax.numpy.bfloat16).astype(np.float32),
                     1, 1, 1)
    err3 = float(jax.numpy.max(jax.numpy.abs(ctrl - exact)))
    err1 = float(jax.numpy.max(jax.numpy.abs(one - exact)))
    # three passes land between one bf16 pass and float32
    assert 0 < err3 < err1 / 20
