"""Operations, bytes and peaks (``chipbench/work.py``), on the CPU."""
import json

import pytest

from chipbench import bench, work

CONFIGS = bench.HERE / "configs"


def nodes_of(config: str) -> list:
    cfg = json.loads((CONFIGS / f"{config}.json").read_text())
    return bench.load_module(CONFIGS / cfg["reference"]).conv_nodes(cfg)


def test_alexnet_conv1_by_hand():
    conv1 = nodes_of("alexnet-fp32")[0]
    # 55 x 55 outputs x 96 channels x (11 x 11 x 3) taps, 2 ops a MAC
    assert work.conv_flops(conv1) == 2 * 55 * 55 * 96 * 11 * 11 * 3
    # fp32: input 227x227x3 and pooled output 27x27x96 per image,
    # weights 11x11x3x96 and bias 96 once per batch
    per_image = 4 * (227 * 227 * 3 + 27 * 27 * 96)
    weights = 4 * (11 * 11 * 3 * 96 + 96)
    assert work.conv_bytes(conv1, 1, "fp32") == per_image + weights
    assert work.conv_bytes(conv1, 8, "fp32") == 8 * per_image + weights
    assert work.conv_bytes(conv1, 1, "int8") == (per_image + weights) // 4


@pytest.mark.parametrize("config,gflop,n_convs", [
    ("alexnet-fp32", 1.332, 5), ("resnet18-fp32", 3.594, 20)])
def test_flops_per_image(config, gflop, n_convs):
    nodes = nodes_of(config)
    assert len(nodes) == n_convs
    assert work.flops_per_image(nodes) / 1e9 == pytest.approx(gflop,
                                                               abs=5e-4)


def test_grouped_conv_counts_its_groups():
    conv2 = nodes_of("alexnet-fp32")[1]
    assert conv2["groups"] == 2
    assert work.conv_flops(conv2) == 2 * 27 * 27 * 256 * 5 * 5 * 96 // 2


def test_residual_operand_counted_once_per_image():
    n = {"name": "c2", "in_h": 7, "in_w": 7, "in_c": 8, "out_c": 8,
         "kernel": 3, "stride": 1, "pad": 1, "groups": 1, "pool": 1,
         "pool_stride": 1}
    plain_bytes = work.conv_bytes(n, 2, "fp32")
    assert work.conv_bytes(dict(n, residual=True), 2, "fp32") \
        == plain_bytes + 2 * 4 * 7 * 7 * 8


def test_bounds_pick_the_larger_time():
    peak = work.peaks("TPU v5 lite")
    nodes = nodes_of("alexnet-fp32")
    for b in (1, 8):
        for (name, t, bound), n in zip(
                work.node_bounds(nodes, b, "fp32", peak), nodes):
            tc = b * work.conv_flops(n) / peak["bf16_flops_per_s"]
            tm = work.conv_bytes(n, b, "fp32") / peak["hbm_bytes_per_s"]
            assert t == max(tc, tm)
            assert bound == ("compute" if tc >= tm else "memory")
    # the batch amortises weights: at b8 most AlexNet convs are
    # compute-bound, at b1 most are bound by bandwidth
    kinds = {b: [k for _, _, k in work.node_bounds(nodes, b, "fp32", peak)]
             for b in (1, 8)}
    assert kinds[8].count("compute") > kinds[1].count("compute")


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError, match="not in"):
        work.peaks("TPU v9000")


def test_peaks_table_names_its_source():
    table = json.loads(work.PEAKS.read_text())
    assert "TPU v5e" in table["source"]
    assert work.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
