"""Compile the serving path's Pallas kernels for a TPU v5e that is
described, not attached: the Mosaic compiler refuses here what the chip
would refuse (unaligned slices, strided loads over wide buffers, more
scoped VMEM than a kernel states), at no chip time.

Real widths: every AlexNet layer at the plans ``plan_graph`` gives (at
batch 1 and at the serving batch 8, whose batch blocks change the
kernel's shapes), the AlexNet fused chains, the int8 megakernel on an
ungrouped strided and a grouped layer, a ResNet-18 residual layer, the
ResNet-18 stem with its taps folded, a MobileNet depthwise layer, and
ConvNeXt-T's norm and GELU epilogues.
Nothing runs, so results are checked by the interpret-mode tests; this
file checks that each kernel compiles.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every xdist worker imports this
file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.decomposition import plan_decomposition
from repro.core.model_zoo import network_graph
from repro.core.schedule import DEFAULT_VMEM_BUDGET, compile_layer
from repro.core.streaming import (_graph_epilogues, _graph_kernel_program,
                                  compile_graph, graph_chain_programs,
                                  graph_kernel_programs, plan_graph)
from repro.kernels.common import megakernel_fold
from repro.kernels.wave_replay.graph import (stacked_shapes,
                                             wave_replay_graph_raw)
from repro.kernels.wave_replay.kernel import wave_replay_raw
from repro.kernels.wave_replay_q.graph import wave_replay_graph_q_raw
from repro.kernels.wave_replay_q.kernel import wave_replay_q_raw

SRAM = 128 * 1024          # the serving default (``--sram-kb 128``)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _programs(net: str):
    g = network_graph(net)
    return g, compile_graph(g, plan_graph(g, SRAM))


def _compile(fn, *args):
    """Compile for the described chip; the kernel must be in it."""
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


def _mega_args(kp, batch, sharding, dtype=jnp.float32):
    l, g = kp.wave.program.layer, kp.wave.program

    def s(shape, dt=dtype):
        return jax.ShapeDtypeStruct(shape, dt, sharding=sharding)
    x = s((batch, kp.pad_h, kp.pad_w, kp.in_c_kpad))
    w = s((l.kernel, l.kernel, kp.w_in_kpad, g.out_c_pad))
    table = s((kp.n_chain, kp.n_tiles, 8), jnp.int32)
    res = (s((batch, kp.out_h_pad, kp.out_w_pad, kp.out_c_pad))
           if kp.residual else None)
    return s, x, w, table, res


def _compile_mega(kp, batch, sharding):
    s, x, w, table, res = _mega_args(kp, batch, sharding)
    b = s((1, kp.out_c_pad))
    norm = s((2, kp.out_c_pad)) if kp.norm else None
    _compile(lambda x, w, b, t, r, n: wave_replay_raw(
        kp, x, w, b, t, residual=r, norm=n, interpret=False),
        x, w, b, table, res, norm)


@pytest.mark.parametrize("layer", ["conv1", "conv2", "conv3", "conv4",
                                   "conv5"])
def test_alexnet_megakernel_compiles(one_chip, layer):
    g, progs = _programs("alexnet")
    for batch in (1, 8):
        kp = graph_kernel_programs(g, progs, batch=batch)[layer]
        _compile_mega(kp, batch, one_chip)


@pytest.mark.parametrize("layer", ["conv1", "conv2"])
def test_alexnet_int8_megakernel_compiles(one_chip, layer):
    g, progs = _programs("alexnet")
    kp = graph_kernel_programs(g, progs, batch=8)[layer]
    s, x, w, table, _ = _mega_args(kp, 8, one_chip, jnp.int8)
    v = s((1, kp.wave.program.out_c_pad), jnp.int32)
    _compile(lambda x, w, b, m, sh, t: wave_replay_q_raw(
        kp, x, w, b, m, sh, t, interpret=False), x, w, v, v, v, table)


@pytest.mark.parametrize("quantized", [False, True],
                         ids=["fp32", "int8"])
def test_alexnet_graphkernel_compiles(one_chip, quantized):
    g, progs = _programs("alexnet")
    _, _, gkps = graph_chain_programs(g, progs, quantized=quantized,
                                      batch=8)
    assert len(gkps) == 2, "AlexNet fuses into two chains"
    dt = jnp.int8 if quantized else jnp.float32
    for gkp in gkps.values():
        h0 = gkp.nodes[0].kp
        (rows, b_max), _ = stacked_shapes(gkp)

        def s(shape, d=dt):
            return jax.ShapeDtypeStruct(shape, d, sharding=one_chip)
        x = s((8, h0.pad_h, h0.pad_w, h0.in_c_kpad))
        wf = s((gkp.w_total, rows, gkp.b_max))
        table = s((gkp.total_steps, 14), jnp.int32)
        n = len(gkp.nodes)
        if quantized:
            v = s((gkp.b_total, 1, b_max), jnp.int32)
            _compile(lambda x, wf, b, m, sh, t, gkp=gkp:
                     wave_replay_graph_q_raw(
                         gkp, x, wf, b, m, sh, t, pre_shifts=[0] * n,
                         fan_chunks=[None] * n, interpret=False),
                     x, wf, v, v, v, table)
        else:
            bf = s((gkp.b_total, 1, b_max))
            _compile(lambda x, wf, bf, t, gkp=gkp: wave_replay_graph_raw(
                gkp, x, wf, bf, t, interpret=False), x, wf, bf, table)


def test_resnet18_residual_layer_compiles(one_chip):
    g, progs = _programs("resnet18")
    kp = graph_kernel_programs(g, progs, batch=8)["s1b1_c2"]
    assert kp.residual and kp.wave.program.layer.in_c == 64
    _compile_mega(kp, 8, one_chip)


@pytest.mark.parametrize("layer", ["s2b1_c1", "s2b1_proj"])
def test_resnet18_strided_layer_compiles(one_chip, layer):
    """64-channel stride-2 layers: too wide to fold the stride into one
    lane tile of channels, so their taps load strided rows."""
    g, progs = _programs("resnet18")
    kp = graph_kernel_programs(g, progs, batch=8)[layer]
    assert kp.wave.program.layer.stride == 2 and kp.c_width == 64
    _compile_mega(kp, 8, one_chip)


@pytest.mark.parametrize("batch", [1, 8])
def test_resnet18_stem_compiles(one_chip, batch):
    """The 7x7/2 stem over 3 channels, its 16 stride-folded taps folded
    into one 192-channel contraction, with the 3/2 pool fused, at both
    serving batches (the node alone, lowered as the graph forward
    lowers it)."""
    layer = network_graph("resnet18").node("stem").layer
    prog = compile_layer(layer, plan_decomposition(layer, SRAM))
    kp = _graph_kernel_program(prog, "relu", False, DEFAULT_VMEM_BUDGET,
                               batch)
    assert megakernel_fold(kp) == "taps" and kp.fuse_pool
    _compile_mega(kp, batch, one_chip)


def test_mobilenet_depthwise_layer_compiles(one_chip):
    g, progs = _programs("mobilenet_v1")
    kp = graph_kernel_programs(g, progs, batch=8)["dw2"]
    l = kp.wave.program.layer
    assert l.groups == l.in_c == 64 and l.stride == 2
    _compile_mega(kp, 8, one_chip)


@pytest.mark.parametrize("node,act,residual", [
    ("s1b1_dw", None, False),      # 56 px, 96 channels, 7x7 depthwise
    ("s1b1_pw1", "gelu", False),   # 96 -> 384, exact GELU
    ("s3b9_pw2", None, True),      # 1536 -> 384, add, then ds4's norm
    ("s4b1_pw1", "gelu", False),   # 768 -> 3072 at 7 px: chained fan-in
])
def test_convnext_epilogue_layer_compiles(one_chip, node, act, residual):
    """ConvNeXt-T's widest kernels at the serving batch, each lowered as
    the graph forward lowers it (the one node alone: the whole graph
    takes half a minute to lower here)."""
    g = network_graph("convnext_t")
    e = _graph_epilogues(g)[node]
    layer = g.node(node).layer
    prog = compile_layer(layer, plan_decomposition(layer, SRAM))
    kp = _graph_kernel_program(prog, e.act, e.residual is not None,
                               DEFAULT_VMEM_BUDGET, 8,
                               norm=e.norm is not None)
    assert (kp.act, kp.residual, kp.norm) == (act, residual, act is None)
    _compile_mega(kp, 8, one_chip)
