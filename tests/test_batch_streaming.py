"""Batch-axis streaming (ISSUE 8 tentpole): the batch rides the kernel
grid / gather tables as a first-class dimension, NOT an outer vmap.

The acceptance bar is exactness, not tolerance: folding the batch into
the grid must replay the SAME per-image schedule — fp32 batched
outputs are bit-identical to running each image alone, and the int8
datapath (integer accumulators, deterministic requantize) matches with
``array_equal`` at every tested batch size. Ragged batches (not a
multiple of the batch block) zero-pad and crop without contaminating
real rows."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.decomposition import ConvLayer
from repro.core.graph import chain_graph
from repro.core.schedule import batch_grid
from repro.core.streaming import (compile_graph, graph_forward_fn,
                                  graph_kernel_programs, graph_operands,
                                  plan_graph)
from repro.models.cnn import init_graph_weights
from repro.quant.calibrate import calibrate_graph


def _graph():
    # conv+pool then two convs (one fusible pair for graphkernel)
    return chain_graph(
        (ConvLayer("c1", 16, 16, 3, 8, 3, pad=1, pool=2),
         ConvLayer("c2", 8, 8, 8, 16, 3, pad=1),
         ConvLayer("c3", 8, 8, 16, 16, 1)),
        name="batch_probe")


def _setup(scale=0.1):
    g = _graph()
    progs = compile_graph(g, plan_graph(g, 64 * 1024))
    weights = init_graph_weights(g, jax.random.key(1), scale=scale)
    return g, progs, weights


def _forward(g, progs, mode, batch, **kw):
    fn = jax.jit(graph_forward_fn(g, progs, mode=mode, batch=batch, **kw))
    ops = graph_operands(g, progs, mode=mode, batch=batch,
                         precision=kw.get("precision", "fp32"))
    return fn, ops


# ---------------------------------------------------------------------------
# batch_grid arithmetic
# ---------------------------------------------------------------------------

def test_batch_grid_clamps_and_covers():
    assert batch_grid(1, 1) == (1, 1)
    assert batch_grid(8, 4) == (2, 4)
    assert batch_grid(7, 4) == (2, 4)      # ragged: pad to 2 blocks
    assert batch_grid(2, 64) == (1, 2)     # block clamps to the batch
    assert batch_grid(64, 1) == (64, 1)
    for batch in (1, 2, 3, 5, 16):
        for block in (1, 2, 4, 64):
            n, bb = batch_grid(batch, block)
            assert n * bb >= batch and (n - 1) * bb < batch


def test_kernel_program_batch_block_scales_vmem():
    """Per-image VMEM terms scale with the batch block; weights are
    shared — so bb images never cost bb full working sets."""
    g, progs, _ = _setup()
    kp1 = graph_kernel_programs(g, progs, batch=1)["c2"]
    kp4 = graph_kernel_programs(g, progs, batch=4)["c2"]
    assert kp1.batch_block == 1
    if kp4.batch_block > 1:
        assert kp4.plan_bytes < kp4.batch_block * kp1.plan_bytes
    assert kp4.plan_bytes >= kp1.plan_bytes


# ---------------------------------------------------------------------------
# fp32: batched == per-image, bitwise
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", ["wave", "megakernel", "graphkernel"])
@pytest.mark.parametrize("batch", [1, 3, 4])
def test_fp32_batched_bit_identical_to_per_image(mode, batch):
    g, progs, weights = _setup()
    x = jax.random.normal(jax.random.key(2), (batch, 16, 16, 3))
    fn_b, ops_b = _forward(g, progs, mode, batch)
    y_batched = np.asarray(fn_b(x, weights, ops_b))
    fn_1, ops_1 = _forward(g, progs, mode, 1)
    for i in range(batch):
        y_i = np.asarray(fn_1(x[i:i + 1], weights, ops_1))[0]
        np.testing.assert_array_equal(
            y_batched[i], y_i,
            err_msg=f"{mode}: image {i} of batch {batch} diverged "
                    f"from its per-image run")


@pytest.mark.parametrize("mode", ["wave", "megakernel", "graphkernel"])
def test_fp32_ragged_batch_padding_is_invisible(mode):
    """A batch smaller than the lowering batch runs through the same
    tables (zero-padded, cropped): real rows are untouched."""
    g, progs, weights = _setup()
    fn, ops = _forward(g, progs, mode, 4)       # lowered for batch 4
    x = jax.random.normal(jax.random.key(3), (4, 16, 16, 3))
    y4 = np.asarray(fn(x, weights, ops))
    y3 = np.asarray(fn(x[:3], weights, ops))
    assert y3.shape[0] == 3
    np.testing.assert_array_equal(y3, y4[:3])


# ---------------------------------------------------------------------------
# int8: batched == per-image, exactly (integer datapath)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", ["megakernel", "graphkernel"])
@pytest.mark.parametrize("batch", [1, 4, 16])
def test_int8_batched_array_equal_to_per_image(mode, batch):
    g, progs, weights = _setup()
    calib = jax.random.normal(jax.random.key(5), (2, 16, 16, 3))
    qg = calibrate_graph(g, weights, calib)
    qw = qg.device_weights()
    x = jax.random.normal(jax.random.key(6), (batch, 16, 16, 3))
    fn_b, ops_b = _forward(g, progs, mode, batch,
                           precision="int8", qgraph=qg)
    y_batched = np.asarray(fn_b(x, qw, ops_b))
    fn_1, ops_1 = _forward(g, progs, mode, 1,
                           precision="int8", qgraph=qg)
    for i in range(batch):
        y_i = np.asarray(fn_1(x[i:i + 1], qw, ops_1))[0]
        np.testing.assert_array_equal(
            y_batched[i], y_i,
            err_msg=f"int8 {mode}: image {i} of batch {batch} diverged")
