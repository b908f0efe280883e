"""Streaming tiled executor == direct convolution (the paper's §3+§5
correctness claim) — interpreted and compiled (scan) executors, the
Pallas kernel backend, and the StreamingSession serving layer."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.decomposition import (ALEXNET_LAYERS, ALEXNET_STACK,
                                      ConvLayer, evaluate,
                                      plan_decomposition)
from repro.core.streaming import (conv2d_direct, maxpool_direct,
                                  run_layer_interpreted, run_layer_streamed,
                                  run_network_streamed)
from repro.kernels.conv_stream import conv2d_stream
from repro.launch.session import StreamingSession

try:
    import hypothesis
    import hypothesis.strategies as st
except ImportError:  # dev-only dependency (requirements.txt)
    hypothesis = None


def _layer_weights(layer, key=1, scale=0.2):
    l = layer
    w = jax.random.normal(jax.random.key(key),
                          (l.kernel, l.kernel, l.in_c // l.groups,
                           l.out_c)) * scale
    return w


def test_alexnet_conv1_streamed_under_paper_budget():
    l1 = ALEXNET_LAYERS[0]
    plan = plan_decomposition(l1, 128 * 1024)
    x = jax.random.normal(jax.random.key(0), (1, 227, 227, 3))
    w = jax.random.normal(jax.random.key(1), (11, 11, 3, 96)) * 0.05
    assert plan.sram_needed <= 128 * 1024
    direct = conv2d_direct(x, w, 4, 0)
    streamed = run_layer_streamed(l1, plan, x, w)
    assert jnp.max(jnp.abs(direct - streamed)) < 1e-3


def test_streamed_network_stack():
    layers = (ConvLayer("a", 16, 16, 3, 8, 3, pad=1, pool=2),
              ConvLayer("b", 8, 8, 8, 16, 3, pad=1))
    plans = [plan_decomposition(l, 64 * 1024) for l in layers]
    weights = []
    for i, l in enumerate(layers):
        w = jax.random.normal(jax.random.key(i), (l.kernel, l.kernel,
                                                  l.in_c, l.out_c)) * 0.2
        b = jnp.zeros((l.out_c,))
        weights.append((w, b))
    x = jax.random.normal(jax.random.key(9), (2, 16, 16, 3))
    got = run_network_streamed(layers, plans, x, weights)
    got_interp = run_network_streamed(layers, plans, x, weights,
                                      mode="interpret")
    # direct reference
    y = x
    for l, (w, b) in zip(layers, weights):
        y = jnp.maximum(conv2d_direct(y, w, l.stride, l.pad) + b, 0)
        if l.pool > 1:
            y = maxpool_direct(y, l.pool, l.pool_stride or l.pool)
    assert jnp.max(jnp.abs(got - y)) < 1e-4
    assert jnp.array_equal(got, got_interp)


def test_streamed_with_pallas_kernel_backend():
    """The executor's pluggable conv backend: Pallas streaming kernel."""
    layer = ConvLayer("pk", 16, 16, 4, 8, 3, stride=1, pad=0)
    plan = evaluate(layer, 2, 1, 2, 1)
    x = jax.random.normal(jax.random.key(0), (1, 16, 16, 4))
    w = jax.random.normal(jax.random.key(1), (3, 3, 4, 8)) * 0.2

    def pallas_conv(xt, wt):
        return conv2d_stream(xt, wt, stride=layer.stride, row_block=4)

    got = run_layer_streamed(layer, plan, x, w, conv_fn=pallas_conv,
                             mode="interpret")
    ref = conv2d_direct(x, w, 1, 0)
    assert jnp.max(jnp.abs(got - ref)) < 1e-4
    # and as a first-class backend of the compiled scan executor
    got_jit = run_layer_streamed(layer, plan, x, w, conv_backend="pallas")
    assert jnp.max(jnp.abs(got_jit - ref)) < 1e-4


# ---------------------------------------------------------------------------
# Compiled (scan) executor: bit-identical replay of the schedule
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("layer", ALEXNET_LAYERS, ids=lambda l: l.name)
def test_scan_executor_bit_identical_alexnet(layer):
    """Across all AlexNet layers (stride 4, pad 2, grouped convs), under
    the paper's own 128 KB plans: the compiled executor reproduces the
    interpreted tile walk bit for bit, and the direct conv bit for bit
    whenever there is no partial-sum split to reassociate."""
    plan = plan_decomposition(layer, 128 * 1024)
    l = layer
    x = jax.random.normal(jax.random.key(0), (2, l.in_h, l.in_w, l.in_c))
    w = _layer_weights(l, scale=0.05)
    b = jax.random.normal(jax.random.key(7), (l.out_c,)) * 0.1
    jit_out = run_layer_streamed(l, plan, x, w, b)
    interp = run_layer_interpreted(l, plan, x, w, b)
    # XLA may reassociate a grouped conv's channel sums differently at
    # the tile and scan shapes: up to ~4e-6 on conv4/conv5's 1728-term
    # sums
    np.testing.assert_allclose(jit_out, interp, rtol=1e-5, atol=1e-5,
                               err_msg="scan executor != tile loop")
    direct = conv2d_direct(x, w, l.stride, l.pad, groups=l.groups) + b
    if plan.in_splits == 1:
        assert jnp.array_equal(jit_out, direct), "scan executor != direct"
    else:  # partial sums reassociate the channel reduction: ULP-level
        assert jnp.max(jnp.abs(jit_out - direct)) < 1e-4


@pytest.mark.parametrize("th,tw,fs,cs", [(1, 1, 1, 1), (3, 2, 2, 1),
                                         (2, 2, 1, 2), (2, 3, 4, 4)])
def test_scan_executor_matches_loop_random_plans(th, tw, fs, cs):
    layer = ConvLayer("t", 21, 17, 8, 12, 3, stride=2, pad=1)
    plan = evaluate(layer, th, tw, fs, cs)
    assert plan is not None
    x = jax.random.normal(jax.random.key(3), (1, 21, 17, 8))
    w = _layer_weights(layer)
    got = run_layer_streamed(layer, plan, x, w)
    ref = run_layer_interpreted(layer, plan, x, w)
    assert jnp.max(jnp.abs(got - ref)) < 1e-5
    assert jnp.max(jnp.abs(got - conv2d_direct(x, w, 2, 1))) < 1e-4


def test_scan_executor_unreachable_trailing_rows():
    """(in - K) % stride != 0 leaves trailing rows the conv window never
    reads; the tile grid is then *smaller* than the padded input and the
    executor must trim, not negative-pad (regression)."""
    layer = ConvLayer("t", 8, 8, 4, 8, 3, stride=2)
    plan = evaluate(layer, 1, 1, 1, 1)
    x = jax.random.normal(jax.random.key(0), (1, 8, 8, 4))
    w = _layer_weights(layer)
    got = run_layer_streamed(layer, plan, x, w)
    assert jnp.array_equal(got, run_layer_interpreted(layer, plan, x, w))
    assert jnp.max(jnp.abs(got - conv2d_direct(x, w, 2, 0))) < 1e-5


def test_scan_executor_rejects_mismatched_input():
    l1 = ALEXNET_LAYERS[0]
    plan = plan_decomposition(l1, 128 * 1024)
    x = jax.random.normal(jax.random.key(0), (1, 55, 55, 3))  # wrong dims
    with pytest.raises(ValueError, match="declared"):
        run_layer_streamed(l1, plan, x, _layer_weights(l1))


# ---------------------------------------------------------------------------
# StreamingSession: compiled multi-image serving
# ---------------------------------------------------------------------------

def _small_net():
    layers = (ConvLayer("a", 16, 16, 3, 8, 3, pad=1, pool=2),
              ConvLayer("b", 8, 8, 8, 16, 3, pad=1))
    weights = []
    for i, l in enumerate(layers):
        w = jax.random.normal(jax.random.key(i), (l.kernel, l.kernel,
                                                  l.in_c, l.out_c)) * 0.2
        weights.append((w, jnp.zeros((l.out_c,))))
    return layers, weights


def _direct_net(layers, weights, x):
    y = x
    for l, (w, b) in zip(layers, weights):
        y = jnp.maximum(conv2d_direct(y, w, l.stride, l.pad,
                                      groups=l.groups) + b, 0)
        if l.pool > 1:
            y = maxpool_direct(y, l.pool, l.pool_stride or l.pool)
    return y


def test_session_reuses_compiled_executable():
    layers, weights = _small_net()
    sess = StreamingSession.for_network(layers, weights,
                                        sram_budget=64 * 1024, max_batch=4)
    x = jax.random.normal(jax.random.key(5), (4, 16, 16, 3))
    y1 = sess.run_batch(x)
    y2 = sess.run_batch(x + 1.0)
    y3 = sess.run_batch(x * 2.0)
    assert sess.compile_count == 1, "repeat batches must not retrace"
    assert sess.calls == 3
    assert jnp.max(jnp.abs(y1 - _direct_net(layers, weights, x))) < 1e-4
    assert not jnp.array_equal(y2, y3)
    # a new batch shape compiles exactly once more
    sess.run_batch(jax.random.normal(jax.random.key(6), (2, 16, 16, 3)))
    assert sess.compile_count == 2


def test_session_microbatch_queue():
    """Single-image submits coalesce into shared compiled batches."""
    layers, weights = _small_net()
    sess = StreamingSession.for_network(layers, weights,
                                        sram_budget=64 * 1024, max_batch=4)
    imgs = jax.random.normal(jax.random.key(8), (6, 16, 16, 3))
    tickets = [sess.submit(imgs[i]) for i in range(6)]
    assert sess.calls == 1          # 4 submits auto-flushed one batch
    assert sess.pending == 2
    outs = [sess.result(t) for t in tickets]   # flushes the remainder
    assert sess.pending == 0
    assert sess.calls == 2
    assert sess.compile_count == 1, "padded partial flush must reuse exe"
    ref = _direct_net(layers, weights, imgs)
    for i, o in enumerate(outs):
        assert jnp.max(jnp.abs(o - ref[i])) < 1e-4
    with pytest.raises(KeyError, match="already fetched"):
        sess.result(tickets[0])           # double-fetch is an error
    t = sess.submit(imgs[0])
    sess.discard(t)                        # abandoned ticket drops cleanly
    assert sess.pending == 0


def test_session_alexnet_stack_smoke():
    """The full pooled AlexNet stack serves a batch through one compile."""
    weights = [(_layer_weights(l, key=i, scale=0.05),
                jnp.zeros((l.out_c,)))
               for i, l in enumerate(ALEXNET_STACK)]
    sess = StreamingSession.for_network(ALEXNET_STACK, weights,
                                        max_batch=2)
    x = jax.random.normal(jax.random.key(0), (2, 227, 227, 3))
    y = sess.run_batch(x)
    assert y.shape == (2, 6, 6, 256)
    assert sess.compile_count == 1
    ref = _direct_net(ALEXNET_STACK, weights, x)
    assert jnp.max(jnp.abs(y - ref)) < 1e-3


# ---------------------------------------------------------------------------
# Property-based cases (skipped cleanly without hypothesis)
# ---------------------------------------------------------------------------

if hypothesis is not None:
    @hypothesis.given(
        st.integers(6, 24), st.integers(6, 24),
        st.integers(1, 8), st.integers(1, 12),
        st.sampled_from([1, 3, 5]), st.sampled_from([1, 2]),
        st.integers(0, 2),
        st.integers(1, 3), st.integers(1, 3), st.sampled_from([1, 2, 3]),
    )
    @hypothesis.settings(max_examples=25, deadline=None)
    def test_streamed_equals_direct_random(h, w, cin, cout, k, stride, pad,
                                           th, tw, fs):
        layer = ConvLayer("t", h, w, cin, cout, k, stride=stride, pad=pad)
        if layer.out_h <= 0 or layer.out_w <= 0 or fs > cout:
            return
        plan = evaluate(layer, th, tw, fs, 1)
        if plan is None:
            return
        x = jax.random.normal(jax.random.key(0), (1, h, w, cin))
        wts = jax.random.normal(jax.random.key(1), (k, k, cin, cout)) * 0.2
        direct = conv2d_direct(x, wts, stride, pad)
        streamed = run_layer_streamed(layer, plan, x, wts)
        interp = run_layer_interpreted(layer, plan, x, wts)
        assert jnp.max(jnp.abs(direct - streamed)) < 1e-4
        assert jnp.max(jnp.abs(interp - streamed)) < 1e-5
else:
    def test_property_cases_need_hypothesis():
        pytest.importorskip("hypothesis")  # skips, visibly
