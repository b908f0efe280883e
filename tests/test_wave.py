"""Wave-parallel schedule replay (ISSUE 2): bit-exactness against the
interpreted tile walk, wave-partition safety properties, the fused
conv+pool network path, and executor-cache hygiene."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.decomposition import (ALEXNET_STACK, ConvLayer, evaluate,
                                      plan_decomposition)
from repro.core.schedule import (WaveProgram, compile_layer,
                                 compile_network, partition_waves,
                                 validate_waves)
from repro.core.streaming import (clear_executor_cache, conv2d_direct,
                                  executor_cache_size, maxpool_direct,
                                  network_forward_fn, network_operands,
                                  run_layer_interpreted, run_layer_streamed,
                                  set_executor_cache_limit)
from repro.launch.session import StreamingSession

try:
    import hypothesis
    import hypothesis.strategies as st
except ImportError:  # dev-only dependency (requirements.txt)
    hypothesis = None

# fp32 executors that sum the same products in another order: conv4/5
# sum K*K*fan = 1728 products into outputs of a few units, and the
# orders differ by up to ~4e-6 (a few dozen ULP at 1.0)
FP32_REASSOC = dict(rtol=1e-5, atol=1e-5)


def _layer_weights(layer, key=1, scale=0.2):
    l = layer
    return jax.random.normal(
        jax.random.key(key),
        (l.kernel, l.kernel, l.in_c // l.groups, l.out_c)) * scale


# ---------------------------------------------------------------------------
# Bit-exactness: wave executor == interpreted tile walk
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("layer", ALEXNET_STACK, ids=lambda l: l.name)
def test_wave_bit_identical_alexnet(layer):
    """Every ALEXNET_STACK layer under its own 128 KB plan — grouped
    conv2/4/5 and the in_splits=256 partial-sum chain of conv3 included:
    the fused wave dispatches reproduce the interpreted walk bit for
    bit (the ISSUE 2 acceptance gate)."""
    l = layer
    plan = plan_decomposition(l, 128 * 1024)
    x = jax.random.normal(jax.random.key(0), (2, l.in_h, l.in_w, l.in_c))
    w = _layer_weights(l, scale=0.05)
    b = jax.random.normal(jax.random.key(7), (l.out_c,)) * 0.1
    wave = run_layer_streamed(l, plan, x, w, b, mode="wave")
    interp = run_layer_interpreted(l, plan, x, w, b)
    # XLA may reassociate a grouped conv's channel sums differently at
    # the wave and tile shapes: a few ULP on conv4/conv5
    np.testing.assert_allclose(wave, interp, **FP32_REASSOC,
                               err_msg="wave executor != tile loop")
    scan = run_layer_streamed(l, plan, x, w, b, mode="jit")
    np.testing.assert_allclose(wave, scan, **FP32_REASSOC,
                               err_msg="wave executor != scan executor")


@pytest.mark.parametrize("th,tw,fs,cs", [(1, 1, 1, 1), (3, 2, 2, 1),
                                         (2, 2, 1, 2), (2, 3, 4, 4),
                                         (2, 2, 3, 8)])
def test_wave_matches_interpreter_synthetic_plans(th, tw, fs, cs):
    """Partial-sum chains (cs > 1) and ragged feature splits."""
    layer = ConvLayer("t", 21, 17, 8, 12, 3, stride=2, pad=1)
    plan = evaluate(layer, th, tw, fs, cs)
    assert plan is not None
    x = jax.random.normal(jax.random.key(3), (1, 21, 17, 8))
    w = _layer_weights(layer)
    wave = run_layer_streamed(layer, plan, x, w, mode="wave")
    interp = run_layer_interpreted(layer, plan, x, w)
    assert jnp.array_equal(wave, interp)
    assert jnp.max(jnp.abs(wave - conv2d_direct(x, w, 2, 1))) < 1e-4


def test_wave_with_pallas_backend():
    """The wave dispatch hands its stacked (T*B, ih, iw, c) batch to the
    pluggable conv backend — Pallas conv_stream included."""
    layer = ConvLayer("pk", 16, 16, 4, 8, 3, stride=1, pad=0)
    plan = evaluate(layer, 2, 2, 2, 1)
    x = jax.random.normal(jax.random.key(0), (1, 16, 16, 4))
    w = jax.random.normal(jax.random.key(1), (3, 3, 4, 8)) * 0.2
    got = run_layer_streamed(layer, plan, x, w, mode="wave",
                             conv_backend="pallas")
    ref = conv2d_direct(x, w, 1, 0)
    assert jnp.max(jnp.abs(got - ref)) < 1e-4


def test_wave_rejects_unknown_mode():
    layer = ConvLayer("m", 8, 8, 3, 4, 3)
    plan = evaluate(layer, 1, 1, 1, 1)
    x = jnp.zeros((1, 8, 8, 3))
    with pytest.raises(ValueError, match="unknown executor mode"):
        run_layer_streamed(layer, plan, x, _layer_weights(layer),
                           mode="warp")


# ---------------------------------------------------------------------------
# Partition safety: no wave co-schedules two writers of one output block
# ---------------------------------------------------------------------------

def _assert_wave_invariants(wprog: WaveProgram):
    seen_chain: dict = {}
    for k, wave in enumerate(wprog.waves):
        blocks = [(s[2], s[3], s[6]) for s in wave]
        # independence: distinct output blocks within a wave
        assert len(set(blocks)) == len(blocks), (
            f"wave {k} co-schedules two writers of one output block")
        # chain order: wave index == position in the block's psum chain
        for blk in blocks:
            assert seen_chain.get(blk, 0) == k
            seen_chain[blk] = k + 1
    # completeness: every program step landed in exactly one wave
    assert sum(len(w) for w in wprog.waves) == wprog.program.n_steps


def test_wave_partition_property_sweep():
    """Deterministic sweep over the planner's whole (tiles, feat, in)
    grid for representative geometries — runs even without hypothesis."""
    layers = [
        ConvLayer("s1", 21, 17, 8, 12, 3, stride=2, pad=1),
        ConvLayer("s2", 27, 27, 96, 256, 5, pad=2, groups=2),
        ConvLayer("s3", 13, 13, 16, 24, 3, pad=1),
    ]
    checked = 0
    for layer in layers:
        for th in (1, 2, 3):
            for tw in (1, 2, 4):
                for fs in (1, 2, 4, 8):
                    for cs in (1, 2, 4):
                        plan = evaluate(layer, th, tw, fs, cs)
                        if plan is None:
                            continue
                        wprog = partition_waves(
                            compile_layer(layer, plan))
                        _assert_wave_invariants(wprog)
                        checked += 1
    assert checked > 30  # the sweep actually exercised the grid


@pytest.mark.parametrize("layer", ALEXNET_STACK, ids=lambda l: l.name)
def test_wave_partition_alexnet_plans(layer):
    plan = plan_decomposition(layer, 128 * 1024)
    wprog = partition_waves(compile_layer(layer, plan))
    _assert_wave_invariants(wprog)
    expected_waves = plan.in_splits if layer.groups == 1 else 1
    assert wprog.n_waves == expected_waves


def test_validate_waves_rejects_duplicate_block():
    """A corrupted wave (two writers of one block) must not validate."""
    layer = ConvLayer("v", 8, 8, 4, 8, 3, pad=1)
    plan = evaluate(layer, 2, 1, 1, 1)
    wprog = partition_waves(compile_layer(layer, plan))
    bad = wprog.waves[0][:1] + wprog.waves[0][:1]  # same block twice
    import dataclasses
    corrupted = dataclasses.replace(wprog, waves=(bad,))
    with pytest.raises(ValueError, match="written twice|raster"):
        validate_waves(corrupted)


# ---------------------------------------------------------------------------
# Whole-network wave path + fused conv+pool backend
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


def test_network_forward_wave_equals_scan():
    layers, weights = _small_net()
    plans = [plan_decomposition(l, 64 * 1024) for l in layers]
    programs = compile_network(layers, plans)
    x = jax.random.normal(jax.random.key(5), (3, 16, 16, 3))
    outs = {}
    for mode in ("wave", "scan"):
        fwd = jax.jit(network_forward_fn(programs, mode=mode))
        outs[mode] = fwd(x, weights, network_operands(programs, mode))
    assert jnp.array_equal(outs["wave"], outs["scan"])
    assert jnp.max(jnp.abs(outs["wave"]
                           - _direct_net(layers, weights, x))) < 1e-4


def test_network_forward_fused_pool_backend():
    """pool layers routed through the fused Pallas conv+ReLU+pool kernel
    never materialise the pre-pool activation in the XLA graph."""
    layers, weights = _small_net()
    plans = [plan_decomposition(l, 64 * 1024) for l in layers]
    programs = compile_network(layers, plans)
    x = jax.random.normal(jax.random.key(6), (2, 16, 16, 3))
    fwd = jax.jit(network_forward_fn(programs, mode="wave",
                                     pool_backend="fused"))
    got = fwd(x, weights, network_operands(programs, "wave"))
    assert jnp.max(jnp.abs(got - _direct_net(layers, weights, x))) < 1e-4


def test_network_forward_rejects_bad_modes():
    layers, weights = _small_net()
    plans = [plan_decomposition(l, 64 * 1024) for l in layers]
    programs = compile_network(layers, plans)
    with pytest.raises(ValueError, match="unknown executor mode"):
        network_forward_fn(programs, mode="turbo")
    with pytest.raises(ValueError, match="no interpret mode"):
        network_forward_fn(programs, mode="interpret")
    with pytest.raises(ValueError, match="unknown pool backend"):
        network_forward_fn(programs, pool_backend="cudnn")
    with pytest.raises(ValueError, match="unknown executor mode"):
        network_operands(programs, mode="waves")
    # "jit" and "scan" are aliases at every level
    x = jnp.zeros((1, 16, 16, 3))
    a = jax.jit(network_forward_fn(programs, mode="jit"))(
        x, weights, network_operands(programs, "jit"))
    bq = jax.jit(network_forward_fn(programs, mode="scan"))(
        x, weights, network_operands(programs, "scan"))
    assert jnp.array_equal(a, bq)


def test_session_wave_mode_serves_alexnet_pool_layers():
    """Grouped pool layers (conv2/conv5, overlapping 3/2 pools) through
    the default wave session AND the fused pool backend."""
    stack = ALEXNET_STACK[:2]      # conv1 (pool 3/2) + conv2 (grouped)
    weights = [(_layer_weights(l, key=i, scale=0.05),
                jnp.zeros((l.out_c,))) for i, l in enumerate(stack)]
    x = jax.random.normal(jax.random.key(0), (2, 227, 227, 3))
    ref = _direct_net(stack, weights, x)
    sess = StreamingSession.for_network(stack, weights, max_batch=2)
    assert sess.mode == "wave"
    y = sess.run_batch(x)
    assert jnp.max(jnp.abs(y - ref)) < 1e-3
    fused = StreamingSession.for_network(stack, weights, max_batch=2,
                                         pool_backend="fused")
    yf = fused.run_batch(x)
    assert jnp.max(jnp.abs(yf - ref)) < 1e-3


def test_session_wave_convs_lower_at_highest_precision():
    """On a TPU the default fp32 conv is one bf16 pass. Every conv the
    serving default (the wave session) lowers asks for HIGHEST, so
    fp32 serving is fp32 on the chip too."""
    layers = (ConvLayer("s", 19, 19, 3, 8, 5, stride=2, pool=3,
                        pool_stride=2),
              ConvLayer("g", 3, 3, 8, 8, 3, pad=1, groups=2))
    weights = [(_layer_weights(l, key=i), jnp.zeros((l.out_c,)))
               for i, l in enumerate(layers)]
    sess = StreamingSession.for_network(layers, weights,
                                        sram_budget=2 * 1024, max_batch=2)
    x = jnp.zeros((2, 19, 19, 3), jnp.float32)
    sess.run_batch(jnp.array(x))
    (ex,) = sess._executables.values()
    text = ex.lower(x, sess.weights, sess._ops).as_text()
    convs = [ln for ln in text.splitlines() if "convolution" in ln]
    assert convs, "the wave session lowered no convolution"
    for ln in convs:
        assert ln.count("precision HIGHEST") == 2, ln[-200:]


def test_session_wave_microbatch_queue():
    layers, weights = _small_net()
    sess = StreamingSession.for_network(layers, weights,
                                        sram_budget=64 * 1024,
                                        max_batch=4, mode="wave")
    imgs = jax.random.normal(jax.random.key(8), (6, 16, 16, 3))
    tickets = [sess.submit(imgs[i]) for i in range(6)]
    outs = [sess.result(t) for t in tickets]
    assert sess.compile_count == 1
    ref = _direct_net(layers, weights, imgs)
    for i, o in enumerate(outs):
        assert jnp.max(jnp.abs(o - ref[i])) < 1e-4


# ---------------------------------------------------------------------------
# Executor cache hygiene (satellite: no id() reuse, bounded growth)
# ---------------------------------------------------------------------------

def test_executor_cache_clear_and_named_conv_fn():
    layer = ConvLayer("c", 12, 12, 4, 8, 3, pad=1)
    plan = evaluate(layer, 2, 1, 2, 1)
    x = jax.random.normal(jax.random.key(0), (1, 12, 12, 4))
    w = _layer_weights(layer)
    clear_executor_cache()
    assert executor_cache_size() == 0

    def my_conv(xt, wt):
        return conv2d_direct(xt, wt, 1, 0)

    for _ in range(3):  # stable callable -> one cached executable
        run_layer_streamed(layer, plan, x, w, conv_fn=my_conv, mode="wave")
    assert executor_cache_size() == 1
    # same name -> same executable even for a *different* callable
    run_layer_streamed(layer, plan, x, w, mode="wave",
                       conv_fn=lambda xt, wt: conv2d_direct(xt, wt, 1, 0),
                       conv_fn_name="xla-equivalent")
    run_layer_streamed(layer, plan, x, w, mode="wave",
                       conv_fn=lambda xt, wt: conv2d_direct(xt, wt, 1, 0),
                       conv_fn_name="xla-equivalent")
    assert executor_cache_size() == 2
    # anonymous fresh lambdas each get their own (never-recycled) token
    run_layer_streamed(layer, plan, x, w, mode="wave",
                       conv_fn=lambda xt, wt: conv2d_direct(xt, wt, 1, 0))
    assert executor_cache_size() == 3
    clear_executor_cache()
    assert executor_cache_size() == 0


def test_executor_cache_lru_bound():
    clear_executor_cache()
    set_executor_cache_limit(2)
    try:
        layer = ConvLayer("e", 12, 12, 4, 8, 3, pad=1)
        x = jax.random.normal(jax.random.key(0), (1, 12, 12, 4))
        w = _layer_weights(layer)
        for th, tw in ((1, 1), (2, 1), (1, 2), (2, 2)):
            plan = evaluate(layer, th, tw, 1, 1)
            run_layer_streamed(layer, plan, x, w, mode="wave")
        assert executor_cache_size() <= 2
        with pytest.raises(ValueError, match=">= 1"):
            set_executor_cache_limit(0)
    finally:
        set_executor_cache_limit(64)
        clear_executor_cache()


# ---------------------------------------------------------------------------
# Property-based cases (skipped cleanly without hypothesis)
# ---------------------------------------------------------------------------

if hypothesis is not None:
    @hypothesis.given(
        st.integers(6, 24), st.integers(6, 24),
        st.integers(1, 8), st.integers(1, 12),
        st.sampled_from([1, 3, 5]), st.sampled_from([1, 2]),
        st.integers(0, 2),
        st.integers(1, 3), st.integers(1, 3),
        st.sampled_from([1, 2, 3]), st.sampled_from([1, 2, 4]),
    )
    @hypothesis.settings(max_examples=40, deadline=None)
    def test_wave_partition_property_random(h, w, cin, cout, k, stride,
                                            pad, th, tw, fs, cs):
        layer = ConvLayer("t", h, w, cin, cout, k, stride=stride, pad=pad)
        if layer.out_h <= 0 or layer.out_w <= 0:
            return
        plan = evaluate(layer, th, tw, fs, cs)
        if plan is None:
            return
        wprog = partition_waves(compile_layer(layer, plan))
        _assert_wave_invariants(wprog)

    @hypothesis.given(
        st.integers(8, 32), st.integers(8, 32),
        st.integers(1, 16), st.integers(1, 24),
        st.sampled_from([1, 3, 5]), st.sampled_from([1, 2]),
        st.integers(0, 2),
        st.sampled_from([8, 16, 32, 64, 128]),   # planner budget, KiB
    )
    @hypothesis.settings(max_examples=40, deadline=None)
    def test_wave_partition_property_planner_budgets(h, w, cin, cout, k,
                                                     stride, pad,
                                                     sram_kib):
        """Whatever plan the *planner* picks under a randomized SRAM
        budget (not just hand-chosen splits or the AlexNet 128 KB
        plans) must wave-partition cleanly, including the
        wave-invariant-window invariant the hoisted gather and the
        megakernel tables rely on."""
        layer = ConvLayer("t", h, w, cin, cout, k, stride=stride, pad=pad)
        if layer.out_h <= 0 or layer.out_w <= 0:
            return
        try:
            plan = plan_decomposition(layer, sram_kib * 1024)
        except ValueError:
            return              # infeasible at this budget
        wprog = partition_waves(compile_layer(layer, plan))
        _assert_wave_invariants(wprog)
        validate_waves(wprog)
        # windows are wave-invariant: the once-per-window gather holds
        for wave in wprog.tile_waves[1:]:
            assert [r[:4] for r in wave] == \
                [r[:4] for r in wprog.tile_waves[0]]
