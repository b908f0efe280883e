"""int8 streaming inference (ISSUE 4): megakernel-vs-int32-reference
bit-exactness on every AlexNet 128 KB plan, end-to-end SNR >= 20 dB per
layer, precision wiring through run_layer_streamed / network_forward_fn
/ StreamingSession, one launch per layer, and the precision-aware
executor cache."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.decomposition import (ALEXNET_STACK, ConvLayer, evaluate,
                                      plan_decomposition)
from repro.core.quantization import (dequantize_int8, quantize_int8_sym)
from repro.core.schedule import compile_layer, compile_network, \
    lower_kernel_program, partition_waves
from repro.core.streaming import (clear_executor_cache, executor_cache_size,
                                  network_forward_fn, network_kernel_programs,
                                  network_operands, run_layer_interpreted,
                                  run_layer_megakernel_q, run_layer_streamed)
from repro.kernels.wave_replay_q import (launch_count, reset_launch_count,
                                         wave_replay_q_from_quant)
from repro.kernels.wave_replay_q.ref import quant_layer_ref_from_quant
from repro.quant import (accuracy_report, calibrate_layer,
                         calibrate_network, quant_reference_acts, snr_db)
from repro.launch.session import StreamingSession


def _weights(layer, key=1, scale=0.05):
    l = layer
    k1, k2 = jax.random.split(jax.random.key(key))
    w = jax.random.normal(
        k1, (l.kernel, l.kernel, l.in_c // l.groups, l.out_c)) * scale
    b = jax.random.normal(k2, (l.out_c,)) * scale
    return w, b


def _alexnet_weights():
    return [( _weights(l, key=i)[0], _weights(l, key=i)[1])
            for i, l in enumerate(ALEXNET_STACK)]


# ---------------------------------------------------------------------------
# Acceptance gate: bit-exact vs the int32 reference on every 128 KB plan
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("layer", ALEXNET_STACK, ids=lambda l: l.name)
def test_int8_megakernel_bit_exact_alexnet(layer):
    """Every ALEXNET_STACK layer under its own 128 KB plan — grouped
    conv2/4/5 (true per-group gemms), conv3's 256-wave partial-sum
    chain, chain coarsening at the default VMEM budget. Integer
    arithmetic end to end, so the comparison is array_equal, not
    tolerance (the ISSUE 4 acceptance gate)."""
    l = layer
    plan = plan_decomposition(l, 128 * 1024)
    x = jax.random.normal(jax.random.key(0), (1, l.in_h, l.in_w, l.in_c))
    w, b = _weights(l)
    lq = calibrate_layer(l, w, b, x)
    xq = quantize_int8_sym(x, lq.in_scale)
    kp = lower_kernel_program(partition_waves(compile_layer(l, plan)))
    got = wave_replay_q_from_quant(kp, xq, lq)
    ref = quant_layer_ref_from_quant(l, xq, lq)
    assert got.dtype == jnp.int8
    assert jnp.array_equal(got, ref)


@pytest.mark.parametrize("vmem_kib", [64, 256, None])
def test_int8_chain_coarsening_stays_bit_exact(vmem_kib):
    """int32 accumulation is associative: 1:1 replay and both coarsened
    chains must produce identical bits, not merely close ones."""
    layer = ConvLayer("chain", 13, 13, 64, 32, 3, pad=1)
    plan = evaluate(layer, 2, 2, 1, 16)       # 16-wave chain
    wprog = partition_waves(compile_layer(layer, plan))
    x = jax.random.normal(jax.random.key(1), (2, 13, 13, 64))
    w, b = _weights(layer)
    lq = calibrate_layer(layer, w, b, x)
    xq = quantize_int8_sym(x, lq.in_scale)
    budget = vmem_kib * 1024 if vmem_kib else None
    kp = lower_kernel_program(wprog, vmem_budget=budget)
    got = wave_replay_q_from_quant(kp, xq, lq)
    ref = quant_layer_ref_from_quant(layer, xq, lq)
    assert jnp.array_equal(got, ref)


def test_int8_ragged_feature_split_bit_exact():
    """out_c_pad > out_c (ragged ungrouped feature split): the padded
    channels carry m=0 requant lanes and crop away — still bit-exact."""
    l = ConvLayer("rag", 12, 12, 8, 10, 3, pad=1)
    plan = evaluate(l, 2, 2, 3, 2)          # fg=4 -> out_c_pad=12
    x = jax.random.normal(jax.random.key(6), (2, 12, 12, 8))
    w, b = _weights(l, scale=0.2)
    lq = calibrate_layer(l, w, b, x)
    xq = quantize_int8_sym(x, lq.in_scale)
    wprog = partition_waves(compile_layer(l, plan))
    assert wprog.program.out_c_pad > l.out_c
    got = wave_replay_q_from_quant(lower_kernel_program(wprog), xq, lq)
    ref = quant_layer_ref_from_quant(l, xq, lq)
    assert jnp.array_equal(got, ref)


def test_int8_fused_relu_pool_epilogue_bit_exact():
    layer = ConvLayer("ep", 20, 20, 8, 16, 3, pad=1, pool=3, pool_stride=2)
    plan = evaluate(layer, 2, 3, 1, 2)
    wprog = partition_waves(compile_layer(layer, plan))
    x = jax.random.normal(jax.random.key(2), (2, 20, 20, 8))
    w, b = _weights(layer, scale=0.2)
    lq = calibrate_layer(layer, w, b, x)
    xq = quantize_int8_sym(x, lq.in_scale)
    kp = lower_kernel_program(wprog, act="relu", fuse_pool=True)
    got = wave_replay_q_from_quant(kp, xq, lq)
    ref = quant_layer_ref_from_quant(layer, xq, lq, relu=True,
                                     fuse_pool=True)
    assert got.shape == (2, layer.pooled_h, layer.pooled_w, 16)
    assert jnp.array_equal(got, ref)
    assert int(got.min()) >= 0                 # ReLU folded into the clip


def test_int8_network_chain_bit_exact_small():
    """End to end through the network path: quantize once, int8 flows
    between layers, final activation equals the int32 reference chain."""
    layers = (ConvLayer("a", 16, 16, 3, 8, 3, pad=1, pool=2),
              ConvLayer("b", 8, 8, 8, 16, 3, pad=1, groups=2))
    weights = [(_weights(l, key=i, scale=0.2)[0],
                jnp.full((l.out_c,), 0.1)) for i, l in enumerate(layers)]
    x = jax.random.normal(jax.random.key(5), (2, 16, 16, 3))
    qnet = calibrate_network(layers, weights, x)
    plans = [plan_decomposition(l, 64 * 1024) for l in layers]
    programs = compile_network(layers, plans)
    fwd = jax.jit(network_forward_fn(programs, mode="megakernel",
                                     precision="int8", qnet=qnet,
                                     dequantize=False))
    ops = network_operands(programs, "megakernel")
    got = fwd(x, qnet.device_weights(), ops)
    ref = quant_reference_acts(qnet, x)[-1]
    assert got.dtype == jnp.int8
    assert jnp.array_equal(got, ref)


# ---------------------------------------------------------------------------
# Accuracy: the 20 dB per-layer SNR gate on the AlexNet stack
# ---------------------------------------------------------------------------

def test_alexnet_int8_snr_at_least_20db_per_layer():
    weights = _alexnet_weights()
    calib = jax.random.normal(jax.random.key(10), (2, 227, 227, 3))
    qnet = calibrate_network(ALEXNET_STACK, weights, calib)
    x = jax.random.normal(jax.random.key(11), (1, 227, 227, 3))
    report = accuracy_report(qnet, weights, x, runner="ref")
    assert len(report) == len(ALEXNET_STACK)
    for rec in report:
        assert rec["snr_db"] >= 20.0, rec      # the acceptance bar


def test_megakernel_runner_matches_ref_runner():
    """The accuracy harness's two runners are the bit-exactness gate
    from another angle: identical SNR because identical activations."""
    layers = ALEXNET_STACK[:2]
    weights = _alexnet_weights()[:2]
    x = jax.random.normal(jax.random.key(12), (1, 227, 227, 3))
    qnet = calibrate_network(layers, weights, x)
    ref_rep = accuracy_report(qnet, weights, x, runner="ref")
    mk_rep = accuracy_report(qnet, weights, x, runner="megakernel")
    assert [r["snr_db"] for r in ref_rep] == [r["snr_db"] for r in mk_rep]


# ---------------------------------------------------------------------------
# Wiring: run_layer_streamed / session / serve-level behaviour
# ---------------------------------------------------------------------------

def test_run_layer_streamed_int8_roundtrip():
    """The layer-level entry takes fp32 in, fp32 out; with an explicit
    LayerQuant it matches dequantize(int32-ref) bit for bit, and
    approximates the float interpreter to quantization accuracy."""
    layer = ConvLayer("r", 14, 14, 6, 10, 3, pad=1)
    plan = evaluate(layer, 2, 2, 1, 2)
    x = jax.random.normal(jax.random.key(3), (2, 14, 14, 6))
    w, b = _weights(layer, scale=0.2)
    lq = calibrate_layer(layer, w, b, x)
    got = run_layer_streamed(layer, plan, x, w, b, mode="megakernel",
                             precision="int8", quant=lq)
    xq = quantize_int8_sym(x, lq.in_scale)
    ref = dequantize_int8(quant_layer_ref_from_quant(layer, xq, lq),
                          lq.out_scale)
    assert jnp.array_equal(got, ref)
    float_ref = run_layer_interpreted(layer, plan, x, w, b)
    assert snr_db(float_ref, got) > 25.0


def test_run_layer_streamed_int8_calibrates_on_the_fly():
    layer = ConvLayer("f", 12, 12, 4, 8, 3, pad=1)
    plan = evaluate(layer, 1, 2, 1, 1)
    x = jax.random.normal(jax.random.key(4), (1, 12, 12, 4))
    w, b = _weights(layer, scale=0.3)
    got = run_layer_streamed(layer, plan, x, w, b, mode="megakernel",
                             precision="int8")
    ref = run_layer_interpreted(layer, plan, x, w, b)
    assert snr_db(ref, got) > 25.0


def test_int8_requires_megakernel_mode():
    layer = ConvLayer("e", 8, 8, 3, 4, 3, pad=1)
    plan = evaluate(layer, 1, 1, 1, 1)
    x = jax.random.normal(jax.random.key(0), (1, 8, 8, 3))
    w, b = _weights(layer)
    for mode in ("wave", "scan", "interpret"):
        with pytest.raises(ValueError, match="quantized megakernel"):
            run_layer_streamed(layer, plan, x, w, b, mode=mode,
                               precision="int8")
    with pytest.raises(ValueError, match="unknown precision"):
        run_layer_streamed(layer, plan, x, w, b, precision="int4")


def test_network_forward_int8_validates_inputs():
    layers = (ConvLayer("v", 8, 8, 3, 4, 3, pad=1),)
    programs = compile_network(layers, [plan_decomposition(layers[0],
                                                           64 * 1024)])
    with pytest.raises(ValueError, match="calibrated QuantizedNetwork"):
        network_forward_fn(programs, mode="megakernel", precision="int8")
    with pytest.raises(ValueError, match="quantized megakernel"):
        network_forward_fn(programs, mode="wave", precision="int8",
                           qnet=object())


def test_session_int8_serves_and_compiles_once():
    layers = (ConvLayer("a", 16, 16, 3, 8, 3, pad=1, pool=2),
              ConvLayer("b", 8, 8, 8, 16, 3, pad=1, groups=2))
    weights = [(_weights(l, key=i, scale=0.2)[0],
                jnp.full((l.out_c,), 0.1)) for i, l in enumerate(layers)]
    calib = jax.random.normal(jax.random.key(6), (2, 16, 16, 3))
    qnet = calibrate_network(layers, weights, calib)
    sess = StreamingSession.for_network(layers, None, sram_budget=64 * 1024,
                                        max_batch=2, mode="megakernel",
                                        precision="int8", qnet=qnet)
    assert sess.precision == "int8"
    x = jax.random.normal(jax.random.key(7), (2, 16, 16, 3))
    reset_launch_count()
    y = sess.run_batch(jnp.array(x))
    assert launch_count() == len(layers)      # one pallas_call per layer
    assert sess.compile_count == 1
    # micro-batch queue shares the same executable
    t0 = sess.submit(x[0])
    out0 = sess.result(t0)
    assert sess.compile_count == 1
    assert out0.shape == y[0].shape
    # output matches the dequantized int32 reference chain
    ref = dequantize_int8(quant_reference_acts(qnet, x)[-1],
                          qnet.out_scale)
    assert jnp.array_equal(y, ref)


def test_session_int8_requires_qnet_and_matching_stack():
    layers = (ConvLayer("a", 8, 8, 3, 4, 3, pad=1),)
    with pytest.raises(ValueError, match="calibrated qnet"):
        StreamingSession.for_network(layers, None, sram_budget=64 * 1024,
                                     mode="megakernel", precision="int8")
    other = (ConvLayer("other", 8, 8, 3, 4, 3, pad=1, pool=2),)
    w = [(_weights(other[0])[0], None)]
    qnet = calibrate_network(
        other, w, jax.random.normal(jax.random.key(0), (1, 8, 8, 3)))
    with pytest.raises(ValueError, match="different layer stack"):
        StreamingSession.for_network(layers, None, sram_budget=64 * 1024,
                                     mode="megakernel", precision="int8",
                                     qnet=qnet)


# ---------------------------------------------------------------------------
# The executor-cache precision fix (ISSUE 4 satellite)
# ---------------------------------------------------------------------------

def test_executor_cache_keeps_fp32_and_int8_apart():
    """Same layer, same plan, same batch shape, same fp32 input dtype:
    the fp32 and int8 megakernel executables must occupy distinct cache
    slots and keep answering correctly when interleaved."""
    layer = ConvLayer("k", 12, 12, 4, 8, 3, pad=1)
    plan = evaluate(layer, 2, 2, 1, 2)
    x = jax.random.normal(jax.random.key(8), (1, 12, 12, 4))
    w, b = _weights(layer, scale=0.2)
    lq = calibrate_layer(layer, w, b, x)
    clear_executor_cache()
    y_f1 = run_layer_streamed(layer, plan, x, w, b, mode="megakernel")
    n_after_fp32 = executor_cache_size()
    y_q1 = run_layer_streamed(layer, plan, x, w, b, mode="megakernel",
                              precision="int8", quant=lq)
    assert executor_cache_size() == n_after_fp32 + 1   # distinct slot
    # interleave: each precision must keep hitting its own executable
    y_f2 = run_layer_streamed(layer, plan, x, w, b, mode="megakernel")
    y_q2 = run_layer_streamed(layer, plan, x, w, b, mode="megakernel",
                              precision="int8", quant=lq)
    assert executor_cache_size() == n_after_fp32 + 1   # cache hits only
    assert jnp.array_equal(y_f1, y_f2)
    assert jnp.array_equal(y_q1, y_q2)
    # and the answers are genuinely different paths (quantized vs not)
    assert not jnp.array_equal(y_f1, y_q1)


def test_int8_cache_distinguishes_scales():
    """Two calibrations of the same geometry bake different scales —
    they must not serve each other's executables."""
    layer = ConvLayer("s", 10, 10, 4, 6, 3, pad=1)
    plan = evaluate(layer, 1, 1, 1, 1)
    x = jax.random.normal(jax.random.key(9), (1, 10, 10, 4))
    w, b = _weights(layer, scale=0.2)
    lq1 = calibrate_layer(layer, w, b, x)
    lq2 = calibrate_layer(layer, w, b, x * 4.0)      # wider scales
    wprog = partition_waves(compile_layer(layer, plan))
    y1 = run_layer_megakernel_q(wprog, x, lq1)
    y2 = run_layer_megakernel_q(wprog, x, lq2)
    xq1 = quantize_int8_sym(x, lq1.in_scale)
    xq2 = quantize_int8_sym(x, lq2.in_scale)
    r1 = dequantize_int8(quant_layer_ref_from_quant(layer, xq1, lq1),
                         lq1.out_scale)
    r2 = dequantize_int8(quant_layer_ref_from_quant(layer, xq2, lq2),
                         lq2.out_scale)
    assert jnp.array_equal(y1, r1)
    assert jnp.array_equal(y2, r2)


# ---------------------------------------------------------------------------
# Schedule reuse: quantization must not perturb the planner
# ---------------------------------------------------------------------------

def test_int8_reuses_fp32_kernel_programs_and_tables():
    layers = ALEXNET_STACK[:2]
    plans = [plan_decomposition(l, 128 * 1024) for l in layers]
    programs = compile_network(layers, plans)
    kprogs = network_kernel_programs(programs)
    ops_f = network_operands(programs, "megakernel")
    # the int8 forward consumes the SAME operand tables object-for-object
    # (network_operands has no precision parameter at all), and the same
    # KernelProgram geometries
    for kp, ops in zip(kprogs, ops_f):
        assert ops.shape == (kp.n_chain, kp.n_tiles, 8)
        assert np.array_equal(np.asarray(ops), kp.operand_table())


# ---------------------------------------------------------------------------
# int8 residual epilogue (ISSUE 5): requantize -> add -> ReLU-clip,
# bit-exact against the int32 reference with the same op order
# ---------------------------------------------------------------------------

def test_q_megakernel_residual_bit_exact():
    from repro.core.quantization import quantize_int8_sym
    layer = ConvLayer("qres", 12, 12, 8, 8, 3, pad=1)
    plan = evaluate(layer, 2, 2, 1, 2)
    kp = lower_kernel_program(partition_waves(compile_layer(layer, plan)),
                              act="relu", residual=True, vmem_budget=None)
    x = jax.random.normal(jax.random.key(0), (2, 12, 12, 8))
    w = jax.random.normal(jax.random.key(1), (3, 3, 8, 8)) * 0.2
    b = jax.random.normal(jax.random.key(2), (8,)) * 0.1
    q = calibrate_layer(layer, w, b, x)
    xq = quantize_int8_sym(x, q.in_scale)
    rq = quantize_int8_sym(
        jax.random.normal(jax.random.key(3), (2, 12, 12, 8)), q.out_scale)
    got = wave_replay_q_from_quant(kp, xq, q, residual=rq)
    ref = quant_layer_ref_from_quant(layer, xq, q, relu=True, residual=rq)
    assert jnp.array_equal(got, ref), "int8 residual epilogue != reference"


def test_residual_add_i8_clips_and_folds_relu():
    from repro.kernels.wave_replay_q.kernel import residual_add_i8
    a = jnp.array([[100, -100, 127, -127]], jnp.int8)
    r = jnp.array([[100, -100, 127, 127]], jnp.int8)
    s = residual_add_i8(a, r, relu=False)
    assert s.tolist() == [[127, -127, 127, 0]]       # saturating int8
    s_relu = residual_add_i8(a, r, relu=True)
    assert s_relu.tolist() == [[127, 0, 127, 0]]     # ReLU folds the clip
