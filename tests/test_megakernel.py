"""Persistent wave-replay megakernel (ISSUE 3): fp32-tolerance parity
with the interpreted tile walk on every AlexNet 128 KB plan, one
pallas_call per layer (dispatch counting), KernelProgram lowering
invariants on randomized geometries/budgets, chain coarsening, VMEM
re-planning, fused bias+ReLU+pool epilogue, and session serving with
donated input buffers."""
import dataclasses

import jax
import jax.numpy as jnp
import pytest

from repro.core.decomposition import (ALEXNET_STACK, ConvLayer, evaluate,
                                      plan_decomposition)
from repro.core.model_zoo import (alexnet_graph, convnext_t_graph,
                                  network_graph, resnet18_graph)
from repro.core.schedule import (DEFAULT_VMEM_BUDGET, KERNEL_OP_COLS,
                                 OP_C0, OP_VC, OP_VR, KernelProgram,
                                 compile_layer, compile_network,
                                 lower_kernel_program, partition_waves,
                                 validate_kernel_program)
from repro.core.streaming import (_graph_kernel_program, compile_graph,
                                  conv2d_direct, graph_forward_fn,
                                  graph_kernel_programs, maxpool_direct,
                                  network_forward_fn, network_operands,
                                  plan_for_vmem, plan_graph,
                                  run_layer_interpreted,
                                  run_layer_megakernel, run_layer_streamed)
from repro.kernels.common import (VMEM_CAPACITY, LaunchVmem,
                                  megakernel_fold, megakernel_geometry,
                                  megakernel_vmem, s2d_factor)
from repro.kernels.wave_replay import (expand_grouped, launch_count,
                                       reset_launch_count,
                                       wave_replay_layer, wave_replay_ref)
from repro.kernels.wave_replay.kernel import wave_replay_raw
from repro.launch.session import StreamingSession
from repro.obs import Tracer, use_tracer
from repro.obs import metrics as obs_metrics
from repro.runtime.errors import BudgetExceeded

try:
    import hypothesis
    import hypothesis.strategies as st
except ImportError:  # dev-only dependency (requirements.txt)
    hypothesis = None


def _weights(layer, key=1, scale=0.1):
    l = layer
    k1, k2 = jax.random.split(jax.random.key(key))
    w = jax.random.normal(
        k1, (l.kernel, l.kernel, l.in_c // l.groups, l.out_c)) * scale
    b = jax.random.normal(k2, (l.out_c,)) * scale
    return w, b


def _wave(layer, plan):
    return partition_waves(compile_layer(layer, plan))


# ---------------------------------------------------------------------------
# Acceptance gate: fp32-tolerance parity on every AlexNet 128 KB plan
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("layer", ALEXNET_STACK, ids=lambda l: l.name)
def test_megakernel_matches_interpreter_alexnet(layer):
    """Every ALEXNET_STACK layer under its own 128 KB plan — grouped
    conv2/4/5 (natural per-group gemms) and conv3's in_splits=256
    partial-sum chain included. The megakernel's im2col matmuls may
    round differently from the XLA conv by a few ULP, hence tolerance
    rather than bit-equality (the ISSUE 3 acceptance gate)."""
    l = layer
    plan = plan_decomposition(l, 128 * 1024)
    x = jax.random.normal(jax.random.key(0), (2, l.in_h, l.in_w, l.in_c))
    w, b = _weights(l, scale=0.05)
    mega = run_layer_streamed(l, plan, x, w, b, mode="megakernel")
    interp = run_layer_interpreted(l, plan, x, w, b)
    scale = float(jnp.max(jnp.abs(interp))) + 1e-6
    assert float(jnp.max(jnp.abs(mega - interp))) / scale < 1e-5


@pytest.mark.parametrize("vmem_kib", [64, 256, None])
def test_megakernel_chain_coarsening_levels(vmem_kib):
    """A deep partial-sum chain replayed 1:1 (``vmem_budget=None``) and
    coarsened under two budget points — all three within fp32 tolerance
    of the interpreter, exercising multi-step VMEM accumulation."""
    layer = ConvLayer("chain", 13, 13, 64, 32, 3, pad=1)
    plan = evaluate(layer, 2, 2, 1, 16)       # 16-wave chain, 4 tiles
    assert plan is not None
    wprog = _wave(layer, plan)
    budget = vmem_kib * 1024 if vmem_kib else None
    kp = lower_kernel_program(wprog, vmem_budget=budget)
    if vmem_kib is None:
        assert kp.chain_chunk == 1 and kp.n_chain == 16
    x = jax.random.normal(jax.random.key(1), (1, 13, 13, 64))
    w, b = _weights(layer)
    got = run_layer_megakernel(wprog, x, w, b, vmem_budget=budget)
    ref = run_layer_interpreted(layer, plan, x, w, b)
    assert float(jnp.max(jnp.abs(got - ref))) < 1e-4


def test_megakernel_fused_epilogue_relu_pool():
    """bias+ReLU+overlapping max-pool on the last chain step, per tile,
    entirely in VMEM — against the direct conv+pool oracle."""
    layer = ConvLayer("ep", 20, 20, 8, 16, 3, pad=1, pool=3, pool_stride=2)
    plan = evaluate(layer, 2, 3, 1, 2)
    assert plan is not None
    wprog = _wave(layer, plan)
    x = jax.random.normal(jax.random.key(2), (2, 20, 20, 8))
    w, b = _weights(layer)
    got = run_layer_megakernel(wprog, x, w, b, relu=True, fuse_pool=True)
    ref = wave_replay_ref(layer, x, w, b, relu=True, fuse_pool=True)
    assert got.shape == ref.shape == (2, layer.pooled_h, layer.pooled_w, 16)
    assert float(jnp.max(jnp.abs(got - ref))) < 1e-4


def test_megakernel_grouped_natural_layout():
    """Grouped layers accumulate per-group Cin/g x Cout/g gemms against
    the natural weight layout (ISSUE 10); the surviving block-diagonal
    reference construction agrees with it and with the direct conv."""
    layer = ConvLayer("g", 14, 14, 8, 12, 3, pad=1, groups=2)
    w, _ = _weights(layer)
    wd = expand_grouped(w, 2)
    assert wd.shape == (3, 3, 8, 12)
    # block-diagonal view: group 0's inputs never feed group 1's features
    assert float(jnp.max(jnp.abs(wd[:, :, :4, 6:]))) == 0.0
    assert float(jnp.max(jnp.abs(wd[:, :, 4:, :6]))) == 0.0
    plan = evaluate(layer, 2, 2, 1, 1)
    x = jax.random.normal(jax.random.key(3), (1, 14, 14, 8))
    got = run_layer_streamed(layer, plan, x, w, mode="megakernel")
    ref = conv2d_direct(x, w, 1, 1, groups=2)
    assert float(jnp.max(jnp.abs(got - ref))) < 1e-4
    # the block-diagonal dense view computes the same function
    bd = conv2d_direct(x, wd, 1, 1, groups=1)
    assert float(jnp.max(jnp.abs(got - bd))) < 1e-4
    # ... but the megakernel's weight operand is the natural g-x smaller
    kp = lower_kernel_program(_wave(layer, plan))
    assert kp.fan_width == 4 and kp.w_in_kpad == 4


@pytest.mark.parametrize(
    "in_c,kernel,pool,batch,fold",
    [(3, 5, 1, 2, "taps"), (40, 1, 1, 2, "none"), (40, 3, 1, 2, "none"),
     (8, 5, 1, 2, "stride"), (3, 7, 3, 3, "taps")],
    ids=["narrow-folded", "projection-staged", "wide-staged",
         "stride-folded", "stem-taps-folded"])
def test_megakernel_strided_fold_or_staged(in_c, kernel, pool, batch, fold):
    """Ungrouped strided layers fold the stride into channels only where
    a step's folded channels fit one lane tile (a 3- or 8-channel
    input); wider ones (ResNet's 1x1 stride-2 projections) load strided
    taps from the lane-tiled window, with no zero weights. Where the
    taps left after the stride fold fit two lane tiles of channels
    (3 channels: 5x5/2 to 9 x 12 = 108, the 7x7/2 stem to 16 x 12 =
    192), the fp32 launch runs each row's taps as one dot. The stem case
    fuses its 3/2 max-pool and pads a ragged batch of 3 to two blocks
    of 2. All match the direct conv."""
    layer = ConvLayer("s", 29 if pool > 1 else 17, 29 if pool > 1 else 17,
                      in_c, 16, kernel, stride=2, pad=kernel // 2,
                      pool=pool, pool_stride=2 if pool > 1 else 0)
    plan = evaluate(layer, 2, 2, 1, 1)
    kp = lower_kernel_program(_wave(layer, plan),
                              act="relu" if pool > 1 else None,
                              fuse_pool=pool > 1, vmem_budget=None,
                              batch_block=2)
    assert (s2d_factor(layer, kp.c_width) == 2) == (fold != "none")
    assert megakernel_fold(kp) == fold
    assert megakernel_fold(kp, quantized=True) == \
        ("none" if fold == "none" else "stride")
    x = jax.random.normal(jax.random.key(4),
                          (batch, layer.in_h, layer.in_w, in_c))
    w, b = _weights(layer)
    got = wave_replay_layer(kp, x, w, b)
    ref = conv2d_direct(x, w, 2, kernel // 2) + b
    if pool > 1:
        ref = maxpool_direct(jnp.maximum(ref, 0), pool, 2)
    assert got.shape == ref.shape
    assert float(jnp.max(jnp.abs(got - ref))) < 1e-4


def test_megakernel_masked_write_zeroes_grid_padding():
    """The epilogue's VR/VC masks zero the uniform-grid padding lanes,
    so the padded output is deterministic (not bias-polluted)."""
    layer = ConvLayer("m", 11, 11, 4, 8, 3, pad=1)   # out 11x11
    plan = evaluate(layer, 2, 2, 1, 1)               # blk 6 -> pad 12
    wprog = _wave(layer, plan)
    kp = lower_kernel_program(wprog)
    tab = kp.operand_table()
    assert (kp.out_h_pad, kp.out_w_pad) == (12, 12)
    assert {(r[OP_VR], r[OP_VC]) for r in tab[0]} == \
        {(6, 6), (6, 5), (5, 6), (5, 5)}
    from repro.kernels.wave_replay.kernel import wave_replay_raw
    from repro.kernels.wave_replay.ops import pad_operands
    x = jax.random.normal(jax.random.key(4), (1, 11, 11, 4))
    w, b = _weights(layer)
    xp, wp, bias = pad_operands(kp, x, w, b)
    padded = wave_replay_raw(kp, xp, wp, bias, jnp.asarray(tab))
    assert float(jnp.max(jnp.abs(padded[:, 11:, :, :]))) == 0.0
    assert float(jnp.max(jnp.abs(padded[:, :, 11:, :]))) == 0.0


# ---------------------------------------------------------------------------
# One pallas_call per layer (dispatch counting) + network/serving paths
# ---------------------------------------------------------------------------

def _small_net():
    layers = (ConvLayer("a", 16, 16, 3, 8, 3, pad=1, pool=2),
              ConvLayer("b", 8, 8, 8, 16, 3, pad=1, groups=2))
    weights = []
    for i, l in enumerate(layers):
        w = jax.random.normal(
            jax.random.key(i),
            (l.kernel, l.kernel, l.in_c // l.groups, l.out_c)) * 0.2
        weights.append((w, jnp.full((l.out_c,), 0.1)))
    return layers, weights


def _direct_net(layers, weights, x):
    y = x
    for l, (w, b) in zip(layers, weights):
        y = jnp.maximum(conv2d_direct(y, w, l.stride, l.pad,
                                      groups=l.groups) + b, 0)
        if l.pool > 1:
            y = maxpool_direct(y, l.pool, l.pool_stride or l.pool)
    return y


def test_network_megakernel_one_launch_per_layer():
    """The ISSUE 3 dispatch gate: tracing the megakernel network forward
    launches exactly ONE pallas_call per conv layer — pooling and ReLU
    ride in the epilogue, not in extra dispatches."""
    layers, weights = _small_net()
    plans = [plan_decomposition(l, 64 * 1024) for l in layers]
    programs = compile_network(layers, plans)
    x = jax.random.normal(jax.random.key(5), (2, 16, 16, 3))
    fwd = jax.jit(network_forward_fn(programs, mode="megakernel"))
    ops = network_operands(programs, "megakernel")
    reset_launch_count()
    got = fwd(x, weights, ops)          # one trace
    assert launch_count() == len(layers)
    got2 = fwd(x, weights, ops)         # cached executable: no new trace
    assert launch_count() == len(layers)
    assert jnp.array_equal(got, got2)
    assert float(jnp.max(jnp.abs(
        got - _direct_net(layers, weights, x)))) < 1e-4


def test_network_megakernel_replays_session_plans_when_unbudgeted():
    """``vmem_budget=None`` must replay the session's own programs 1:1
    (no re-planning) and still match."""
    layers, weights = _small_net()
    plans = [plan_decomposition(l, 64 * 1024) for l in layers]
    programs = compile_network(layers, plans)
    x = jax.random.normal(jax.random.key(6), (1, 16, 16, 3))
    fwd = jax.jit(network_forward_fn(programs, mode="megakernel",
                                     vmem_budget=None))
    ops = network_operands(programs, "megakernel", vmem_budget=None)
    got = fwd(x, weights, ops)
    assert float(jnp.max(jnp.abs(
        got - _direct_net(layers, weights, x)))) < 1e-4


def test_session_megakernel_serves_alexnet_prefix():
    """conv1 (pool 3/2) + conv2 (grouped, pooled) through a megakernel
    session: one compile, micro-batch queue intact, donated inputs."""
    stack = ALEXNET_STACK[:2]
    weights = [(_weights(l, key=i, scale=0.05)[0],
                jnp.zeros((l.out_c,))) for i, l in enumerate(stack)]
    x = jax.random.normal(jax.random.key(0), (2, 227, 227, 3))
    ref = _direct_net(stack, weights, x)
    sess = StreamingSession.for_network(stack, weights, max_batch=2,
                                        mode="megakernel")
    assert sess.donate            # donation is the serving default
    y = sess.run_batch(jnp.array(x))      # pass a copy: input is donated
    assert float(jnp.max(jnp.abs(y - ref))) < 1e-3
    assert sess.compile_count == 1
    t0, t1 = sess.submit(x[0]), sess.submit(x[1])
    out0 = sess.result(t0)
    assert float(jnp.max(jnp.abs(out0 - ref[0]))) < 1e-3
    sess.discard(t1)
    assert sess.compile_count == 1        # same batch shape, no retrace


def test_session_donate_flag_plumbed():
    layers, weights = _small_net()
    sess = StreamingSession.for_network(layers, weights,
                                        sram_budget=64 * 1024,
                                        max_batch=2, donate=False)
    assert not sess.donate
    x = jax.random.normal(jax.random.key(7), (2, 16, 16, 3))
    y1 = sess.run_batch(x)
    y2 = sess.run_batch(x)      # donate=False: reuse is always safe
    assert jnp.array_equal(y1, y2)


# ---------------------------------------------------------------------------
# Lowering invariants: rectangular SMEM tables, bounds, masks, chains
# ---------------------------------------------------------------------------

def _assert_kernel_invariants(kp: KernelProgram):
    validate_kernel_program(kp)     # the library's own checks
    tab = kp.operand_table()
    assert tab.shape == (kp.n_chain, kp.n_tiles, KERNEL_OP_COLS)
    assert kp.n_chain * kp.chain_chunk >= kp.wave.n_waves
    l = kp.wave.program.layer
    if l.groups == 1:
        assert kp.c_width == kp.fan_width
    else:
        # natural per-group fan (ISSUE 10): the weight operand never
        # widens to the block-diagonal dense c_width
        assert kp.fan_width == l.in_c // l.groups
        assert kp.w_in_kpad == kp.fan_width
    assert kp.vmem_bytes > 0
    # chain steps cover the padded channel range without overlap
    if kp.wave.program.layer.groups == 1:
        c0s = [int(tab[j][0][OP_C0]) for j in range(kp.n_chain)]
        assert c0s == [j * kp.c_width for j in range(kp.n_chain)]


def test_kernel_lowering_sweep_plan_grid():
    """Deterministic sweep across tile/feat/chain shapes x pool fusion —
    runs even without hypothesis."""
    layers = [
        ConvLayer("s1", 21, 17, 8, 12, 3, stride=2, pad=1),
        ConvLayer("s2", 27, 27, 96, 64, 5, pad=2, groups=2,
                  pool=3, pool_stride=2),
        ConvLayer("s3", 13, 13, 16, 24, 3, pad=1, pool=2),
    ]
    checked = 0
    for layer in layers:
        for th in (1, 2, 3):
            for tw in (1, 2):
                for fs in (1, 2):
                    for cs in (1, 2, 4):
                        plan = evaluate(layer, th, tw, fs, cs)
                        if plan is None:
                            continue
                        wprog = _wave(layer, plan)
                        for fuse in ({False, layer.pool > 1}):
                            for budget in (None, 64 * 1024, 8 * 2 ** 20):
                                _assert_kernel_invariants(
                                    lower_kernel_program(
                                        wprog, act="relu", fuse_pool=fuse,
                                        vmem_budget=budget))
                                checked += 1
    assert checked > 50


@pytest.mark.parametrize("layer", ALEXNET_STACK, ids=lambda l: l.name)
def test_kernel_lowering_alexnet_plans(layer):
    plan = plan_decomposition(layer, 128 * 1024)
    wprog = _wave(layer, plan)
    kp = lower_kernel_program(wprog, vmem_budget=None)
    _assert_kernel_invariants(kp)
    assert kp.n_chain == wprog.n_waves          # 1:1 replay
    kp2 = lower_kernel_program(wprog)           # default budget coarsens
    _assert_kernel_invariants(kp2)
    assert kp2.n_chain <= kp.n_chain


def test_lowering_rejects_poolless_fuse():
    layer = ConvLayer("np", 8, 8, 3, 4, 3, pad=1)
    wprog = _wave(layer, evaluate(layer, 1, 1, 1, 1))
    with pytest.raises(ValueError, match="without a pool"):
        lower_kernel_program(wprog, fuse_pool=True)


def test_validate_rejects_corrupted_table():
    layer = ConvLayer("v", 8, 8, 4, 8, 3, pad=1)
    kp = lower_kernel_program(_wave(layer, evaluate(layer, 2, 1, 1, 1)))
    bad_row = (10_000,) + kp.table[0][0][1:]
    corrupted = dataclasses.replace(
        kp, table=((bad_row,) + kp.table[0][1:],) + kp.table[1:])
    with pytest.raises(ValueError, match="outside the padded"):
        validate_kernel_program(corrupted)


def test_launch_vmem_counts_the_tiled_footprint():
    """A launch's scoped-VMEM limit comes from ``vmem_bytes``, the tiled
    footprint it holds (never below the planner's element count) and
    never above the chip. A compiled launch that needs more VMEM than a
    v5e core has is refused at trace time with the count in the
    message: VGG-16's first layer at 224x224 and batch 8, whose 3- and
    64-channel rows pad to 128 lanes."""
    def serving_programs(net):
        g = network_graph(net)
        return graph_kernel_programs(
            g, compile_graph(g, plan_graph(g, 128 * 1024)), batch=8)
    alexnet, vgg = serving_programs("alexnet"), serving_programs("vgg16")
    for kp in [*alexnet.values(), *vgg.values()]:
        assert kp.vmem_bytes >= kp.plan_bytes
    for name, kp in alexnet.items():
        limit = megakernel_vmem(kp).compiler_params(
            name, interpret=False).vmem_limit_bytes
        assert kp.vmem_bytes <= limit <= VMEM_CAPACITY
    kp = vgg["c1_1"]
    assert kp.vmem_bytes > VMEM_CAPACITY
    l = kp.wave.program.layer
    args = [jax.ShapeDtypeStruct(s, jnp.float32) for s in (
        (8, kp.pad_h, kp.pad_w, kp.in_c_kpad),
        (l.kernel, l.kernel, kp.w_in_kpad, kp.out_c_pad),
        (1, kp.out_c_pad))]
    table = jax.ShapeDtypeStruct((kp.n_chain, kp.n_tiles, KERNEL_OP_COLS),
                                 jnp.int32)
    with pytest.raises(BudgetExceeded, match="c1_1: the kernel holds"):
        jax.eval_shape(lambda x, w, b, t: wave_replay_raw(
            kp, x, w, b, t, interpret=False), *args, table)


def _serving_kernel_programs(g, batch, quantized=False):
    """The per-node programs a session lowers for ``g`` at ``batch``,
    with each node's ``fold`` span attribute."""
    t = Tracer()
    with use_tracer(t):
        kprogs = graph_kernel_programs(
            g, compile_graph(g, plan_graph(g, 128 * 1024)), batch=batch,
            quantized=quantized)
    return kprogs, {s.attrs["node"]: s.attrs["fold"]
                    for s in t.spans("kernel")}


@pytest.mark.parametrize("net,first,stride_geometry", [
    ("resnet18", "stem", (2, 4, 1, 114, 114, 12, 12)),
    ("alexnet", "conv1", (4, 3, 1, 57, 57, 48, 48)),
    ("convnext_t", "stem", (4, 1, 1, 56, 56, 48, 48)),
])
def test_tap_fold_engages_only_on_the_resnet_stem(net, first,
                                                  stride_geometry):
    """Of the three benchmark graphs at the serving batch 8, the fp32
    launch folds the taps of ResNet-18's stem alone (16 taps of 12
    channels after the stride fold: 192). AlexNet's conv1 (9 taps of
    48: 432 channels) and ConvNeXt-T's stem (one tap after its 4x4/4
    fold) keep the stride fold alone, at the geometry it gave before
    the tap fold. The int8 launch never folds taps: the stem's int8
    window stays the stride-folded one. The forward builder counts the
    folded nodes in ``graph.taps_folded``."""
    g = {"resnet18": lambda: resnet18_graph(224, 64),
         "alexnet": alexnet_graph, "convnext_t": convnext_t_graph}[net]()
    progs = compile_graph(g, plan_graph(g, 128 * 1024))
    with obs_metrics.use_registry(obs_metrics.MetricsRegistry()) as reg:
        graph_forward_fn(g, progs, mode="megakernel", batch=8)
        assert reg.counter("graph.taps_folded").value == \
            (net == "resnet18")
    kprogs, folds = _serving_kernel_programs(g, 8)
    assert [n for n, f in folds.items() if f == "taps"] == \
        (["stem"] if net == "resnet18" else [])
    kp = kprogs[first]
    assert folds[first] == ("taps" if net == "resnet18" else "stride")
    assert megakernel_geometry(kp) == stride_geometry
    _, k, _, _, _, _, fan = stride_geometry
    assert megakernel_vmem(kp).blocks[1] == (
        ((1, 1, k * k * fan, 64) if net == "resnet18"
         else (k, k, fan, kp.out_c_pad)), jnp.float32)
    # the planner's program is the one the stride fold alone gave
    assert (kp.n_tiles, kp.n_chain, kp.batch_block, kp.c_width) == \
        {"resnet18": (1, 1, 2, 3), "alexnet": (1, 1, 4, 3),
         "convnext_t": (1, 1, 4, 3)}[net]
    q_kprogs, q_folds = _serving_kernel_programs(g, 8, quantized=True)
    assert "taps" not in q_folds.values()
    assert q_folds[first] == "stride"
    window = megakernel_vmem(q_kprogs[first], quantized=True).blocks[0]
    bb = q_kprogs[first].batch_block
    assert window == ((bb,) + stride_geometry[3:6], jnp.int8)


@pytest.mark.parametrize("batch,bb", [(1, 1), (8, 2)])
def test_folded_stem_vmem_counts_256_lanes(batch, bb):
    """The folded stem's row operand, its 16 taps of 12 channels side by
    side in lanes (111, 192), is counted at two lane tiles (256 lanes)
    in the launch's VMEM, beside the stride-folded window and the
    (192, 64) weights, and at 192 channels in the planner's
    ``plan_bytes``; the batch block the clamp picks gives a launch
    under a v5e core's VMEM, with a scoped limit that covers it (CPU
    count). The node alone, lowered as the graph forward lowers it."""
    layer = resnet18_graph(224, 64).node("stem").layer
    kp = _graph_kernel_program(
        compile_layer(layer, plan_decomposition(layer, 128 * 1024)),
        "relu", False, DEFAULT_VMEM_BUDGET, batch)
    assert kp.batch_block == bb and megakernel_fold(kp) == "taps"
    vmem = megakernel_vmem(kp)
    assert vmem.blocks[:2] == (((bb, 114, 114, 12), jnp.float32),
                               ((1, 1, 192, 64), jnp.float32))
    row = ((111, 192), jnp.float32)
    assert vmem.temps[-1] == row
    assert LaunchVmem((), (), (row,)).bytes == 112 * 256 * 4
    unfolded = megakernel_vmem(kp, quantized=True)
    assert len(vmem.temps) == len(unfolded.temps) + 1
    # the planner counts the row operand at 192 channels, batch-shared
    assert kp.plan_bytes == 4 * (bb * (111 * 111 * 64 + 227 * 227 * 3)
                                 + 7 * 7 * 3 * 64 + 111 * 192)
    assert kp.vmem_bytes == vmem.bytes < VMEM_CAPACITY
    limit = vmem.compiler_params("stem", interpret=False).vmem_limit_bytes
    assert kp.vmem_bytes <= limit <= VMEM_CAPACITY


def test_plan_for_vmem_prefers_fewest_steps():
    layer = ALEXNET_STACK[2]        # conv3: 128 KB plan needs 256 waves
    plan = plan_for_vmem(layer, 8 * 2 ** 20, False)
    kp = lower_kernel_program(_wave(layer, plan), act="relu",
                              vmem_budget=8 * 2 ** 20)
    assert kp.n_tiles * kp.n_chain < 256
    assert kp.plan_bytes <= 8 * 2 ** 20
    # a tiny budget forces real decomposition again
    tight = plan_for_vmem(layer, 512 * 1024, False)
    kp_tight = lower_kernel_program(_wave(layer, tight), act="relu",
                                    vmem_budget=None)
    assert kp_tight.n_tiles * kp_tight.n_chain > 1


# ---------------------------------------------------------------------------
# Property-based lowering checks (skipped cleanly without hypothesis)
# ---------------------------------------------------------------------------

if hypothesis is not None:
    @hypothesis.given(
        st.integers(6, 24), st.integers(6, 24),
        st.integers(1, 8), st.integers(1, 12),
        st.sampled_from([1, 3, 5]), st.sampled_from([1, 2]),
        st.integers(0, 2),
        st.sampled_from([16, 32, 64, 128]),          # SRAM KiB
        st.sampled_from([None, 64 * 1024, 2 ** 23]),  # kernel VMEM
        st.booleans(),
    )
    @hypothesis.settings(max_examples=40, deadline=None)
    def test_kernel_lowering_property_random(h, w, cin, cout, k, stride,
                                             pad, sram_kib, vmem, relu):
        """Randomized geometry x randomized *planner budget* x kernel
        budget: whatever plan_decomposition picks must lower to a valid
        rectangular KernelProgram."""
        layer = ConvLayer("t", h, w, cin, cout, k, stride=stride, pad=pad)
        if layer.out_h <= 0 or layer.out_w <= 0:
            return
        try:
            plan = plan_decomposition(layer, sram_kib * 1024)
        except ValueError:
            return                      # no feasible plan at this budget
        wprog = _wave(layer, plan)
        _assert_kernel_invariants(lower_kernel_program(
            wprog, act="relu" if relu else None, vmem_budget=vmem))

    @hypothesis.given(
        st.integers(8, 20), st.integers(8, 20),
        st.integers(2, 6), st.integers(2, 8),
        st.sampled_from([2, 3]), st.integers(1, 2), st.integers(1, 2),
        st.integers(1, 4),
    )
    @hypothesis.settings(max_examples=12, deadline=None)
    def test_megakernel_matches_reference_random(h, w, cin, cout, k,
                                                 th, tw, cs):
        """Randomized small geometries: megakernel output vs the XLA
        oracle (end-to-end through padding, tables, and epilogue)."""
        layer = ConvLayer("r", h, w, cin, cout, k, pad=1)
        plan = evaluate(layer, th, tw, 1, cs)
        if plan is None:
            return
        x = jax.random.normal(jax.random.key(0), (1, h, w, cin))
        wts, b = _weights(layer)
        got = wave_replay_layer(lower_kernel_program(_wave(layer, plan)),
                                x, wts, b)
        ref = wave_replay_ref(layer, x, wts, b)
        assert float(jnp.max(jnp.abs(got - ref))) < 1e-4


# ---------------------------------------------------------------------------
# Residual epilogue (ISSUE 5): the accumulation-SRAM add in the kernel
# ---------------------------------------------------------------------------

def test_megakernel_residual_epilogue_matches_ref():
    """residual=True lowers one extra operand, added after bias and
    before ReLU — compared against the XLA oracle with the same order."""
    layer = ConvLayer("res", 12, 12, 8, 8, 3, pad=1)
    plan = evaluate(layer, 2, 2, 1, 2)
    kp = lower_kernel_program(partition_waves(compile_layer(layer, plan)),
                              act="relu", residual=True, vmem_budget=None)
    x = jax.random.normal(jax.random.key(0), (2, 12, 12, 8))
    w = jax.random.normal(jax.random.key(1), (3, 3, 8, 8)) * 0.2
    b = jax.random.normal(jax.random.key(2), (8,)) * 0.1
    r = jax.random.normal(jax.random.key(3), (2, 12, 12, 8))
    got = wave_replay_layer(kp, x, w, b, residual=r)
    ref = wave_replay_ref(layer, x, w, b, relu=True, residual=r)
    assert float(jnp.max(jnp.abs(got - ref))) < 1e-4


def test_megakernel_residual_validation():
    layer = ConvLayer("resv", 8, 8, 4, 4, 3, pad=1, pool=2)
    plan = evaluate(layer, 1, 1, 1, 1)
    wprog = partition_waves(compile_layer(layer, plan))
    with pytest.raises(ValueError, match="residual add cannot fuse"):
        lower_kernel_program(wprog, act="relu", fuse_pool=True,
                             residual=True)
    nopool = ConvLayer("resv2", 8, 8, 4, 4, 3, pad=1)
    kp = lower_kernel_program(
        partition_waves(compile_layer(nopool, evaluate(nopool, 1, 1, 1, 1))),
        residual=True, vmem_budget=None)
    x = jnp.zeros((1, 8, 8, 4))
    w = jnp.zeros((3, 3, 4, 4))
    with pytest.raises(ValueError, match="needs the residual"):
        wave_replay_layer(kp, x, w)
    kp_plain = lower_kernel_program(
        partition_waves(compile_layer(nopool, evaluate(nopool, 1, 1, 1, 1))),
        residual=False, vmem_budget=None)
    with pytest.raises(ValueError, match="without residual"):
        from repro.kernels.wave_replay.kernel import wave_replay_raw
        from repro.kernels.wave_replay.ops import pad_operands
        xp, wp, bias = pad_operands(kp_plain, x, w, None)
        wave_replay_raw(kp_plain, xp, wp, bias,
                        jnp.asarray(kp_plain.operand_table()),
                        residual=jnp.zeros((1, 8, 8, 4)))
