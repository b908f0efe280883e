"""Whole-graph persistent megakernel (ISSUE 6): chain partitioning,
the VMEM activation arena, the flat cross-layer SMEM program, launch
counting, and the single-wave coarsening fix for conv1-shaped layers.
DESIGN.md §2.5 maps the machinery onto the paper's layer-sequencing
controller + accumulation SRAM banks."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.decomposition import ALEXNET_STACK, ConvLayer
from repro.core.graph import (INPUT, GraphNode, NetworkGraph,
                              fusible_chains)
from repro.core.model_zoo import (alexnet_graph, resnet18_graph,
                                  vgg16_graph)
from repro.core.schedule import (DEFAULT_VMEM_BUDGET, GOP_NODE, GOP_WOFF,
                                 ArenaValue, chain_plan_bytes, plan_arena,
                                 validate_graph_kernel)
from repro.core.streaming import (_coarsen_single_wave, compile_graph,
                                  graph_chain_programs, graph_forward_fn,
                                  graph_operands, plan_for_vmem,
                                  plan_graph, run_graph_streamed,
                                  run_layer_streamed)
from repro.kernels import wave_replay as wr
from repro.kernels import wave_replay_q as wrq
from repro.models.cnn import init_graph_weights
from repro.quant.calibrate import calibrate_graph

try:
    import hypothesis
    import hypothesis.strategies as st
except ImportError:  # dev-only dependency (requirements.txt)
    hypothesis = None

BUDGET = 64 * 1024


def _conv(name, h, c_in, c_out, inputs, stride=1, relu=True, pool=1,
          kernel=3, pad=1):
    return GraphNode(name, "conv", inputs,
                     layer=ConvLayer(name, h, h, c_in, c_out, kernel,
                                     stride=stride, pad=pad, pool=pool),
                     act="relu" if relu else None)


def _identity_block():
    nodes = (
        _conv("stem", 8, 3, 8, (INPUT,)),
        _conv("c1", 8, 8, 8, ("stem",)),
        _conv("c2", 8, 8, 8, ("c1",), relu=False),
        GraphNode("add", "add", ("c2", "stem"), act="relu"),
    )
    return NetworkGraph("identity_block", (8, 8, 3), nodes, "add")


def _count_launches(g, mode, vmem_budget=DEFAULT_VMEM_BUDGET):
    """Trace-time launch count of one whole-graph forward."""
    plans = plan_graph(g, BUDGET)
    progs = compile_graph(g, plans)
    ws = init_graph_weights(g, jax.random.key(0))
    x = jnp.zeros((1,) + g.in_shape)
    fn = graph_forward_fn(g, progs, mode=mode, vmem_budget=vmem_budget)
    ops = graph_operands(g, progs, mode=mode, vmem_budget=vmem_budget)
    wr.reset_launch_count()
    wrq.reset_launch_count()
    jax.eval_shape(fn, x, ws, ops)
    return wr.launch_count() + wrq.launch_count()


# ---------------------------------------------------------------------------
# Arena allocator properties
# ---------------------------------------------------------------------------

def test_plan_arena_reuses_only_dead_slots():
    vals = (ArenaValue("a", -1, 0, (4, 4, 8), (1, 1)),
            ArenaValue("b", 0, 1, (4, 4, 8), (1, 1)),
            ArenaValue("c", 1, 2, (4, 4, 8), (1, 1)),   # a died at 0 < 1
            ArenaValue("d", 2, 3, (4, 4, 8), (1, 1)))   # b died at 1 < 2
    plan = plan_arena(vals)
    assert plan.slot_of("c") == plan.slot_of("a")
    assert plan.slot_of("d") == plan.slot_of("b")
    assert len(plan.slot_shapes) == 2


def test_plan_arena_death_at_birth_keeps_slot():
    """A value dying AT node i must not share a slot with the value
    node i produces — the producer zeroes its output slot while still
    reading its inputs."""
    vals = (ArenaValue("a", -1, 0, (4, 4, 8), (1, 1)),
            ArenaValue("b", 0, 1, (4, 4, 8), (1, 1)))
    plan = plan_arena(vals)
    assert plan.slot_of("a") != plan.slot_of("b")


def test_plan_arena_slot_shapes_are_elementwise_max():
    vals = (ArenaValue("a", -1, 0, (8, 4, 2), (1, 1)),
            ArenaValue("b", 1, 2, (2, 6, 4), (0, 0)))
    plan = plan_arena(vals)
    assert plan.slot_shapes == ((8, 6, 4),)
    assert plan.slot_bytes_f32 == 4 * 8 * 6 * 4


def test_plan_arena_rejects_bad_orders():
    with pytest.raises(ValueError):
        plan_arena((ArenaValue("a", 2, 3, (1, 1, 1), (0, 0)),
                    ArenaValue("b", 0, 1, (1, 1, 1), (0, 0))))
    with pytest.raises(ValueError):
        plan_arena((ArenaValue("a", 2, 1, (1, 1, 1), (0, 0)),))


if hypothesis is not None:
    @st.composite
    def _arena_values(draw):
        n = draw(st.integers(1, 12))
        vals, birth = [], -1
        for i in range(n):
            birth = draw(st.integers(birth, birth + 2))
            death = draw(st.integers(birth, birth + 4))
            shape = tuple(draw(st.integers(1, 16)) for _ in range(3))
            vals.append(ArenaValue(f"v{i}", birth, death, shape, (0, 0)))
        return tuple(vals)

    @hypothesis.given(_arena_values())
    @hypothesis.settings(max_examples=60, deadline=None)
    def test_plan_arena_never_aliases_live_values(vals):
        plan = plan_arena(vals)
        by_slot = {}
        for v, s in zip(plan.values, plan.slots):
            for prev in by_slot.get(s, ()):
                # same slot: earlier occupant must be strictly dead
                assert prev.death < v.birth, (prev, v)
            by_slot.setdefault(s, []).append(v)
            # the slot fits every member
            sh = plan.slot_shapes[s]
            assert all(a <= b for a, b in zip(v.shape, sh))
else:
    def test_arena_property_cases_need_hypothesis():
        pytest.importorskip("hypothesis")  # skips, visibly


# ---------------------------------------------------------------------------
# Lowering invariants + corrupted-table rejection
# ---------------------------------------------------------------------------

def _lowered_chain(g=None, quantized=False, budget=DEFAULT_VMEM_BUDGET):
    g = g or _identity_block()
    progs = compile_graph(g, plan_graph(g, BUDGET))
    chains, kprogs, gkps = graph_chain_programs(g, progs, budget,
                                                quantized=quantized)
    return g, chains, gkps


def test_lowered_chain_passes_validation():
    g, chains, gkps = _lowered_chain()
    assert [c.convs for c in chains] == [("stem", "c1", "c2")]
    gkp = gkps["stem"]
    validate_graph_kernel(gkp)          # every invariant group
    # node rows are contiguous and cover every per-layer step
    tbl = gkp.operand_table()
    assert tbl.shape == (gkp.total_steps, 14)
    assert list(tbl[:, GOP_NODE]) == sorted(tbl[:, GOP_NODE])


def test_validation_catches_corrupted_graph_table():
    g, chains, gkps = _lowered_chain()
    gkp = gkps["stem"]
    bad = np.array(gkp.operand_table())
    bad[-1, GOP_WOFF] = gkp.w_total     # window runs off the flat buffer
    with pytest.raises(ValueError):
        validate_graph_kernel(dataclasses.replace(
            gkp, table=tuple(map(tuple, bad))))


def test_chain_vmem_bytes_is_precision_independent():
    """fp32 and int8 partition identically: the budget model charges
    4 B/elem for both."""
    g = _identity_block()
    progs = compile_graph(g, plan_graph(g, BUDGET))
    kprogs = dict(graph_chain_programs(g, progs, DEFAULT_VMEM_BUDGET)[1])
    f32 = fusible_chains(g, kprogs, quantized=False)
    i8 = fusible_chains(g, kprogs, quantized=True)
    assert [c.convs for c in f32] == [c.convs for c in i8]


# ---------------------------------------------------------------------------
# Residual arena slots round-trip bit-exactly
# ---------------------------------------------------------------------------

def test_residual_slot_roundtrip_bit_exact_fp32():
    """The shortcut activation parked in its arena slot across two conv
    nodes re-emerges bit-identical: fused chain == per-layer megakernel
    exactly (same accumulation order, same epilogue adds)."""
    g = _identity_block()
    plans = plan_graph(g, BUDGET)
    ws = init_graph_weights(g, jax.random.key(0))
    x = jax.random.normal(jax.random.key(1), (2,) + g.in_shape)
    a = run_graph_streamed(g, plans, x, ws, mode="megakernel")
    b = run_graph_streamed(g, plans, x, ws, mode="graphkernel")
    assert jnp.array_equal(a, b)


def test_residual_slot_roundtrip_bit_exact_int8():
    g = _identity_block()
    plans = plan_graph(g, BUDGET)
    ws = init_graph_weights(g, jax.random.key(0))
    x = jax.random.normal(jax.random.key(1), (2,) + g.in_shape)
    qg = calibrate_graph(g, ws, x)
    a = run_graph_streamed(g, plans, x, None, mode="megakernel",
                           precision="int8", qgraph=qg)
    b = run_graph_streamed(g, plans, x, None, mode="graphkernel",
                           precision="int8", qgraph=qg)
    assert jnp.array_equal(a, b)


# ---------------------------------------------------------------------------
# Launch-count regression: megakernel = 1/conv node, graphkernel =
# 1/fused chain — counted at trace time, network by network
# ---------------------------------------------------------------------------

NETS = (("alexnet", lambda: alexnet_graph()),
        ("vgg16", lambda: vgg16_graph(in_hw=32, width=8)),
        ("resnet18", lambda: resnet18_graph(in_hw=32, width=8)))


@pytest.mark.parametrize("name,mk", NETS, ids=[n for n, _ in NETS])
def test_launch_counts_megakernel_vs_graphkernel(name, mk):
    g = mk()
    progs = compile_graph(g, plan_graph(g, BUDGET))
    chains = graph_chain_programs(g, progs, DEFAULT_VMEM_BUDGET)[0]
    n_conv = len(g.conv_nodes())
    assert _count_launches(g, "megakernel") == n_conv
    n_gk = _count_launches(g, "graphkernel")
    assert n_gk == len(chains)
    assert n_gk < n_conv                 # fusion must actually fuse


def test_launch_counts_int8_graphkernel():
    g = resnet18_graph(in_hw=32, width=8)
    plans = plan_graph(g, BUDGET)
    progs = compile_graph(g, plans)
    chains = graph_chain_programs(g, progs, DEFAULT_VMEM_BUDGET,
                                  quantized=True)[0]
    ws = init_graph_weights(g, jax.random.key(0))
    x = jnp.zeros((1,) + g.in_shape)
    qg = calibrate_graph(g, ws, jax.random.normal(jax.random.key(7),
                                                  (2,) + g.in_shape))
    fn = graph_forward_fn(g, progs, mode="graphkernel",
                          precision="int8", qgraph=qg)
    ops = graph_operands(g, progs, mode="graphkernel", precision="int8")
    wr.reset_launch_count()
    wrq.reset_launch_count()
    jax.eval_shape(fn, x, qg.device_weights(), ops)
    assert wrq.launch_count() == len(chains)
    assert wr.launch_count() == 0


# ---------------------------------------------------------------------------
# Whole-AlexNet as ONE pallas_call (the ISSUE 6 acceptance shape)
# ---------------------------------------------------------------------------

ALEXNET_WHOLE_BUDGET = 16 * 2 ** 20     # fits the 12.4 MB arena


def test_whole_alexnet_is_one_kernel_launch():
    g = alexnet_graph()
    progs = compile_graph(g, plan_graph(g, BUDGET))
    chains, _, gkps = graph_chain_programs(g, progs,
                                           ALEXNET_WHOLE_BUDGET)
    assert [len(c.convs) for c in chains] == [5]
    gkp = gkps[chains[0].convs[0]]
    validate_graph_kernel(gkp)
    assert gkp.plan_bytes <= ALEXNET_WHOLE_BUDGET
    assert _count_launches(g, "graphkernel",
                           vmem_budget=ALEXNET_WHOLE_BUDGET) == 1


def test_whole_alexnet_one_kernel_parity():
    """All five AlexNet conv layers through ONE pallas_call: fp32 within
    tolerance of the wave executor, int8 bit-exact against the
    per-layer quantized megakernel."""
    g = alexnet_graph()
    plans = plan_graph(g, BUDGET)
    progs = compile_graph(g, plans)
    ws = init_graph_weights(g, jax.random.key(0))
    x = jax.random.normal(jax.random.key(1), (1,) + g.in_shape)
    ref = run_graph_streamed(g, plans, x, ws, mode="wave")
    fn = jax.jit(graph_forward_fn(g, progs, mode="graphkernel",
                                  vmem_budget=ALEXNET_WHOLE_BUDGET))
    ops = graph_operands(g, progs, mode="graphkernel",
                         vmem_budget=ALEXNET_WHOLE_BUDGET)
    got = fn(x, ws, ops)
    assert float(jnp.max(jnp.abs(got - ref))) <= 1e-3

    qg = calibrate_graph(g, ws, jax.random.normal(jax.random.key(7),
                                                  (2,) + g.in_shape))
    mk = run_graph_streamed(g, plans, x, None, mode="megakernel",
                            precision="int8", qgraph=qg)
    fn_q = jax.jit(graph_forward_fn(g, progs, mode="graphkernel",
                                    precision="int8", qgraph=qg,
                                    vmem_budget=ALEXNET_WHOLE_BUDGET))
    ops_q = graph_operands(g, progs, mode="graphkernel",
                           precision="int8",
                           vmem_budget=ALEXNET_WHOLE_BUDGET)
    got_q = fn_q(x, qg.device_weights(), ops_q)
    assert jnp.array_equal(got_q, mk)


# ---------------------------------------------------------------------------
# Single-wave coarsening (the conv1 megakernel regression fix)
# ---------------------------------------------------------------------------

def test_conv1_single_wave_plan_coarsens_to_one_step():
    """AlexNet conv1's 128 KB plan is 7 tiny tiles x 1 wave — chain
    coarsening can't help (no chain), so the megakernel path must
    re-plan at its VMEM budget: one tile, one wave, one grid step."""
    from repro.core.decomposition import plan_decomposition
    from repro.core.schedule import compile_layer, partition_waves
    conv1 = ALEXNET_STACK[0]
    wprog = partition_waves(
        compile_layer(conv1, plan_decomposition(conv1, 128 * 1024)))
    assert (wprog.n_tiles, wprog.n_waves) == (7, 1)
    plan = plan_for_vmem(conv1, DEFAULT_VMEM_BUDGET, True,
                         residual=False)
    assert (plan.tiles_h, plan.tiles_w, plan.feat_splits,
            plan.in_splits) == (1, 1, 1, 1)
    coarse = _coarsen_single_wave(wprog, True, DEFAULT_VMEM_BUDGET)
    assert (coarse.n_tiles, coarse.n_waves) == (1, 1)
    # no budget, multi-wave, or grouped schedules: untouched
    assert _coarsen_single_wave(wprog, True, None) is wprog


def test_conv1_megakernel_coarsened_matches_interpreter():
    from repro.core.decomposition import plan_decomposition
    from repro.core.streaming import run_layer_interpreted
    conv1 = ALEXNET_STACK[0]
    plan = plan_decomposition(conv1, 128 * 1024)
    key = jax.random.key(3)
    x = jax.random.normal(key, (1, conv1.in_h, conv1.in_w, conv1.in_c))
    w = jax.random.normal(jax.random.key(4),
                          (conv1.kernel, conv1.kernel, conv1.in_c,
                           conv1.out_c)) * 0.05
    ref = run_layer_interpreted(conv1, plan, x, w, None)
    got = run_layer_streamed(conv1, plan, x, w, None, mode="megakernel")
    assert got.shape == ref.shape
    assert float(jnp.max(jnp.abs(got - ref))) <= 1e-3
