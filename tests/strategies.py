"""Shared hypothesis strategies for graph/executor property tests.

``residual_graphs`` generates random-but-valid residual topologies
(stem + 1-4 basic blocks with random width/stride/shortcut/ReLU
choices) — the IR-level strategy test_graph.py's property cases run
over. ``streaming_graphs`` generates smaller graphs sized for the
cross-executor differential harness (test_differential.py): every
example compiles through all five executors, so dimensions stay tiny
and the generator mixes in the features the kernels special-case
(grouped convs, fused pools, projection shortcuts, no-ReLU tails).

Import this module only under a hypothesis guard — it imports
hypothesis unconditionally (dev-only dependency)."""
import hypothesis.strategies as st

from repro.core.decomposition import ConvLayer
from repro.core.graph import INPUT, GraphNode, NetworkGraph


def conv_node(name, h, c_in, c_out, inputs, stride=1, relu=True, pool=1,
              kernel=3, pad=1, groups=1):
    return GraphNode(name, "conv", inputs,
                     layer=ConvLayer(name, h, h, c_in, c_out, kernel,
                                     stride=stride, pad=pad, pool=pool,
                                     groups=groups),
                     act="relu" if relu else None)


# test_graph.py's original helper name, re-exported for its callers
_conv = conv_node


@st.composite
def residual_graphs(draw):
    """Random-but-valid residual networks: a stem then 1-4 blocks,
    each with random width/stride/shortcut/ReLU choices."""
    h = draw(st.sampled_from([8, 12, 16]))
    c = draw(st.integers(2, 6))
    width = draw(st.integers(2, 6))
    nodes = [conv_node("stem", h, c, width, (INPUT,))]
    prev, c_in = "stem", width
    for bi in range(draw(st.integers(1, 4))):
        stride = draw(st.sampled_from([1, 2])) if h >= 4 else 1
        c_out = c_in if stride == 1 else 2 * c_in
        ho = (h + 2 - 3) // stride + 1
        relu_c2 = draw(st.booleans())
        nodes.append(conv_node(f"b{bi}_c1", h, c_in, c_out, (prev,),
                               stride=stride))
        nodes.append(conv_node(f"b{bi}_c2", ho, c_out, c_out,
                               (f"b{bi}_c1",), relu=relu_c2))
        if stride != 1 or c_in != c_out:
            nodes.append(GraphNode(
                f"b{bi}_proj", "conv", (prev,),
                layer=ConvLayer(f"b{bi}_proj", h, h, c_in, c_out, 1,
                                stride=stride), act=None))
            short = f"b{bi}_proj"
        else:
            short = prev
        nodes.append(GraphNode(f"b{bi}_add", "add",
                               (f"b{bi}_c2", short),
                               act="relu" if draw(st.booleans()) else None))
        prev, c_in, h = f"b{bi}_add", c_out, ho
    return NetworkGraph("rand", (nodes[0].layer.in_h,
                                 nodes[0].layer.in_w, c),
                        tuple(nodes), prev)


@st.composite
def streaming_graphs(draw, allow_groups=True):
    """Random graphs sized for the cross-executor differential harness.

    Tiny spatial dims (8-16 px) and channel counts (2-8), 2-4 conv
    nodes, mixing linear stretches, one optional residual block, fused
    max-pools, strides, grouped and depthwise convs — ``groups`` drawn
    from {2, 4, Cin} with ragged per-group out-channel multipliers, so
    the per-group gemm AND the depthwise MAC kernel paths (ISSUE 10)
    both get fuzzed (``allow_groups=False`` for the int8 harness, whose
    grouped kernel requires unpadded out channels) — and a random
    no-ReLU tail. Shapes follow the same arithmetic the graph validator
    enforces, so every draw is a valid NetworkGraph.
    """
    h = draw(st.sampled_from([8, 12, 16]))
    c = draw(st.integers(2, 4))
    width = draw(st.sampled_from([2, 4, 6, 8]))
    pool0 = draw(st.sampled_from([1, 1, 2]))
    nodes = [conv_node("stem", h, c, width, (INPUT,), pool=pool0)]
    h = h // pool0
    prev, c_in = "stem", width

    if draw(st.booleans()) and h >= 4:
        # one residual block (optionally strided, with projection)
        stride = draw(st.sampled_from([1, 2]))
        c_out = c_in if stride == 1 else 2 * c_in
        ho = (h + 2 - 3) // stride + 1
        nodes.append(conv_node("r_c1", h, c_in, c_out, (prev,),
                               stride=stride))
        nodes.append(conv_node("r_c2", ho, c_out, c_out, ("r_c1",),
                               relu=False))
        if stride != 1 or c_in != c_out:
            nodes.append(GraphNode(
                "r_proj", "conv", (prev,),
                layer=ConvLayer("r_proj", h, h, c_in, c_out, 1,
                                stride=stride), act=None))
            short = "r_proj"
        else:
            short = prev
        nodes.append(GraphNode("r_add", "add", ("r_c2", short),
                               act="relu" if draw(st.booleans()) else None))
        prev, c_in, h = "r_add", c_out, ho
    else:
        # a linear stretch, optionally grouped / depthwise / pooled /
        # strided: groups from {2, 4, Cin} (Cin = depthwise), per-group
        # out channels a ragged multiplier in {1, 2, 3}
        for li in range(draw(st.integers(1, 2))):
            groups = 1
            if allow_groups and draw(st.booleans()):
                opts = [g for g in (2, 4, c_in)
                        if 1 < g <= c_in and c_in % g == 0]
                if opts:
                    groups = draw(st.sampled_from(sorted(set(opts))))
            if groups > 1:
                c_out = groups * draw(st.sampled_from([1, 2, 3]))
            else:
                c_out = draw(st.sampled_from([c_in, 2 * c_in]))
            pool = 2 if h >= 8 and draw(st.booleans()) else 1
            nodes.append(conv_node(f"l{li}", h, c_in, c_out, (prev,),
                                   pool=pool, groups=groups))
            prev, c_in, h = f"l{li}", c_out, h // pool

    # random no-ReLU 1x1 tail (exercises the epilogue-relu=False path)
    if draw(st.booleans()):
        nodes.append(conv_node("tail", h, c_in, c_in, (prev,),
                               relu=False, kernel=1, pad=0))
        prev = "tail"
    return NetworkGraph("rand_stream",
                        (nodes[0].layer.in_h, nodes[0].layer.in_w, c),
                        tuple(nodes), prev)
