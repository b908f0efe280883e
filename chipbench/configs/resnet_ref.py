"""Plain reference for ResNet-18 (He et al., arXiv:1512.03385, Table 1,
"18-layer") without its global pool and fc1000.

Stem: 7x7/2 conv, bias, ReLU, 3x3/2 max-pool. Then four stages of two
basic blocks: conv3x3 + bias + ReLU, conv3x3 + bias, plus the shortcut
(identity, or a 1x1 strided projection conv with bias where the stride
or width changes), then ReLU. No batch norm: random weights stand in
for trained ones and their folded scales. The configuration states the
stem pool's padding (``stem.pool_pad``).
"""
import jax.numpy as jnp

from chipbench import plain


def _block_nodes(tag, h, cin, cout, stride):
    ho = plain.out_hw(h, 3, stride, 1)
    nodes = [
        dict(name=f"{tag}_c1", in_h=h, in_w=h, in_c=cin, out_c=cout,
             kernel=3, stride=stride, pad=1, groups=1, pool=1,
             pool_stride=1, relu=True, residual=False),
        dict(name=f"{tag}_c2", in_h=ho, in_w=ho, in_c=cout, out_c=cout,
             kernel=3, stride=1, pad=1, groups=1, pool=1, pool_stride=1,
             relu=False, residual=True),
    ]
    if stride != 1 or cin != cout:
        nodes.append(dict(name=f"{tag}_proj", in_h=h, in_w=h, in_c=cin,
                          out_c=cout, kernel=1, stride=stride, pad=0,
                          groups=1, pool=1, pool_stride=1, relu=False,
                          residual=False))
    return nodes, ho


def _plan(cfg):
    """[(block tag, its conv nodes)] after the stem node."""
    s = cfg["stem"]
    hw = cfg["in_shape"][0]
    stem = dict(name="stem", in_h=hw, in_w=hw, in_c=cfg["in_shape"][2],
                out_c=s["out_c"], kernel=s["kernel"], stride=s["stride"],
                pad=s["pad"], groups=1, pool=s["pool"],
                pool_stride=s["pool_stride"], pool_pad=s["pool_pad"],
                relu=True, residual=False)
    h = plain.out_hw(hw, s["kernel"], s["stride"], s["pad"])
    h = plain.out_hw(h, s["pool"], s["pool_stride"], s["pool_pad"])
    blocks, c = [], s["out_c"]
    for si, (cout, stride) in enumerate(cfg["stages"], start=1):
        for bi in range(1, cfg["blocks_per_stage"] + 1):
            nodes, h = _block_nodes(f"s{si}b{bi}", h, c,
                                    cout, stride if bi == 1 else 1)
            blocks.append(nodes)
            c = cout
    return stem, blocks


def conv_nodes(cfg: dict) -> list:
    stem, blocks = _plan(cfg)
    return [stem] + [n for b in blocks for n in b]


def forward(cfg: dict, params, x, conv_fn=plain.conv):
    stem, blocks = _plan(cfg)
    pp = cfg["stem"]["pool_pad"]
    x = plain.conv_node(x, params, dict(stem, pool=1), conv_fn)
    x = jnp.pad(x, ((0, 0), (pp, pp), (pp, pp), (0, 0)),
                constant_values=-jnp.inf)
    x = plain.maxpool(x, stem["pool"], stem["pool_stride"])
    for nodes in blocks:
        y = plain.conv_node(x, params, nodes[0], conv_fn)
        y = plain.conv_node(y, params, nodes[1], conv_fn, relu=False)
        short = (plain.conv_node(x, params, nodes[2], conv_fn, relu=False)
                 if len(nodes) == 3 else x)
        x = jnp.maximum(y + short, 0)
    return x
