"""Plain reference for a chain of conv layers (AlexNet's conv stack).

The configuration lists the layers in order under ``layers``; each conv
is followed by its bias, a ReLU and, where ``pool > 1``, a VALID
max-pool. Spatial sizes and input channels follow from ``in_shape``.
"""
from chipbench import plain


def conv_nodes(cfg: dict) -> list:
    """Every conv node with its full shape, in execution order."""
    h, w, c = cfg["in_shape"]
    nodes = []
    for layer in cfg["layers"]:
        n = dict(layer, in_h=h, in_w=w, in_c=c, relu=True, residual=False)
        n.setdefault("stride", 1)
        n.setdefault("pad", 0)
        n.setdefault("groups", 1)
        n.setdefault("pool", 1)
        n.setdefault("pool_stride", n["pool"])
        nodes.append(n)
        h = plain.pooled_hw(plain.out_hw(h, n["kernel"], n["stride"],
                                         n["pad"]), n["pool"],
                            n["pool_stride"])
        w = plain.pooled_hw(plain.out_hw(w, n["kernel"], n["stride"],
                                         n["pad"]), n["pool"],
                            n["pool_stride"])
        c = n["out_c"]
    return nodes


def forward(cfg: dict, params, x, conv_fn=plain.conv):
    for n in conv_nodes(cfg):
        x = plain.conv_node(x, params, n, conv_fn)
    return x
