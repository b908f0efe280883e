"""Plain reference for ConvNeXt-T (Liu et al., "A ConvNet for the
2020s", arXiv:2201.03545) without its head (global pool, LayerNorm,
fc1000).

Stem: a 4x4/4 conv with bias, then a LayerNorm over channels. Four
stages of ``depths`` blocks at ``dims`` channels; stages 2-4 are led by
a LayerNorm and a 2x2/2 conv. A block: a 7x7 depthwise conv (pad 3),
LayerNorm, a 1x1 conv to ``expansion`` x C, exact GELU, a 1x1 conv back
to C, the layer scale, and the residual add, with no activation after
it. Every LayerNorm takes ``norm_eps``.

``params`` holds each conv's ``(w, b)``. A norm's ``(gamma, beta)``
under its name and a block's layer scale under ``"<block>_scale"`` are
applied where ``params`` has them, and are the identity where it has
not (``plain.init_params`` makes conv parameters only).
"""
import jax
import jax.numpy as jnp

from chipbench import plain


def _conv(name, h, cin, cout, kernel, stride=1, pad=0, groups=1,
          residual=False):
    return dict(name=name, in_h=h, in_w=h, in_c=cin, out_c=cout,
                kernel=kernel, stride=stride, pad=pad, groups=groups,
                pool=1, pool_stride=1, relu=False, residual=residual)


def _plan(cfg):
    """The stem node, then per stage its downsampling node (None in
    stage 1) and its blocks as (tag, dw, pw1, pw2)."""
    hw, _, cin = cfg["in_shape"]
    st, blk, ds = cfg["stem"], cfg["block"], cfg["downsample"]
    stem = _conv("stem", hw, cin, cfg["dims"][0], st["kernel"],
                 st["stride"])
    h = plain.out_hw(hw, st["kernel"], st["stride"], 0)
    c, stages = cfg["dims"][0], []
    for si, (cout, depth) in enumerate(zip(cfg["dims"], cfg["depths"]),
                                       start=1):
        down = None
        if si > 1:
            down = _conv(f"ds{si}", h, c, cout, ds["kernel"], ds["stride"])
            h, c = plain.out_hw(h, ds["kernel"], ds["stride"], 0), cout
        blocks = []
        for bi in range(1, depth + 1):
            t = f"s{si}b{bi}"
            e = blk["expansion"] * c
            blocks.append((t, _conv(f"{t}_dw", h, c, c, blk["dw_kernel"],
                                    pad=blk["dw_pad"], groups=c),
                           _conv(f"{t}_pw1", h, c, e, 1),
                           _conv(f"{t}_pw2", h, e, c, 1, residual=True)))
        stages.append((down, blocks))
    return stem, stages


def conv_nodes(cfg: dict) -> list:
    stem, stages = _plan(cfg)
    nodes = [stem]
    for down, blocks in stages:
        nodes += [down] if down is not None else []
        nodes += [n for b in blocks for n in b[1:]]
    return nodes


def layer_norm(x, params, name: str, eps: float):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    y = (x - mean) / jnp.sqrt(var + eps)
    if name in params:
        gamma, beta = params[name]
        y = y * gamma + beta
    return y


def forward(cfg: dict, params, x, conv_fn=plain.conv):
    stem, stages = _plan(cfg)
    eps = cfg["norm_eps"]
    x = plain.conv_node(x, params, stem, conv_fn, relu=False)
    x = layer_norm(x, params, "stem_norm", eps)
    for down, blocks in stages:
        if down is not None:
            x = layer_norm(x, params, f"{down['name']}_norm", eps)
            x = plain.conv_node(x, params, down, conv_fn, relu=False)
        for tag, dw, pw1, pw2 in blocks:
            y = plain.conv_node(x, params, dw, conv_fn, relu=False)
            y = layer_norm(y, params, f"{tag}_norm", eps)
            y = plain.conv_node(y, params, pw1, conv_fn, relu=False)
            y = jax.nn.gelu(y, approximate=False)
            y = plain.conv_node(y, params, pw2, conv_fn, relu=False)
            if f"{tag}_scale" in params:
                y = y * params[f"{tag}_scale"]
            x = x + y
    return x
