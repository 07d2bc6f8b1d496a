"""Plain reference lowering of the paper's nine CNNs to GEMM rows.

Reads the layer tables of `configs/cnn_zoo.json`, which transcribe each
network's own publication (the file names them, and lists under
`assumed` where a table departs from its paper). A convolution lowers by
im2col to one GEMM per group: M = H_out * W_out, K = C_in/g * k * k,
N = C_out/g, with H_out = ceil(H_in / stride) for "same" padding and
(H_in - k) // stride + 1 for "valid". A fully connected layer is a
batch-1 GEMM. Each family below builds its blocks from its table:

* plain (AlexNet, VGG): the listed layers;
* inception (GoogLeNet): 1x1 | 1x1 -> 3x3 | 1x1 -> 5x5 | pool -> 1x1;
* inception_bn (BN-Inception): the 5x5 branch is a double 3x3, and a
  branch of width 0 is absent;
* bottleneck (ResNet, ResNeXt): per stage a 1x1 projection shortcut,
  then [1x1 c_mid, 3x3 c_mid in `groups` groups, 1x1 c_out] blocks, the
  stage's stride on its first 3x3 and on the shortcut;
* dense (DenseNet-BC): [1x1 bottleneck * growth, 3x3 growth] per layer,
  a 1x1 compressing transition between blocks;
* bneck (MobileNetV3) and mbconv (EfficientNet): 1x1 expansion (when it
  widens), depthwise k x k, squeeze-and-excitation as two batch-1 FC
  layers exp -> se -> exp (Hu et al., arXiv:1709.01507), 1x1 projection.

Rows are (M, K, N, groups, repeats). Nothing here imports the program.
"""
from __future__ import annotations


def _conv(h, c_in, c_out, k, stride=1, pad="same", repeats=1, groups=1):
    if pad == "same":
        h_out = -(-h // stride)
    elif pad == "valid":
        h_out = (h - k) // stride + 1
    else:
        raise ValueError(f"unknown padding {pad!r}")
    return (h_out * h_out, c_in // groups * k * k, c_out // groups, groups,
            repeats), h_out


def _layers(table):
    rows = []
    for kind, *a in table:
        if kind == "conv":
            rows.append(_conv(*a)[0])
        elif kind == "fc":
            rows.append(_fc(*a))
        else:
            raise ValueError(f"unknown layer kind {kind!r}")
    return rows


def _fc(d_in, d_out):
    return (1, d_in, d_out, 1, 1)


def make_divisible(v, divisor):
    """The channel rounding of the MobileNet reference code: the nearest
    multiple of `divisor`, at least `divisor`, and not 10% below `v`."""
    new = max(divisor, int(v + divisor / 2) // divisor * divisor)
    return new + divisor if new < 0.9 * v else new


def _inception(modules):
    rows = []
    for h, c, b1, b3r, b3, b5r, b5, bp in modules:
        rows += [_conv(h, c, b1, 1)[0], _conv(h, c, b3r, 1)[0],
                 _conv(h, b3r, b3, 3)[0], _conv(h, c, b5r, 1)[0],
                 _conv(h, b5r, b5, 5)[0], _conv(h, c, bp, 1)[0]]
    return rows


def _inception_bn(modules):
    rows = []
    for h, c, b1, b3r, b3, d3r, d3, bp in modules:
        if b1:
            rows.append(_conv(h, c, b1, 1)[0])
        rows += [_conv(h, c, b3r, 1)[0], _conv(h, b3r, b3, 3)[0],
                 _conv(h, c, d3r, 1)[0], _conv(h, d3r, d3, 3)[0],
                 _conv(h, d3, d3, 3)[0]]
        if bp:
            rows.append(_conv(h, c, bp, 1)[0])
    return rows


def _bottleneck(net):
    rows, h, c, g = [], net["h_in"], net["c_in"], net["groups"]
    for blocks, c_mid, c_out, stride in net["stages"]:
        rows.append(_conv(h, c, c_out, 1, stride)[0])        # shortcut
        for b in range(blocks):
            s = stride if b == 0 else 1
            rows.append(_conv(h, c, c_mid, 1)[0])
            row, h = _conv(h, c_mid, c_mid, 3, s, groups=g)
            rows += [row, _conv(h, c_mid, c_out, 1)[0]]
            c = c_out
    return rows


def _dense(net):
    rows, h, c = [], net["h_in"], net["c_in"]
    k, wide = net["growth"], net["bottleneck"] * net["growth"]
    for i, layers in enumerate(net["blocks"]):
        for _ in range(layers):
            rows += [_conv(h, c, wide, 1)[0], _conv(h, wide, k, 3)[0]]
            c += k
        if i < len(net["blocks"]) - 1:
            c_next = int(c * net["compression"])
            rows.append(_conv(h, c, c_next, 1)[0])
            c, h = c_next, h // 2
    return rows + [_fc(c, net["num_classes"])]


def _mobile_block(h, c_in, k, exp, c_out, stride, se):
    rows = [_conv(h, c_in, exp, 1)[0]] if exp != c_in else []
    row, h_out = _conv(h, exp, exp, k, stride, groups=exp)   # depthwise
    rows.append(row)
    if se:
        rows += [_fc(exp, se), _fc(se, exp)]
    return rows + [_conv(h_out, exp, c_out, 1)[0]]


def _se_of(net, of):
    se = net["se"]
    if se["of"] != of:
        raise ValueError(f"this family squeezes from {of!r}, not "
                         f"{se['of']!r}")
    return se


def _bneck(net):
    se = _se_of(net, "exp")
    return [r for h, c, k, exp, c_out, has_se, s in net["bnecks"]
            for r in _mobile_block(
                h, c, k, exp, c_out, s,
                make_divisible(exp * se["ratio"], se["divisor"])
                if has_se else 0)]


def _mbconv(net):
    se = _se_of(net, "c_in")
    rows = []
    for expand, k, stride, repeats, c_in, c_out, h_in in net["stages"]:
        h = h_in
        for i in range(repeats):
            c, s = (c_in, stride) if i == 0 else (c_out, 1)
            rows += _mobile_block(h, c, k, c * expand, c_out, s,
                                  max(1, int(c * se["ratio"])))
            h = -(-h // s)
    return rows


_FAMILIES = {"plain": lambda net: _layers(net["layers"]),
             "inception": lambda net: _inception(net["modules"]),
             "inception_bn": lambda net: _inception_bn(net["modules"]),
             "bottleneck": _bottleneck, "dense": _dense, "bneck": _bneck,
             "mbconv": _mbconv}


def lower(cfg, shape):
    """GEMM rows of the network named `shape` (one image, batch 1)."""
    net = cfg["networks"][shape]
    return (_layers(net.get("stem", [])) + _FAMILIES[net["family"]](net)
            + _layers(net.get("head", [])))
