"""Plain reference lowering of a bottleneck ResNet to GEMM rows.

He et al., "Deep Residual Learning for Image Recognition", CVPR 2016,
Table 1: a 7x7/2 stem, a 3x3/2 max-pool, four stages of bottleneck
blocks [1x1 c_mid, 3x3 c_mid, 1x1 c_out] whose first block carries a
1x1 projection shortcut, and a fully connected classifier. A stage's
stride sits on its first 3x3 (the configuration file says so under
`stride_on`). A convolution lowers by im2col to one GEMM per group:
M = H_out * W_out, K = C_in/g * k * k, N = C_out/g, with "same" padding
(H_out = ceil(H_in / stride)). Rows are (M, K, N, groups, repeats).
"""
from __future__ import annotations


def _conv(h_in, c_in, c_out, k, stride=1, groups=1):
    h_out = -(-h_in // stride)
    return (h_out * h_out, c_in // groups * k * k, c_out // groups, groups,
            1), h_out


def lower(cfg, shape=None):
    """GEMM rows of the network in `cfg` (one image, batch 1)."""
    if cfg["stride_on"] != "conv3x3":
        raise ValueError(f"unsupported stride_on {cfg['stride_on']!r}")
    stem = cfg["stem"]
    row, h = _conv(cfg["image_size"], cfg["in_channels"], stem["c_out"],
                   stem["kernel"], stem["stride"])
    rows = [row]
    h = -(-h // cfg["pool_stride"])
    c = stem["c_out"]
    for st in cfg["stages"]:
        s, c_mid, c_out = st["stride"], st["c_mid"], st["c_out"]
        rows.append(_conv(h, c, c_out, 1, s)[0])            # projection
        for b in range(st["blocks"]):
            stride = s if b == 0 else 1
            rows.append(_conv(h, c, c_mid, 1)[0])
            row, h = _conv(h, c_mid, c_mid, 3, stride)
            rows.append(row)
            rows.append(_conv(h, c_mid, c_out, 1)[0])
            c = c_out
    rows.append((1, c, cfg["num_classes"], 1, 1))
    return rows
