"""Layer tables for the paper's CNN evaluation set (224x224 inference,
batch 1), lowered to GEMM workloads.

Models (paper §4.2): AlexNet, VGG-16, GoogLeNet, BN-Inception, ResNet-152,
DenseNet-201, ResNeXt-152 (g=32), MobileNetV3-Large, EfficientNet-B0.
Tables follow the original publications; pooling/activation layers carry no
GEMMs and are omitted (the systolic model sees matrix multiplies only). The
squeeze-and-excitation blocks of MobileNetV3 and EfficientNet lower to their
two batch-1 FC layers each.

These flat lists erase connectivity (skip/concat/branch edges) and with it
the Unified-Buffer residency cost of each network. The graph-IR builders in
`repro.graph.builders` construct the same models as DAGs — same layer
specs, same order, `Graph.flatten()` reproduces these lists exactly — with
the connectivity needed for liveness/occupancy analysis and the
capacity-aware DSE (`repro.core.dse.capacity_sweep`).
"""
from __future__ import annotations

from typing import Dict, List

from repro.core.workloads import FC, Conv, Gemm, Workload, lower


def alexnet() -> List[Workload]:
    ls = [
        Conv(224, 3, 64, k=11, stride=4, pad="valid"),     # 55
        Conv(27, 64, 192, k=5),                            # after pool
        Conv(13, 192, 384, k=3),
        Conv(13, 384, 256, k=3),
        Conv(13, 256, 256, k=3),
        FC(9216, 4096), FC(4096, 4096), FC(4096, 1000),
    ]
    return lower(ls)


def vgg16() -> List[Workload]:
    ls = [
        Conv(224, 3, 64), Conv(224, 64, 64),
        Conv(112, 64, 128), Conv(112, 128, 128),
        Conv(56, 128, 256), Conv(56, 256, 256, repeats=2),
        Conv(28, 256, 512), Conv(28, 512, 512, repeats=2),
        Conv(14, 512, 512, repeats=3),
        FC(25088, 4096), FC(4096, 4096), FC(4096, 1000),
    ]
    return lower(ls)


def _inception(h, c_in, b1, b3r, b3, b5r, b5, bp) -> List[Conv]:
    """GoogLeNet inception module (1x1 / 1x1->3x3 / 1x1->5x5 / pool->1x1)."""
    return [
        Conv(h, c_in, b1, k=1),
        Conv(h, c_in, b3r, k=1), Conv(h, b3r, b3, k=3),
        Conv(h, c_in, b5r, k=1), Conv(h, b5r, b5, k=5),
        Conv(h, c_in, bp, k=1),
    ]


def googlenet() -> List[Workload]:
    ls = [
        Conv(224, 3, 64, k=7, stride=2),
        Conv(56, 64, 64, k=1), Conv(56, 64, 192, k=3),
    ]
    ls += _inception(28, 192, 64, 96, 128, 16, 32, 32)
    ls += _inception(28, 256, 128, 128, 192, 32, 96, 64)
    ls += _inception(14, 480, 192, 96, 208, 16, 48, 64)
    ls += _inception(14, 512, 160, 112, 224, 24, 64, 64)
    ls += _inception(14, 512, 128, 128, 256, 24, 64, 64)
    ls += _inception(14, 512, 112, 144, 288, 32, 64, 64)
    ls += _inception(14, 528, 256, 160, 320, 32, 128, 128)
    ls += _inception(7, 832, 256, 160, 320, 32, 128, 128)
    ls += _inception(7, 832, 384, 192, 384, 48, 128, 128)
    ls += [FC(1024, 1000)]
    return lower(ls)


def _inception_bn(h, c_in, b1, b3r, b3, bd3r, bd3, bp) -> List[Conv]:
    """BN-Inception module: 5x5 branch replaced by double 3x3."""
    out = []
    if b1:
        out.append(Conv(h, c_in, b1, k=1))
    out += [Conv(h, c_in, b3r, k=1), Conv(h, b3r, b3, k=3)]
    out += [Conv(h, c_in, bd3r, k=1), Conv(h, bd3r, bd3, k=3),
            Conv(h, bd3, bd3, k=3)]
    if bp:
        out.append(Conv(h, c_in, bp, k=1))
    return out


def bn_inception() -> List[Workload]:
    ls = [
        Conv(224, 3, 64, k=7, stride=2),
        Conv(56, 64, 64, k=1), Conv(56, 64, 192, k=3),
    ]
    ls += _inception_bn(28, 192, 64, 64, 64, 64, 96, 32)
    ls += _inception_bn(28, 256, 64, 64, 96, 64, 96, 64)
    ls += _inception_bn(28, 320, 0, 128, 160, 64, 96, 0)      # stride module
    ls += _inception_bn(14, 576, 224, 64, 96, 96, 128, 128)
    ls += _inception_bn(14, 576, 192, 96, 128, 96, 128, 128)
    ls += _inception_bn(14, 576, 160, 128, 160, 128, 160, 128)
    ls += _inception_bn(14, 576, 96, 128, 192, 160, 192, 128)
    ls += _inception_bn(14, 576, 0, 128, 192, 192, 256, 0)    # stride module
    ls += _inception_bn(7, 1024, 352, 192, 320, 160, 224, 128)
    ls += _inception_bn(7, 1024, 352, 192, 320, 192, 224, 128)
    ls += [FC(1024, 1000)]
    return lower(ls)


def _bottleneck(h, c_in, c_mid, c_out, n_blocks, groups=1, first_stride=2):
    ls = [Conv(h * first_stride, c_in, c_out, k=1, stride=first_stride,
               name="downsample")]
    for i in range(n_blocks):
        cin = c_in if i == 0 else c_out
        s = first_stride if i == 0 else 1
        hh = h * first_stride if i == 0 else h
        ls += [
            Conv(hh, cin, c_mid, k=1),
            Conv(hh, c_mid, c_mid, k=3, stride=s, groups=groups),
            Conv(h, c_mid, c_out, k=1),
        ]
    return ls


def resnet152() -> List[Workload]:
    ls = [Conv(224, 3, 64, k=7, stride=2)]
    ls += _bottleneck(56, 64, 64, 256, 3, first_stride=1)
    ls += _bottleneck(28, 256, 128, 512, 8)
    ls += _bottleneck(14, 512, 256, 1024, 36)
    ls += _bottleneck(7, 1024, 512, 2048, 3)
    ls += [FC(2048, 1000)]
    return lower(ls)


def resnext152_32x4d() -> List[Workload]:
    """ResNeXt-152 (g=32): grouped 3x3 in every bottleneck (paper §4.2)."""
    ls = [Conv(224, 3, 64, k=7, stride=2)]
    ls += _bottleneck(56, 64, 128, 256, 3, groups=32, first_stride=1)
    ls += _bottleneck(28, 256, 256, 512, 8, groups=32)
    ls += _bottleneck(14, 512, 512, 1024, 36, groups=32)
    ls += _bottleneck(7, 1024, 1024, 2048, 3, groups=32)
    ls += [FC(2048, 1000)]
    return lower(ls)


def densenet201(k: int = 32) -> List[Workload]:
    ls = [Conv(224, 3, 64, k=7, stride=2)]
    c, h = 64, 56
    for blocks in (6, 12, 48, 32):
        for _ in range(blocks):
            ls += [Conv(h, c, 4 * k, k=1), Conv(h, 4 * k, k, k=3)]
            c += k
        if blocks != 32:                      # transition: 1x1 halving + pool
            ls += [Conv(h, c, c // 2, k=1)]
            c //= 2
            h //= 2
    ls += [FC(c, 1000)]
    return lower(ls)


def make_divisible(v: float, divisor: int = 8) -> int:
    """Round a channel count to the nearest multiple of `divisor`, never
    below it nor more than 10% under `v` (the rounding of the MobileNet
    authors' reference code)."""
    new = max(divisor, int(v + divisor / 2) // divisor * divisor)
    return new + divisor if new < 0.9 * v else new


def _squeeze_excite(c: int, se: int) -> List[FC]:
    """Squeeze-and-excitation (Hu et al., arXiv:1709.01507) on a block's
    depthwise output: two batch-1 FC layers, c -> se -> c. Its global
    pooling and its gating multiply carry no GEMM."""
    return [FC(c, se), FC(se, c)]


def mobilenetv3_large() -> List[Workload]:
    """Howard et al., arXiv:1905.02244, Table 1. Bneck rows: (h_in, c_in,
    exp, c_out, k, stride, se). Depthwise = groups=exp. SE squeezes to
    make_divisible(exp // 4, 8), as the authors' reference code does."""
    rows = [
        (112, 16, 16, 16, 3, 1, False),
        (112, 16, 64, 24, 3, 2, False), (56, 24, 72, 24, 3, 1, False),
        (56, 24, 72, 40, 5, 2, True), (28, 40, 120, 40, 5, 1, True),
        (28, 40, 120, 40, 5, 1, True),
        (28, 40, 240, 80, 3, 2, False), (14, 80, 200, 80, 3, 1, False),
        (14, 80, 184, 80, 3, 1, False), (14, 80, 184, 80, 3, 1, False),
        (14, 80, 480, 112, 3, 1, True), (14, 112, 672, 112, 3, 1, True),
        (14, 112, 672, 160, 5, 2, True), (7, 160, 960, 160, 5, 1, True),
        (7, 160, 960, 160, 5, 1, True),
    ]
    ls = [Conv(224, 3, 16, k=3, stride=2)]
    for (h, cin, exp, cout, kk, s, se) in rows:
        if exp != cin:
            ls.append(Conv(h, cin, exp, k=1))
        ls.append(Conv(h, exp, exp, k=kk, stride=s, groups=exp))  # depthwise
        if se:
            ls += _squeeze_excite(exp, make_divisible(exp // 4))
        ls.append(Conv(h // s, exp, cout, k=1))
    ls += [Conv(7, 160, 960, k=1), FC(960, 1280), FC(1280, 1000)]
    return lower(ls)


def efficientnet_b0() -> List[Workload]:
    """Tan & Le, arXiv:1905.11946, Table 1. Every MBConv block has SE,
    squeezing to max(1, int(0.25 * the block's input channels))."""
    rows = [  # (h_in, c_in, c_out, expand, k, stride, repeats)
        (112, 32, 16, 1, 3, 1, 1),
        (112, 16, 24, 6, 3, 2, 2),
        (56, 24, 40, 6, 5, 2, 2),
        (28, 40, 80, 6, 3, 2, 3),
        (14, 80, 112, 6, 5, 1, 3),
        (14, 112, 192, 6, 5, 2, 4),
        (7, 192, 320, 6, 3, 1, 1),
    ]
    ls = [Conv(224, 3, 32, k=3, stride=2)]
    for (h, cin, cout, e, kk, s, reps) in rows:
        for i in range(reps):
            ci = cin if i == 0 else cout
            st = s if i == 0 else 1
            hh = h if i == 0 else h // s
            exp = ci * e
            if e != 1:
                ls.append(Conv(hh, ci, exp, k=1))
            ls.append(Conv(hh, exp, exp, k=kk, stride=st, groups=exp))
            ls += _squeeze_excite(exp, max(1, int(ci * 0.25)))
            ls.append(Conv(hh // st, exp, cout, k=1))
    ls += [Conv(7, 320, 1280, k=1), FC(1280, 1000)]
    return lower(ls)


ZOO: Dict[str, callable] = {
    "alexnet": alexnet,
    "vgg16": vgg16,
    "googlenet": googlenet,
    "bn_inception": bn_inception,
    "resnet152": resnet152,
    "resnext152_32x4d": resnext152_32x4d,
    "densenet201": densenet201,
    "mobilenetv3_large": mobilenetv3_large,
    "efficientnet_b0": efficientnet_b0,
}


def get_workloads(name: str) -> List[Workload]:
    return ZOO[name]()
