"""Graph builders: the CNN zoo with real connectivity + a transformer block.

Each builder constructs the same layer specs as the flat tables in
`core/cnn_zoo.py`, in the same order, but wires them into a DAG with the
connectivity the flat lists erase: skip edges (ResNet/ResNeXt and the
stride-1 MBConv blocks of MobileNetV3/EfficientNet), dense concatenations
(DenseNet-201), and branch/join modules (GoogLeNet/BN-Inception). Pooling
layers — omitted from the GEMM tables — appear as `pool` nodes so tensor
shapes stay consistent across stages; `Graph.flatten()` skips them and
reproduces `cnn_zoo.get_workloads(name)` exactly (pinned by the
flatten-equivalence test).

Two deliberate modeling choices, inherited from the legacy tables:

  * `repeats` on a Conv stays collapsed in one node. Every repeated layer
    in the zoo maps c -> c at constant spatial size, so the collapse is
    liveness-neutral (in + out of the repeated layer is the live set at
    every step of the chain) and `flatten()` stays bit-identical.
  * BN-Inception grid-reduction modules keep their convs at the input
    resolution (as the legacy table does) with the downsampling expressed
    as a pool after the join.

`transformer_block` builds one decoder layer over the `configs.base`
architectures with the residual edges the flat `lm_workloads` extraction
drops — the block input stays live across the whole attention span.

`lm_graph` stacks those blocks into FULL-model serving graphs for every
family of the configs zoo (attention / MoE / hybrid mamba / ssm xLSTM /
enc-dec audio), following the `lm_workloads` lowering conventions so the
aggregated `flatten()` reproduces `extract_workloads(cfg, shape)` GEMM for
GEMM (pinned by the flatten-equivalence test). What the flat list cannot
express — and the graph makes first-class — is serving state:

  * decode: every layer's KV cache (and SSM/recurrent state) enters as an
    `input` tensor and is pinned through the whole pass by the terminal
    `output` sink — caches are carried state, not transients, so decode
    liveness/spill accounting sees their full residency;
  * prefill: the K/V projection outputs ARE the cache being built; they are
    pinned to the end of the pass the same way;
  * audio: the encoder output feeds every decoder layer's cross-attention,
    so it stays live across the whole decoder naturally via graph edges.
"""
from __future__ import annotations

from typing import Callable, Dict, List, Sequence, Tuple

from repro.configs.base import ArchConfig, ShapeConfig, resolve_dims
from repro.core.cnn_zoo import make_divisible
from repro.core.workloads import FC, Conv, Gemm
from repro.graph.ir import Graph, Node, Tensor

DEFAULT_ACT_BITS = 8.0


class _B:
    """Tiny builder DSL: each method appends one node and returns its name."""

    def __init__(self, name: str, act_bits: float = DEFAULT_ACT_BITS):
        self.g = Graph(name)
        self.bits = act_bits
        self._n = 0

    def _name(self, prefix: str) -> str:
        self._n += 1
        return f"{prefix}{self._n}"

    def input(self, shape: Tuple[int, ...]) -> str:
        return self.g.add(Node(self._name("in"), "input",
                               Tensor(shape, self.bits)))

    def conv(self, src: str, spec: Conv) -> str:
        out = Tensor((spec.h_out, spec.w_out, spec.c_out), self.bits)
        return self.g.add(Node(self._name("conv"), "gemm", out, spec), (src,))

    def fc(self, src: str, spec: FC) -> str:
        out = Tensor((spec.batch, spec.d_out), self.bits)
        return self.g.add(Node(self._name("fc"), "gemm", out, spec), (src,))

    def gemm(self, srcs: Sequence[str], spec: Gemm,
             out_shape: Tuple[int, ...]) -> str:
        return self.g.add(Node(self._name(spec.name or "gemm"), "gemm",
                               Tensor(out_shape, self.bits), spec),
                          tuple(srcs))

    def pool(self, src: str, shape: Tuple[int, ...]) -> str:
        return self.g.add(Node(self._name("pool"), "pool",
                               Tensor(shape, self.bits)), (src,))

    def add(self, *srcs: str) -> str:
        out = self.g.node(srcs[0]).out
        return self.g.add(Node(self._name("add"), "add",
                               Tensor(out.shape, self.bits)), srcs)

    def gate(self, src: str, scale: str) -> str:
        """Channel-wise gating multiply: `src` scaled by a per-channel
        vector `scale`; a join that materializes a tensor like `src`."""
        out = self.g.node(src).out
        return self.g.add(Node(self._name("gate"), "add",
                               Tensor(out.shape, self.bits)), (src, scale))

    def concat(self, *srcs: str) -> str:
        shapes = [self.g.node(s).out.shape for s in srcs]
        h, w = shapes[0][0], shapes[0][1]
        out = Tensor((h, w, sum(s[2] for s in shapes)), self.bits)
        return self.g.add(Node(self._name("cat"), "concat", out), srcs)


# ------------------------------------------------------------------ chains --

def alexnet(act_bits: float = DEFAULT_ACT_BITS) -> Graph:
    b = _B("alexnet", act_bits)
    x = b.input((224, 224, 3))
    c = b.conv(x, Conv(224, 3, 64, k=11, stride=4, pad="valid"))
    c = b.pool(c, (27, 27, 64))
    c = b.conv(c, Conv(27, 64, 192, k=5))
    c = b.pool(c, (13, 13, 192))
    c = b.conv(c, Conv(13, 192, 384, k=3))
    c = b.conv(c, Conv(13, 384, 256, k=3))
    c = b.conv(c, Conv(13, 256, 256, k=3))
    c = b.pool(c, (6, 6, 256))
    c = b.fc(c, FC(9216, 4096))
    c = b.fc(c, FC(4096, 4096))
    b.fc(c, FC(4096, 1000))
    return b.g


def vgg16(act_bits: float = DEFAULT_ACT_BITS) -> Graph:
    b = _B("vgg16", act_bits)
    x = b.input((224, 224, 3))
    c = b.conv(x, Conv(224, 3, 64))
    c = b.conv(c, Conv(224, 64, 64))
    c = b.pool(c, (112, 112, 64))
    c = b.conv(c, Conv(112, 64, 128))
    c = b.conv(c, Conv(112, 128, 128))
    c = b.pool(c, (56, 56, 128))
    c = b.conv(c, Conv(56, 128, 256))
    c = b.conv(c, Conv(56, 256, 256, repeats=2))
    c = b.pool(c, (28, 28, 256))
    c = b.conv(c, Conv(28, 256, 512))
    c = b.conv(c, Conv(28, 512, 512, repeats=2))
    c = b.pool(c, (14, 14, 512))
    c = b.conv(c, Conv(14, 512, 512, repeats=3))
    c = b.pool(c, (7, 7, 512))
    c = b.fc(c, FC(25088, 4096))
    c = b.fc(c, FC(4096, 4096))
    b.fc(c, FC(4096, 1000))
    return b.g


# -------------------------------------------------------- branch/join nets --

def _inception(b: _B, src: str, h, c_in, b1, b3r, b3, b5r, b5, bp) -> str:
    """GoogLeNet module: 4 branches from `src`, concatenated (node order
    matches cnn_zoo._inception: b1, b3r, b3, b5r, b5, bp)."""
    n1 = b.conv(src, Conv(h, c_in, b1, k=1))
    n3 = b.conv(b.conv(src, Conv(h, c_in, b3r, k=1)), Conv(h, b3r, b3, k=3))
    n5 = b.conv(b.conv(src, Conv(h, c_in, b5r, k=1)), Conv(h, b5r, b5, k=5))
    p = b.pool(src, (h, h, c_in))          # 3x3 stride-1 maxpool branch
    np_ = b.conv(p, Conv(h, c_in, bp, k=1))
    return b.concat(n1, n3, n5, np_)


def googlenet(act_bits: float = DEFAULT_ACT_BITS) -> Graph:
    b = _B("googlenet", act_bits)
    x = b.input((224, 224, 3))
    c = b.conv(x, Conv(224, 3, 64, k=7, stride=2))
    c = b.pool(c, (56, 56, 64))
    c = b.conv(c, Conv(56, 64, 64, k=1))
    c = b.conv(c, Conv(56, 64, 192, k=3))
    c = b.pool(c, (28, 28, 192))
    c = _inception(b, c, 28, 192, 64, 96, 128, 16, 32, 32)
    c = _inception(b, c, 28, 256, 128, 128, 192, 32, 96, 64)
    c = b.pool(c, (14, 14, 480))
    c = _inception(b, c, 14, 480, 192, 96, 208, 16, 48, 64)
    c = _inception(b, c, 14, 512, 160, 112, 224, 24, 64, 64)
    c = _inception(b, c, 14, 512, 128, 128, 256, 24, 64, 64)
    c = _inception(b, c, 14, 512, 112, 144, 288, 32, 64, 64)
    c = _inception(b, c, 14, 528, 256, 160, 320, 32, 128, 128)
    c = b.pool(c, (7, 7, 832))
    c = _inception(b, c, 7, 832, 256, 160, 320, 32, 128, 128)
    c = _inception(b, c, 7, 832, 384, 192, 384, 48, 128, 128)
    c = b.pool(c, (1, 1, 1024))            # global average pool
    b.fc(c, FC(1024, 1000))
    return b.g


def _inception_bn(b: _B, src: str, h, c_in, b1, b3r, b3, bd3r, bd3, bp) -> str:
    """BN-Inception module; b1 == bp == 0 marks a grid-reduction module
    whose pass-through branch is the pooled input (downsampling itself is a
    pool after the join, keeping the legacy per-conv resolutions)."""
    branches: List[str] = []
    if b1:
        branches.append(b.conv(src, Conv(h, c_in, b1, k=1)))
    branches.append(b.conv(b.conv(src, Conv(h, c_in, b3r, k=1)),
                           Conv(h, b3r, b3, k=3)))
    d = b.conv(b.conv(src, Conv(h, c_in, bd3r, k=1)), Conv(h, bd3r, bd3, k=3))
    branches.append(b.conv(d, Conv(h, bd3, bd3, k=3)))
    p = b.pool(src, (h, h, c_in))
    if bp:
        branches.append(b.conv(p, Conv(h, c_in, bp, k=1)))
    else:
        branches.append(p)                 # reduction: pooled pass-through
    return b.concat(*branches)


def bn_inception(act_bits: float = DEFAULT_ACT_BITS) -> Graph:
    b = _B("bn_inception", act_bits)
    x = b.input((224, 224, 3))
    c = b.conv(x, Conv(224, 3, 64, k=7, stride=2))
    c = b.pool(c, (56, 56, 64))
    c = b.conv(c, Conv(56, 64, 64, k=1))
    c = b.conv(c, Conv(56, 64, 192, k=3))
    c = b.pool(c, (28, 28, 192))
    c = _inception_bn(b, c, 28, 192, 64, 64, 64, 64, 96, 32)
    c = _inception_bn(b, c, 28, 256, 64, 64, 96, 64, 96, 64)
    c = _inception_bn(b, c, 28, 320, 0, 128, 160, 64, 96, 0)
    c = b.pool(c, (14, 14, 576))           # reduction-module downsample
    c = _inception_bn(b, c, 14, 576, 224, 64, 96, 96, 128, 128)
    c = _inception_bn(b, c, 14, 576, 192, 96, 128, 96, 128, 128)
    c = _inception_bn(b, c, 14, 576, 160, 128, 160, 128, 160, 128)
    # legacy-table quirk: this module and the next emit 608 channels
    # (160+160+160+128 and 96+192+192+128) but the downstream convs declare
    # c_in=576; keep the graph faithful to the table on both sides.
    b.g.channel_quirks.add(c)
    c = _inception_bn(b, c, 14, 576, 96, 128, 192, 160, 192, 128)
    b.g.channel_quirks.add(c)
    c = _inception_bn(b, c, 14, 576, 0, 128, 192, 192, 256, 0)
    c = b.pool(c, (7, 7, 1024))            # reduction-module downsample
    c = _inception_bn(b, c, 7, 1024, 352, 192, 320, 160, 224, 128)
    c = _inception_bn(b, c, 7, 1024, 352, 192, 320, 192, 224, 128)
    c = b.pool(c, (1, 1, 1024))            # global average pool
    b.fc(c, FC(1024, 1000))
    return b.g


# ------------------------------------------------------------ residual nets --

def _res_stage(b: _B, src: str, h, c_in, c_mid, c_out, n_blocks,
               groups: int = 1, first_stride: int = 2) -> str:
    """Bottleneck stage; the projection ("downsample") conv is inserted
    first (legacy node order) but wired as block 0's skip path."""
    ds = b.conv(src, Conv(h * first_stride, c_in, c_out, k=1,
                          stride=first_stride, name="downsample"))
    x = src
    for i in range(n_blocks):
        cin = c_in if i == 0 else c_out
        s = first_stride if i == 0 else 1
        hh = h * first_stride if i == 0 else h
        c1 = b.conv(x, Conv(hh, cin, c_mid, k=1))
        c2 = b.conv(c1, Conv(hh, c_mid, c_mid, k=3, stride=s, groups=groups))
        c3 = b.conv(c2, Conv(h, c_mid, c_out, k=1))
        x = b.add(c3, ds if i == 0 else x)   # residual join
    return x


def _resnet(name: str, c_mids: Tuple[int, ...], groups: int,
            act_bits: float) -> Graph:
    b = _B(name, act_bits)
    x = b.input((224, 224, 3))
    c = b.conv(x, Conv(224, 3, 64, k=7, stride=2))
    c = b.pool(c, (56, 56, 64))
    c = _res_stage(b, c, 56, 64, c_mids[0], 256, 3, groups, first_stride=1)
    c = _res_stage(b, c, 28, 256, c_mids[1], 512, 8, groups)
    c = _res_stage(b, c, 14, 512, c_mids[2], 1024, 36, groups)
    c = _res_stage(b, c, 7, 1024, c_mids[3], 2048, 3, groups)
    c = b.pool(c, (1, 1, 2048))            # global average pool
    b.fc(c, FC(2048, 1000))
    return b.g


def resnet152(act_bits: float = DEFAULT_ACT_BITS) -> Graph:
    return _resnet("resnet152", (64, 128, 256, 512), 1, act_bits)


def resnext152_32x4d(act_bits: float = DEFAULT_ACT_BITS) -> Graph:
    return _resnet("resnext152_32x4d", (128, 256, 512, 1024), 32, act_bits)


def densenet201(k: int = 32, act_bits: float = DEFAULT_ACT_BITS) -> Graph:
    b = _B("densenet201", act_bits)
    x = b.input((224, 224, 3))
    c = b.conv(x, Conv(224, 3, 64, k=7, stride=2))
    cur = b.pool(c, (56, 56, 64))
    ch, h = 64, 56
    for blocks in (6, 12, 48, 32):
        feats = [cur]                       # all stay live until transition
        for _ in range(blocks):
            src = feats[0] if len(feats) == 1 else b.concat(*feats)
            c1 = b.conv(src, Conv(h, ch, 4 * k, k=1))
            feats.append(b.conv(c1, Conv(h, 4 * k, k, k=3)))
            ch += k
        cur = b.concat(*feats)
        if blocks != 32:                    # transition: 1x1 halving + pool
            t = b.conv(cur, Conv(h, ch, ch // 2, k=1))
            ch //= 2
            h //= 2
            cur = b.pool(t, (h, h, ch))
    cur = b.pool(cur, (1, 1, ch))           # global average pool
    b.fc(cur, FC(ch, 1000))
    return b.g


# -------------------------------------------------------- inverted residual --

def _mbconv(b: _B, src: str, h, cin, exp, cout, kk, s, se=0) -> str:
    """Expand (if exp != cin) -> depthwise -> squeeze-and-excitation (if
    se: pool, FC exp -> se, FC se -> exp, gate) -> project, with a
    residual add when the block preserves shape (stride 1, cin == cout)."""
    e = b.conv(src, Conv(h, cin, exp, k=1)) if exp != cin else src
    d = b.conv(e, Conv(h, exp, exp, k=kk, stride=s, groups=exp))
    if se:
        z = b.pool(d, (1, 1, exp))          # global average pool
        z = b.fc(b.fc(z, FC(exp, se)), FC(se, exp))
        d = b.gate(d, z)
    p = b.conv(d, Conv(h // s, exp, cout, k=1))
    return b.add(p, src) if (s == 1 and cin == cout) else p


def mobilenetv3_large(act_bits: float = DEFAULT_ACT_BITS) -> Graph:
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
    b = _B("mobilenetv3_large", act_bits)
    x = b.input((224, 224, 3))
    c = b.conv(x, Conv(224, 3, 16, k=3, stride=2))
    for (h, cin, exp, cout, kk, s, se) in rows:
        c = _mbconv(b, c, h, cin, exp, cout, kk, s,
                    make_divisible(exp // 4) if se else 0)
    c = b.conv(c, Conv(7, 160, 960, k=1))
    c = b.pool(c, (1, 1, 960))             # global average pool
    c = b.fc(c, FC(960, 1280))
    b.fc(c, FC(1280, 1000))
    return b.g


def efficientnet_b0(act_bits: float = DEFAULT_ACT_BITS) -> Graph:
    rows = [  # (h_in, c_in, c_out, expand, k, stride, repeats)
        (112, 32, 16, 1, 3, 1, 1),
        (112, 16, 24, 6, 3, 2, 2),
        (56, 24, 40, 6, 5, 2, 2),
        (28, 40, 80, 6, 3, 2, 3),
        (14, 80, 112, 6, 5, 1, 3),
        (14, 112, 192, 6, 5, 2, 4),
        (7, 192, 320, 6, 3, 1, 1),
    ]
    b = _B("efficientnet_b0", act_bits)
    x = b.input((224, 224, 3))
    c = b.conv(x, Conv(224, 3, 32, k=3, stride=2))
    for (h, cin, cout, e, kk, s, reps) in rows:
        for i in range(reps):
            ci = cin if i == 0 else cout
            st = s if i == 0 else 1
            hh = h if i == 0 else h // s
            c = _mbconv(b, c, hh, ci, ci * e, cout, kk, st,
                        max(1, int(ci * 0.25)))
    c = b.conv(c, Conv(7, 320, 1280, k=1))
    c = b.pool(c, (1, 1, 1280))            # global average pool
    b.fc(c, FC(1280, 1000))
    return b.g


# ------------------------------------------------------------- transformers --

def _lm_dims(cfg: ArchConfig, shape: ShapeConfig):
    """(dims, B, Sq, Skv, eff_kv, T) under the `lm_workloads` conventions."""
    d = resolve_dims(cfg, 1)
    B = shape.global_batch
    if shape.kind == "decode":
        Sq, Skv, T = 1, shape.seq_len, B
    else:
        Sq = Skv = shape.seq_len
        T = B * Sq
    win = cfg.sliding_window
    eff_kv = min(Skv, win) if win else Skv
    return d, B, Sq, Skv, eff_kv, T


def _attn_mixer(b: _B, x: str, cfg: ArchConfig, *, hd: int, B: int, Sq: int,
                eff_kv: int, T: int, rep: int = 1, kv=None,
                kv_out=None) -> str:
    """Self-attention with residual: QKV projections, per-(batch x head)
    score/value GEMMs (via `groups`), output projection, residual add.
    `kv` (decode) is the layer's cache tensor, wired into the score/value
    GEMMs; `kv_out` (prefill) collects the K/V projection nodes — they ARE
    the cache being built and get pinned by the graph sink."""
    dm, qh, kvh = cfg.d_model, cfg.num_heads, cfg.num_kv_heads
    q = b.gemm([x], Gemm(T, dm, qh * hd, repeats=rep, name="wq"),
               (T, qh * hd))
    k = b.gemm([x], Gemm(T, dm, kvh * hd, repeats=rep, name="wk"),
               (T, kvh * hd))
    v = b.gemm([x], Gemm(T, dm, kvh * hd, repeats=rep, name="wv"),
               (T, kvh * hd))
    if kv_out is not None:
        kv_out += [k, v]
    s = b.gemm([q, k] if kv is None else [q, k, kv],
               Gemm(Sq, hd, eff_kv, groups=B * qh, repeats=rep,
                    name="scores"), (B * qh, Sq, eff_kv))
    av = b.gemm([s, v] if kv is None else [s, v, kv],
                Gemm(Sq, eff_kv, hd, groups=B * qh, repeats=rep,
                     name="attnv"), (T, qh * hd))
    o = b.gemm([av], Gemm(T, dm, dm, repeats=rep, name="wo"), (T, dm))
    return b.add(o, x)                      # residual: x live across attn


def _cross_attn(b: _B, x: str, enc: str, cfg: ArchConfig, *, hd: int, B: int,
                Sq: int, Se: int, T: int, rep: int = 1) -> str:
    """Enc-dec cross attention (audio): q from decoder tokens, kv over the
    encoder output — which therefore stays live across ALL decoder layers.
    Projections follow the flat lowering: one (T, d, d) GEMM each for the
    query and output sides (encoder K/V are amortized, as in the flat
    extraction)."""
    dm, qh = cfg.d_model, cfg.num_heads
    cq = b.gemm([x], Gemm(T, dm, dm, repeats=rep, name="xq"), (T, dm))
    s = b.gemm([cq, enc], Gemm(Sq, hd, Se, groups=B * qh, repeats=rep,
                               name="xscores"), (B * qh, Sq, Se))
    av = b.gemm([s, enc], Gemm(Sq, Se, hd, groups=B * qh, repeats=rep,
                               name="xattnv"), (T, qh * hd))
    co = b.gemm([av], Gemm(T, dm, dm, repeats=rep, name="xo"), (T, dm))
    return b.add(co, x)


def _mlp_block(b: _B, x: str, cfg: ArchConfig, T: int, rep: int = 1) -> str:
    """Dense MLP with residual; gated (silu) MLPs carry up & gate branches."""
    dm, dff = cfg.d_model, cfg.d_ff
    if dff == 0:
        return x
    if cfg.mlp_activation == "silu":        # gated MLP: up & gate branches
        up = b.gemm([x], Gemm(T, dm, dff, repeats=rep, name="wup"), (T, dff))
        gate = b.gemm([x], Gemm(T, dm, dff, repeats=rep, name="wgate"),
                      (T, dff))
        hmid = b.add(up, gate)              # elementwise gate merge
    else:
        hmid = b.gemm([x], Gemm(T, dm, dff, repeats=rep, name="wup"),
                      (T, dff))
    down = b.gemm([hmid], Gemm(T, dff, dm, repeats=rep, name="wdown"),
                  (T, dm))
    return b.add(down, x)                   # residual: x live across MLP


def _moe_block(b: _B, x: str, cfg: ArchConfig, T: int, rep: int = 1) -> str:
    """Routed MoE MLP: router GEMM + per-active-expert grouped GEMMs with
    per-expert M scaled to the expected routed token count. The down
    projection's output tensor is the post-combine (T, d) activation (the
    top-k weighted scatter back to tokens), so the residual join is
    shape-consistent."""
    dm, dff, E = cfg.d_model, cfg.d_ff, cfg.num_experts
    te = max(1, T * cfg.experts_per_token // E)
    r = b.gemm([x], Gemm(T, dm, E, repeats=rep, name="router"), (T, E))
    up = b.gemm([x, r], Gemm(te, dm, dff, groups=E, repeats=rep,
                             name="eup"), (te * E, dff))
    gate = b.gemm([x, r], Gemm(te, dm, dff, groups=E, repeats=rep,
                               name="egate"), (te * E, dff))
    hmid = b.add(up, gate)
    down = b.gemm([hmid], Gemm(te, dff, dm, groups=E, repeats=rep,
                               name="edown"), (T, dm))
    return b.add(down, x)


def _mamba_block(b: _B, x: str, cfg: ArchConfig, T: int, rep: int = 1,
                 state=None) -> str:
    """Mamba mixer projections (the scan itself carries no GEMM); `state`
    (decode) is the layer's recurrent SSM/conv state, consumed at the scan
    position (out_proj)."""
    dm = cfg.d_model
    din = cfg.mamba_expand * dm
    dr = max(1, (dm + 15) // 16)
    ds = cfg.mamba_d_state
    ip = b.gemm([x], Gemm(T, dm, 2 * din, repeats=rep, name="in_proj"),
                (T, 2 * din))
    xp = b.gemm([ip], Gemm(T, din, dr + 2 * ds, repeats=rep, name="x_proj"),
                (T, dr + 2 * ds))
    dt = b.gemm([xp], Gemm(T, dr, din, repeats=rep, name="dt_proj"),
                (T, din))
    op = b.gemm([dt] if state is None else [dt, state],
                Gemm(T, din, dm, repeats=rep, name="out_proj"), (T, dm))
    return b.add(op, x)


def _mlstm_block(b: _B, x: str, cfg: ArchConfig, T: int, rep: int = 1,
                 state=None) -> str:
    d = cfg.d_model
    din = 2 * d
    up = b.gemm([x], Gemm(T, d, 2 * din, repeats=rep, name="m_up"),
                (T, 2 * din))
    qkvg = b.gemm([up] if state is None else [up, state],
                  Gemm(T, din, 3 * din + 2 * cfg.num_heads, repeats=rep,
                       name="m_qkvg"), (T, 3 * din + 2 * cfg.num_heads))
    down = b.gemm([qkvg], Gemm(T, din, d, repeats=rep, name="m_down"),
                  (T, d))
    return b.add(down, x)


def _slstm_block(b: _B, x: str, cfg: ArchConfig, T: int, rep: int = 1,
                 state=None) -> str:
    d = cfg.d_model
    a = b.gemm([x] if state is None else [x, state],
               Gemm(T, d, 4 * d, repeats=rep, name="s_in"), (T, 4 * d))
    o = b.gemm([a], Gemm(T, d, d, repeats=rep, name="s_out"), (T, d))
    return b.add(o, x)


def transformer_block(cfg: ArchConfig, shape: ShapeConfig,
                      act_bits: float = DEFAULT_ACT_BITS) -> Graph:
    """One decoder layer as a DAG, following the `lm_workloads` lowering
    conventions (per-head score/value GEMMs via `groups`, sliding-window
    KV truncation) but keeping the residual edges: the block input stays
    live across the whole attention span, and the post-attention residual
    across the MLP — the transformer's connectivity cost."""
    d, B, Sq, Skv, eff_kv, T = _lm_dims(cfg, shape)
    b = _B(f"transformer_block[{shape.kind}]", act_bits)
    x = b.input((T, cfg.d_model))
    r1 = _attn_mixer(b, x, cfg, hd=d.head_dim, B=B, Sq=Sq, eff_kv=eff_kv,
                     T=T)
    _mlp_block(b, r1, cfg, T)
    return b.g


# ------------------------------------------------------- full-model serving --

def _layer_plan(cfg: ArchConfig):
    """Per-layer (mixer, mlp) kinds, mirroring the flat lowering's layer
    counting exactly: `is_attn_layer`/`is_moe_layer` for attention/MoE
    placement, mamba on the non-attention layers of hybrids, and the ssm
    family alternating sLSTM/mLSTM with n_mlstm = num_layers // 2."""
    plan = []
    for i in range(cfg.num_layers):
        if cfg.family == "ssm":
            plan.append(("mlstm" if i % 2 else "slstm", None))
            continue
        if cfg.is_attn_layer(i):
            mixer = "attn"
        elif cfg.family == "hybrid":
            mixer = "mamba"
        else:
            mixer = None
        if cfg.is_moe_layer(i):
            mlp = "moe"
        elif cfg.d_ff:
            mlp = "mlp"
        else:
            mlp = None
        plan.append((mixer, mlp))
    return plan


def _state_shape(cfg: ArchConfig, mixer: str, B: int, eff_kv: int, hd: int):
    """Decode-time per-layer serving-state tensor shape. KV caches are the
    real thing (2 x B x eff_kv x kv_heads x head_dim, sliding-window
    capped); recurrent states are the standard per-architecture fixed-size
    carries (mamba SSM+conv state, mLSTM matrix memory, sLSTM cell/gate
    registers)."""
    if mixer == "attn":
        return (2, B, eff_kv, cfg.num_kv_heads * hd)
    din = cfg.mamba_expand * cfg.d_model
    if mixer == "mamba":
        return (B, din, cfg.mamba_d_state + cfg.mamba_d_conv)
    if mixer == "mlstm":
        dh = max(1, 2 * cfg.d_model // max(cfg.num_heads, 1))
        return (B, cfg.num_heads, dh, dh)
    return (B, 4, cfg.d_model)              # slstm


def lm_graph(cfg: ArchConfig, shape: ShapeConfig,
             act_bits: float = DEFAULT_ACT_BITS) -> Graph:
    """Full-model serving graph: `transformer_block`-style layers stacked
    per `_layer_plan` across every family of the configs zoo, with the
    residual edges AND the serving state the flat lowering cannot express.

    Aggregated `flatten()` reproduces `extract_workloads(cfg, shape)` GEMM
    for GEMM (same (M, K, N, groups) keys, same total repeats — every
    closed-form metric is linear in repeats, so analyze_network agrees
    exactly; pinned by the flatten-equivalence test in test_scenarios).

    Serving state is held live for the whole pass by the terminal `output`
    sink: in decode, each layer's KV cache / recurrent state enters as an
    input tensor up front (all caches co-resident, as on a real serving
    box); in prefill, the K/V projections being written ARE the cache and
    are pinned the same way. Training pins nothing (no cache carried)."""
    d, B, Sq, Skv, eff_kv, T = _lm_dims(cfg, shape)
    rep = 3 if shape.kind == "train" else 1
    hd = d.head_dim
    plan = _layer_plan(cfg)
    b = _B(f"{cfg.name}[{shape.kind}]", act_bits)

    x = b.input((T, cfg.d_model))
    state = {}
    if shape.kind == "decode":
        for i, (mixer, _) in enumerate(plan):
            if mixer is not None:
                state[i] = b.input(_state_shape(cfg, mixer, B, eff_kv, hd))
    pinned = list(state.values())
    kv_out = pinned if shape.kind == "prefill" else None

    enc = None
    if cfg.family == "audio":               # bidirectional encoder stack
        Te = B * cfg.encoder_seq
        # the flat lowering routes the encoder through _attn_workloads,
        # which applies the sliding-window cap to ITS kv span too
        enc_kv = min(cfg.encoder_seq, cfg.sliding_window) \
            if cfg.sliding_window else cfg.encoder_seq
        enc = b.input((Te, cfg.d_model))
        for _ in range(cfg.encoder_layers):
            enc = _attn_mixer(b, enc, cfg, hd=hd, B=B, Sq=cfg.encoder_seq,
                              eff_kv=enc_kv, T=Te, rep=rep)
            enc = _mlp_block(b, enc, cfg, Te, rep=rep)

    cur = x
    for i, (mixer, mlp) in enumerate(plan):
        if mixer == "attn":
            cur = _attn_mixer(b, cur, cfg, hd=hd, B=B, Sq=Sq, eff_kv=eff_kv,
                              T=T, rep=rep, kv=state.get(i), kv_out=kv_out)
        elif mixer == "mamba":
            cur = _mamba_block(b, cur, cfg, T, rep=rep, state=state.get(i))
        elif mixer == "mlstm":
            cur = _mlstm_block(b, cur, cfg, T, rep=rep, state=state.get(i))
        elif mixer == "slstm":
            cur = _slstm_block(b, cur, cfg, T, rep=rep, state=state.get(i))
        if cfg.family == "audio":
            cur = _cross_attn(b, cur, enc, cfg, hd=hd, B=B, Sq=Sq,
                              Se=cfg.encoder_seq, T=T, rep=rep)
        if mlp == "moe":
            cur = _moe_block(b, cur, cfg, T, rep=rep)
        elif mlp == "mlp":
            cur = _mlp_block(b, cur, cfg, T, rep=rep)

    # unembedding (decode/prefill emit one position per sequence)
    t_out = B if shape.kind in ("decode", "prefill") else T
    logits = b.gemm([cur], Gemm(t_out, cfg.d_model, cfg.vocab_size,
                                repeats=rep, name="unembed"),
                    (t_out, cfg.vocab_size))
    b.g.add(Node("sink", "output", Tensor((0,), b.bits)),
            tuple([logits] + pinned))
    return b.g


GRAPH_ZOO: Dict[str, Callable[..., Graph]] = {
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


def build_graph(name: str, **kw) -> Graph:
    """Graph-IR counterpart of `cnn_zoo.get_workloads(name)`."""
    return GRAPH_ZOO[name](**kw)
