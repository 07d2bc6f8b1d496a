"""DAG IR for neural-network workloads with explicit activation tensors.

Nodes carry the existing layer specs (`Conv`/`FC`/`Gemm` from
`core/workloads.py`) plus the activation tensor they produce; edges are
tensors flowing producer -> consumer. Connectivity that the flat lists
erase is first-class here:

  * residual-add edges (ResNet/ResNeXt/MobileNet/EfficientNet): the skip
    tensor stays live across its whole bypass span;
  * dense-concat edges (DenseNet): every feature map in a block stays live
    until the transition layer;
  * branch/join edges (GoogLeNet/BN-Inception): sibling branches hold their
    outputs until the join.

Node kinds:

  ``input``   network input (materializes a tensor, no layer spec)
  ``gemm``    a Conv/FC/Gemm layer (the only kind `flatten()` emits)
  ``pool``    pooling/resampling (materializes, no GEMM — the flat lists
              omit these, so `flatten()` skips them too)
  ``add``     elementwise join (residual add / gated multiply): consumes
              all inputs, materializes a new tensor
  ``concat``  channel concatenation modeled as a *view*: it does NOT
              materialize — consumers of the concat keep the underlying
              source tensors live instead (DenseNet-style buffers are
              contiguous allocations, not copies)
  ``output``  graph sink: a non-materializing terminal consumer that pins
              its inputs live through the end of the schedule. Full-model
              serving graphs use it to keep KV-cache tensors resident for
              the whole pass (the cache is the state carried to the next
              decode step, not a transient)

``Graph.flatten()`` returns the GEMM workload tuples in node-insertion
order, which builders keep identical to the legacy `cnn_zoo` tables — so
every existing `analyze_network`/`grid_sweep` call site works unchanged on
`graph.flatten()` and produces bit-identical metrics.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, Iterable, List, Optional, Tuple

from repro.core.workloads import Conv, FC, Workload

VIEW_KINDS = frozenset({"concat", "output"})
KINDS = frozenset({"input", "gemm", "pool", "add", "concat", "output"})


@dataclasses.dataclass(frozen=True)
class Tensor:
    """An activation tensor: shape + per-element bitwidth."""
    shape: Tuple[int, ...]
    bits: float = 8.0

    @property
    def elems(self) -> int:
        return int(math.prod(self.shape))

    @property
    def size_bits(self) -> float:
        return self.elems * self.bits


@dataclasses.dataclass(frozen=True)
class Node:
    """One operation. `layer` is a Conv/FC/Gemm for kind == "gemm", else
    None. `out` is the tensor this node produces (for views: the virtual
    concatenated tensor, never separately allocated)."""
    name: str
    kind: str
    out: Tensor
    layer: Optional[object] = None

    @property
    def materializes(self) -> bool:
        return self.kind not in VIEW_KINDS


class Graph:
    """Append-only DAG; node insertion order is the legacy layer order."""

    def __init__(self, name: str = ""):
        self.name = name
        # Source nodes whose consumers may disagree on channel count:
        # inherited quirks of the legacy layer tables (e.g. BN-Inception
        # module 7 produces 608 channels, module 8's convs declare 576).
        self.channel_quirks: set = set()
        self.nodes: List[Node] = []
        self._by_name: Dict[str, Node] = {}
        self._preds: Dict[str, Tuple[str, ...]] = {}
        self._succs: Dict[str, List[str]] = {}

    def add(self, node: Node, preds: Iterable[str] = ()) -> str:
        preds = tuple(preds)
        if node.name in self._by_name:
            raise ValueError(f"duplicate node {node.name!r}")
        if node.kind not in KINDS:
            raise ValueError(f"unknown node kind {node.kind!r}")
        for p in preds:
            if p not in self._by_name:
                raise ValueError(f"{node.name}: unknown predecessor {p!r}")
        self.nodes.append(node)
        self._by_name[node.name] = node
        self._preds[node.name] = preds
        self._succs[node.name] = []
        for p in preds:
            self._succs[p].append(node.name)
        return node.name

    def node(self, name: str) -> Node:
        return self._by_name[name]

    def preds(self, name: str) -> Tuple[str, ...]:
        return self._preds[name]

    def succs(self, name: str) -> Tuple[str, ...]:
        return tuple(self._succs[name])

    def __len__(self) -> int:
        return len(self.nodes)

    # ---------------------------------------------------------------- API --

    def gemm_nodes(self) -> List[Node]:
        return [n for n in self.nodes if n.kind == "gemm"]

    def flatten(self) -> List[Workload]:
        """Legacy flat workload list: GEMM tuples in insertion order.

        Builders construct nodes in exactly the order of the `cnn_zoo`
        tables, so this reproduces `get_workloads(name)` bit-for-bit (the
        flatten-equivalence test pins it)."""
        return [n.layer.gemm() for n in self.gemm_nodes()]

    def storage_roots(self, name: str) -> Tuple[str, ...]:
        """The materialized tensors a node's output is backed by: itself if
        it materializes, else the union of its inputs' roots (views chain)."""
        n = self._by_name[name]
        if n.materializes:
            return (name,)
        roots: List[str] = []
        for p in self._preds[name]:
            for r in self.storage_roots(p):
                if r not in roots:
                    roots.append(r)
        return tuple(roots)

    def edge_bits(self, src: str, dst: str) -> float:
        """Bits that cross a partition boundary when `src` and `dst` land
        on different devices: the materialized storage roots backing src's
        output (a view ships its underlying tensors, not the virtual
        concatenation). Edges INTO a non-materializing `output` sink cost
        nothing — the sink only pins carried state (KV caches) that stays
        resident on whatever device produced it."""
        if src not in self._preds[dst]:
            raise ValueError(f"no edge {src!r} -> {dst!r} (edges are "
                             "directed producer -> consumer)")
        if self._by_name[dst].kind == "output":
            return 0.0
        return float(sum(self._by_name[r].out.size_bits
                         for r in self.storage_roots(src)))

    def cut_bits(self, left: Iterable[str]) -> float:
        """Total bits crossing the cut from `left` to the rest of the
        graph: every materialized root tensor produced inside `left` with
        at least one consumer outside it ships ONCE (a tensor consumed by
        several right-side nodes is multicast, not re-sent per edge).
        `output`-sink consumers are excluded, same as :meth:`edge_bits` —
        this is the activation traffic a pipeline boundary pays, which the
        fleet interconnect model (repro.fleet.interconnect) prices in
        cycles and Eq. 1-relative energy."""
        left = set(left)
        shipped: set = set()
        for n in self.nodes:
            if n.name in left or n.kind == "output":
                continue
            for p in self._preds[n.name]:
                for r in self.storage_roots(p):
                    if r in left:
                        shipped.add(r)
        return float(sum(self._by_name[r].out.size_bits for r in shipped))

    def as_chain(self) -> "Graph":
        """Connectivity-ablated copy: the same materializing nodes in
        insertion order, linked into a pure chain (joins/views dropped).

        This is the implicit topology of the legacy flat lists — each layer
        consumes only its immediate predecessor — and the baseline against
        which the connectivity cost (peak-occupancy ratio) is measured.
        `flatten()` of the chain equals `flatten()` of the original."""
        g = Graph(self.name + "+chain")
        prev: Optional[str] = None
        for n in self.nodes:
            if not n.materializes or n.kind == "add":
                continue   # joins/views carry no layer; drop them
            g.add(Node(n.name, n.kind, n.out, n.layer),
                  () if prev is None else (prev,))
            prev = n.name
        return g

    def validate(self) -> None:
        """Shape-consistency checks catching builder bugs: conv inputs must
        match (h_in, w_in, c_in); FC inputs must carry d_in elements per
        batch row; joins must agree on element count."""
        for n in self.nodes:
            preds = [self._by_name[p] for p in self._preds[n.name]]
            if n.kind == "input":
                assert not preds, n.name
                continue
            assert preds, f"{n.name}: no inputs"
            if n.kind == "gemm" and isinstance(n.layer, Conv):
                (src,) = preds
                h, w, c = src.out.shape
                assert (h, w) == (n.layer.h_in,
                                  n.layer.w_in or n.layer.h_in), \
                    f"{n.name}: spatial {src.out.shape} vs {n.layer}"
                assert c == n.layer.c_in \
                    or self._preds[n.name][0] in self.channel_quirks, \
                    f"{n.name}: channels {c} vs c_in={n.layer.c_in}"
                assert n.out.shape == (n.layer.h_out, n.layer.w_out,
                                       n.layer.c_out), n.name
            elif n.kind == "gemm" and isinstance(n.layer, FC):
                (src,) = preds
                assert src.out.elems == n.layer.d_in * n.layer.batch, \
                    f"{n.name}: {src.out.elems} != d_in {n.layer.d_in}"
            elif n.kind == "add":
                # every input matches the output, or is a per-channel
                # vector broadcast over it (a gating multiply)
                assert all(p.out.elems == n.out.elems
                           or p.out.elems == n.out.shape[-1]
                           for p in preds) \
                    and preds[0].out.elems == n.out.elems, \
                    f"{n.name}: mismatched join {[p.out.shape for p in preds]}"
            elif n.kind == "concat":
                assert n.out.elems == sum(p.out.elems for p in preds), \
                    f"{n.name}: concat elems"
