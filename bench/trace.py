"""Reduction of a profiler trace to device busy time, time per device
operation, and idle gaps attributed to the benchmark's own host spans.

Inputs are plain interval lists on the profiler's clock, in ns: device
operations `(name, start, end, device)` and host spans `(name, start,
end)`, the spans properly nested as one host thread opens them. `load`
reads both from a JAX profiler `.xplane.pb`: the operations of every
chip's "XLA Ops" line, named by their HLO instruction (such as
`%dse_eval_batched.1`, the sweep kernel's custom call), and the host
events whose names start with `SPAN_PREFIX` (written by
`jax.profiler.TraceAnnotation`).
"""
from __future__ import annotations

import glob
import os
from collections import defaultdict

SPAN_PREFIX = "bench."
OPS_LINE = "XLA Ops"


def load(log_dir):
    """(device ops, host spans, chip count) of the newest trace."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        return [], [], 0
    pd = ProfileData.from_file(paths[-1])
    ops, spans, devices = [], [], 0
    for plane in pd.planes:
        # a chip is a device plane with an ops line (not, for example,
        # the runtime's "/device:CUSTOM:..." planes)
        device = plane.name.startswith("/device:") and any(
            line.name == OPS_LINE for line in plane.lines)
        dev = devices
        devices += device
        for line in plane.lines:
            if device and line.name != OPS_LINE:
                continue
            for e in line.events:
                start = int(e.start_ns)
                end = start + int(e.duration_ns)
                if device:
                    # the HLO instruction's name, without its text
                    ops.append((e.name.split(" = ")[0], start, end, dev))
                elif e.name.startswith(SPAN_PREFIX):
                    spans.append((e.name[len(SPAN_PREFIX):], start, end))
    return ops, spans, devices


def _merge(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _segments(spans):
    """Cut the host timeline into segments, each labelled with the
    innermost span open over it."""
    events = sorted([(s, 1, -e, n) for n, s, e in spans]
                    + [(e, 0, 0, n) for n, s, e in spans])
    out, stack, prev = [], [], None
    for t, is_start, _, name in events:
        if stack and prev is not None and t > prev:
            out.append((prev, t, stack[-1]))
        if is_start:
            stack.append(name)
        elif name in stack:
            del stack[len(stack) - 1 - stack[::-1].index(name)]
        prev = t
    return out


def reduce(ops, spans, window, devices=1):
    """Busy and idle accounting of `ops` inside `window` = (start, end).

    Returns `busy_s` (each device's union of op intervals, averaged over
    `devices`), `window_s`, `op_s` (seconds per op name, summed over
    devices) and `idle_s` (seconds in which no device was busy, per
    innermost host span; "none" where no span was open)."""
    lo, hi = window
    clipped = [(n, max(s, lo), min(e, hi), d) for n, s, e, d in ops
               if e > lo and s < hi]
    op_s = defaultdict(float)
    per_dev = defaultdict(list)
    for n, s, e, d in clipped:
        op_s[n] += (e - s) * 1e-9
        per_dev[d].append((s, e))
    busy_ns = sum(e - s for iv in per_dev.values() for s, e in _merge(iv))
    busy = _merge([(s, e) for _, s, e, _ in clipped])
    gaps, t = [], lo
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    idle = defaultdict(float)
    segs = _segments(spans)
    i = 0
    for gs, ge in gaps:
        covered = 0
        while i < len(segs) and segs[i][1] <= gs:
            i += 1
        j = i
        while j < len(segs) and segs[j][0] < ge:
            ov = min(ge, segs[j][1]) - max(gs, segs[j][0])
            if ov > 0:
                idle[segs[j][2]] += ov * 1e-9
                covered += ov
            j += 1
        if ge - gs > covered:
            idle["none"] += (ge - gs - covered) * 1e-9
    return {"busy_s": busy_ns * 1e-9 / max(devices, 1),
            "window_s": (hi - lo) * 1e-9, "op_s": dict(op_s),
            "idle_s": dict(idle)}


def top(d, k=10):
    """The `k` largest entries of a {name: seconds} dict, as pairs."""
    return [[n, v] for n, v in sorted(d.items(), key=lambda x: -x[1])[:k]]
