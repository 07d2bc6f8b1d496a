"""Zero-dependency event tracing for the simulators and the DSE drivers.

One `Tracer` records a flat event list — nestable B/E spans, `X` complete
events, async `b`/`e` request lifelines, `I` instants and `C` counter
samples — in ONE clock domain:

  * ``clock="wall"`` — host time (`time.perf_counter` relative to the
    tracer's birth); timestamps default to "now". The DSE drivers
    (`core.dse`, `core.search`), the sweep kernel's wrappers
    (`kernels.ops`) and the cost-table build trace their stages and
    lockstep rounds on this clock. An enabled wall-clock tracer also
    opens ``jax.profiler.TraceAnnotation("repro." + name)`` for every
    span, so under a JAX profiler session the spans land in its
    `.xplane.pb` on the same clock as the device's operations.
  * ``clock="sim"``  — simulated time; every event MUST carry an explicit
    timestamp (the simulation clock is the caller's, not the host's).
    `traffic.sim` / `fleet.sim` emit per-request lifecycle events here,
    which is what makes the export deterministic: a seeded replay traces
    to byte-identical JSON on every run.

Off by default, and OFF MUST BE FREE: every method begins with an
``enabled`` check, and hot loops are expected to hoist
``tr is not None and tr.enabled`` into a local before the loop so a
disabled tracer costs one attribute read per *call site*, not per event
(the 1M-request replay benchmark enforces <= 3% disabled overhead).

Events are stored as plain tuples ``(ph, name, track, ts, dur, ident,
args)`` with `ts`/`dur` in SECONDS of the tracer's clock domain;
`obs.export` converts to Chrome-trace microseconds. `track` is a free
string — the exporter maps each distinct track to its own Perfetto
thread lane (one per server/pool for simulated traces, one per sweep
stage for wall traces)."""
from __future__ import annotations

import time
from typing import List, Optional, Tuple

CLOCKS = ("wall", "sim")

# event tuple layout (kept a tuple, not a dataclass: emission is hot)
PH, NAME, TRACK, TS, DUR, ID, ARGS = range(7)


class _NullSpan:
    """Context manager returned by `span()` on a disabled tracer."""
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL_SPAN = _NullSpan()

# profiler annotations carry this prefix, to tell them from other host
# events in a JAX profiler trace
PROFILER_PREFIX = "repro."
_ANNOTATION = None


def _annotation(name: str):
    """Open a JAX profiler annotation for span `name` (jax imported on
    first use, so the tracer stays free of it until a wall span opens).
    Without a profiler session running it records nothing."""
    global _ANNOTATION
    if _ANNOTATION is None:
        from jax.profiler import TraceAnnotation as _ANNOTATION
    ann = _ANNOTATION(PROFILER_PREFIX + name)
    ann.__enter__()
    return ann


class _Span:
    __slots__ = ("_tr", "_name", "_track", "_args")

    def __init__(self, tr, name, track, args):
        self._tr = tr
        self._name = name
        self._track = track
        self._args = args

    def __enter__(self):
        self._tr.begin(self._name, self._track, **(self._args or {}))
        return self

    def __exit__(self, *exc):
        self._tr.end(self._track)
        return False


class Tracer:
    """Append-only event recorder for one clock domain.

    All emission methods no-op when ``enabled`` is False; flipping
    `enabled` mid-run is allowed (spans opened while enabled should be
    closed before disabling, or the trace will report unbalanced spans).
    """

    __slots__ = ("enabled", "clock", "events", "_stacks", "_anns", "_t0")

    def __init__(self, enabled: bool = True, clock: str = "wall"):
        if clock not in CLOCKS:
            raise ValueError(f"unknown clock {clock!r} (have {CLOCKS})")
        self.enabled = bool(enabled)
        self.clock = clock
        self.events: List[Tuple] = []
        self._stacks = {}               # track -> [span names] (B/E pairing)
        self._anns = {}                 # track -> [open profiler annotations]
        self._t0 = time.perf_counter()

    # ------------------------------------------------------------- clock --
    def now(self) -> float:
        """Wall seconds since tracer creation (wall clock only)."""
        return time.perf_counter() - self._t0

    def _ts(self, ts: Optional[float]) -> float:
        if ts is not None:
            return float(ts)
        if self.clock == "sim":
            raise ValueError("sim-clock tracer events need an explicit ts")
        return self.now()

    # ---------------------------------------------------------- emission --
    def begin(self, name: str, track: str = "main",
              ts: Optional[float] = None, **args) -> None:
        """Open a nested span on `track` (Chrome 'B'); on the wall clock
        also a profiler annotation, closed by the matching `end`."""
        if not self.enabled:
            return
        self._stacks.setdefault(track, []).append(name)
        if self.clock == "wall":
            self._anns.setdefault(track, []).append(_annotation(name))
        self.events.append(("B", name, track, self._ts(ts), None, None,
                            args or None))

    def end(self, track: str = "main", ts: Optional[float] = None,
            **args) -> None:
        """Close the innermost open span on `track` (Chrome 'E')."""
        if not self.enabled:
            return
        stack = self._stacks.get(track)
        if not stack:
            raise RuntimeError(f"end() with no open span on {track!r}")
        name = stack.pop()
        if self.clock == "wall":
            self._anns[track].pop().__exit__(None, None, None)
        self.events.append(("E", name, track, self._ts(ts), None, None,
                            args or None))

    def span(self, name: str, track: str = "main", **args):
        """``with tracer.span("stage"):`` — wall-clock B/E pair."""
        if not self.enabled:
            return _NULL_SPAN
        return _Span(self, name, track, args)

    def complete(self, name: str, track: str, ts: float, dur: float,
                 **args) -> None:
        """A closed span in one event (Chrome 'X'): known start + length."""
        if not self.enabled:
            return
        self.events.append(("X", name, track, float(ts), float(dur), None,
                            args or None))

    def instant(self, name: str, track: str = "main",
                ts: Optional[float] = None, **args) -> None:
        """Zero-duration marker (Chrome 'I', thread scope)."""
        if not self.enabled:
            return
        self.events.append(("I", name, track, self._ts(ts), None, None,
                            args or None))

    def counter(self, name: str, track: str = "main",
                ts: Optional[float] = None, **values) -> None:
        """Sampled counter/gauge series (Chrome 'C'); each keyword becomes
        one series on the counter track."""
        if not self.enabled:
            return
        self.events.append(("C", name, track, self._ts(ts), None, None,
                            values))

    def async_begin(self, name: str, track: str, ident, ts: float,
                    **args) -> None:
        """Open one lifeline of an overlapping family (Chrome 'b'): many
        ids may be in flight on one track — the per-request lane."""
        if not self.enabled:
            return
        self.events.append(("b", name, track, float(ts), None, ident,
                            args or None))

    def async_instant(self, name: str, track: str, ident, ts: float,
                      **args) -> None:
        if not self.enabled:
            return
        self.events.append(("n", name, track, float(ts), None, ident,
                            args or None))

    def async_end(self, name: str, track: str, ident, ts: float,
                  **args) -> None:
        if not self.enabled:
            return
        self.events.append(("e", name, track, float(ts), None, ident,
                            args or None))

    # ------------------------------------------------------------- query --
    def tracks(self) -> List[str]:
        """Distinct track names in first-appearance order."""
        seen, out = set(), []
        for ev in self.events:
            t = ev[TRACK]
            if t not in seen:
                seen.add(t)
                out.append(t)
        return out

    def open_spans(self) -> dict:
        """track -> list of still-open span names (empty when balanced)."""
        return {t: list(s) for t, s in self._stacks.items() if s}

    def clear(self) -> None:
        self.events.clear()
        self._stacks.clear()
        self._anns.clear()

    def __len__(self) -> int:
        return len(self.events)


# ------------------------------------------------- module-level wall tracer --
#
# The DSE drivers trace into this shared wall-clock tracer so a whole
# sweep (cost-table build -> lockstep rounds -> summaries) lands in one
# exportable timeline without threading a Tracer through every signature.

_TRACER = Tracer(enabled=False, clock="wall")


def tracer() -> Tracer:
    """The process-wide wall-clock tracer (disabled by default)."""
    return _TRACER


def set_tracer(tr: Tracer) -> Tracer:
    """Swap the process-wide tracer (returns the previous one)."""
    global _TRACER
    old, _TRACER = _TRACER, tr
    return old


def enable_tracing() -> Tracer:
    """Start a fresh enabled wall-clock tracer as the process tracer."""
    set_tracer(Tracer(enabled=True, clock="wall"))
    return _TRACER


def disable_tracing() -> Tracer:
    """Disable process-wide tracing (events so far are kept)."""
    _TRACER.enabled = False
    return _TRACER
