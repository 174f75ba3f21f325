"""From a profiler trace to device busy time, per-op time and idle gaps.

The JAX profiler writes ``<dir>/plugins/profile/<run>/*.xplane.pb``;
:func:`load_xplane` flattens it into :class:`Event` rows (plane, line,
name, start and duration in ns, and the event's stats on device
planes).  Everything else here works on those rows, so the reduction is
tested on synthetic traces.

Clock: host and device events of one trace share the profiler's
timebase.  The benchmark enters one ``jax.profiler.TraceAnnotation``
(:data:`WINDOW_MARKER`) when its window opens; that event's start on the
profiler clock and the host clock read at the same moment map the
program's own spans (``time.perf_counter`` seconds) onto the trace.
"""
from __future__ import annotations

import dataclasses
import glob
import os
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

WINDOW_MARKER = "bench.window_start"
DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclasses.dataclass
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float
    stats: Optional[dict] = None

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def load_xplane(path: str) -> List[Event]:
    """Every event of the trace; stats are kept on device planes only
    (host planes hold many events and their stats are not read)."""
    from jax.profiler import ProfileData

    out: List[Event] = []
    for plane in ProfileData.from_file(path).planes:
        device = is_device_plane(plane.name)
        for line in plane.lines:
            for ev in line.events:
                stats = None
                if device:
                    try:
                        stats = {k: v for k, v in ev.stats}
                    except (TypeError, ValueError):
                        stats = None
                out.append(Event(plane.name, line.name, ev.name,
                                 float(ev.start_ns), float(ev.duration_ns),
                                 stats))
    return out


def is_device_plane(name: str) -> bool:
    return name.startswith(DEVICE_PLANE_PREFIX)


def device_planes(events: Iterable[Event]) -> List[str]:
    return sorted({e.plane for e in events if is_device_plane(e.plane)})


def device_ops(events: Iterable[Event], plane: Optional[str] = None
               ) -> List[Event]:
    """Op-level device events: the ``XLA Ops`` line of each device
    plane (of ``plane`` alone, if given)."""
    return [e for e in events if is_device_plane(e.plane)
            and e.line == OPS_LINE and (plane is None or e.plane == plane)]


def device_modules(events: Iterable[Event]) -> List[Event]:
    """Program-level device events: one per executable launch."""
    return [e for e in events if is_device_plane(e.plane)
            and e.line == MODULES_LINE]


def marker_ns(events: Iterable[Event], name: str = WINDOW_MARKER) -> float:
    starts = [e.start_ns for e in events
              if e.name == name and not is_device_plane(e.plane)]
    if not starts:
        raise ValueError(f"trace holds no {name!r} marker")
    return min(starts)


def clip(events: Iterable[Event], lo: float, hi: float
         ) -> List[Tuple[float, float]]:
    """[start, end) intervals of ``events`` clipped to [lo, hi)."""
    out = []
    for e in events:
        a, b = max(e.start_ns, lo), min(e.end_ns, hi)
        if b > a:
            out.append((a, b))
    return out


def union(intervals: Sequence[Tuple[float, float]]
          ) -> List[Tuple[float, float]]:
    """Merged, sorted, disjoint cover of ``intervals``."""
    merged: List[List[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def busy_ns(events: Iterable[Event], lo: float, hi: float) -> float:
    return sum(b - a for a, b in union(clip(events, lo, hi)))


def idle_gaps(events: Iterable[Event], lo: float, hi: float
              ) -> List[Tuple[float, float]]:
    """The stretches of [lo, hi) in which no event runs."""
    gaps, t = [], lo
    for a, b in union(clip(events, lo, hi)):
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if hi > t:
        gaps.append((t, hi))
    return gaps


def time_by_name(events: Iterable[Event], lo: float, hi: float,
                 short: bool = False) -> Dict[str, float]:
    """Summed duration (ns) of each event name, clipped to [lo, hi).
    ``short`` keeps an HLO op's name (``%fusion.3``), not its text."""
    out: Dict[str, float] = {}
    for e in events:
        a, b = max(e.start_ns, lo), min(e.end_ns, hi)
        if b > a:
            name = e.name.split(" = ", 1)[0] if short else e.name
            out[name] = out.get(name, 0.0) + (b - a)
    return out


# Spans that cover waiting rather than work, and the per-ticket roots:
# a gap is labelled by the innermost span of what the host was doing.
WAITING_SPANS = frozenset({"ticket", "queue", "inbox"})


def label_gaps(gaps: Sequence[Tuple[float, float]], spans: Sequence[dict],
               to_trace_ns) -> Dict[str, float]:
    """Idle time (ns) by the innermost program span open at each gap's
    midpoint (``"no span"`` where none is).  ``spans`` are tracer
    entries (``name``, ``t0``, ``t1`` on the host clock);
    ``to_trace_ns`` maps a host-clock second onto the trace."""
    import numpy as np

    work = [s for s in spans
            if s.get("kind", "span") == "span" and s["t1"] is not None
            and s["name"] not in WAITING_SPANS]
    starts = np.array([to_trace_ns(s["t0"]) for s in work], np.float64)
    ends = np.array([to_trace_ns(s["t1"]) for s in work], np.float64)
    names = [s["name"] for s in work]
    out: Dict[str, float] = {}
    for a, b in gaps:
        mid = 0.5 * (a + b)
        open_ = np.flatnonzero((starts <= mid) & (mid < ends))
        label = (names[open_[np.argmin(ends[open_] - starts[open_])]]
                 if len(open_) else "no span")
        out[label] = out.get(label, 0.0) + (b - a)
    return out


def top(d: Dict[str, float], n: int = 10, scale: float = 1e-9
        ) -> List[list]:
    """The ``n`` largest entries as ``[name, value * scale]``."""
    return [[k, v * scale] for k, v in
            sorted(d.items(), key=lambda kv: -kv[1])[:n]]


# The AOT serve executable is ``jax.jit(ShardedExecutor._serve_fn)``;
# its device module carries the function's name.
SERVE_MODULE = "_serve_fn"


def per_batch_ms(trace: Optional[dict], n_batches: int, serve: bool
                 ) -> Optional[float]:
    """Device time (ms) per micro-batch of the serve executable's
    module events (``serve``) or of every other module."""
    if not trace or not n_batches:
        return None
    mods = [m for m in trace["modules"] if (SERVE_MODULE in m.name) == serve]
    if not mods:
        return None
    return busy_ns(mods, trace["lo"], trace["hi"]) * 1e-6 / n_batches
