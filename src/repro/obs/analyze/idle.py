"""Device idle time put down to what the program was doing on the host.

A JAX profiler trace (``jax.profiler.trace(dir)``) holds, on one clock,
the device's operations, the program's spans (``repro:<layer>/<phase>``,
opened by :meth:`repro.obs.Tracer.region` and :func:`repro.obs.region`)
and JAX's own compile events.  :func:`load` reads those three out of the
``.xplane.pb`` files; :func:`attribute` works on the result alone, so it
can be checked on events made by hand:

  * each idle gap of the device is named by the innermost span covering
    its midpoint; a compile event that is innermost reads as
    ``<span>/compile`` (e.g. ``decide/kernel/compile``);
  * for each span name: how many spans, their seconds, and the device's
    idle seconds under them (at any depth), with the part under compile
    events split out, and the compiles inside them.

A compile event is one of :data:`COMPILE_EVENTS`, taken only from the
host lines that carry the program's spans.  ``lower_sharding_computation``
runs once for every program JAX has to build (it is then compiled, or
fetched from the persistent compile cache, which no event marks), so
the compiles inside a span are its lowerings.

CLI: ``python -m repro.obs.analyze idle TRACE_DIR``.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
from typing import Optional

from repro.obs.trace import PROFILER_PREFIX

__all__ = ["ProfilerTrace", "load", "attribute", "COMPILE_EVENTS",
           "LOWERING"]

#: JAX's host events of building a program, read as compile
COMPILE_EVENTS = ("lower_sharding_computation", "backend_compile",
                  "backend_compile_and_load")
#: the compile event that runs once per program built
LOWERING = "lower_sharding_computation"
#: the per-operation line of a device plane
OPS_LINE = "XLA Ops"


@dataclasses.dataclass
class ProfilerTrace:
    """What :func:`attribute` reads; times in ns on the profiler clock."""
    #: per device: ``[(op, start, end)]``
    device: dict[str, list[tuple[str, float, float]]]
    #: ``[(name, start, end, line)]`` of the program's spans, ``name``
    #: without the ``repro:`` prefix (``decide/kernel``)
    spans: list[tuple[str, float, float, int]]
    #: ``[(jax event, start, end, line)]`` on the spans' host lines
    compiles: list[tuple[str, float, float, int]]
    #: ``(start, end)`` of the window to read, or ``None`` for the
    #: extent of the program's spans
    window: Optional[tuple[float, float]] = None


def load(trace_dir: str, *, window_event: Optional[str] = None
         ) -> ProfilerTrace:
    """Read every ``.xplane.pb`` under ``trace_dir``.  ``window_event``
    names a host event (on any line) whose first occurrence bounds the
    window."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    out = ProfilerTrace({}, [], [])
    n_lines = 0
    for path in paths:
        for plane in ProfileData.from_file(path).planes:
            if plane.name.startswith("/device:"):
                out.device[plane.name] = [
                    (e.name, e.start_ns, e.end_ns)
                    for line in plane.lines if line.name == OPS_LINE
                    for e in line.events if e.duration_ns > 0]
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    n_lines += 1
                    spans, compiles = [], []
                    for e in line.events:
                        if e.name.startswith(PROFILER_PREFIX):
                            spans.append((e.name[len(PROFILER_PREFIX):],
                                          e.start_ns, e.end_ns, n_lines))
                        elif e.name in COMPILE_EVENTS:
                            compiles.append((e.name, e.start_ns, e.end_ns,
                                             n_lines))
                        elif e.name == window_event and out.window is None:
                            out.window = (e.start_ns, e.end_ns)
                    if spans:
                        out.spans += spans
                        out.compiles += compiles
    return out


def _union(intervals) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _intersect(a, b) -> list[tuple[float, float]]:
    """Intersection of two merged, sorted interval lists."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            out.append((s, e))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def _length(intervals) -> float:
    return sum(e - s for s, e in intervals)


def _innermost(events, starts, t: float):
    """The latest-starting of ``events`` (sorted by start, ``starts``
    their starts) that covers ``t``, else ``None``."""
    for i in range(bisect.bisect_right(starts, t) - 1, -1, -1):
        if events[i][1] > t:
            return events[i]
    return None


def attribute(trace: ProfilerTrace, *, top: int = 10) -> dict:
    """Idle gaps named by program span, and per span name its count,
    seconds, idle seconds and compiles (see the module docstring).
    Seconds of busy and idle are averaged over the ``devices`` that ran
    anything in the window, so ``idle_s`` of a name never exceeds
    ``window_s - busy_s``; with no device operation in the window (a
    CPU trace) every idle reads 0."""
    if trace.window is not None:
        t0, t1 = trace.window
    elif trace.spans:
        t0 = min(s for _, s, _, _ in trace.spans)
        t1 = max(e for _, _, e, _ in trace.spans)
    else:
        raise ValueError("no program span and no window in the trace")
    idle_per_dev, gaps, busy = [], [], []
    for ops in trace.device.values():
        merged = _union((max(s, t0), min(e, t1)) for _, s, e in ops
                        if e > t0 and s < t1)
        if not merged:
            continue
        busy.append(_length(merged))
        edges = [t0] + [x for iv in merged for x in iv] + [t1]
        idle = [(s, e) for s, e in zip(edges[0::2], edges[1::2]) if e > s]
        idle_per_dev.append(idle)
        gaps += idle
    n_dev = max(len(idle_per_dev), 1)

    def idle_under(intervals) -> float:
        return sum(_length(_intersect(idle, intervals))
                   for idle in idle_per_dev) / n_dev

    def clip(events):
        return [(n, max(s, t0), min(e, t1), ln) for n, s, e, ln in events
                if e > t0 and s < t1]

    spans, compiles = clip(trace.spans), clip(trace.compiles)
    program = {}
    for name in sorted({n for n, *_ in spans}):
        mine = [ev for ev in spans if ev[0] == name]
        cover = _union((s, e) for _, s, e, _ in mine)
        lines = {ln for *_, ln in mine}
        under = _intersect(_union((s, e) for _, s, e, ln in compiles
                                  if ln in lines), cover)
        lowerings = sum(1 for n, s, e, ln in compiles
                        if n == LOWERING and ln in lines
                        and any(a <= 0.5 * (s + e) < b for a, b in cover))
        program[name] = {
            "count": len(mine), "seconds": _length(cover) * 1e-9,
            "idle_s": idle_under(cover) * 1e-9,
            "compiles": lowerings, "compile_s": _length(under) * 1e-9,
            "compile_idle_s": idle_under(under) * 1e-9}

    # (start, end, name, line) by start and, of those that start
    # together, the outer first
    def by_start(events):
        out = sorted(((s, e, n, ln) for n, s, e, ln in events),
                     key=lambda ev: (ev[0], -ev[1]))
        return out, [ev[0] for ev in out]

    span_idx, comp_idx = by_start(spans), by_start(compiles)

    def gap_name(s: float, e: float) -> str:
        mid = 0.5 * (s + e)
        span = _innermost(*span_idx, mid)
        comp = _innermost(*comp_idx, mid)
        if comp is not None and span is None:
            return "compile"
        if span is None:
            return "outside spans"
        if comp is not None and comp[3] == span[3] and comp[0] >= span[0]:
            return f"{span[2]}/compile"
        return span[2]

    by_name: dict[str, float] = {}
    for s, e in gaps:
        name = gap_name(s, e)
        by_name[name] = by_name.get(name, 0.0) + (e - s) * 1e-9 / n_dev
    gaps.sort(key=lambda g: g[0] - g[1])
    window_s = (t1 - t0) * 1e-9
    return {"window_s": window_s, "devices": len(idle_per_dev),
            "busy_s": sum(busy) / n_dev * 1e-9,
            "idle_gaps": [[gap_name(s, e), (e - s) * 1e-9]
                          for s, e in gaps[:top]],
            "idle_by_span": by_name, "program": program}
