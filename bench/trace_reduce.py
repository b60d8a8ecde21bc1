"""Reduce a JAX profiler trace to device busy and idle time, time per
device operation, and the longest idle gaps by what the host was doing.

``load(dir)`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote into
``Events``: the device operations of each chip and the benchmark's own
host spans (``bench:<name>``, written by ``common.Spans``).  ``reduce``
works on ``Events`` alone, so it can be checked on events made by hand.
"""
from __future__ import annotations

import dataclasses
import glob
import os

#: the per-operation line of a TPU plane, where XLA records each op run
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench:"


@dataclasses.dataclass
class Events:
    #: per device: ``[(name, start_ns, end_ns)]`` of the operations
    device: dict[str, list[tuple[str, float, float]]]
    #: ``[(name, start_ns, end_ns)]`` of the benchmark's host spans
    host: list[tuple[str, float, float]]


#: how much of an operation's name the breakdown keeps
NAME_CHARS = 160


def load(trace_dir: str) -> Events:
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    device: dict[str, list] = {}
    host: list = []
    for path in paths:
        data = ProfileData.from_file(path)
        for plane in data.planes:
            if plane.name.startswith("/device:TPU:"):
                evs = []
                for line in plane.lines:
                    if line.name != OPS_LINE:
                        continue
                    evs.extend((e.name, e.start_ns, e.end_ns)
                               for e in line.events if e.duration_ns > 0)
                device[plane.name] = evs
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    host.extend((e.name[len(SPAN_PREFIX):], e.start_ns,
                                 e.end_ns) for e in line.events
                                if e.name.startswith(SPAN_PREFIX))
    return Events(device, host)


def union(intervals) -> list[tuple[float, float]]:
    """Merged, sorted ``(start, end)`` intervals."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(intervals, t0: float, t1: float):
    return [(max(s, t0), min(e, t1)) for s, e in intervals
            if e > t0 and s < t1]


def _host_activity(host, t0: float, t1: float, window_name: str) -> str:
    """The innermost benchmark span covering ``[t0, t1]``'s midpoint (the
    latest-starting one), else ``"outside calls"``."""
    mid = 0.5 * (t0 + t1)
    best = None
    for name, s, e in host:
        if name == window_name or not (s <= mid < e):
            continue
        if best is None or s > best[1]:
            best = (name, s)
    return best[0] if best else "outside calls"


def reduce(ev: Events, *, window_name: str = "window",
           top: int = 10) -> dict:
    """Busy and idle over the traced window (the host span
    ``window_name``), time per operation name, and the ``top`` longest
    idle gaps named by host activity.  Busy seconds are averaged over the
    devices that ran anything."""
    wins = [(s, e) for n, s, e in ev.host if n == window_name]
    if not wins:
        raise ValueError(f"no host span {window_name!r} in the trace")
    t0, t1 = wins[0]
    window_s = (t1 - t0) * 1e-9
    busy, per_op, gaps = [], {}, []
    for evs in ev.device.values():
        ops = [(n, max(s, t0), min(e, t1)) for n, s, e in evs
               if e > t0 and s < t1]
        if not ops:
            continue
        merged = union((s, e) for _, s, e in ops)
        busy.append(sum(e - s for s, e in merged) * 1e-9)
        for n, s, e in ops:
            per_op[n] = per_op.get(n, 0.0) + (e - s) * 1e-9
        edges = [t0] + [x for iv in merged for x in iv] + [t1]
        for gs, ge in zip(edges[0::2], edges[1::2]):
            if ge > gs:
                gaps.append((ge - gs, gs, ge))
    if not busy:
        return {"window_s": window_s, "busy_s": 0.0, "per_op": {},
                "device_ops": [], "idle_gaps": []}
    gaps.sort(reverse=True)
    idle = {}
    for d, gs, ge in gaps:
        name = _host_activity(ev.host, gs, ge, window_name)
        idle.setdefault(name, []).append(d * 1e-9)
    # the longest gaps, each named by what the host was doing in it
    idle_gaps = [[_host_activity(ev.host, gs, ge, window_name), d * 1e-9]
                 for d, gs, ge in gaps[:top]]
    device_ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
    return {"window_s": window_s, "busy_s": sum(busy) / len(busy),
            "per_op": per_op,
            # an op's name is its HLO text, which can run to kilobytes
            "device_ops": [[n[:NAME_CHARS], s] for n, s in device_ops],
            "idle_gaps": idle_gaps,
            "idle_by_host": {k: sum(v) for k, v in idle.items()}}


def op_seconds(reduced: dict, match) -> float | None:
    """Summed device seconds of the operations whose name ``match(name)``
    accepts; ``None`` where no operation matched."""
    hits = [s for n, s in reduced.get("per_op", {}).items() if match(n)]
    return sum(hits) if hits else None
