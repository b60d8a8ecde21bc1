"""Zero-perturbation tracing: per-task lifecycle spans, instant events,
and a bounded flight recorder.

A :class:`Tracer` collects two kinds of structured events:

``SpanEvent``
    a named interval on a *track* — in the simulators a track is one
    node (or pool) and spans are the task lifecycle
    (``sojourn ⊃ queue_wait · service · transfer``); in the serving
    engines a track is the engine and spans are prefill/decode phases.
``InstantEvent``
    a point event — replan, split re-pick, pool saturation,
    Page–Hinkley drift trigger, oracle refit, registry publish.

:meth:`~NullTracer.region` puts the program's hot layers on the
profiler's clock: it always opens a ``jax.profiler.TraceAnnotation``
named ``repro:<track>/<name>`` (free unless a profiler trace is being
taken, where it lands on the same timeline as the device's operations
and JAX's own compile events), and on a live :class:`Tracer` it also
records a ``SpanEvent`` on the caller's clock.  Library code with no
``obs=`` seam uses the module-level :func:`region` (the
:data:`NULL_TRACER`'s).

Timestamps are *whatever clock the caller lives on*: virtual seconds
inside :mod:`repro.sim` (the engines pass event-loop / slab times —
the tracer itself never reads a wall clock for them, keeping
``repro.sim`` DET002-clean), wall seconds in :mod:`repro.serve` and the
benchmarks (callers pass their already-measured ``perf_counter``
values).  The tracer only *observes* values the engines already
compute: it draws no RNG, touches no float path, and with the
:data:`NULL_TRACER` default every hook is a no-op — which is what makes
the traced and untraced runs bit-for-bit identical (pinned in
``tests/test_obs.py``).

Ingestion paths mirror :class:`repro.sim.telemetry.Telemetry`:

  * :meth:`Tracer.span` / :meth:`Tracer.instant` /
    :meth:`Tracer.task_spans` — the event loop's per-event path;
  * :meth:`Tracer.span_arrays` — the fleet engine's slab path: one call
    ingests parallel columns for a whole run's completions, deferred
    and only materialised into span objects on first read.

The last ``ring`` events (spans and instants interleaved in ingestion
order) are kept in a bounded flight-recorder deque — after a
deadline miss or a drift trigger, :meth:`Tracer.last` replays the
recent history for a post-mortem without holding the full trace.

Export: :meth:`Tracer.export_chrome` writes Chrome trace-event JSON
(loadable in Perfetto / ``chrome://tracing``) via
:mod:`repro.obs.chrome`.
"""
from __future__ import annotations

import dataclasses
from collections import deque
from typing import Optional, Sequence

__all__ = ["SpanEvent", "InstantEvent", "NullTracer", "Tracer",
           "NULL_TRACER", "postmortem_dump", "region"]

#: prefix of the program's spans in a profiler trace
PROFILER_PREFIX = "repro:"

_TraceAnnotation = None


def _annotation(track: str, name: str):
    """The profiler's host event ``repro:<track>/<name>``.  jax is
    imported on first use, so importing :mod:`repro.obs` does not pull
    it in."""
    global _TraceAnnotation
    if _TraceAnnotation is None:
        from jax.profiler import TraceAnnotation
        _TraceAnnotation = TraceAnnotation
    return _TraceAnnotation(f"{PROFILER_PREFIX}{track}/{name}")


@dataclasses.dataclass(frozen=True)
class SpanEvent:
    """One named interval ``[t0, t1]`` on ``(track, tid)``.

    ``track`` maps to a Chrome trace *process* (one per node / pool /
    engine), ``tid`` to a thread within it (one per task, so each
    task's lifecycle renders as its own row and B/E nesting is exact).
    """
    track: str
    tid: int
    name: str
    t0: float
    t1: float
    args: Optional[dict] = None

    @property
    def duration(self) -> float:
        return self.t1 - self.t0


@dataclasses.dataclass(frozen=True)
class InstantEvent:
    """One point event at ``ts`` on ``(track, tid)``."""
    track: str
    tid: int
    name: str
    ts: float
    args: Optional[dict] = None


class NullTracer:
    """The no-op tracer — the default for every ``obs=`` seam.

    Every hook returns immediately; hot paths additionally guard on
    :attr:`enabled` so that with tracing off not even the event's
    argument tuple is built.  Keeping the interface on a real class
    (rather than ``None`` checks at every call site) means
    instrumentation reads as straight-line code.
    """

    __slots__ = ()
    enabled = False

    def span(self, track: str, name: str, t0: float, t1: float, *,
             tid: int = 0, args: Optional[dict] = None) -> None:
        pass

    def instant(self, track: str, name: str, ts: float, *,
                tid: int = 0, args: Optional[dict] = None) -> None:
        pass

    def task_spans(self, track: str, tid: int, name: str,
                   arrived_s: float, started_s: float, finished_s: float,
                   *, transfer_s: float = 0.0,
                   args: Optional[dict] = None) -> None:
        pass

    def span_arrays(self, tracks, tids, names, arrived_s, started_s,
                    finished_s, *, transfer_s=None,
                    args_cols=None) -> None:
        pass

    def instant_arrays(self, track, name, ts, *, tid: int = 0,
                       args_cols=None) -> None:
        pass

    def region(self, track: str, name: str, *, tid: int = 0,
               args: Optional[dict] = None, now=None):
        """Context manager around one phase of the program: the profiler
        annotation ``repro:<track>/<name>`` (no span is recorded here)."""
        return _annotation(track, name)

    def last(self, n: int = 64) -> list:
        return []

    def export_chrome(self, path: str) -> None:
        raise ValueError(
            "cannot export a trace from the no-op tracer — pass "
            "obs=Tracer() to the run you want traced")


#: module-level singleton every ``obs=None`` seam resolves to
NULL_TRACER = NullTracer()


class Tracer(NullTracer):
    """Collecting tracer (see module docstring for the event model).

    ``ring`` bounds the flight-recorder deque (most recent events, spans
    and instants interleaved in ingestion order).  The tracer is
    append-only and clock-agnostic: callers stamp every event
    themselves, so one class serves virtual-time simulation and
    wall-time serving alike.
    """

    __slots__ = ("spans", "instants", "_pending", "_ring")
    enabled = True

    def __init__(self, ring: int = 4096):
        self.spans: list[SpanEvent] = []
        self.instants: list[InstantEvent] = []
        self._pending: list[tuple] = []      # deferred column batches
        self._ring: deque = deque(maxlen=int(ring))

    # -- ingestion: per-event path ----------------------------------------
    def span(self, track: str, name: str, t0: float, t1: float, *,
             tid: int = 0, args: Optional[dict] = None) -> None:
        """Record one complete interval (callers know both endpoints —
        the sim emits lifecycle spans at the completion event, serving
        emits phase spans from already-measured wall times)."""
        if t1 < t0:
            raise ValueError(f"span {name!r} ends before it starts "
                             f"({t1} < {t0})")
        if self._pending:
            self._materialise()
        ev = SpanEvent(str(track), int(tid), str(name), float(t0),
                       float(t1), args)
        self.spans.append(ev)
        self._ring.append(ev)

    def instant(self, track: str, name: str, ts: float, *,
                tid: int = 0, args: Optional[dict] = None) -> None:
        if self._pending:
            self._materialise()
        ev = InstantEvent(str(track), int(tid), str(name), float(ts),
                          args)
        self.instants.append(ev)
        self._ring.append(ev)

    def task_spans(self, track: str, tid: int, name: str,
                   arrived_s: float, started_s: float, finished_s: float,
                   *, transfer_s: float = 0.0,
                   args: Optional[dict] = None) -> None:
        """One task's lifecycle as properly-nested spans on its own
        ``(track, tid)`` row::

            sojourn   [arrived, finished]
              queue_wait [arrived, started]          (omitted if 0)
              service    [started, max(started, finished - transfer)]
              transfer   [service end, finished]  (omitted if 0)

        The ``sojourn`` span carries ``args`` (split, deadline, ...).
        The service end is clamped to ``started``: with a zero service
        time, ``finished - transfer`` can round below ``started``.
        """
        arrived_s = float(arrived_s)
        started_s = float(started_s)
        finished_s = float(finished_s)
        transfer_s = float(transfer_s)
        self.span(track, "sojourn", arrived_s, finished_s, tid=tid,
                  args={"task": name, **(args or {})})
        if started_s > arrived_s:
            self.span(track, "queue_wait", arrived_s, started_s, tid=tid)
        service_end = max(finished_s - transfer_s, started_s)
        self.span(track, "service", started_s, service_end, tid=tid)
        if transfer_s > 0.0:
            self.span(track, "transfer", service_end, finished_s,
                      tid=tid)

    def region(self, track: str, name: str, *, tid: int = 0,
               args: Optional[dict] = None, now=None) -> "_Region":
        """Context manager around one phase of the program: the profiler
        annotation ``repro:<track>/<name>``, and a span on ``(track,
        tid)`` stamped by ``now()`` (the caller's clock) on entry and
        exit.  ``args`` is read when the region closes, so the caller
        may add what it learnt inside (e.g. the split it chose)."""
        if now is None:
            raise ValueError(
                f"region {track}/{name}: a live Tracer stamps spans on "
                "the caller's clock; pass now=")
        return _Region(self, track, name, tid, args, now)

    # -- ingestion: the fleet engine's slab path --------------------------
    def span_arrays(self, tracks: Sequence[str], tids, names,
                    arrived_s, started_s, finished_s, *,
                    transfer_s=None, args_cols=None) -> None:
        """Batched :meth:`task_spans`: parallel columns (all length n)
        for one slab of completed tasks, deferred — equivalent to n
        ``task_spans`` calls in column order, but the hot loop only pays
        one tuple append (mirrors ``Telemetry.complete_arrays``).

        ``args_cols`` maps arg key -> a parallel column stamped onto
        each row's ``sojourn`` span (``deadline_s``, ``split``, ...);
        ``None`` entries in a column mean "no such arg for this row"."""
        n = len(names)
        for label, col in (("tracks", tracks), ("tids", tids),
                           ("arrived_s", arrived_s),
                           ("started_s", started_s),
                           ("finished_s", finished_s)):
            if len(col) != n:
                raise ValueError(f"column {label} has {len(col)} rows, "
                                 f"expected {n}")
        if transfer_s is not None and len(transfer_s) != n:
            raise ValueError(f"column transfer_s has {len(transfer_s)} "
                             f"rows, expected {n}")
        for key, col in (args_cols or {}).items():
            if len(col) != n:
                raise ValueError(f"args column {key!r} has {len(col)} "
                                 f"rows, expected {n}")
        self._pending.append(("spans", list(tracks), tids, list(names),
                              arrived_s, started_s, finished_s,
                              transfer_s, args_cols))

    def instant_arrays(self, track: str, name: str, ts, *, tid: int = 0,
                       args_cols: Optional[dict] = None) -> None:
        """Batched :meth:`instant`: one deferred column append for a run
        of same-named instants (``ts`` is the timestamp column;
        ``args_cols`` maps arg key -> a parallel column).  Equivalent to
        ``len(ts)`` instant calls in column order."""
        n = len(ts)
        for key, col in (args_cols or {}).items():
            if len(col) != n:
                raise ValueError(f"args column {key!r} has {len(col)} "
                                 f"rows, expected {n}")
        self._pending.append(("instants", str(track), str(name), ts,
                              int(tid), args_cols))

    def _materialise(self) -> None:
        batches, self._pending = self._pending, []
        for batch in batches:
            if batch[0] == "spans":
                (_, tracks, tids, names, arrived, started, finished,
                 transfer, args_cols) = batch
                for k in range(len(names)):
                    args = None
                    if args_cols is not None:
                        args = {key: col[k].item()
                                if hasattr(col[k], "item") else col[k]
                                for key, col in args_cols.items()
                                if col[k] is not None}
                        args = args or None
                    self.task_spans(
                        tracks[k], int(tids[k]), names[k],
                        float(arrived[k]), float(started[k]),
                        float(finished[k]),
                        transfer_s=0.0 if transfer is None
                        else float(transfer[k]),
                        args=args)
            else:
                _, track, name, ts, tid, args_cols = batch
                for k in range(len(ts)):
                    self.instant(
                        track, name, float(ts[k]), tid=tid,
                        args=None if args_cols is None else
                        {key: col[k].item()
                         if hasattr(col[k], "item") else col[k]
                         for key, col in args_cols.items()})

    # -- reads ------------------------------------------------------------
    def __len__(self) -> int:
        n = len(self.spans) + len(self.instants)
        return n + sum(len(b[3]) for b in self._pending)

    def all_spans(self) -> list[SpanEvent]:
        if self._pending:
            self._materialise()
        return self.spans

    def all_instants(self) -> list[InstantEvent]:
        if self._pending:
            self._materialise()
        return self.instants

    def last(self, n: int = 64) -> list:
        """The flight recorder: the most recent ``min(n, ring)`` events
        in ingestion order — the post-mortem view after a deadline miss
        or drift trigger."""
        if self._pending:
            self._materialise()
        if n <= 0:
            return []
        buf = list(self._ring)
        return buf[-int(n):]

    # -- export -----------------------------------------------------------
    def export_chrome(self, path: str) -> dict:
        """Write the trace as Chrome trace-event JSON (Perfetto /
        ``chrome://tracing``); returns the trace object.  One Chrome
        *process* per track, one *thread* per tid; lifecycle spans emit
        matched B/E pairs with children nested inside parents."""
        from repro.obs.chrome import export_chrome
        return export_chrome(self, path)


class _Region:
    """:meth:`Tracer.region`'s context manager."""

    __slots__ = ("tracer", "track", "name", "tid", "args", "now", "ann",
                 "t0")

    def __init__(self, tracer: Tracer, track: str, name: str, tid: int,
                 args: Optional[dict], now):
        self.tracer, self.track, self.name = tracer, track, name
        self.tid, self.args, self.now = tid, args, now
        self.ann = _annotation(track, name)

    def __enter__(self) -> "_Region":
        self.ann.__enter__()
        self.t0 = self.now()
        return self

    def __exit__(self, *exc) -> None:
        t1 = self.now()
        self.ann.__exit__(*exc)
        self.tracer.span(self.track, self.name, self.t0, t1, tid=self.tid,
                         args=self.args)


#: :meth:`NullTracer.region` of :data:`NULL_TRACER`: the profiler
#: annotation alone, for library code that has no ``obs=`` seam
region = NULL_TRACER.region


def postmortem_dump(tracer, *, clock_s: float, error: str = "",
                    path: str = "results/postmortem.json",
                    n: int = 64) -> Optional[dict]:
    """Flight-recorder post-mortem: the last ``n`` traced events plus
    the crashing clock reading, written to ``path`` and summarised on
    stderr.  The engines call this from their crash handlers *before*
    re-raising — with the :data:`NULL_TRACER` (tracing off) it is a
    no-op, and any failure inside the dump itself is swallowed so a
    broken disk never masks the original exception.  Returns the dump
    dict (or None when disabled / failed)."""
    if not getattr(tracer, "enabled", False):
        return None
    try:
        import json
        import os
        import sys
        events = [{"kind": type(ev).__name__, **dataclasses.asdict(ev)}
                  for ev in tracer.last(n)]
        dump = {"clock_s": float(clock_s), "error": str(error),
                "n_events": len(events), "events": events}
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        with open(path, "w") as f:
            json.dump(dump, f, indent=1, default=float)
        print(f"[repro.obs] post-mortem: {len(events)} flight-recorder "
              f"events at t={clock_s:.6g}s -> {path}"
              + (f" ({error})" if error else ""), file=sys.stderr)
        return dump
    except Exception:
        return None
