"""repro.obs — zero-perturbation observability: tracing, metrics,
Perfetto/Prometheus export.

The profiling premise of the paper — capture per-run data, make
offloading decisions predictable — applied to our own stack: every
runtime subsystem (both sim engines, the queueing layer, the online
oracle, both serving engines) accepts an ``obs=`` tracer and emits

  * per-task lifecycle **spans** (``sojourn ⊃ queue_wait · service ·
    transfer``), one track per node/pool, stamped in virtual time
    inside ``repro.sim`` and wall time in ``repro.serve``;
  * **instant events** for the control plane: replans, split re-picks,
    pool saturation, Page–Hinkley drift triggers, oracle refits,
    registry publishes;
  * **metrics** via :class:`MetricsRegistry` — counters, gauges, and
    fixed-boundary histograms with a Prometheus text-exposition dump;
  * **program spans on the profiler clock** via :func:`region` /
    ``Tracer.region`` — ``repro:<layer>/<phase>`` annotations around the
    decide, predict and serving hot paths, read against the device's
    operations by :mod:`repro.obs.analyze.idle`.

The hard contract is *zero perturbation*: the default
:data:`NULL_TRACER` no-ops every hook, and a live :class:`Tracer` only
observes values the engines already compute — no RNG draws, no float-
path changes — so traced runs are bit-for-bit identical to untraced
ones and every engine-equivalence pin holds with tracing on
(``tests/test_obs.py``).

Export: :func:`export_chrome` writes Chrome trace-event JSON loadable
in Perfetto; :func:`validate_chrome` is the span-pairing checker;
``Tracer.last(n)`` is the bounded flight recorder for post-mortems
(:func:`postmortem_dump` writes it out when an engine crashes).

The consumption layer lives one package down in :mod:`repro.obs.
analyze`: phase attribution and deadline-miss classification
(:func:`~repro.obs.analyze.attribute`), differential profiling
(:func:`~repro.obs.analyze.diff`), mergeable streaming quantiles
(:class:`QuantileSketch`, also a registry kind via
``MetricsRegistry.quantile``), and the ``regress`` CI gate
(``python -m repro.obs.analyze``).  See ``docs/observability.md``.
"""
from repro.obs.chrome import export_chrome, validate_chrome
from repro.obs.metrics import (LATENCY_BOUNDARIES, Counter, Gauge,
                               Histogram, MetricsRegistry)
from repro.obs.trace import (NULL_TRACER, InstantEvent, NullTracer,
                             SpanEvent, Tracer, postmortem_dump, region)

__all__ = [
    "Tracer", "NullTracer", "NULL_TRACER", "SpanEvent", "InstantEvent",
    "MetricsRegistry", "Counter", "Gauge", "Histogram",
    "LATENCY_BOUNDARIES", "export_chrome", "validate_chrome",
    "postmortem_dump", "region", "QuantileSketch",
]


def __getattr__(name):
    # QuantileSketch lives in the analyze layer above metrics; a lazy
    # attribute keeps `from repro.obs import QuantileSketch` working
    # without repro.obs importing its own consumption layer eagerly
    if name == "QuantileSketch":
        from repro.obs.analyze.sketch import QuantileSketch
        return QuantileSketch
    raise AttributeError(f"module {__name__!r} has no attribute "
                         f"{name!r}")
