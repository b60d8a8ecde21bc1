"""repro.obs.analyze — the consumption layer over the trace/metrics
substrate: turn recorded spans into answers.

:mod:`repro.obs` (the layer below) records with zero perturbation;
this package reads what it recorded:

  * :func:`attribute` / :class:`RunAttribution` — per-task and per-run
    phase attribution (``sojourn = queue_wait + service + transfer``),
    critical paths, and the deadline-miss classifier
    (:mod:`~repro.obs.analyze.attribution`);
  * :func:`diff` — differential profiling of two runs: per-phase
    quantile deltas, K-S statistics, top-k regressed tasks
    (:mod:`~repro.obs.analyze.diff`);
  * :class:`QuantileSketch` — mergeable fixed-centroid streaming
    quantiles; also a :class:`repro.obs.MetricsRegistry` kind via
    ``registry.quantile(name)`` (:mod:`~repro.obs.analyze.sketch`);
  * :func:`compare_rows` / the ``regress`` CLI — baseline regression
    gating for CI (:mod:`~repro.obs.analyze.regress`);
  * :mod:`~repro.obs.analyze.idle` — a JAX profiler trace's device idle
    time put down to the program's ``repro:`` spans and JAX's compile
    events.

CLI: ``python -m repro.obs.analyze {attribution,diff,idle,regress} ...``.

Import note: :mod:`repro.obs.metrics` lazily imports
:class:`QuantileSketch` *inside* ``MetricsRegistry.quantile`` — keep
this package's module-scope imports pointed at sibling submodules only
so that deferral never re-enters a half-initialised ``repro.obs``.
"""
from repro.obs.analyze.attribution import (MISS_CAUSES, RunAttribution,
                                           attribute)
from repro.obs.analyze.diff import DiffReport, PhaseDiff, diff, \
    ks_statistic
from repro.obs.analyze.regress import (RegressionReport, compare_files,
                                       compare_rows, load_rows, selftest)
from repro.obs.analyze.sketch import DEFAULT_QUANTILES, QuantileSketch
from repro.obs.analyze.tables import PHASES, TaskTable, TraceTable, load

__all__ = [
    "attribute", "RunAttribution", "MISS_CAUSES",
    "diff", "DiffReport", "PhaseDiff", "ks_statistic",
    "compare_rows", "compare_files", "load_rows", "selftest",
    "RegressionReport",
    "QuantileSketch", "DEFAULT_QUANTILES",
    "TraceTable", "TaskTable", "load", "PHASES",
]
