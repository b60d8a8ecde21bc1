"""Shared pieces of the chip benchmark: module lookup by name, seeds, the
compile clock, host spans, the traced sub-window and small statistics.

Nothing here touches a device at import time.
"""
from __future__ import annotations

import contextlib
import importlib.util
import json
import math
import sys
import time
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path, name: str | None = None):
    """Import a file of the benchmark by its path (names may hold ``-``
    and ``.``, which ``import`` cannot)."""
    name = name or f"bench_{path.parent.name}_{path.stem}".replace(
        "-", "_").replace(".", "_")
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def seed_streams(seed: int, n: int) -> list[np.random.Generator]:
    """``n`` independent generators from one ``--seed`` of any size."""
    return [np.random.default_rng(s)
            for s in np.random.SeedSequence(int(seed)).spawn(n)]


def jax_seed(seed: int, salt: int = 0) -> int:
    """A 31-bit seed for ``jax.random.key`` from a seed of any size."""
    return int(np.random.SeedSequence([int(seed), salt])
               .generate_state(1)[0] >> 1)


class CompileClock:
    """Seconds JAX spent tracing, lowering and compiling, and how many
    backend compiles ran, from ``jax.monitoring`` (copied from the chip
    smoke's clock, with the count added)."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")
    BACKEND = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.compiles = 0
        self.traces = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if event in self.EVENTS:
            self.seconds += duration
        if event == self.BACKEND:
            self.compiles += 1
        elif event == self.EVENTS[0]:
            self.traces += 1


class Spans:
    """Host spans around each call into the system.  They go into the
    profiler's trace (``jax.profiler.TraceAnnotation``) only while a
    trace is being taken; otherwise they cost a context manager."""

    def __init__(self):
        self.on = False

    def __call__(self, name: str):
        if not self.on:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(f"bench:{name}")


class TraceWindow:
    """Takes the profiler trace over ``[start_s, start_s + length_s)`` of
    the measured window; ``poll(elapsed)`` is called between calls into
    the system, so the sub-window is cut at call boundaries."""

    def __init__(self, enabled: bool, out_dir: str | None, start_s: float,
                 length_s: float, spans: Spans):
        self.enabled = enabled
        self.out_dir = out_dir
        self.start_s = start_s
        self.end_s = start_s + length_s
        self.spans = spans
        self.state = "before"
        self._window = None
        self.t0 = self.t1 = None

    def poll(self, elapsed: float) -> None:
        if not self.enabled:
            return
        if self.state == "before" and elapsed >= self.start_s:
            self._start()
        elif self.state == "tracing" and elapsed >= self.end_s:
            self.stop()

    def _start(self) -> None:
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.out_dir, profiler_options=opts)
        self.spans.on = True
        self._window = jax.profiler.TraceAnnotation("bench:window")
        self._window.__enter__()
        self.t0 = time.perf_counter()
        self.state = "tracing"

    def stop(self) -> None:
        if self.state != "tracing":
            return
        import jax
        self.t1 = time.perf_counter()
        self._window.__exit__(None, None, None)
        self.spans.on = False
        jax.profiler.stop_trace()
        self.state = "done"


def quantile(values, q: float) -> float:
    """Nearest-rank ``q`` quantile (the value with ``ceil(q n)`` values at
    or below it): every sample counts, nothing is interpolated."""
    v = np.sort(np.asarray(values, np.float64))
    if v.size == 0:
        raise ValueError("no samples")
    k = max(int(math.ceil(q * v.size)), 1)
    return float(v[k - 1])


class Reservoir:
    """A uniform sample of ``k`` items from a stream of unknown length,
    drawn from a seeded generator (Algorithm R)."""

    def __init__(self, k: int, rng: np.random.Generator):
        self.k, self.rng, self.n = k, rng, 0
        self.items: list = []

    def offer(self, make):
        """Count one item; ``make()`` builds it only if it is kept."""
        self.n += 1
        if len(self.items) < self.k:
            self.items.append(make())
            return
        j = int(self.rng.integers(0, self.n))
        if j < self.k:
            self.items[j] = make()


def rel_gap(got, best) -> float:
    """Widest ``(got - best) / best``; a split that costs the optimum
    reads 0, one that costs more than an optimum of 0 reads inf."""
    got, best = np.asarray(got, np.float64), np.asarray(best, np.float64)
    with np.errstate(divide="ignore", invalid="ignore"):
        gap = np.where(got == best, 0.0, (got - best) / best)
    return float(np.max(gap))
