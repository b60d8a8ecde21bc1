"""Pluggable cost models for offloading decisions (the CostModel API).

The decision core in :mod:`repro.core.decisions` is deliberately dumb: it
argmins a ``[n_envs, L+1]`` matrix.  *What* that matrix measures is this
module's job.  A :class:`CostModel` maps ``(layers, EnvArrays)`` to a
``[n_envs, L+1, n_objectives]`` component tensor with named objectives,
plus a scalarisation that collapses the objective axis for argmin-style
consumers.  Everything downstream — ``decisions.decide_all`` /
``sweep_links``, ``scheduler.etc_matrix``, ``ServeEngine.offload_plan``,
``ContinuousBatchEngine`` re-planning — takes a cost model and stays
oblivious to whether costs are analytic, predicted, or multi-objective.

Three implementations ship here:

  * :class:`AnalyticCost`   — the FLOPs/roofline time model; bit-for-bit
    identical to ``decisions.latency_matrix`` (latency is its only
    objective), so ``decide_all(..., cost=AnalyticCost())`` reproduces the
    historical behaviour exactly.
  * :class:`PredictorCost`  — wraps any *fitted* profiling regressor
    (:class:`repro.core.predictors.Regressor`: GBT / MLP / ridge).  The
    model predicts per-layer execution times from layer + hardware
    features (``DeviceSpec.as_features``), in ONE vectorised ``predict``
    call per decision sweep regardless of how many environments are being
    swept — the paper's profiling→prediction→decision loop at fleet scale.
  * :class:`CompositeCost`  — multi-objective: latency, energy (joules
    from ``tdp_watts``), price, and deadline slack, with scalarisation
    weights and :func:`pareto_front` extraction over the batched matrix.

Usage::

    from repro.core import costs as co, decisions as dec

    cost = co.CompositeCost(weights={"latency_s": 1.0, "energy_j": 0.02})
    plan = dec.decide_all(layers, envs, cost=cost)
    plan.objective("energy_j")            # [E] joules at the chosen split
    front = co.pareto_front(cost.components(layers, envs))  # [E, L+1] mask
"""
# repro: module-tags=fma-sensitive
# (scalarize_weighted must accumulate term-by-term — see its docstring;
#  DET001 rejects any @ / dot / matmul creeping back into this module)
from __future__ import annotations

import dataclasses
from typing import (TYPE_CHECKING, Callable, ClassVar, Mapping, Optional,
                    Protocol, Sequence)

import numpy as np

from repro.core.decisions import (EnvArrays, latency_components, make_envs,
                                  transfer_bytes, transfer_matrix)
from repro.core.offload import DEFAULT_EFFICIENCY, LayerCost
from repro.hw import DeviceSpec

if TYPE_CHECKING:                # typing-only: keep this module numpy-only
    from repro.core.predictors.common import Regressor


class CostModel(Protocol):
    """Maps (layers, envs) to named per-objective cost components.

    ``components`` returns ``[n_envs, L+1, len(objectives)]``; column ``s``
    is the cost of running layers ``[0, s)`` on-device and the rest on the
    edge.  ``scalarize`` collapses the objective axis to the ``[E, L+1]``
    matrix that argmin-style consumers rank splits by.  Implementations
    may additionally expose ``latency_parts(layers, envs) -> (device,
    transfer, edge)`` latency matrices (used to fill the per-split
    breakdown in :class:`repro.core.decisions.DecisionPlan`) and
    ``task_matrix(tasks, nodes)`` (a fast path for
    :func:`repro.core.scheduler.etc_matrix`).
    """

    @property
    def objectives(self) -> tuple[str, ...]: ...

    def components(self, layers: Sequence[LayerCost],
                   envs: EnvArrays) -> np.ndarray: ...

    def scalarize(self, components: np.ndarray) -> np.ndarray: ...


# --------------------------------------------------------------------------
# Pareto-front extraction over batched component tensors
# --------------------------------------------------------------------------
def pareto_front(costs: np.ndarray) -> np.ndarray:
    """Boolean mask of non-dominated points, all objectives minimised.

    ``costs`` is ``[N, K]`` (one candidate set) or ``[E, S, K]`` (batched:
    one candidate set per environment); the mask has the input's leading
    shape.  Point ``j`` dominates ``i`` iff it is no worse on every
    objective and strictly better on at least one.
    """
    c = np.asarray(costs, np.float64)
    if c.ndim < 2:
        raise ValueError(f"costs must be [N, K] or [E, S, K], got {c.shape}")
    # [..., i, j]: does j weakly/strictly improve on i in every/any objective
    le = np.all(c[..., None, :, :] <= c[..., :, None, :], axis=-1)
    lt = np.any(c[..., None, :, :] < c[..., :, None, :], axis=-1)
    dominated = np.any(le & lt, axis=-1)
    return ~dominated


def pareto_pick(components: np.ndarray, objectives: Sequence[str],
                weights: Optional[Mapping[str, float]] = None, *,
                subset: Optional[Sequence[str]] = None,
                scalar: Optional[np.ndarray] = None
                ) -> tuple[np.ndarray, np.ndarray]:
    """``(front, picks)``: the non-dominated mask and the scalarised
    argmin *restricted to that front*, per environment.

    ``components`` is ``[..., S, K]`` (candidate splits × objectives).
    ``subset`` names the objectives the domination test runs on
    (default: all of ``objectives``).  The ranking over the front is the
    weighted sum over the full stack, or ``scalar`` — a precomputed
    ``[..., S]`` ranking matrix (e.g. ``cost.scalarize(components)``
    from a model with a bespoke scalarisation) — when given.
    Restricting the argmin to the front is what lets a streaming
    scheduler re-pick along a live Pareto front as the environment
    drifts and still guarantee every pick is non-dominated — an
    unrestricted weighted argmin only guarantees that for strictly
    positive weights.
    """
    comp = np.asarray(components, np.float64)
    names = tuple(objectives)
    if subset is None:
        dom = comp
    else:
        unknown = set(subset) - set(names)
        if unknown:
            raise KeyError(f"unknown objective(s) {sorted(unknown)}; "
                           f"known: {list(names)}")
        dom = comp[..., [names.index(n) for n in subset]]
    front = pareto_front(dom)
    if scalar is None:
        scalar = scalarize_weighted(comp, names, weights)
    else:
        scalar = np.asarray(scalar, np.float64)
        if scalar.shape != comp.shape[:-1]:
            raise ValueError(f"scalar must be {comp.shape[:-1]}, "
                             f"got {scalar.shape}")
    picks = np.argmin(np.where(front, scalar, np.inf), axis=-1)
    return front, picks


def weight_vector(objectives: Sequence[str],
                  weights: Optional[Mapping[str, float]]) -> np.ndarray:
    """Objective-ordered weight vector.  ``weights`` maps objective name →
    weight; omitted names weigh 0, ``None`` means equal weight 1 for every
    objective.  Unknown names raise — a typo would otherwise zero the cost
    matrix and silently degenerate the argmin."""
    if weights is None:
        return np.ones(len(objectives), np.float64)
    unknown = set(weights) - set(objectives)
    if unknown:
        raise KeyError(f"unknown objective(s) {sorted(unknown)}; "
                       f"known: {list(objectives)}")
    return np.asarray([float(weights.get(n, 0.0)) for n in objectives],
                      np.float64)


def scalarize_weighted(components: np.ndarray,
                       objectives: Sequence[str],
                       weights: Optional[Mapping[str, float]]) -> np.ndarray:
    """Weighted sum over the trailing objective axis (see
    :func:`weight_vector` for the weight semantics).

    Accumulated term-by-term in objective order rather than via ``@``, so
    the result does not depend on the operand's shape: the Pallas path's
    f64 re-evaluation at the chosen splits (``[E, K]``) scores each
    split exactly as the host sweep's ``[E, L+1, K]`` tensor does (BLAS
    dot kernels round differently per shape).
    """
    comp = np.asarray(components, np.float64)
    w = weight_vector(objectives, weights)
    if w.size == 0:
        return np.zeros(comp.shape[:-1], np.float64)
    out = comp[..., 0] * w[0]
    for k in range(1, w.size):
        out = out + comp[..., k] * w[k]
    return out


# --------------------------------------------------------------------------
# Accelerator lowering: the scalar spec the Pallas decide kernel consumes
# --------------------------------------------------------------------------
#: canonical objective order of the accelerator decision kernels
ACCEL_OBJECTIVES = ("latency_s", "energy_j", "price", "deadline_slack_s")


@dataclasses.dataclass(frozen=True)
class AccelSpec:
    """Parameters that fully determine a lowerable cost model.

    The Pallas decide kernel (``repro.kernels.decide_split``) evaluates
    one fixed objective stack — latency, energy, price, deadline slack,
    in :data:`ACCEL_OBJECTIVES` order — and scalarises it with
    ``weights``.  Latency-only models are the ``weights = (1, 0, 0, 0)``
    special case.  *Where* per-layer compute times come from is the
    ``lowered`` seam: ``None`` means the analytic roofline (``flops /
    (peak × efficiency)`` from the shared ``EnvArrays`` tensors);
    otherwise it is a :class:`repro.oracle.lowered.LoweredLayerTimes` —
    a fitted profiling regressor compiled to array form, whose
    environment-invariant ``(t_dev, t_edge)`` vectors the kernel turns
    into cumulative-split times on-device.
    """
    efficiency: float
    weights: tuple[float, float, float, float]
    radio_watts: float = 0.0
    price_per_edge_s: float = 0.0
    price_per_gb: float = 0.0
    deadline_s: float = float("inf")
    #: predicted queueing delay at the edge pool (s) — added to the
    #: latency of every offloading split (all but the run-local last
    #: column).  0.0 keeps the historical zero-contention math exactly.
    queue_wait_s: float = 0.0
    #: tail-aware objective: predicted excess of the tail statistic
    #: (p99 or CVaR) of the RTT distribution over its mean, and the
    #: scalarisation weight of the resulting ``tail_latency_s``
    #: objective.  Both 0.0 → the tail column is dropped entirely.
    tail_excess_s: float = 0.0
    tail_weight: float = 0.0
    #: objective names the resulting DecisionPlan carries (a prefix view
    #: of the canonical stack: just latency, or all four)
    objectives: tuple[str, ...] = ("latency_s",)
    #: lowered predictor layer-times, or None for the analytic roofline
    lowered: Optional[object] = dataclasses.field(default=None,
                                                  compare=False)


def lower_to_accel(cost: Optional[CostModel],
                   efficiency: float = DEFAULT_EFFICIENCY) -> AccelSpec:
    """``cost`` → :class:`AccelSpec`, or raise ``TypeError`` if the model
    cannot run on-accelerator.

    ``None`` lowers to the analytic latency-only default at
    ``efficiency``.  Cost models opt in by exposing ``accel_spec()``:
    :class:`AnalyticCost` and :class:`CompositeCost` are pure array math
    over ``EnvArrays``; :class:`PredictorCost` lowers by compiling its
    fitted regressor to array form (``repro.oracle.lowered`` — ridge →
    dot, MLP → jitted matmul chain, GBT → flattened node arrays walked
    by the ``tree_predict`` kernels), and raises ``TypeError`` only when
    the wrapped model is outside those families (arbitrary host Python
    — use ``backend='numpy'``).
    """
    if cost is None:
        return AccelSpec(efficiency, (1.0, 0.0, 0.0, 0.0))
    fn = getattr(cost, "accel_spec", None)
    if fn is None:
        raise TypeError(
            f"{type(cost).__name__} does not lower to the accelerator "
            "decision kernel: backend='pallas' needs pure array "
            "math over EnvArrays or a lowerable fitted regressor "
            "(AnalyticCost, CompositeCost, PredictorCost over a ridge/"
            "MLP/GBT model) — use backend='numpy'")
    return fn()


# --------------------------------------------------------------------------
# Analytic cost: the roofline time model, latency-only
# --------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class AnalyticCost:
    """FLOPs / (peak × efficiency) latency — wraps ``latency_matrix``
    bit-for-bit, as the single objective ``latency_s``."""

    efficiency: float = DEFAULT_EFFICIENCY

    objectives: ClassVar[tuple[str, ...]] = ("latency_s",)

    def __post_init__(self):
        # memo keyed on (layers, envs) identity — components() and the
        # DecisionPlan breakdown inside one decide_all share one compute.
        # Callers must treat layers/envs as immutable (no in-place edits).
        object.__setattr__(self, "_parts_cache", (None, None, None))

    def components(self, layers, envs) -> np.ndarray:
        dev_cum, xfer, edge_cum = self.latency_parts(layers, envs)
        return (dev_cum + xfer + edge_cum)[..., None]

    def scalarize(self, components: np.ndarray) -> np.ndarray:
        return np.asarray(components)[..., 0]

    def latency_parts(self, layers, envs):
        cached = self._parts_cache
        if cached[0] is layers and cached[1] is envs:
            return cached[2]
        parts = latency_components(layers, envs, self.efficiency)
        object.__setattr__(self, "_parts_cache", (layers, envs, parts))
        return parts

    def accel_spec(self) -> AccelSpec:
        return AccelSpec(self.efficiency, (1.0, 0.0, 0.0, 0.0))


# --------------------------------------------------------------------------
# Predictor cost: the trained profiling model in the decision loop
# --------------------------------------------------------------------------
def default_layer_features(layers: Sequence[LayerCost],
                           spec: DeviceSpec) -> np.ndarray:
    """``[L, F]`` feature rows for per-layer execution-time prediction:
    log-scaled layer size plus the hardware features the paper's profiling
    models train on (``DeviceSpec.as_features``, incl. ``hw_tdp_watts``)."""
    n = len(layers)
    hw = spec.as_features()
    flops = np.fromiter((lc.flops for lc in layers), np.float64, count=n)
    act = np.fromiter((lc.act_bytes for lc in layers), np.float64, count=n)
    cols = [
        np.log10(np.maximum(flops, 1.0)),
        np.log10(np.maximum(act, 1.0)),
        np.full(n, np.log10(max(hw["hw_peak_flops"], 1.0))),
        np.full(n, np.log10(max(hw["hw_hbm_bw"], 1.0))),
        np.full(n, hw["hw_clock_ghz"]),
        np.full(n, hw["hw_is_accelerated"]),
        np.full(n, hw["hw_tdp_watts"]),
    ]
    return np.stack(cols, axis=1).astype(np.float32)


@dataclasses.dataclass
class PredictorCost:
    """Latency from a fitted profiling regressor (GBT / MLP / ridge).

    Per-layer times for the device and edge come from ONE batched
    ``model.predict`` over ``[2L, F]`` feature rows — independent of the
    number of environments being swept, so fleet-scale sweeps stay one
    predict call.  Transfer latency keeps the analytic link model (the
    profiler predicts compute, the radio is observed state).

    Predictions and latency parts are memoised on the *identity* of the
    layers/envs arguments: treat them as immutable (build fresh objects
    per scenario rather than mutating in place), and build a fresh
    PredictorCost after refitting the model.
    """

    model: "Regressor"                   # fitted: predict([N, F]) -> [N]
    device: DeviceSpec
    edge: DeviceSpec
    feature_fn: Callable[[Sequence[LayerCost], DeviceSpec], np.ndarray] = \
        default_layer_features
    target_index: int = 0                # column, for multi-target models

    objectives: ClassVar[tuple[str, ...]] = ("latency_s",)

    def __post_init__(self):
        self._times_cache: tuple = (None, None)
        self._parts_cache: tuple = (None, None, None)
        self._accel_cache: tuple = (None, None)

    def layer_times(self, layers) -> tuple[np.ndarray, np.ndarray]:
        """Predicted per-layer times ``(device [L], edge [L])`` — one
        ``predict`` call, clamped to ≥ 0.  Memoised on the layers object,
        so ``components`` + ``latency_parts`` within one decision sweep
        share a single predict call."""
        if self._times_cache[0] is layers:
            return self._times_cache[1]
        feats = np.concatenate([self.feature_fn(layers, self.device),
                                self.feature_fn(layers, self.edge)], axis=0)
        pred = np.asarray(self.model.predict(feats), np.float64)
        if pred.ndim == 2:
            pred = pred[:, self.target_index]
        pred = np.maximum(pred, 0.0)
        times = (pred[:len(layers)], pred[len(layers):])
        self._times_cache = (layers, times)
        return times

    def latency_parts(self, layers, envs):
        cached = self._parts_cache
        if cached[0] is layers and cached[1] is envs:
            return cached[2]
        t_dev, t_edge = self.layer_times(layers)
        dev_cum = np.concatenate(([0.0], np.cumsum(t_dev)))
        edge_cum = np.concatenate((np.cumsum(t_edge[::-1])[::-1], [0.0]))
        shape = (len(envs), len(layers) + 1)
        parts = (np.broadcast_to(dev_cum, shape),
                 transfer_matrix(layers, envs),
                 np.broadcast_to(edge_cum, shape))
        self._parts_cache = (layers, envs, parts)
        return parts

    def components(self, layers, envs) -> np.ndarray:
        dev_cum, xfer, edge_cum = self.latency_parts(layers, envs)
        return (dev_cum + xfer + edge_cum)[..., None]

    def scalarize(self, components: np.ndarray) -> np.ndarray:
        return np.asarray(components)[..., 0]

    def accel_spec(self) -> AccelSpec:
        """Lower to the accelerator decision kernels by compiling the
        fitted regressor to array form (``repro.oracle.lowered``);
        raises ``TypeError`` when the model has no array lowering.
        Memoised on the model identity so repeated sweeps reuse the
        compiled form (and its per-layer-set predict memo)."""
        if self._accel_cache[0] is self.model:
            return self._accel_cache[1]
        from repro.oracle.lowered import lower_layer_times
        spec = AccelSpec(DEFAULT_EFFICIENCY, (1.0, 0.0, 0.0, 0.0),
                         lowered=lower_layer_times(self))
        self._accel_cache = (self.model, spec)
        return spec

    def task_matrix(self, tasks, nodes) -> np.ndarray:
        """Predicted ``[T, N]`` expected-time-to-compute matrix for
        :func:`repro.core.scheduler.etc_matrix` — one ``predict`` over all
        (task, node) pairs, plus the analytic input-transfer term."""
        layers = [LayerCost(t.name, flops=t.flops, act_bytes=0.0)
                  for t in tasks]
        feats = np.concatenate([self.feature_fn(layers, n.spec)
                                for n in nodes], axis=0)     # [N*T, F]
        pred = np.asarray(self.model.predict(feats), np.float64)
        if pred.ndim == 2:
            pred = pred[:, self.target_index]
        comp = np.maximum(pred, 0.0).reshape(len(nodes), len(tasks)).T
        link = np.asarray([n.spec.link_bw for n in nodes], np.float64)
        inp = np.asarray([t.input_bytes for t in tasks], np.float64)
        return comp + inp[:, None] / np.maximum(link, 1.0)[None, :]


# --------------------------------------------------------------------------
# Composite cost: latency + energy + price + deadline slack
# --------------------------------------------------------------------------
@dataclasses.dataclass
class CompositeCost:
    """Multi-objective cost over a latency-producing base model.

    Objectives, in order:

      * ``latency_s``        — end-to-end latency from ``base``
      * ``energy_j``         — device compute at ``dev_tdp_watts``, radio
                               at ``radio_watts`` during transfer, edge
                               compute at ``edge_tdp_watts``
      * ``price``            — billed edge seconds + shipped gigabytes
      * ``deadline_slack_s`` — ``max(0, latency - deadline_s)`` overrun
      * ``tail_latency_s``   — only when ``tail=`` is set: predicted
                               tail completion (latency + the p99/CVaR_α
                               excess of ``rtt`` over its mean for every
                               offloading split), so schedulers can
                               trade tail latency against energy/price

    ``scalarize`` applies ``weights`` (objective name → weight; ``None``
    means equal weights); :meth:`pareto` extracts the non-dominated splits
    per environment when no single scalarisation is trusted.
    """

    base: CostModel = dataclasses.field(default_factory=AnalyticCost)
    weights: Optional[Mapping[str, float]] = None
    radio_watts: float = 2.5             # device NIC/radio power while TX
    price_per_edge_s: float = 0.0
    price_per_gb: float = 0.0
    deadline_s: float = np.inf
    #: ``"p99"`` or ``"cvar"`` turns on the fifth ``tail_latency_s``
    #: objective over the ``rtt`` delay process; ``None`` (default)
    #: keeps the historical 4-objective stack byte-identical.
    tail: Optional[str] = None
    tail_alpha: float = 0.99
    rtt: Optional[object] = None         # a queueing.DelayProcess

    objectives: ClassVar[tuple[str, ...]] = (
        "latency_s", "energy_j", "price", "deadline_slack_s")

    def __post_init__(self):
        if not hasattr(self.base, "latency_parts"):
            raise TypeError(
                f"CompositeCost base {type(self.base).__name__} must "
                "expose latency_parts(layers, envs) — the energy/price/"
                "slack objectives need the (device, transfer, edge) "
                "latency decomposition, not just totals")
        if self.tail is not None:
            if self.tail not in ("p99", "cvar"):
                raise ValueError(f"tail must be 'p99' or 'cvar', "
                                 f"got {self.tail!r}")
            if self.rtt is None:
                raise ValueError(
                    "tail= needs an rtt= delay process (e.g. "
                    "repro.sim.queueing.WeibullRTT) to take the tail "
                    "statistic over")
            # shadow the ClassVar: this instance carries five objectives
            self.objectives = CompositeCost.objectives + (
                "tail_latency_s",)

    def tail_excess_s(self) -> float:
        """Predicted excess of the tail RTT statistic over its mean —
        the per-offload premium the ``tail_latency_s`` column adds."""
        if self.tail is None:
            return 0.0
        return max(self.rtt.tail_stat(self.tail, self.tail_alpha)
                   - self.rtt.mean(), 0.0)

    def components(self, layers, envs) -> np.ndarray:
        dev_t, xfer_t, edge_t = self.base.latency_parts(layers, envs)
        total = dev_t + xfer_t + edge_t
        dev_w = _tdp_or_zero(envs.dev_tdp_watts, len(envs))
        edge_w = _tdp_or_zero(envs.edge_tdp_watts, len(envs))
        energy = dev_t * dev_w[:, None] + xfer_t * self.radio_watts \
            + edge_t * edge_w[:, None]
        price = edge_t * self.price_per_edge_s \
            + transfer_bytes(layers, envs) / 1e9 * self.price_per_gb
        slack = np.maximum(total - self.deadline_s, 0.0)
        if self.tail is None:
            return np.stack([total, energy, price, slack], axis=-1)
        tail_col = total.copy()
        tail_col[..., :-1] += self.tail_excess_s()  # last split: no RTT
        return np.stack([total, energy, price, slack, tail_col],
                        axis=-1)

    def scalarize(self, components: np.ndarray) -> np.ndarray:
        return scalarize_weighted(components, self.objectives, self.weights)

    def latency_parts(self, layers, envs):
        return self.base.latency_parts(layers, envs)

    def pareto(self, layers, envs) -> np.ndarray:
        """``[E, L+1]`` mask of Pareto-optimal splits per environment."""
        return pareto_front(self.components(layers, envs))

    def accel_spec(self) -> AccelSpec:
        base_fn = getattr(self.base, "accel_spec", None)
        if base_fn is None:
            raise TypeError(
                f"CompositeCost over base {type(self.base).__name__} does "
                "not lower to the accelerator decision kernels — the base "
                "must be pure array math (AnalyticCost) or a lowerable "
                "PredictorCost; use backend='numpy'")
        base = base_fn()        # may itself raise for host-only regressors
        if base.objectives != ("latency_s",):
            raise TypeError(
                "CompositeCost needs a latency-only base (AnalyticCost "
                "or PredictorCost) to lower — a base carrying its own "
                "objective stack would be silently overwritten")
        w = weight_vector(self.objectives, self.weights)
        return dataclasses.replace(
            base, weights=tuple(float(x) for x in w[:4]),
            radio_watts=self.radio_watts,
            price_per_edge_s=self.price_per_edge_s,
            price_per_gb=self.price_per_gb,
            deadline_s=float(self.deadline_s),
            tail_excess_s=float(self.tail_excess_s()),
            tail_weight=float(w[4]) if self.tail is not None else 0.0,
            objectives=self.objectives)


def _tdp_or_zero(tdp: Optional[np.ndarray], n: int) -> np.ndarray:
    if tdp is None:
        return np.zeros(n)
    return np.asarray(tdp, np.float64)


# --------------------------------------------------------------------------
# Queue-aware cost: live pool state folded into any cost model
# --------------------------------------------------------------------------
@dataclasses.dataclass
class QueueAwareCost:
    """Sojourn-aware wrapper: predicted completion = wait + service (+
    transfer) over any base :class:`CostModel`.

    Two seams feed the predicted queueing delay:

      * ``edge_pool`` — a live :class:`repro.sim.queueing.ServerPool`
        for the edge server the split decision offloads to; its current
        ``wait(now)`` is added to every offloading split (all columns
        but the run-local last one), and re-read on every call so the
        wrapper tracks pool state with zero bookkeeping;
      * ``pools`` — a :class:`repro.sim.queueing.NodePools` for the
        placement path: :meth:`task_matrix` adds per-node waits to the
        base ETC matrix, so min-min/HEFT see contention directly.

    ``rtt`` (a ``DelayProcess``) optionally adds the *mean* RTT to
    offloading latencies; pair with ``CompositeCost(tail=...)`` when the
    tail, not the mean, should drive the pick.  Advance virtual time
    with :meth:`set_now` (the simulators do this before each decision).
    """

    base: CostModel = dataclasses.field(default_factory=AnalyticCost)
    edge_pool: Optional[object] = None      # queueing.ServerPool
    pools: Optional[object] = None          # queueing.NodePools
    rtt: Optional[object] = None            # queueing.DelayProcess
    wait_s: float = 0.0                     # static extra wait (tests)
    now: float = 0.0

    def set_now(self, now: float) -> None:
        self.now = float(now)

    @property
    def objectives(self) -> tuple[str, ...]:
        return self.base.objectives

    def _edge_wait(self) -> float:
        w = float(self.wait_s)
        if self.edge_pool is not None:
            w += float(self.edge_pool.wait(self.now))
        if self.rtt is not None:
            w += float(self.rtt.mean())
        return w

    def components(self, layers, envs) -> np.ndarray:
        comp = np.array(self.base.components(layers, envs), np.float64)
        w = self._edge_wait()
        if w > 0.0:
            comp[..., :-1, 0] += w          # offloading splits wait
        return comp

    def scalarize(self, components: np.ndarray) -> np.ndarray:
        return self.base.scalarize(components)

    def latency_parts(self, layers, envs):
        dev_t, xfer_t, edge_t = self.base.latency_parts(layers, envs)
        w = self._edge_wait()
        if w > 0.0:
            xfer_t = np.array(xfer_t, np.float64)
            xfer_t[..., :-1] += w           # book the wait with transfer
        return dev_t, xfer_t, edge_t

    def task_matrix(self, tasks, nodes) -> np.ndarray:
        etc = etc_from_cost(self.base, tasks, nodes)
        extra = np.zeros(etc.shape[1], np.float64)
        if self.pools is not None:
            extra = extra + self.pools.waits(self.now)
        if self.rtt is not None:
            extra = extra + float(self.rtt.mean())
        if self.wait_s:
            extra = extra + float(self.wait_s)
        return etc + extra[None, :]

    def accel_spec(self) -> AccelSpec:
        spec = lower_to_accel(self.base)
        return dataclasses.replace(
            spec, queue_wait_s=float(spec.queue_wait_s
                                     + self._edge_wait()))


# --------------------------------------------------------------------------
# Cost-model-driven ETC matrices for the scheduler
# --------------------------------------------------------------------------
def etc_from_cost(cost: CostModel, tasks, nodes) -> np.ndarray:
    """``[T, N]`` scalarised cost of running each task wholly on each node.

    Each task becomes a one-layer chain evaluated at split 0 — the task
    ships its input over the node's link and executes remotely — which for
    :class:`AnalyticCost` reproduces ``Node.exec_time`` exactly.  Cost
    models exposing a ``task_matrix`` fast path (:class:`PredictorCost`)
    are dispatched to it instead.
    """
    fast = getattr(cost, "task_matrix", None)
    if fast is not None:
        return fast(tasks, nodes)
    specs = [n.spec for n in nodes]
    link = np.asarray([s.link_bw for s in specs], np.float64)
    out = np.empty((len(tasks), len(specs)))
    for i, t in enumerate(tasks):
        layers = [LayerCost(t.name, flops=t.flops, act_bytes=0.0)]
        envs = make_envs(specs, specs, link_bw=link, link_latency_s=0.0,
                         input_bytes=t.input_bytes)
        out[i] = cost.scalarize(cost.components(layers, envs))[:, 0]
    return out
