"""Batched, array-native offloading decisions (the vectorized decision core).

:mod:`repro.core.offload` answers "where do I split for *this* device,
edge, and link?".  This module answers the fleet-scale question: given
*vectors* of link bandwidths, device specs, and edge specs — thousands of
concurrent users, each in a different radio condition — compute the full
``[n_envs, L+1]`` latency matrix in one shot of numpy broadcasting and
argmin every row.  One call replaces ``n_envs × (L+1)`` scalar
``split_time`` evaluations, which is what makes scenario sweeps (link
grids × device mixes × models) and high-rate decision serving tractable.

*What* is being minimised is pluggable: every decision entry point takes a
``cost=`` :class:`repro.core.costs.CostModel` mapping ``(layers, envs)``
to a ``[n_envs, L+1, n_objectives]`` component tensor — analytic roofline
latency (the default), latency predicted by the trained profiling model
(``PredictorCost``), or multi-objective latency/energy/price/deadline
stacks (``CompositeCost``).  Without ``cost=`` the historical analytic
latency-only behaviour is preserved bit-for-bit.

*Where* the sweep runs is also pluggable: ``decide_all``/``sweep_links``
take ``backend="numpy" | "pallas"``.  ``"numpy"`` (default) is this
module's host path and the reference every other path is tested
against; ``"pallas"`` is a fused TPU kernel
(``repro.kernels.decide_split``) that never materialises the
``[n_envs, L+1]`` cost tensor in HBM and re-costs the chosen splits in
f64 (within f32-argmin tolerance).  Cost models lower via
``costs.lower_to_accel``: ``AnalyticCost`` and ``CompositeCost`` are
pure array math over ``EnvArrays``; ``PredictorCost`` lowers by
compiling its fitted regressor to array form (``repro.oracle.lowered`` —
ridge → dot, MLP → jitted matmul chain, GBT → the ``tree_predict``
kernels), so predictor-driven sweeps run on-accelerator too.  Only
regressors outside those families raise ``TypeError`` on ``"pallas"``
rather than silently copying back.  The multi-chip path is
``repro.sim.decide_all_sharded``.

Usage::

    from repro.core import costs as co
    from repro.core import decisions as dec
    from repro.core import offload as off
    from repro.hw import get_device

    layers = off.workload_layer_costs(wc)
    envs = dec.make_envs(get_device("pi5-arm"),
                         get_device("edge-server-a100"),
                         link_bw=np.geomspace(1e5, 1e10, 4096),
                         input_bytes=4 * 32 * 784)
    plan = dec.decide_all(layers, envs)         # analytic, latency-only
    plan.splits, plan.total_time_s              # [4096] each
    plan[0]                                     # -> offload.SplitDecision

    dec.decide_all(layers, envs, backend="pallas")  # fused TPU kernel

    cost = co.CompositeCost(weights={"latency_s": 1, "energy_j": 0.05})
    plan = dec.decide_all(layers, envs, cost=cost)
    plan.objective("energy_j")                  # [4096] joules at the split
    co.pareto_front(cost.components(layers, envs))   # [4096, L+1] mask

    gbt = MultiTargetGBT().fit(x, y)            # trained profiling model
    plan = dec.decide_all(layers, envs,
                          cost=co.PredictorCost(gbt, device, edge))

Scalar oracles for every path here live in ``repro.core.offload``
(``split_time`` / ``optimal_split_ref``); the equivalence tests in
``tests/test_decisions.py`` and ``tests/test_costs.py`` pin this module
and the cost models to them.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Sequence, Union

import numpy as np

from repro.core.offload import (DEFAULT_EFFICIENCY as EFFICIENCY, LayerCost,
                                OffloadEnv, SplitDecision)
from repro.hw import DeviceSpec
from repro.obs.trace import region


@dataclasses.dataclass(frozen=True)
class EnvArrays:
    """Struct-of-arrays form of ``n_envs`` :class:`OffloadEnv` instances."""
    dev_flops: np.ndarray            # [E] effective f32 peak of the device
    edge_flops: np.ndarray           # [E] effective f32 peak of the edge
    link_bw: np.ndarray              # [E] bytes/s
    link_latency_s: np.ndarray       # [E]
    input_bytes: np.ndarray          # [E]
    # board power, for the energy objective (None when built by hand from
    # raw arrays; make_envs/stack_envs always fill them from the specs)
    dev_tdp_watts: Optional[np.ndarray] = None      # [E]
    edge_tdp_watts: Optional[np.ndarray] = None     # [E]

    def __len__(self) -> int:
        return self.dev_flops.shape[0]


def _spec_attr(spec, attr: str) -> Union[float, np.ndarray]:
    if isinstance(spec, DeviceSpec):
        return getattr(spec, attr)
    return np.asarray([getattr(s, attr) for s in spec], np.float64)


def make_envs(device, edge, link_bw,
              link_latency_s=0.005, input_bytes=0.0) -> EnvArrays:
    """Broadcast scalars/vectors of specs and link states into an
    :class:`EnvArrays`.  ``device``/``edge`` may be a single
    :class:`DeviceSpec` or a sequence of them."""
    with region("decide", "envs"):
        arrs = np.broadcast_arrays(
            np.atleast_1d(np.asarray(_spec_attr(device, "peak_flops_f32"),
                                     np.float64)),
            np.atleast_1d(np.asarray(_spec_attr(edge, "peak_flops_f32"),
                                     np.float64)),
            np.atleast_1d(np.asarray(link_bw, np.float64)),
            np.atleast_1d(np.asarray(link_latency_s, np.float64)),
            np.atleast_1d(np.asarray(input_bytes, np.float64)),
            np.atleast_1d(np.asarray(_spec_attr(device, "tdp_watts"),
                                     np.float64)),
            np.atleast_1d(np.asarray(_spec_attr(edge, "tdp_watts"),
                                     np.float64)))
        return EnvArrays(*arrs)


def stack_envs(envs: Sequence[OffloadEnv]) -> EnvArrays:
    """Struct-of-arrays from a list of scalar :class:`OffloadEnv`."""
    return EnvArrays(
        np.asarray([e.device.peak_flops_f32 for e in envs], np.float64),
        np.asarray([e.edge.peak_flops_f32 for e in envs], np.float64),
        np.asarray([e.link_bw for e in envs], np.float64),
        np.asarray([e.link_latency_s for e in envs], np.float64),
        np.asarray([e.input_bytes for e in envs], np.float64),
        np.asarray([e.device.tdp_watts for e in envs], np.float64),
        np.asarray([e.edge.tdp_watts for e in envs], np.float64))


def layer_arrays(layers: Sequence[LayerCost]
                 ) -> tuple[np.ndarray, np.ndarray]:
    """``(flops, act_bytes)`` of a layer chain, each f64 ``[L]``."""
    n = len(layers)
    flops = np.fromiter((lc.flops for lc in layers), np.float64, count=n)
    act = np.fromiter((lc.act_bytes for lc in layers), np.float64, count=n)
    return flops, act


def env_columns(envs: EnvArrays) -> tuple[np.ndarray, ...]:
    """The seven f64 ``[E]`` columns of ``envs`` in field order, board
    power zero where ``envs`` carries none."""
    e = len(envs)

    def tdp(x):
        return np.zeros(e) if x is None else np.asarray(x, np.float64)

    return tuple(np.asarray(x, np.float64) for x in
                 (envs.dev_flops, envs.edge_flops, envs.link_bw,
                  envs.link_latency_s, envs.input_bytes)) \
        + (tdp(envs.dev_tdp_watts), tdp(envs.edge_tdp_watts))


def transfer_bytes(layers: Sequence[LayerCost], envs: EnvArrays
                   ) -> np.ndarray:
    """Bytes crossing the link per split, ``[E, L+1]`` (0 at split == L):
    the raw input at split 0, the split layer's activation otherwise."""
    n = len(envs)
    act = np.fromiter((lc.act_bytes for lc in layers), np.float64,
                      count=len(layers))
    out = np.concatenate(
        [envs.input_bytes[:, None],
         np.broadcast_to(act[None, :], (n, len(layers)))], axis=1)
    out[:, -1] = 0.0                 # split == L ships nothing
    return out


def transfer_matrix(layers: Sequence[LayerCost], envs: EnvArrays
                    ) -> np.ndarray:
    """Transfer latency per split, ``[E, L+1]``: link latency plus shipped
    bytes over bandwidth (0 at split == L)."""
    xfer = envs.link_latency_s[:, None] + transfer_bytes(layers, envs) \
        / np.maximum(envs.link_bw, 1.0)[:, None]
    xfer[:, -1] = 0.0                # split == L ships nothing
    return xfer


def latency_components(layers: Sequence[LayerCost], envs: EnvArrays,
                       efficiency: float = EFFICIENCY
                       ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """``(device, transfer, edge)`` latency matrices, each ``[E, L+1]``.

    Column ``s`` of each matrix is the corresponding component of running
    layers ``[0, s)`` on-device and the rest on-edge — the batched twin of
    ``offload.split_components``.
    """
    n = len(envs)
    flops = np.fromiter((lc.flops for lc in layers), np.float64,
                        count=len(layers))
    t_dev = flops[None, :] / (envs.dev_flops[:, None] * efficiency)
    t_edge = flops[None, :] / (envs.edge_flops[:, None] * efficiency)
    zero = np.zeros((n, 1))
    dev_cum = np.concatenate([zero, np.cumsum(t_dev, axis=1)], axis=1)
    edge_cum = np.concatenate(
        [np.cumsum(t_edge[:, ::-1], axis=1)[:, ::-1], zero], axis=1)
    return dev_cum, transfer_matrix(layers, envs), edge_cum


def latency_matrix(layers: Sequence[LayerCost], envs: EnvArrays,
                   efficiency: float = EFFICIENCY) -> np.ndarray:
    """Total latency of every (environment, split) pair: ``[E, L+1]``."""
    dev_cum, xfer, edge_cum = latency_components(layers, envs, efficiency)
    return dev_cum + xfer + edge_cum


@dataclasses.dataclass(frozen=True)
class DecisionPlan:
    """Per-environment optimal decisions, struct-of-arrays (all ``[E]``).

    With a multi-objective cost model, ``objectives``/``components`` carry
    the named per-objective cost at each chosen split and ``scalar_cost``
    the scalarised value the argmin ranked by; latency-only plans leave
    them at their defaults.
    """
    splits: np.ndarray
    total_time_s: np.ndarray
    device_time_s: np.ndarray
    transfer_time_s: np.ndarray
    edge_time_s: np.ndarray
    objectives: tuple[str, ...] = ("latency_s",)
    components: Optional[np.ndarray] = None       # [E, n_objectives]
    scalar_cost: Optional[np.ndarray] = None      # [E]

    def __len__(self) -> int:
        return self.splits.shape[0]

    def __getitem__(self, i: int) -> SplitDecision:
        return SplitDecision(int(self.splits[i]),
                             float(self.total_time_s[i]),
                             float(self.device_time_s[i]),
                             float(self.transfer_time_s[i]),
                             float(self.edge_time_s[i]))

    def objective(self, name: str) -> np.ndarray:
        """``[E]`` cost of the named objective at each chosen split."""
        if self.components is None:
            if name == "latency_s":
                return self.total_time_s
            raise KeyError(f"plan carries no components for {name!r}")
        return self.components[:, self.objectives.index(name)]


def decide_all(layers: Sequence[LayerCost], envs: EnvArrays,
               efficiency: float = EFFICIENCY, *,
               cost=None, backend: str = "numpy") -> DecisionPlan:
    """Optimal split per environment: one argmin over the cost matrix.

    ``cost`` is a :class:`repro.core.costs.CostModel`; ``None`` keeps the
    historical analytic latency-only path (identical to
    ``cost=AnalyticCost(efficiency)`` but without building components).
    The argmin ranks splits by ``cost.scalarize(components)``.
    ``efficiency`` only applies to the analytic default — with ``cost=``
    the model owns its parameters, so combining the two is rejected
    rather than silently ignoring one.

    ``backend`` selects where the sweep runs: ``"numpy"`` on the host
    (default), ``"pallas"`` as the fused TPU kernel (within f32
    tolerance) — see :mod:`repro.kernels.decide_split`.
    ``None``/``AnalyticCost``/``CompositeCost`` lower as pure array
    math; ``PredictorCost`` lowers through its compiled regressor
    (``repro.oracle.lowered``) and only raises when the wrapped model
    has no array form.
    """
    with region("decide", "call"):
        if cost is not None and efficiency != EFFICIENCY:
            raise ValueError(
                "efficiency= is ignored when cost= is given; set it on the "
                "cost model instead (e.g. AnalyticCost(efficiency=...))")
        if backend == "pallas":
            from repro.kernels.decide_split import ops
            return ops.decide_pallas(layers, envs, efficiency, cost=cost)
        if backend != "numpy":
            raise ValueError(f"unknown decide backend {backend!r}; "
                             "expected 'numpy' or 'pallas'")
        if cost is None:
            dev_cum, xfer, edge_cum = latency_components(layers, envs,
                                                         efficiency)
            total = dev_cum + xfer + edge_cum
            s = np.argmin(total, axis=1)
            rows = np.arange(len(envs))
            return DecisionPlan(s, total[rows, s], dev_cum[rows, s],
                                xfer[rows, s], edge_cum[rows, s])
        comp = np.asarray(cost.components(layers, envs), np.float64)
        scalar = cost.scalarize(comp)
        s = np.argmin(scalar, axis=1)
        rows = np.arange(comp.shape[0])
        objectives = tuple(cost.objectives)
        comp_s = comp[rows, s]
        if "latency_s" in objectives:
            total = comp_s[:, objectives.index("latency_s")]
        else:
            # no latency objective -> the scalarised weighted cost is in
            # arbitrary units, not seconds; total_time_s must not lie
            # (scalar_cost below still carries the value the argmin ranked by)
            total = np.full(len(rows), np.nan)
        parts_fn = getattr(cost, "latency_parts", None)
        if parts_fn is not None:
            dev_cum, xfer, edge_cum = parts_fn(layers, envs)
            dev_t, xfer_t, edge_t = (dev_cum[rows, s], xfer[rows, s],
                                     edge_cum[rows, s])
        else:                            # no latency decomposition available
            dev_t = xfer_t = edge_t = np.full(len(rows), np.nan)
        return DecisionPlan(s, total, dev_t, xfer_t, edge_t,
                            objectives=objectives, components=comp_s,
                            scalar_cost=scalar[rows, s])


def pad_envs(envs: EnvArrays, multiple: int) -> tuple[EnvArrays, int]:
    """Pad the environment axis up to a multiple of ``multiple`` by
    repeating the last row — the shard-friendly layout for splitting the
    env axis across devices (padded rows compute real but discarded
    decisions, so the maths stays row-wise identical).  Returns
    ``(padded, original_length)``; the caller trims results back with
    ``[:original_length]``."""
    if multiple <= 0:
        raise ValueError(f"multiple must be positive, got {multiple}")
    e = len(envs)
    pad = (-e) % multiple
    if pad == 0:
        return envs, e
    if e == 0:
        raise ValueError("cannot pad an empty EnvArrays (no row to "
                         "repeat)")
    idx = np.concatenate([np.arange(e), np.full(pad, e - 1, np.intp)])
    return take_envs(envs, idx), e


def take_envs(envs: EnvArrays, idx) -> EnvArrays:
    """Row-subset of an :class:`EnvArrays` (``idx`` is an integer index
    array or boolean mask over the environment axis)."""
    idx = np.asarray(idx)

    def take(a):
        return None if a is None else a[idx]

    return EnvArrays(envs.dev_flops[idx], envs.edge_flops[idx],
                     envs.link_bw[idx], envs.link_latency_s[idx],
                     envs.input_bytes[idx], take(envs.dev_tdp_watts),
                     take(envs.edge_tdp_watts))


def replan(layers: Sequence[LayerCost], envs: EnvArrays,
           prev: DecisionPlan, changed, *,
           efficiency: float = EFFICIENCY, cost=None,
           backend: str = "numpy") -> DecisionPlan:
    """Incremental :func:`decide_all`: re-decide only the ``changed``
    environments and splice the fresh rows into ``prev``.

    ``changed`` is an integer index array or boolean mask over the
    environment axis — in a streaming run, the environments whose link
    state or backlog actually drifted since ``prev`` was computed
    (:mod:`repro.sim.state` tracks them).  Rows outside ``changed`` are
    carried over untouched, so the result is bit-for-bit what a full
    ``decide_all`` over the updated ``envs`` would return, at the cost
    of the changed rows only.
    """
    idx = np.asarray(changed)
    if idx.dtype == bool:
        if idx.shape != (len(envs),):
            raise ValueError(
                f"boolean changed mask must be [{len(envs)}], "
                f"got {idx.shape}")
        idx = np.flatnonzero(idx)
    if len(prev) != len(envs):
        raise ValueError(
            f"prev plan covers {len(prev)} envs, got {len(envs)}")
    if idx.size == 0:
        return prev
    sub = decide_all(layers, take_envs(envs, idx), efficiency,
                     cost=cost, backend=backend)
    if sub.objectives != prev.objectives:
        raise ValueError(
            f"cost model changed between plans: prev objectives "
            f"{prev.objectives}, new {sub.objectives} — replan only "
            "splices rows of the same objective stack")

    def scatter(old, new):
        if old is None or new is None:
            if (old is None) != (new is None):
                raise ValueError(
                    "prev and updated plans disagree on carrying "
                    "components/scalar_cost — same cost= required")
            return None
        out = np.asarray(old).copy()
        out[idx] = new
        return out

    return DecisionPlan(scatter(prev.splits, sub.splits),
                        scatter(prev.total_time_s, sub.total_time_s),
                        scatter(prev.device_time_s, sub.device_time_s),
                        scatter(prev.transfer_time_s, sub.transfer_time_s),
                        scatter(prev.edge_time_s, sub.edge_time_s),
                        objectives=prev.objectives,
                        components=scatter(prev.components, sub.components),
                        scalar_cost=scatter(prev.scalar_cost,
                                            sub.scalar_cost))


def sweep_links(layers: Sequence[LayerCost], env_base: OffloadEnv,
                link_bws, efficiency: float = EFFICIENCY, *,
                cost=None, backend: str = "numpy") -> DecisionPlan:
    """Optimal decisions for one device/edge pair across a bandwidth grid —
    the common "radio conditions sweep" shorthand.  ``efficiency``/
    ``cost``/``backend`` pass straight through to :func:`decide_all`
    (including its efficiency-vs-cost conflict guard)."""
    envs = make_envs(env_base.device, env_base.edge,
                     link_bw=np.asarray(link_bws, np.float64),
                     link_latency_s=env_base.link_latency_s,
                     input_bytes=env_base.input_bytes)
    return decide_all(layers, envs, efficiency, cost=cost, backend=backend)
