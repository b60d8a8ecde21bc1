"""The Pallas decide path: ``decisions.decide_all(..., backend="pallas")``.

:func:`decide_pallas` runs the ``[n_envs, L+1]`` offloading sweep on the
fused TPU kernel (:mod:`repro.kernels.decide_split.kernel`), which never
materialises the cost tensor in HBM.  The kernel ranks splits in f32,
the TPU-native width; the chosen splits are then re-evaluated in f64 on
the host (:func:`_reevaluate`: O(E) gathers, no matrices), so the plan's
costs match the numpy ``decide_all`` within f32-argmin tolerance.

Cost models lower through :func:`repro.core.costs.lower_to_accel`:
``AnalyticCost`` and ``CompositeCost`` are pure array math over
``EnvArrays``; ``PredictorCost`` lowers by compiling its fitted
regressor to array form (``repro.oracle.lowered`` — the ``AccelSpec``
carries a ``lowered`` layer-times program whose per-layer device/edge
time vectors replace the analytic roofline reconstruction).  Only
regressors outside the lowerable families (ridge / MLP / GBT) are
rejected with a ``TypeError``.
"""
from __future__ import annotations

from typing import Sequence

import jax.numpy as jnp
import numpy as np

from repro.core.costs import AccelSpec, lower_to_accel, scalarize_weighted
from repro.core.decisions import (DecisionPlan, EnvArrays, env_columns,
                                  layer_arrays)
from repro.core.offload import DEFAULT_EFFICIENCY, LayerCost
from repro.obs.trace import region

#: the kernel's env and split block sizes
BLOCK_E = 32768
BLOCK_S = 128


def _plan(cost, spec: AccelSpec, s, dev_s, xfer_s, edge_s, total_s,
          comp_s=None, scal_s=None) -> DecisionPlan:
    """Assemble the DecisionPlan mirroring the numpy ``decide_all``
    surface for the same ``cost`` argument."""
    s = np.asarray(s)
    dev_s, xfer_s, edge_s, total_s = (np.asarray(x, np.float64)
                                      for x in (dev_s, xfer_s, edge_s,
                                                total_s))
    if cost is None:
        return DecisionPlan(s, total_s, dev_s, xfer_s, edge_s)
    if comp_s is None:                          # latency-only cost model
        comp_s, scal_s = total_s[:, None], total_s
    else:
        comp_s, scal_s = np.asarray(comp_s, np.float64), \
            np.asarray(scal_s, np.float64)
    if "latency_s" in spec.objectives:
        total = comp_s[:, spec.objectives.index("latency_s")]
    else:                                       # scalar cost is not seconds
        total = np.full(len(s), np.nan)
    return DecisionPlan(s, total, dev_s, xfer_s, edge_s,
                        objectives=spec.objectives, components=comp_s,
                        scalar_cost=scal_s)


def _decide_pallas(layers, flops, act, env_arrs, spec: AccelSpec, cost):
    from repro.kernels.decide_split.kernel import (decide_split_kernel,
                                                   pack_spec)
    dev, edge, bw, lat, inp, dev_w, edge_w = env_arrs
    n = flops.shape[0]
    with region("decide", "prep"):
        bvec = np.concatenate(([0.0], act))
        bvec[-1] = 0.0                                   # split == L
        if spec.lowered is not None:
            # predictor mode: prefix sums of the lowered per-layer times,
            # unit divisors (the rows already are seconds)
            t_dev, t_edge = spec.lowered.times(layers)
            dcum = np.concatenate(([0.0], np.cumsum(t_dev)))
            ecum = np.concatenate(([0.0], np.cumsum(t_edge)))
            dev_div = np.ones_like(dev)
            edge_div = np.ones_like(edge)
        else:
            fcum = np.concatenate(([0.0], np.cumsum(flops)))  # [L+1] f64
            dcum = ecum = fcum
            dev_div = dev * spec.efficiency
            edge_div = edge * spec.efficiency
        etot = float(ecum[-1])
        spec_vec = pack_spec(spec.weights,
                             radio_watts=spec.radio_watts,
                             price_per_edge_s=spec.price_per_edge_s,
                             price_per_gb=spec.price_per_gb,
                             deadline_s=spec.deadline_s, edge_total=etot,
                             queue_wait_s=spec.queue_wait_s,
                             tail_excess_s=spec.tail_excess_s,
                             tail_weight=spec.tail_weight)
    with region("decide", "h2d"):
        f32 = [jnp.asarray(x, jnp.float32)
               for x in (dcum, ecum, bvec, dev_div, edge_div, bw, lat, inp,
                         dev_w, edge_w)]
        spec_arr = jnp.asarray(spec_vec)
    with region("decide", "kernel"):
        s, _ = decide_split_kernel(*f32, spec_arr, block_e=BLOCK_E,
                                   block_s=BLOCK_S)
    with region("decide", "sync"):
        s = np.asarray(s, np.int64)
    with region("decide", "reeval"):
        return _reevaluate(s, n, dcum, ecum, etot, bvec, dev_div, edge_div,
                           env_arrs, spec, cost)


def _reevaluate(s, n, dcum, ecum, etot, bvec, dev_div, edge_div, env_arrs,
                spec: AccelSpec, cost) -> DecisionPlan:
    """Exact f64 costs at the kernel-chosen splits ``s``: O(E) gathers,
    no ``[E, S]`` matrix."""
    _, _, bw, lat, inp, dev_w, edge_w = env_arrs
    dev_s = dcum[s] / dev_div
    edge_s = (etot - ecum[s]) / edge_div
    ship = np.where(s == n, 0.0, np.where(s == 0, inp, bvec[s]))
    xfer_s = np.where(s == n, 0.0, lat + ship / np.maximum(bw, 1.0))
    total_s = dev_s + xfer_s + edge_s
    # queue wait bumps the latency objective (and the booked transfer)
    # on offloading splits — zero when no pool is attached
    bump = np.where(s == n, 0.0, spec.queue_wait_s) \
        if spec.queue_wait_s != 0.0 else None
    if cost is None or spec.objectives == ("latency_s",):
        if bump is not None:
            total_s = total_s + bump
            xfer_s = xfer_s + bump
            return _plan(cost, spec, s, dev_s, xfer_s, edge_s, total_s,
                         total_s[:, None], total_s)
        return _plan(cost, spec, s, dev_s, xfer_s, edge_s, total_s)
    energy = dev_s * dev_w + xfer_s * spec.radio_watts + edge_s * edge_w
    price = edge_s * spec.price_per_edge_s + ship / 1e9 * spec.price_per_gb
    slack = np.maximum(total_s - spec.deadline_s, 0.0)
    cols = [total_s, energy, price, slack]
    weights = list(spec.weights)
    if len(spec.objectives) > 4:         # tail_latency_s objective
        cols.append(total_s + np.where(s == n, 0.0, spec.tail_excess_s))
        weights.append(spec.tail_weight)
    if bump is not None:
        cols[0] = total_s + bump
        xfer_s = xfer_s + bump
        total_s = cols[0]
    comp_s = np.stack(cols, axis=-1)
    scal_s = scalarize_weighted(comp_s, spec.objectives,
                                dict(zip(spec.objectives, weights)))
    return _plan(cost, spec, s, dev_s, xfer_s, edge_s, total_s,
                 comp_s, scal_s)


def decide_pallas(layers: Sequence[LayerCost], envs: EnvArrays,
                  efficiency: float = DEFAULT_EFFICIENCY, *,
                  cost=None) -> DecisionPlan:
    """``decide_all`` on the fused kernel: one cost+argmin over
    ``[E, L+1]``, within f32 tolerance of the numpy path (the kernel runs
    interpreted off the TPU).  Predictor-driven costs run their lowered
    regressor on-device (``AccelSpec.lowered``); raises ``TypeError``
    only for cost models with no array lowering — see
    :func:`repro.core.costs.lower_to_accel`.
    """
    spec = lower_to_accel(cost, efficiency)
    flops, act = layer_arrays(layers)
    env_arrs = env_columns(envs)
    if len(envs) == 0:                          # nothing to grid over
        empty = np.zeros(0)
        return _plan(cost, spec, np.zeros(0, np.int64), empty, empty,
                     empty, empty,
                     None if spec.objectives == ("latency_s",)
                     else np.zeros((0, len(spec.objectives))),
                     empty)
    return _decide_pallas(layers, flops, act, env_arrs, spec, cost)
