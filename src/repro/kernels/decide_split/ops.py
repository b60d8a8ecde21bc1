"""jit'd decision core: the ``[n_envs, L+1]`` offloading sweep on-accelerator.

:func:`decide_accel` is the accelerator twin of
:func:`repro.core.decisions.decide_all`.  ``backend="jax"`` runs the
latency prefix sums → transfer matrix → scalarise → argmin pipeline as
jitted XLA next to the model, bit-for-bit equal (f64) to the numpy
reference; ``backend="pallas"`` calls the fused TPU kernel
(:mod:`repro.kernels.decide_split.kernel`), which never materialises the
cost tensor in HBM and matches within f32 tolerance.

Cost models lower through :func:`repro.core.costs.lower_to_accel`:
``AnalyticCost`` and ``CompositeCost`` are pure array math over
``EnvArrays``; ``PredictorCost`` lowers by compiling its fitted
regressor to array form (``repro.oracle.lowered`` — the ``AccelSpec``
carries a ``lowered`` layer-times program whose per-layer device/edge
time vectors replace the analytic roofline reconstruction).  Only
regressors outside the lowerable families (ridge / MLP / GBT) are
rejected with a ``TypeError``.

Bit-for-bit notes (why this file looks the way it does):

  * XLA lowers ``cumsum`` to a parallel prefix whose rounding differs
    from numpy's sequential accumulate, so the prefix sums here run as a
    sequential ``lax.scan`` — the exact float ordering of ``np.cumsum``.
  * Inside one jit XLA contracts multiply-add chains into FMAs, which
    perturbs the last ulp of the energy/price objectives and the weighted
    scalarisation.  The multi-objective assembly therefore runs as
    *eager* jnp ops — still device-resident, but one primitive per
    dispatch, which XLA cannot contract.  The latency-only pipelines
    (analytic and predictor-driven) have no mul→add chain and stay
    fully jitted; lowered tree-model inference is add-only (leaf values
    pre-scaled on the host), so it too stays bit-for-bit under jit.
  * Everything executes in f64 under ``repro.x64.x64`` so
    host and accelerator decisions are interchangeable; the Pallas path
    runs the kernel in f32 (the TPU-native width) and re-evaluates the
    chosen splits in f64 on the host — O(E) gathers, no matrices.
"""
# repro: module-tags=fma-sensitive
# (DET001: a @ / dot / matmul here would let XLA FMA-contract and break
#  the f64 bitwise equality with the numpy host path described above)
from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.costs import (ACCEL_OBJECTIVES, AccelSpec, lower_to_accel,
                              scalarize_weighted)
from repro.core.decisions import DecisionPlan, EnvArrays
from repro.core.offload import DEFAULT_EFFICIENCY, LayerCost
from repro.obs.trace import region
from repro.x64 import x64

def _layer_arrays(layers: Sequence[LayerCost]):
    n = len(layers)
    flops = np.fromiter((lc.flops for lc in layers), np.float64, count=n)
    act = np.fromiter((lc.act_bytes for lc in layers), np.float64, count=n)
    return flops, act


def _env_arrays(envs: EnvArrays):
    e = len(envs)

    def tdp(x):
        return np.zeros(e) if x is None else np.asarray(x, np.float64)

    return tuple(np.asarray(x, np.float64) for x in
                 (envs.dev_flops, envs.edge_flops, envs.link_bw,
                  envs.link_latency_s, envs.input_bytes)) \
        + (tdp(envs.dev_tdp_watts), tdp(envs.edge_tdp_watts))


def _seq_cumsum(x):
    """Row-wise cumsum via sequential scan: numpy's exact float ordering
    (XLA's native cumsum is a parallel prefix with different rounding)."""
    def step(carry, col):
        carry = carry + col
        return carry, carry

    _, out = jax.lax.scan(step, jnp.zeros(x.shape[:1], x.dtype), x.T)
    return out.T


@jax.jit
def _latency_parts(flops, act, dev, edge, bw, lat, inp, eff):
    """jnp twin of ``decisions.latency_components`` + ``transfer_bytes``:
    ``(dev_cum, xfer, edge_cum, shipped_bytes)``, each ``[E, L+1]``."""
    e, n = dev.shape[0], flops.shape[0]
    t_dev = flops[None, :] / (dev[:, None] * eff)
    t_edge = flops[None, :] / (edge[:, None] * eff)
    zero = jnp.zeros((e, 1), t_dev.dtype)
    dev_cum = jnp.concatenate([zero, _seq_cumsum(t_dev)], axis=1)
    edge_cum = jnp.concatenate(
        [_seq_cumsum(t_edge[:, ::-1])[:, ::-1], zero], axis=1)
    tb = jnp.concatenate(
        [inp[:, None], jnp.broadcast_to(act[None, :], (e, n))], axis=1)
    tb = tb.at[:, -1].set(0.0)                  # split == L ships nothing
    xfer = lat[:, None] + tb / jnp.maximum(bw, 1.0)[:, None]
    xfer = xfer.at[:, -1].set(0.0)
    return dev_cum, xfer, edge_cum, tb


@jax.jit
def _predictor_parts(t_dev, t_edge, act, bw, lat, inp):
    """Predictor twin of :func:`_latency_parts`: the per-layer time
    vectors are environment-invariant (one device/edge pair per
    ``PredictorCost``), so both cumulative rows are computed once and
    broadcast — the exact float ordering of the host
    ``PredictorCost.latency_parts``."""
    e, n = bw.shape[0], t_dev.shape[0]
    zero1 = jnp.zeros((1, 1), t_dev.dtype)
    dcum = jnp.concatenate([zero1, _seq_cumsum(t_dev[None, :])], axis=1)[0]
    ecum = jnp.concatenate(
        [_seq_cumsum(t_edge[None, ::-1])[:, ::-1], zero1], axis=1)[0]
    tb = jnp.concatenate(
        [inp[:, None], jnp.broadcast_to(act[None, :], (e, n))], axis=1)
    tb = tb.at[:, -1].set(0.0)
    xfer = lat[:, None] + tb / jnp.maximum(bw, 1.0)[:, None]
    xfer = xfer.at[:, -1].set(0.0)
    shape = (e, n + 1)
    return (jnp.broadcast_to(dcum, shape), xfer,
            jnp.broadcast_to(ecum, shape), tb)


@jax.jit
def _decide_latency(flops, act, dev, edge, bw, lat, inp, eff):
    """Latency-only decide: fully fused, bit-for-bit vs the numpy path."""
    dev_cum, xfer, edge_cum, _ = _latency_parts(flops, act, dev, edge, bw,
                                                lat, inp, eff)
    total = dev_cum + xfer + edge_cum
    s = jnp.argmin(total, axis=1)
    rows = jnp.arange(dev.shape[0])
    return s, total[rows, s], dev_cum[rows, s], xfer[rows, s], \
        edge_cum[rows, s]


@jax.jit
def _decide_predictor(t_dev, t_edge, act, bw, lat, inp):
    """Latency-only predictor decide: fully fused (the broadcast +
    transfer + argmin pipeline is add/divide only — no FMA chain)."""
    dev_cum, xfer, edge_cum, _ = _predictor_parts(t_dev, t_edge, act, bw,
                                                  lat, inp)
    total = dev_cum + xfer + edge_cum
    s = jnp.argmin(total, axis=1)
    rows = jnp.arange(bw.shape[0])
    return s, total[rows, s], dev_cum[rows, s], xfer[rows, s], \
        edge_cum[rows, s]


def _composite_decide(parts, tb, dev_w, edge_w, spec: AccelSpec):
    """Multi-objective decide over jitted parts.  Eager on purpose — see
    the module docstring's FMA note; mirrors ``CompositeCost.components``
    + ``scalarize_weighted`` op-for-op."""
    dev_cum, xfer, edge_cum = parts
    total = dev_cum + xfer + edge_cum
    energy = dev_cum * dev_w[:, None] + xfer * spec.radio_watts \
        + edge_cum * edge_w[:, None]
    price = edge_cum * spec.price_per_edge_s + tb / 1e9 * spec.price_per_gb
    slack = jnp.maximum(total - spec.deadline_s, 0.0)
    comp = jnp.stack([total, energy, price, slack], axis=-1)
    w = spec.weights
    scal = comp[..., 0] * w[0]
    for k in range(1, 4):
        scal = scal + comp[..., k] * w[k]
    s = jnp.argmin(scal, axis=1)
    rows = jnp.arange(dev_cum.shape[0])
    return s, comp[rows, s], scal[rows, s], dev_cum[rows, s], \
        xfer[rows, s], edge_cum[rows, s]


def _queue_decide(parts, tb, dev_w, edge_w, spec: AccelSpec):
    """Queue-/tail-aware decide over jitted parts.  Eager like
    :func:`_composite_decide`; mirrors the host ``QueueAwareCost``
    (edge-pool wait bumps the latency objective on offloading splits)
    and ``CompositeCost(tail=...)`` (fifth ``tail_latency_s`` column =
    total + tail-RTT excess on offloading splits) op-for-op."""
    dev_cum, xfer, edge_cum = parts
    total = dev_cum + xfer + edge_cum
    n_obj = len(spec.objectives)
    wait = spec.queue_wait_s
    rows = jnp.arange(dev_cum.shape[0])
    if n_obj == 1:                       # latency-only base + queue wait
        lat_col = jnp.concatenate(
            [total[:, :-1] + wait, total[:, -1:]], axis=1)
        s = jnp.argmin(lat_col, axis=1)
        xfer_q = jnp.concatenate(
            [xfer[:, :-1] + wait, xfer[:, -1:]], axis=1)
        scal_s = lat_col[rows, s]
        return s, scal_s[:, None], scal_s, dev_cum[rows, s], \
            xfer_q[rows, s], edge_cum[rows, s]
    energy = dev_cum * dev_w[:, None] + xfer * spec.radio_watts \
        + edge_cum * edge_w[:, None]
    price = edge_cum * spec.price_per_edge_s + tb / 1e9 * spec.price_per_gb
    slack = jnp.maximum(total - spec.deadline_s, 0.0)
    cols = [total, energy, price, slack]
    weights = list(spec.weights)
    if n_obj == 5:                       # tail_latency_s objective
        cols.append(jnp.concatenate(
            [total[:, :-1] + spec.tail_excess_s, total[:, -1:]], axis=1))
        weights.append(spec.tail_weight)
    if wait != 0.0:
        cols[0] = jnp.concatenate(
            [total[:, :-1] + wait, total[:, -1:]], axis=1)
        xfer = jnp.concatenate(
            [xfer[:, :-1] + wait, xfer[:, -1:]], axis=1)
    comp = jnp.stack(cols, axis=-1)
    scal = comp[..., 0] * weights[0]
    for k in range(1, n_obj):
        scal = scal + comp[..., k] * weights[k]
    s = jnp.argmin(scal, axis=1)
    return s, comp[rows, s], scal[rows, s], dev_cum[rows, s], \
        xfer[rows, s], edge_cum[rows, s]


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


def _decide_jax(layers, flops, act, env_arrs, spec: AccelSpec, cost):
    dev, edge, bw, lat, inp, dev_w, edge_w = env_arrs
    # queue-wait / tail objectives take the eager extended path; when
    # both are off the historical branches run untouched (bit-for-bit)
    queued = (spec.queue_wait_s != 0.0 or len(spec.objectives) > 4)
    with region("decide", "jax"), x64():
        if spec.lowered is not None:
            t_dev, t_edge = spec.lowered.times(layers)
            pargs = tuple(jnp.asarray(x) for x in
                          (t_dev, t_edge, act, bw, lat, inp))
            if spec.objectives == ("latency_s",) and not queued:
                s, total_s, dev_s, xfer_s, edge_s = _decide_predictor(
                    *pargs)
                return _plan(cost, spec, s, dev_s, xfer_s, edge_s, total_s)
            dev_cum, xfer, edge_cum, tb = _predictor_parts(*pargs)
        else:
            args = tuple(jnp.asarray(x) for x in
                         (flops, act, dev, edge, bw, lat, inp))
            if spec.objectives == ("latency_s",) and not queued:
                s, total_s, dev_s, xfer_s, edge_s = _decide_latency(
                    *args, spec.efficiency)
                return _plan(cost, spec, s, dev_s, xfer_s, edge_s, total_s)
            dev_cum, xfer, edge_cum, tb = _latency_parts(*args,
                                                         spec.efficiency)
        decide = _queue_decide if queued else _composite_decide
        s, comp_s, scal_s, dev_s, xfer_s, edge_s = decide(
            (dev_cum, xfer, edge_cum), tb, jnp.asarray(dev_w),
            jnp.asarray(edge_w), spec)
        total_s = np.asarray(comp_s)[:, 0]
        return _plan(cost, spec, s, dev_s, xfer_s, edge_s, total_s,
                     comp_s, scal_s)


def _decide_pallas(layers, flops, act, env_arrs, spec: AccelSpec, cost,
                   interpret: Optional[bool], block_e: int, block_s: int):
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
        s, _ = decide_split_kernel(*f32, spec_arr, block_e=block_e,
                                   block_s=block_s, interpret=interpret)
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


def decide_accel(layers: Sequence[LayerCost], envs: EnvArrays,
                 efficiency: float = DEFAULT_EFFICIENCY, *,
                 cost=None, backend: str = "jax",
                 interpret: Optional[bool] = None,
                 block_e: int = 256, block_s: int = 128) -> DecisionPlan:
    """Accelerator ``decide_all``: one fused cost+argmin over ``[E, L+1]``.

    ``backend="jax"`` is jitted XLA, bit-for-bit (f64) with the numpy
    path; ``backend="pallas"`` is the fused TPU kernel, within f32
    tolerance (``interpret``/``block_e``/``block_s`` tune it; interpret
    defaults to True off-TPU).  Predictor-driven costs run their lowered
    regressor on-device (``AccelSpec.lowered``); raises ``TypeError``
    only for cost models with no array lowering — see
    :func:`repro.core.costs.lower_to_accel`.
    """
    if backend not in ("jax", "pallas"):
        raise ValueError(
            f"unknown accelerator backend {backend!r}; expected 'jax' or "
            "'pallas' (the host path is decisions.decide_all with "
            "backend='numpy')")
    spec = lower_to_accel(cost, efficiency)
    flops, act = _layer_arrays(layers)
    env_arrs = _env_arrays(envs)
    if backend == "pallas":
        if len(envs) == 0:                      # nothing to grid over
            empty = np.zeros(0)
            return _plan(cost, spec, np.zeros(0, np.int64), empty, empty,
                         empty, empty,
                         None if spec.objectives == ("latency_s",)
                         else np.zeros((0, len(spec.objectives))),
                         empty)
        return _decide_pallas(layers, flops, act, env_arrs, spec, cost,
                              interpret, block_e, block_s)
    return _decide_jax(layers, flops, act, env_arrs, spec, cost)


def latency_matrix_jax(layers: Sequence[LayerCost], envs: EnvArrays,
                       efficiency: float = DEFAULT_EFFICIENCY) -> np.ndarray:
    """jit-computed ``[E, L+1]`` latency matrix, bit-for-bit (f64) with
    ``decisions.latency_matrix`` — the equivalence-test surface."""
    flops, act = _layer_arrays(layers)
    dev, edge, bw, lat, inp, _, _ = _env_arrays(envs)
    with x64():
        dev_cum, xfer, edge_cum, _ = _latency_parts(
            *(jnp.asarray(x) for x in (flops, act, dev, edge, bw, lat,
                                       inp)), efficiency)
        return np.asarray(dev_cum + xfer + edge_cum)
