"""Oracle benchmark: predictor-serving throughput + the closed loop.

Three sections, all emitted through the shared ``results/`` record
schema (full runs additionally write the committed ``BENCH_5.json``
baseline at the repo root):

  * **predict sweep** — fitted-GBT inference throughput, host
    ``GBTRegressor.predict`` vs the lowered jitted-XLA descent vs the
    fused Pallas tree kernel, at 1024–65536-row sweeps (the per-request
    feature batches a fleet-scale ETC/decision sweep generates).  The
    jitted path is asserted to be at least as fast as host numpy at the
    largest swept size (warm cache; compile excluded by the timing
    warm-up).  Pallas rows off-TPU run in interpret mode — correctness
    smoke, not a performance number — and are flagged
    ``interpret: true``.
  * **predictor-driven decide** — ``decide_all(cost=PredictorCost(...))``
    throughput on numpy and Pallas at the 16384-env fleet size (the
    PR-3 sweep, now with the profiling model in the loop; Pallas rows
    off-TPU are interpret-mode and flagged so).
  * **closed-loop drift** — a structured machine-slowdown scenario:
    observations stream through an ``OnlineOracle``; rolling nRMSE
    degrades at the change point, Page–Hinkley triggers, the
    fresh-window refit recovers accuracy (asserted).  A second row pins
    the always-on gain correction tracking a *uniform* 2× slowdown
    without any refit.

Run:  PYTHONPATH=src python benchmarks/bench_oracle.py [--smoke]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

if __package__ in (None, ""):            # `python benchmarks/bench_...py`
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from benchmarks.common import emit
from repro.core import costs as co
from repro.core import decisions as dec
from repro.core import offload as off
from repro.hw import EDGE_DEVICES, get_device
from repro.oracle import OnlineOracle, lower_predictor

DEVICE_NAME, EDGE_NAME = "pi5-arm", "edge-server-a100"


def times_us(fn, reps: int):
    """(median, best) wall-clock per call in microseconds (first call
    outside timing warms caches + jit)."""
    fn()
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts) * 1e6), float(np.min(ts) * 1e6)


def synth_layers(n: int, seed: int = 0):
    rng = np.random.default_rng(seed)
    return [off.LayerCost(f"l{i}",
                          flops=float(rng.uniform(1e8, 1e11)),
                          act_bytes=float(rng.uniform(1e3, 1e7)))
            for i in range(n)]


def layer_training_set(layers):
    feats, ys = [], []
    for spec in EDGE_DEVICES.values():
        feats.append(co.default_layer_features(layers, spec))
        ys.append([off.layer_time(lc.flops, spec) for lc in layers])
    return np.concatenate(feats), np.concatenate(ys)


def fit_profiling_gbt(n_trees: int = 40, max_depth: int = 5,
                      n_layers: int = 64, seed: int = 0):
    """Profiling GBT over task-shaped features (``act_bytes=0``, the
    ETC/oracle query form — keeping train and serve distributions
    aligned so the activation column stays constant).  The defaults are
    throughput-bench sized; the closed-loop drift scenario fits a
    high-capacity one (≈2% relative error) so residuals measure drift,
    not model noise."""
    rng = np.random.default_rng(seed)
    layers = [off.LayerCost(f"l{i}", flops=float(f), act_bytes=0.0)
              for i, f in enumerate(rng.uniform(1e8, 1e11, n_layers))]
    x, y = layer_training_set(layers)
    from repro.core.predictors import GBTRegressor
    return GBTRegressor(n_trees=n_trees, max_depth=max_depth,
                        seed=seed).fit(x, y)


# --------------------------------------------------------------------------
# predict-throughput sweep
# --------------------------------------------------------------------------
def bench_predict(smoke: bool) -> list[dict]:
    import jax
    interpret = jax.default_backend() != "tpu"
    reps = 3 if smoke else 7
    sizes = (1024, 4096) if smoke else (1024, 4096, 16384, 65536)
    model = fit_profiling_gbt()
    lowered = lower_predictor(model)
    rng = np.random.default_rng(1)
    specs = list(EDGE_DEVICES.values())
    rows = []
    for n in sizes:
        qlayers = [off.LayerCost("q", flops=float(f), act_bytes=0.0)
                   for f in rng.uniform(1e8, 1e11, n // len(specs))]
        x = np.concatenate([co.default_layer_features(qlayers, s)
                            for s in specs])[:n]
        cell = {}
        for backend in ("host", "jax", "pallas"):
            if backend == "pallas" and interpret and n > 4096:
                continue             # interpret-mode grid loop too slow
            fn = (lambda: model.predict(x)) if backend == "host" \
                else (lambda: lowered.predict(x, backend=backend))
            t, best = times_us(fn, reps)
            cell[backend] = best
            row = {
                "name": f"tree_predict_{backend}_{n}",
                "backend": backend,
                "n_rows": n,
                "us_per_call": t,
                "best_us": best,
                "predictions_per_s": n * 1e6 / t,
            }
            if backend == "pallas":
                row["interpret"] = interpret
            if backend != "host" and "host" in cell:
                row["speedup_vs_host"] = cell["host"] / best
            rows.append(row)
        if n == sizes[-1]:
            # best-of-reps with a 5% shared-runner allowance, mirroring
            # the PR-3 decide smoke
            assert cell["jax"] <= cell["host"] * 1.05, (
                f"jitted tree predict slower than host numpy at the "
                f"largest sweep: best {cell['jax']:.0f}us vs "
                f"{cell['host']:.0f}us (n={n})")
    return rows


# --------------------------------------------------------------------------
# predictor-driven decide sweep
# --------------------------------------------------------------------------
def bench_decide(smoke: bool) -> list[dict]:
    import jax
    interpret = jax.default_backend() != "tpu"
    reps = 3 if smoke else 7
    n_envs = 4096 if smoke else 16384
    layers = synth_layers(64)
    model = fit_profiling_gbt()
    device, edge = get_device(DEVICE_NAME), get_device(EDGE_NAME)
    envs = dec.make_envs(device, edge,
                         link_bw=np.geomspace(1e5, 1e10, n_envs),
                         input_bytes=1e5)
    rows, cell = [], {}
    for backend in ("numpy", "pallas"):
        cost = co.PredictorCost(model, device, edge)
        t, best = times_us(lambda: dec.decide_all(layers, envs, cost=cost,
                                                  backend=backend), reps)
        cell[backend] = best
        row = {
            "name": f"decide_predictor_{backend}_envs{n_envs}",
            "backend": backend,
            "n_envs": n_envs,
            "n_layers": 64,
            "us_per_call": t,
            "best_us": best,
            "decisions_per_s": n_envs * 1e6 / t,
        }
        if backend != "numpy":
            row["interpret"] = interpret
            row["speedup_vs_numpy"] = cell["numpy"] / best
        rows.append(row)
    return rows


# --------------------------------------------------------------------------
# closed-loop drift scenario
# --------------------------------------------------------------------------
def bench_drift(smoke: bool) -> list[dict]:
    rng = np.random.default_rng(19)
    specs = list(EDGE_DEVICES.values())
    model = fit_profiling_gbt(n_trees=150, max_depth=8, n_layers=512)
    device, edge = get_device(DEVICE_NAME), get_device(EDGE_NAME)
    # total leaves room for the rolling-nRMSE window to flush its
    # pre-refit pairs after the refit lands (trigger ≈ drift + 20,
    # refit = trigger + min_refit, window = 256 pairs)
    drift_at, total = (150, 650) if smoke else (250, 800)
    oracle = OnlineOracle(model, device, edge, window=256,
                          min_refit=120, correction="none")
    track, drift_step, refit_step = [], None, None
    for step in range(total):
        spec = specs[int(rng.integers(len(specs)))]
        flops = float(rng.uniform(1e8, 1e11))
        f = oracle.feature_fn(
            [off.LayerCost("q", flops=flops, act_bytes=0.0)], spec)[0]
        t = off.layer_time(flops, spec)
        if step >= drift_at and spec.tdp_watts in (12, 15):
            t *= 3.0                 # pi5 + jetson slow down: structured
        out = oracle.observe(f, t)
        track.append(oracle.rolling_nrmse())
        if out["drift"] and drift_step is None:
            drift_step = step
        if out["refit_version"] is not None and refit_step is None:
            refit_step = step
    pre = float(np.mean(track[drift_at - 50:drift_at]))
    peak = float(np.max(track[drift_at:]))
    recovered = float(np.mean(track[-50:]))
    assert oracle.refits >= 1, "drift scenario produced no refit"
    assert recovered < 0.5 * peak, (
        f"online refit failed to recover accuracy: nRMSE {recovered:.4f} "
        f"vs drift peak {peak:.4f}")
    rows = [{
        "name": "oracle_drift_closed_loop",
        "n_observations": total,
        "drift_injected_at": drift_at,
        "drift_detected_at": drift_step,
        "refit_at": refit_step,
        "nrmse_pre_drift": pre,
        "nrmse_drift_peak": peak,
        "nrmse_recovered": recovered,
        "drift_triggers": oracle.drift_triggers,
        "refits": oracle.refits,
        "registry_version": oracle.version,
    }]

    # uniform 2x slowdown: the always-on gain correction alone recovers
    oracle2 = OnlineOracle(model, device, edge, correction="gain",
                           refit_on_drift=False)
    resid_raw, resid_corr = [], []
    for step in range(150 if smoke else 300):
        spec = specs[int(rng.integers(len(specs)))]
        flops = float(rng.uniform(1e8, 1e11))
        f = oracle2.feature_fn(
            [off.LayerCost("q", flops=flops, act_bytes=0.0)], spec)[0]
        t = 2.0 * off.layer_time(flops, spec)
        corrected = oracle2.predict_one(f)
        raw = corrected / oracle2.gain
        resid_raw.append(abs(t - raw) / t)
        resid_corr.append(abs(t - corrected) / t)
        oracle2.observe(f, t, predicted_s=corrected)
    tail = slice(len(resid_corr) // 2, None)
    rows.append({
        "name": "oracle_gain_tracks_uniform_slowdown",
        "gain": oracle2.gain,
        "mean_rel_err_uncorrected": float(np.mean(resid_raw[tail])),
        "mean_rel_err_corrected": float(np.mean(resid_corr[tail])),
    })
    assert abs(oracle2.gain - 2.0) < 0.25, oracle2.gain
    assert np.mean(resid_corr[tail]) < 0.5 * np.mean(resid_raw[tail])
    return rows


def main(smoke: bool = False) -> list[dict]:
    rows = bench_predict(smoke) + bench_decide(smoke) + bench_drift(smoke)
    if not smoke:                    # smoke must not clobber the baseline
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        with open(os.path.join(root, "BENCH_5.json"), "w") as f:
            json.dump(rows, f, indent=1, default=float)
    emit(rows, "oracle")
    return rows


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small sizes / few reps for CI")
    main(smoke=ap.parse_args().smoke)
