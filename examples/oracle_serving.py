"""Oracle serving end to end: lowered predictor sweeps + the closed loop.

1. Fit the paper's profiling GBT on (layer, hardware) features.
2. Run a 16384-environment predictor-driven offloading sweep on the
   Pallas decide kernel (the fitted trees execute as jitted XLA).
3. Stream realised execution times through an OnlineOracle while two
   device classes silently slow down 3x: watch the rolling nRMSE
   degrade, the Page-Hinkley detector fire, and a fresh-window refit
   (published to the versioned registry) recover accuracy.
4. Ride the oracle along a streaming simulation — with a static world
   it is bit-transparent: identical placements to the oracle-free path.

Run:  PYTHONPATH=src python examples/oracle_serving.py
"""
from __future__ import annotations

import numpy as np

from repro.core import costs as co
from repro.core import decisions as dec
from repro.core import offload as off
from repro.core import scheduler as sch
from repro.core.predictors import GBTRegressor
from repro.hw import EDGE_DEVICES, get_device
from repro.oracle import OnlineOracle
from repro.sim import simulate_stream

DEVICE, EDGE = get_device("pi5-arm"), get_device("edge-server-a100")
SPECS = list(EDGE_DEVICES.values())


def fit_profiler(rng, n_layers=256, n_trees=120, max_depth=8):
    layers = [off.LayerCost(f"l{i}", flops=float(f), act_bytes=0.0)
              for i, f in enumerate(rng.uniform(1e8, 1e11, n_layers))]
    x = np.concatenate([co.default_layer_features(layers, s)
                        for s in SPECS])
    y = np.concatenate([[off.layer_time(lc.flops, s) for lc in layers]
                        for s in SPECS])
    return GBTRegressor(n_trees=n_trees, max_depth=max_depth).fit(x, y)


def main():
    rng = np.random.default_rng(0)
    print("== 1. fit the profiling predictor ==")
    gbt = fit_profiler(rng)

    print("\n== 2. predictor-driven sweep, lowered to the accelerator ==")
    layers = [off.LayerCost(f"l{i}", flops=float(rng.uniform(1e8, 1e9)),
                            act_bytes=float(rng.uniform(1e3, 1e5)))
              for i in range(48)]
    envs = dec.make_envs(DEVICE, EDGE,
                         link_bw=np.geomspace(1e4, 1e10, 16384),
                         input_bytes=1e7)
    cost = co.PredictorCost(gbt, DEVICE, EDGE)
    plan_np = dec.decide_all(layers, envs, cost=cost)
    plan_pl = dec.decide_all(layers, envs, cost=cost, backend="pallas")
    assert np.array_equal(plan_np.splits, plan_pl.splits)
    on_dev = np.bincount(np.minimum(plan_pl.splits, 2), minlength=3)
    print(f"  16384 envs swept on backend='pallas'; splits exactly match "
          f"numpy\n  all-edge: {on_dev[0]}, partial: "
          f"{len(envs) - on_dev[0] - (plan_pl.splits == len(layers)).sum()},"
          f" all-device: {(plan_pl.splits == len(layers)).sum()}")

    print("\n== 3. online drift -> detection -> refit -> recovery ==")
    oracle = OnlineOracle(gbt, DEVICE, EDGE, window=256, min_refit=120,
                          correction="none")
    track = []
    for step in range(700):
        spec = SPECS[int(rng.integers(len(SPECS)))]
        flops = float(rng.uniform(1e8, 1e11))
        f = oracle.feature_fn(
            [off.LayerCost("q", flops=flops, act_bytes=0.0)], spec)[0]
        t = off.layer_time(flops, spec)
        if step >= 200 and spec.tdp_watts in (12, 15):
            t *= 3.0                 # pi5 + jetson quietly slow down
        out = oracle.observe(f, t)
        track.append(oracle.rolling_nrmse())
        if out["drift"]:
            print(f"  step {step:3d}: drift detected "
                  f"(injected at 200), nRMSE {track[-1]:.4f}")
        if out["refit_version"] is not None:
            print(f"  step {step:3d}: refit on fresh window -> "
                  f"registry v{out['refit_version']}")
    print(f"  nRMSE pre-drift {np.mean(track[150:200]):.4f} -> "
          f"peak {max(track[200:]):.4f} -> "
          f"recovered {np.mean(track[-50:]):.4f} "
          f"(registry version {oracle.version})")

    print("\n== 4. oracle riding the streaming simulator ==")
    nodes = [sch.Node(SPECS[j % len(SPECS)]) for j in range(4)]
    tasks = [sch.Task(f"t{i}", flops=float(rng.uniform(1e9, 5e11)),
                      input_bytes=float(rng.uniform(1e4, 1e6)))
             for i in range(60)]
    arrivals = np.sort(rng.uniform(0.0, 12.0, len(tasks)))
    plain = simulate_stream(tasks, arrivals, nodes,
                            cost=co.PredictorCost(gbt, DEVICE, EDGE))
    riding = OnlineOracle(gbt, DEVICE, EDGE)
    with_oracle = simulate_stream(tasks, arrivals, nodes, oracle=riding)
    same = all(a.node == b.node and a.finished_s == b.finished_s
               for a, b in zip(plain.records, with_oracle.records))
    s = with_oracle.summary()
    print(f"  static world: placements identical to oracle-free path: "
          f"{same}")
    print(f"  {s['oracle_observations']} completions observed, "
          f"{s.get('oracle_drift_triggers', 0)} drift triggers, "
          f"rolling nRMSE {s['oracle_nrmse']:.2e} (float noise only)")

    # now give the sim a ground truth the predictor doesn't know:
    # pi5 + jetson quietly start running 3x slower a third of the way in
    def ground_truth(task, spec, etc_s, start_s):
        slow = 3.0 if start_s >= 130.0 and spec.tdp_watts in (12, 15) \
            else 1.0
        return slow * off.layer_time(task.flops, spec)

    many = [sch.Task(f"d{i}", flops=float(rng.uniform(1e8, 1e11)),
                     input_bytes=0.0) for i in range(400)]
    arr = np.sort(rng.uniform(0.0, 400.0, len(many)))
    learner = OnlineOracle(gbt, DEVICE, EDGE, window=256, min_refit=64,
                           correction="none")
    drifted = simulate_stream(many, arr, nodes, oracle=learner,
                              service_time_fn=ground_truth)
    d = drifted.summary()
    print(f"  drifted world (service_time_fn): "
          f"{d.get('oracle_drift_triggers', 0)} drift triggers, "
          f"{d.get('oracle_refits', 0)} refits through the sim loop, "
          f"final rolling nRMSE {d['oracle_nrmse']:.4f} "
          f"(registry v{learner.version})")


if __name__ == "__main__":
    main()
