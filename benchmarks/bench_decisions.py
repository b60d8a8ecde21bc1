"""Decision-throughput benchmark: scalar oracles vs the vectorized core.

Measures decisions/sec for the three hot decision paths —

  * ``optimal_split``  — O(L²) scalar oracle vs O(L) prefix-sum argmin,
                         varying model depth L
  * environment sweep  — per-env scalar loop vs one ``[n_envs, L+1]``
                         batched latency matrix
  * Q-learning train   — 3000 scalar ``split_time`` episodes vs the
                         table-driven batched trainer
  * ``min_min``/``max_min``/``heft`` — nested-loop ETC heuristics vs the
                         masked-matrix argmin versions, varying T×N

``--cost {analytic,predictor,composite,all}`` switches to the cost-model
sweep mode instead: decisions/sec of ``decide_all`` per CostModel over a
1024-environment link grid.  The predictor row also reports
``predict_calls`` — the whole 1024-env sweep must be ONE vectorised
``predict`` call (asserted), the API's fleet-scale guarantee.

``--backend {numpy,pallas,all}`` switches to the decision-backend
sweep: ``decide_all`` throughput per backend over a (n_envs ∈ {1024,
16384}) × (L ∈ {64, 1024}) grid, written to ``BENCH_3.json`` at the
repo root (full runs only — the committed baseline).  Pallas rows
off-TPU run the kernel in interpret mode — correctness smoke, not a
performance number — and are flagged ``interpret: true``.

Run:  PYTHONPATH=src python benchmarks/bench_decisions.py [--smoke]
      PYTHONPATH=src python benchmarks/bench_decisions.py --cost all
      PYTHONPATH=src python benchmarks/bench_decisions.py --backend all
"""
from __future__ import annotations

import argparse
import os
import sys
import time

import numpy as np

if __package__ in (None, ""):            # `python benchmarks/bench_...py`
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from benchmarks.common import emit
from repro.core import decisions as dec
from repro.core import offload as off
from repro.core import scheduler as sch
from repro.hw import EDGE_DEVICES, get_device


def wall_us(fn, *args, reps: int = 5):
    """Median wall-clock per call in microseconds (pure CPU, no jax)."""
    fn(*args)                        # warm caches
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        fn(*args)
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts) * 1e6)


def synth_layers(L: int, seed: int = 0) -> list[off.LayerCost]:
    rng = np.random.default_rng(seed)
    return [off.LayerCost(f"l{i}",
                          flops=float(rng.uniform(1e8, 1e11)),
                          act_bytes=float(rng.uniform(1e3, 1e7)))
            for i in range(L)]


def make_env(link_bw: float = 0.125e9) -> off.OffloadEnv:
    return off.OffloadEnv(device=get_device("pi5-arm"),
                          edge=get_device("edge-server-a100"),
                          link_bw=link_bw, input_bytes=1e5)


def qtrain_scalar_ref(layers, env, episodes: int, seed: int = 0):
    """Replica of the seed's per-episode scalar Q-learning loop."""
    import dataclasses
    buckets = (0.125e9 / 16, 0.125e9 / 4, 0.125e9, 1.25e9)
    n_actions = len(layers) + 1
    q = np.zeros((len(buckets), n_actions))
    rng = np.random.default_rng(seed)
    for _ in range(episodes):
        s = int(rng.integers(len(buckets)))
        if rng.random() < 0.2:
            a = int(rng.integers(n_actions))
        else:
            a = int(np.argmax(q[s]))
        e = dataclasses.replace(env, link_bw=buckets[s])
        q[s, a] += 0.2 * (-off.split_time(layers, a, e).total_time_s
                          - q[s, a])
    return q


class _CountingModel:
    """Regressor proxy counting ``predict`` calls (vectorisation proof)."""

    def __init__(self, inner):
        self.inner = inner
        self.calls = 0

    def predict(self, x):
        self.calls += 1
        return self.inner.predict(x)


def _fit_profiling_gbt(layers):
    """Small GBT over (layer, hardware) features → analytic layer times,
    standing in for the paper's trained profiling model."""
    from repro.core.costs import default_layer_features
    from repro.core.predictors import GBTRegressor
    feats, ys = [], []
    for spec in EDGE_DEVICES.values():
        feats.append(default_layer_features(layers, spec))
        ys.append([off.layer_time(lc.flops, spec) for lc in layers])
    return GBTRegressor(n_trees=40, max_depth=4).fit(
        np.concatenate(feats), np.concatenate(ys))


def main_costs(which: str, smoke: bool = False) -> list[dict]:
    """decisions/sec of ``decide_all`` per cost model, 1024-env link sweep."""
    from repro.core import costs as co
    reps = 3 if smoke else 7
    n_envs = 1024                       # ≥1024: the fleet-sweep guarantee
    layers = synth_layers(64)
    device, edge = get_device("pi5-arm"), get_device("edge-server-a100")
    # two link-state grids, alternated per call: every sweep sees fresh
    # envs (as in live serving), so per-(layers, envs) memoisation inside
    # the cost models cannot flatter the numbers — only the per-layer
    # predict memo (keyed on the layer set) legitimately persists
    env_grids = [dec.make_envs(device, edge,
                               link_bw=np.geomspace(1e5, 1e10, n_envs) * f,
                               input_bytes=1e5)
                 for f in (1.0, 1.1)]
    calls = {"n": 0}

    def sweep(cost):
        calls["n"] += 1
        return dec.decide_all(layers, env_grids[calls["n"] % 2], cost=cost)

    selected = {}
    counting = None
    if which in ("analytic", "all"):
        selected["analytic"] = co.AnalyticCost()
    if which in ("predictor", "all"):
        counting = _CountingModel(_fit_profiling_gbt(layers))
        selected["predictor"] = co.PredictorCost(counting, device, edge)
    if which in ("composite", "all"):
        selected["composite"] = co.CompositeCost(
            weights={"latency_s": 1.0, "energy_j": 0.05, "price": 1.0},
            price_per_edge_s=0.1, price_per_gb=0.01, deadline_s=0.05)
    rows = []
    for name, cost in selected.items():
        if counting is not None:
            counting.calls = 0
        t = wall_us(lambda: sweep(cost), reps=reps)
        row = {
            "name": f"cost_{name}_sweep{n_envs}",
            "us_per_call": t,
            "decisions_per_s": n_envs * 1e6 / t,
            "n_objectives": len(cost.objectives),
        }
        if name == "predictor":
            # memoised on the layer set: every repeated 1024-env sweep
            # shares ONE vectorised predict call — no per-env Python loop
            assert counting.calls == 1, (
                f"predictor sweep must be ONE vectorised predict call, "
                f"got {counting.calls} over {reps + 1} sweeps")
            row["predict_calls"] = counting.calls
        rows.append(row)
    emit(rows, "decisions_cost")
    return rows


def main_backends(which: str, smoke: bool = False) -> list[dict]:
    """``decide_all`` throughput per backend over an (n_envs, L) grid.

    Full (non-smoke) runs write ``BENCH_3.json`` at the repo root — the
    committed baseline of the bench trajectory (``results/`` is
    gitignored).
    """
    import json

    import jax
    backends = ["numpy", "pallas"] if which == "all" else [which]
    interpret = jax.default_backend() != "tpu"
    reps = 4 if smoke else 7

    def times_us(fn):
        """(median, best) wall-clock per call in microseconds.  Best-of-N
        estimates true speed; the median keeps the reported throughput
        honest about typical latency."""
        fn()                         # warm caches + jit compile
        ts = []
        for _ in range(reps):
            t0 = time.perf_counter()
            fn()
            ts.append(time.perf_counter() - t0)
        return float(np.median(ts) * 1e6), float(np.min(ts) * 1e6)
    device, edge = get_device("pi5-arm"), get_device("edge-server-a100")
    cells = [(64, 1024), (64, 16384)] if smoke \
        else [(64, 1024), (64, 16384), (1024, 1024), (1024, 16384)]
    rows = []
    for L, n_envs in cells:
        layers = synth_layers(L)
        envs = dec.make_envs(device, edge,
                             link_bw=np.geomspace(1e5, 1e10, n_envs),
                             input_bytes=1e5)
        cell = {}
        for backend in backends:
            if backend == "pallas" and interpret and n_envs > 1024:
                continue             # interpret-mode grid loop too slow
            t, best = times_us(lambda: dec.decide_all(layers, envs,
                                                      backend=backend))
            cell[backend] = best
            row = {
                "name": f"decide_{backend}_L{L}_envs{n_envs}",
                "backend": backend,
                "n_envs": n_envs,
                "n_layers": L,
                "us_per_call": t,
                "best_us": best,
                "decisions_per_s": n_envs * 1e6 / t,
            }
            if backend == "pallas":
                row["interpret"] = interpret
            if backend != "numpy" and "numpy" in cell:
                row["speedup_vs_numpy"] = cell["numpy"] / best
            rows.append(row)
    if not smoke:                    # smoke must not clobber the baseline
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        with open(os.path.join(root, "BENCH_3.json"), "w") as f:
            json.dump(rows, f, indent=1, default=float)
    emit(rows, "decisions_backend")
    return rows


def main(smoke: bool = False) -> list[dict]:
    rows = []
    reps = 2 if smoke else 7

    # -- all-splits offloading, varying depth -------------------------------
    env = make_env()
    for L in (16, 64) if smoke else (16, 64, 256, 1024):
        layers = synth_layers(L)
        t_ref = wall_us(off.optimal_split_ref, layers, env, reps=reps)
        t_vec = wall_us(off.optimal_split, layers, env, reps=reps)
        rows.append({
            "name": f"optimal_split_L{L}",
            "us_per_call": t_vec,
            "us_scalar": t_ref,
            "speedup": t_ref / t_vec,
            "decisions_per_s": 1e6 / t_vec,
        })

    # -- batched environment sweep ------------------------------------------
    layers = synth_layers(64)
    for n_envs in (256,) if smoke else (256, 1024):
        bws = np.geomspace(1e5, 1e10, n_envs)

        def sweep_scalar():
            import dataclasses
            return [off.optimal_split_ref(layers,
                                          dataclasses.replace(env,
                                                              link_bw=b))
                    for b in bws]

        def sweep_vec():
            return dec.sweep_links(layers, env, bws)

        t_ref = wall_us(sweep_scalar, reps=min(reps, 3))
        t_vec = wall_us(sweep_vec, reps=reps)
        rows.append({
            "name": f"env_sweep_{n_envs}",
            "us_per_call": t_vec,
            "us_scalar": t_ref,
            "speedup": t_ref / t_vec,
            "decisions_per_s": n_envs * 1e6 / t_vec,
        })

    # -- Q-learning training -------------------------------------------------
    episodes = 300 if smoke else 3000
    layers_q = synth_layers(12)
    t_ref = wall_us(qtrain_scalar_ref, layers_q, env, episodes, reps=reps)
    t_vec = wall_us(
        lambda: off.QLearningPolicy(layers_q, env,
                                    episodes=episodes).train(), reps=reps)
    rows.append({
        "name": f"qlearning_train_{episodes}ep",
        "us_per_call": t_vec,
        "us_scalar": t_ref,
        "speedup": t_ref / t_vec,
        "episodes_per_s": episodes * 1e6 / t_vec,
    })

    # -- ETC schedulers ------------------------------------------------------
    shapes = [(100, 16)] if smoke else [(40, 5), (100, 16), (400, 32)]
    for n_tasks, n_nodes in shapes:
        rng = np.random.default_rng(n_tasks)
        specs = list(EDGE_DEVICES.values())
        nodes = [sch.Node(specs[j % len(specs)]) for j in range(n_nodes)]
        tasks = [sch.Task(f"t{i}", flops=float(rng.uniform(1e9, 5e11)),
                          input_bytes=float(rng.uniform(1e4, 1e7)))
                 for i in range(n_tasks)]
        etc = sch.etc_matrix(tasks, nodes)
        for name in ("min_min", "max_min", "heft"):
            t_ref = wall_us(sch.SCHEDULERS_REF[name], tasks, nodes, etc,
                            reps=reps)
            t_vec = wall_us(sch.SCHEDULERS[name], tasks, nodes, etc,
                            reps=reps)
            rows.append({
                "name": f"{name}_{n_tasks}x{n_nodes}",
                "us_per_call": t_vec,
                "us_scalar": t_ref,
                "speedup": t_ref / t_vec,
                "schedules_per_s": 1e6 / t_vec,
            })

    emit(rows, "decisions")
    return rows


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small sizes / few reps for CI")
    ap.add_argument("--cost", choices=("analytic", "predictor", "composite",
                                       "all"),
                    help="run the cost-model sweep mode instead")
    ap.add_argument("--backend", choices=("numpy", "pallas", "all"),
                    help="run the decision-backend sweep mode instead")
    args = ap.parse_args()
    if args.cost:
        main_costs(args.cost, smoke=args.smoke)
    elif args.backend:
        main_backends(args.backend, smoke=args.smoke)
    else:
        main(smoke=args.smoke)
