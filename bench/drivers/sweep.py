"""Closed-loop planner sweep: one planner re-plans every user of the site
for one app per call, back to back.

Traffic parameters (``traffic/<name>.json``): ``apps`` (per-layer FLOPs
and activation bytes, and the input bytes), ``snapshots`` of the users'
links drawn from the seed (log-normal bandwidth with
``link_bw_median`` and ``link_bw_sigma``, latency uniform in
``link_latency_s``), the decide ``backend``, and ``check_calls``, how
many calls of each app in the window are kept (a seeded uniform sample)
for the comparison with the reference.  Call ``i`` serves app ``i % A`` on
snapshot ``(i // A) % S``.
"""
from __future__ import annotations

import time

import numpy as np

from common import Reservoir, rel_gap, seed_streams


def links(traffic: dict, n: int, rng) -> tuple[np.ndarray, np.ndarray]:
    bw = traffic["link_bw_median"] * np.exp(
        traffic["link_bw_sigma"] * rng.standard_normal(n))
    lat = rng.uniform(*traffic["link_latency_s"], n)
    return bw, lat


class State:
    pass


def setup(ctx):
    from repro.core import costs as co
    from repro.core import decisions as dec
    from repro.core.offload import LayerCost
    from repro.hw import get_device
    cfg, tr = ctx.cfg, ctx.traffic
    r_ens, r_links, r_keep = seed_streams(ctx.seed, 3)
    st = State()
    st.ens = ctx.cfg_mod.make_ensemble(cfg, r_ens)
    st.gbt = ctx.cfg_mod.load_program_predictor(st.ens, cfg, ctx.tmpdir)
    st.device, st.edge = get_device(cfg["device"]), get_device(cfg["edge"])
    st.cost = co.PredictorCost(st.gbt, st.device, st.edge)
    st.apps = [[LayerCost(f"l{i}", flops=f, act_bytes=a)
                for i, (f, a) in enumerate(app["layers"])]
               for app in tr["apps"]]
    st.snaps = [links(tr, cfg["users"], r_links)
                for _ in range(tr["snapshots"])]
    st.keep = [Reservoir(tr["check_calls"], r_keep) for _ in st.apps]
    st.dec = dec
    # warm-up: every (app, snapshot) shape the window will use
    for a in range(len(st.apps)):
        call(ctx, st, a, 0)
    return st


def call(ctx, st, app: int, snap: int):
    bw, lat = st.snaps[snap]
    with ctx.spans("make_envs"):
        envs = st.dec.make_envs(st.device, st.edge, link_bw=bw,
                                link_latency_s=lat,
                                input_bytes=ctx.traffic["apps"][app][
                                    "input_bytes"])
    with ctx.spans("decide_all"):
        return st.dec.decide_all(st.apps[app], envs, cost=st.cost,
                                 backend=ctx.traffic["backend"])


def window(ctx, st, seconds: float) -> dict:
    n_apps, n_snap = len(st.apps), len(st.snaps)
    calls = decided = 0
    traced = []                          # split count of each traced call
    t0 = time.perf_counter()
    elapsed = 0.0
    while elapsed < seconds:
        ctx.trace.poll(elapsed)
        app, snap = calls % n_apps, (calls // n_apps) % n_snap
        if ctx.trace.state == "tracing":
            traced.append(len(st.apps[app]) + 1)
        plan = call(ctx, st, app, snap)
        elapsed = time.perf_counter() - t0
        calls += 1
        decided += len(plan)
        st.keep[app].offer(lambda: (app, snap, np.asarray(plan.splits),
                                    np.asarray(plan.total_time_s)))
    return {"e2e": {"decisions_per_s": decided / elapsed},
            "counters": {"users": ctx.cfg["users"],
                         "traced_splits": traced},
            "attempted": calls, "failed": 0,
            "info": {"calls": calls, "decided": decided,
                     "window_s": elapsed}}


def release(st) -> None:
    st.cost = st.gbt = None


def reference_gaps(ctx, st, kept, dtype=np.float64, xp=np):
    """Over the kept calls: the widest relative gap between the reference
    cost of the split the program chose and the reference's best, and
    the widest relative distance of the program's reported latency from
    that best.  Kept calls without splits (``None``) are decided by the
    reference itself, its costs computed in ``dtype`` with ``xp`` (the
    control); the gaps are always read in f64."""
    m = ctx.cfg_mod
    gap = err = 0.0
    for app, snap, splits, total in kept:
        layers = np.asarray(ctx.traffic["apps"][app]["layers"])
        t_dev, t_edge = m.layer_times_ref(st.ens, ctx.cfg, layers[:, 0],
                                          layers[:, 1])
        bw, lat = st.snaps[snap]
        inp = np.full(bw.shape, ctx.traffic["apps"][app]["input_bytes"])
        for lo in range(0, bw.shape[0], 1 << 16):
            sl = slice(lo, lo + (1 << 16))
            cost = m.split_costs_ref(t_dev, t_edge, layers[:, 1], bw[sl],
                                     lat[sl], inp[sl])
            best = cost.min(axis=1)
            if splits is None:
                low = np.asarray(m.split_costs_ref(
                    *(xp.asarray(a, dtype) for a in (
                        t_dev, t_edge, layers[:, 1], bw[sl], lat[sl],
                        inp[sl])), xp=xp, dtype=dtype)).astype(np.float64)
                chose = np.argmin(low, axis=1)
                reported = low[np.arange(low.shape[0]), chose]
            else:
                chose, reported = splits[sl], total[sl]
            got = cost[np.arange(cost.shape[0]), chose]
            gap = max(gap, rel_gap(got, best))
            err = max(err, rel_gap(np.abs(reported - best) + best, best))
    return gap, err


def kept(st) -> list:
    return [item for r in st.keep for item in r.items]


def readings(ctx, st) -> dict:
    gap, err = reference_gaps(ctx, st, kept(st))
    return {"split_gap_rel": gap, "latency_err_rel": err}


def control(ctx, st) -> dict:
    """The reference in the program's place, its costs in bfloat16 on
    the device: the same calls, its own splits and latencies."""
    import jax.numpy as jnp
    low = [(app, snap, None, None) for app, snap, _, _ in kept(st)]
    gap, err = reference_gaps(ctx, st, low, dtype=jnp.bfloat16, xp=jnp)
    return {"split_gap_rel": gap, "latency_err_rel": err}
