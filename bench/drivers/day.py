"""Closed-loop fleet days: one user simulates whole what-if days of the
edge fleet back to back through the program's streaming simulator.

Traffic parameters (``traffic/<name>.json``): ``days`` distinct days made
from the seed in set-up and cycled; each day lasts ``horizon_s`` and its
arrivals are ``diurnal_tasks`` drawn from a daily tide of
``diurnal_amplitude`` plus ``burst_tasks`` spread over the on-periods of
a Markov-modulated source (mean dwells ``burst_dwell_s``: off, on), all
instants distinct, so every day holds the same number of tasks.  Tasks
have uniform FLOPs ``task_flops`` and input bytes ``task_input_bytes``.
Node ``j``'s uplink has the base
``link_base_bw + j * link_bw_step`` under a daily tide of
``link_amplitude`` and log-normal noise ``link_noise_sigma``, drawn anew
every ``link_update_s``.  ``check_days`` calls of the window are kept (a
seeded uniform sample) and compared whole with the reference.

The link trajectories are data the benchmark makes from the seed: the
program replays them through its link-process interface, and the
reference reads the same table.
"""
from __future__ import annotations

import dataclasses
import time

import numpy as np

from common import Reservoir, seed_streams


class TableLink:
    """A link process that replays one node's column of a bandwidth
    table: ``value`` before the first step is ``v0``, the ``k``-th step
    of ``dt`` returns row ``k - 1``."""

    def __init__(self, v0: float, column: np.ndarray, dt: float):
        self.v0, self.column, self.dt, self.k = v0, column, dt, 0

    @property
    def value(self) -> float:
        return float(self.column[self.k - 1]) if self.k else self.v0

    def step(self, dt: float) -> float:
        return float(self.step_batch(dt, 1)[0])

    def step_batch(self, dt: float, n: int) -> np.ndarray:
        if dt != self.dt or self.k + n > self.column.shape[0]:
            raise ValueError(f"the link table holds {self.column.shape[0]} "
                             f"steps of {self.dt} s; asked {self.k} + {n} "
                             f"of {dt} s")
        out = self.column[self.k:self.k + n]
        self.k += n
        return out


def tide(n: int, amplitude: float, horizon: float, rng) -> np.ndarray:
    """``n`` instants in ``[0, horizon)`` from the daily tide's density
    ``1 + amplitude * sin(2 pi t / horizon)`` (a Poisson tide given its
    count), by thinning uniform draws."""
    out = np.zeros(0)
    while out.shape[0] < n:
        t = rng.uniform(0.0, horizon, 2 * n)
        keep = rng.uniform(0.0, 1.0 + amplitude, 2 * n) \
            < 1.0 + amplitude * np.sin(2 * np.pi * t / horizon)
        out = np.concatenate([out, t[keep]])
    return out[:n]


def bursts(n: int, dwell, horizon: float, rng) -> np.ndarray:
    """``n`` instants spread uniformly over the on-periods of a two-state
    Markov-modulated source (off first, exponential dwells of means
    ``dwell`` = (off, on)): bursts given their count."""
    spans, t, on = [], 0.0, False
    while t < horizon:
        end = min(t + rng.exponential(dwell[int(on)]), horizon)
        if on:
            spans.append((t, end))
        t, on = end, not on
    if not spans:
        return np.zeros(0)
    lo, hi = np.asarray(spans).T
    which = rng.choice(len(spans), n, p=(hi - lo) / np.sum(hi - lo))
    return lo[which] + (hi - lo)[which] * rng.uniform(0.0, 1.0, n)


def make_day(tr: dict, n_nodes: int, rng) -> dict:
    """One day: arrival instants (sorted, distinct), task FLOPs and input
    bytes, and the ``[K, N]`` link table with each node's value ``v0``
    before the first tick.  Every day holds the same number of tasks."""
    h = float(tr["horizon_s"])
    arr = np.unique(np.concatenate([
        tide(tr["diurnal_tasks"], tr["diurnal_amplitude"], h, rng),
        bursts(tr["burst_tasks"], tr["burst_dwell_s"], h, rng)]))
    n = arr.shape[0]
    dt = float(tr["link_update_s"])
    k = int(np.ceil(h / dt)) + 16      # ticks past the day's last finish
    ticks = np.cumsum(np.full(k, dt))
    base_bw = tr["link_base_bw"] + tr["link_bw_step"] * np.arange(n_nodes)
    table = (base_bw[None, :]
             * (1 + tr["link_amplitude"]
                * np.sin(2 * np.pi * ticks / h))[:, None]
             * np.exp(tr["link_noise_sigma"]
                      * rng.standard_normal((k, n_nodes))))
    return {"arrivals": arr, "flops": rng.uniform(*tr["task_flops"], n),
            "input_bytes": rng.uniform(*tr["task_input_bytes"], n),
            "v0": base_bw, "table": table, "dt": dt}


class State:
    pass


def setup(ctx):
    from repro.core import scheduler as sch
    from repro.hw import get_device
    cfg, tr = ctx.cfg, ctx.traffic
    fleet = cfg["fleet"]
    r_days, r_keep = seed_streams(ctx.seed, 2)
    st = State()
    st.specs = [fleet["node_specs"][j % len(fleet["node_specs"])]
                for j in range(fleet["nodes"])]
    # the program's nodes carry the configuration's device numbers
    st.nodes = [sch.Node(dataclasses.replace(
        get_device(name), peak_flops_f32=cfg["devices"][name][
            "peak_flops_f32"], link_bw=cfg["devices"][name]["link_bw"]))
        for name in st.specs]
    st.days = [make_day(tr, fleet["nodes"], r_days)
               for _ in range(tr["days"])]
    st.tasks = [[sch.Task(f"t{i}", flops=float(f), input_bytes=float(b))
                 for i, (f, b) in enumerate(zip(d["flops"],
                                                d["input_bytes"]))]
                for d in st.days]
    st.keep = Reservoir(tr["check_days"], r_keep)
    call(ctx, st, 0)                 # warm-up: the fleet's one scan shape
    return st


def call(ctx, st, d: int):
    from repro import sim
    day = st.days[d]
    links = sim.ClusterLinks([TableLink(v, day["table"][:, j], day["dt"])
                              for j, v in enumerate(day["v0"])])
    with ctx.spans("simulate_stream"):
        return sim.simulate_stream(
            st.tasks[d], day["arrivals"], st.nodes, policy="min_min",
            links=links, link_update_dt=day["dt"], engine="fleet")


def window(ctx, st, seconds: float) -> dict:
    nd = len(st.days)
    calls = done = lossy = 0
    traced = []                          # task count of each traced call
    t0 = time.perf_counter()
    elapsed = 0.0
    while elapsed < seconds:
        ctx.trace.poll(elapsed)
        d = calls % nd
        n = len(st.tasks[d])
        if ctx.trace.state == "tracing":
            traced.append(n)
        tel = call(ctx, st, d)
        elapsed = time.perf_counter() - t0
        calls += 1
        done += len(tel)
        lossy += len(tel) != n
        st.keep.offer(lambda: (d, tel))
    return {"e2e": {"sim_tasks_per_s": done / elapsed},
            "counters": {"nodes": len(st.nodes), "traced_tasks": traced},
            "attempted": calls, "failed": lossy,
            "info": {"calls": calls, "tasks": done, "window_s": elapsed}}


def release(st) -> None:
    st.nodes = None


def program_answers(tel, n: int):
    """``(node, start, finish)`` per task index from the program's
    records (a task it never completed keeps node -1)."""
    node = np.full(n, -1, np.int64)
    start, finish = np.zeros(n), np.zeros(n)
    for r in tel.records:
        i = int(r.name[1:])
        node[i], start[i], finish[i] = r.node_id, r.started_s, r.finished_s
    return node, start, finish


def gaps(ctx, st, low=None) -> dict:
    """Over the kept days: the share of tasks placed on another node than
    the reference places them, and the widest relative distance of a
    task's sojourn (finish less arrival) from the reference's, among the
    tasks placed alike.  ``low`` (a dtype) puts the reference, computed
    in that precision, in the program's place."""
    m, cfg = ctx.cfg_mod, ctx.cfg
    peak_eff = np.asarray([cfg["devices"][s]["peak_flops_f32"]
                           for s in st.specs]) * cfg["efficiency"]
    spec_bw = np.asarray([cfg["devices"][s]["link_bw"] for s in st.specs])
    moved = total = 0
    err = 0.0
    for d, tel in st.keep.items:
        day = st.days[d]
        args = (peak_eff, spec_bw, day["v0"], day["table"], day["dt"],
                day["arrivals"], day["flops"], day["input_bytes"])
        node_r, _, fin_r = m.placements_ref(*args)
        if low is None:
            node, _, fin = program_answers(tel, fin_r.shape[0])
        else:
            node, _, fin = m.placements_ref(*args, dtype=low)
        same = node == node_r
        moved += int(np.count_nonzero(~same))
        total += node.shape[0]
        soj_r = fin_r[same] - day["arrivals"][same]
        soj = fin[same].astype(np.float64) - day["arrivals"][same]
        if soj.size:
            err = max(err, float(np.max(np.abs(soj - soj_r) / soj_r)))
    return {"placement_moved": moved / max(total, 1),
            "sojourn_err_rel": err}


def readings(ctx, st) -> dict:
    return gaps(ctx, st)


def control(ctx, st) -> dict:
    """The reference's placement computed in float32."""
    return gaps(ctx, st, np.float32)
