"""repro.sim.fleet test lanes.

The fleet engine's contract is *bit-for-bit f64 equality* with the host
event loop on every supported configuration — so nearly everything here
is exact ``==``, no tolerances: a hypothesis property sweeps small
random configs across all four arrival processes × all four link-drift
processes × both policies × (no splits / Pareto planner / decide-at-
admission) × (believed / ground-truth service times), and deterministic
pins cover the orderings that only bite on exact ties (arrivals or
finishes landing exactly on link ticks).  The satellites ride along:
``step_batch`` vs scalar ``step`` equality, ``EventQueue.push_batch``
FIFO order, ``DriftingEnv.snapshot`` build counts, and the sharded
``decide_all`` (single-device fallback in the fast lane, an 8-device
``shard_map`` subprocess in tier-1).
"""
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest
from hypothesis_shim import given, settings, st

from repro import sim
from repro.core import decisions as dec
from repro.core import offload as off
from repro.core import scheduler as sch
from repro.core.workloads import WorkloadConfig
from repro.hw import EDGE_DEVICES, get_device

SPECS = list(EDGE_DEVICES.values())


def make_tasks(n, seed=3, deadlines=False):
    rng = np.random.default_rng(seed)
    return [sch.Task(f"t{i}", flops=float(rng.uniform(1e9, 5e11)),
                     input_bytes=float(rng.uniform(1e4, 1e7)),
                     deadline_s=float(rng.uniform(0.02, 2.0))
                     if deadlines else None)
            for i in range(n)]


def make_nodes(n):
    return [sch.Node(SPECS[j % len(SPECS)]) for j in range(n)]


def make_links(kind, n, seed):
    if kind == "fixed":
        return sim.ClusterLinks([sim.FixedLink(50e6 * (j + 1))
                                 for j in range(n)])
    if kind == "walk":
        return sim.ClusterLinks.random_walk(
            [40e6 + 5e6 * j for j in range(n)], sigma=0.4, seed=seed)
    if kind == "twostate":
        return sim.ClusterLinks([sim.TwoStateLink(20e6 * (j + 1),
                                                  4e6 * (j + 1),
                                                  seed=seed + j)
                                 for j in range(n)])
    return sim.ClusterLinks([sim.DiurnalLink(30e6 + 10e6 * j,
                                             amplitude=0.6, period_s=7.0,
                                             noise_sigma=0.2,
                                             seed=seed + j)
                             for j in range(n)])


def make_link_process(kind, seed):
    return {"fixed": lambda: sim.FixedLink(60e6),
            "walk": lambda: sim.RandomWalkLink(60e6, sigma=0.5,
                                               seed=seed),
            "twostate": lambda: sim.TwoStateLink(80e6, 8e6, seed=seed),
            "diurnal": lambda: sim.DiurnalLink(60e6, amplitude=0.7,
                                               period_s=5.0,
                                               noise_sigma=0.3,
                                               seed=seed)}[kind]()


def make_env(kind, seed):
    return sim.DriftingEnv(get_device("jetson-orin-nano"),
                           get_device("edge-server-a100"),
                           make_link_process(kind, seed),
                           input_bytes=2e6)


@pytest.fixture(scope="module")
def cnn_layers():
    wc = WorkloadConfig("cnn", 2, epochs=5, optimiser="adam", lr=1e-3,
                        batch_size=32)
    return off.workload_layer_costs(wc)


def rec_tuple(r):
    return (r.name, r.arrived_s, r.started_s, r.finished_s, r.node,
            r.node_id, r.deadline_s, r.energy_j, r.split, r.switches)


def make_arrivals(kind, n, seed):
    if kind == "poisson":
        return sim.poisson_arrivals(8.0, n=n, seed=seed)
    if kind == "trace":                  # coarse grid: forces exact ties
        rng = np.random.default_rng(seed)
        return np.sort(np.round(rng.uniform(0, 3, n), 1))
    if kind == "mmpp":
        a = sim.mmpp_arrivals([5.0, 60.0], [0.5, 0.2], horizon=6.0,
                              seed=seed)
    else:
        a = sim.diurnal_arrivals(10.0, horizon=6.0, amplitude=0.8,
                                 period_s=2.0, seed=seed)
    if len(a) >= n:
        return a[:n]
    return np.concatenate([a, 6.0 + np.arange(n - len(a), dtype=float)])


def run_both(*, n_tasks, n_nodes, arrival, linkkind, policy, mode,
             ground_truth, seed, cnn_layers, dt=0.5,
             split_backend="numpy"):
    """One config through both engines (fresh stateful processes each)
    -> (event Telemetry, fleet Telemetry, event links, fleet links)."""
    out = []
    end_links = []
    for engine in ("event", "fleet"):
        tasks = make_tasks(n_tasks, seed=seed, deadlines=True)
        links = make_links(linkkind, n_nodes, seed + 100)
        kw = {}
        if mode == "planner":
            kw["split_planner"] = sim.ParetoStreamScheduler()
        if mode in ("planner", "decide"):
            kw["split_env"] = make_env(linkkind, seed + 7)
            kw["split_layers"] = cnn_layers
        if mode == "decide":
            kw["split_backend"] = split_backend
        if ground_truth:
            kw["service_time_fn"] = \
                lambda task, spec, etc, start: etc * (
                    1.1 + 0.2 * np.sin(start + task.flops * 1e-12))
        tel = sim.simulate_stream(
            tasks, make_arrivals(arrival, n_tasks, seed),
            make_nodes(n_nodes), policy=policy, links=links,
            link_update_dt=dt, engine=engine, **kw)
        out.append(tel)
        end_links.append(links.values())
    return out[0], out[1], end_links[0], end_links[1]


def assert_bit_for_bit(ev, fl, lv_ev=None, lv_fl=None):
    assert [rec_tuple(r) for r in ev.records] \
        == [rec_tuple(r) for r in fl.records]
    assert ev.summary() == fl.summary()
    assert ev.counters == fl.counters
    if lv_ev is not None:                # drift processes end identical
        np.testing.assert_array_equal(lv_ev, lv_fl)


# --------------------------------------------------------------------------
# tentpole: fleet engine == host event loop, bit for bit
# --------------------------------------------------------------------------
@given(data=st.data())
@settings(max_examples=40, deadline=None)
def test_fleet_equivalence_property(data, cnn_layers):
    """The satellite-3 property: random small configs over all four
    arrival processes × all four link processes, both policies, all
    three split modes, believed and ground-truth service times —
    telemetry records (placements, splits, switches, energy), summary,
    and counters all exactly equal, and the drift processes end in the
    same state."""
    cfg = dict(
        n_tasks=data.draw(st.integers(1, 32), label="n_tasks"),
        n_nodes=data.draw(st.integers(1, 8), label="n_nodes"),
        arrival=data.draw(st.sampled_from(
            ["poisson", "trace", "mmpp", "diurnal"]), label="arrival"),
        linkkind=data.draw(st.sampled_from(
            ["fixed", "walk", "twostate", "diurnal"]), label="link"),
        policy=data.draw(st.sampled_from(["min_min", "heft"]),
                         label="policy"),
        mode=data.draw(st.sampled_from(["none", "planner", "decide"]),
                       label="mode"),
        ground_truth=data.draw(st.booleans(), label="ground_truth"),
        dt=data.draw(st.sampled_from([0.25, 0.5, 1.0]), label="dt"),
        seed=data.draw(st.integers(0, 2**16), label="seed"))
    ev, fl, lv_ev, lv_fl = run_both(cnn_layers=cnn_layers, **cfg)
    assert_bit_for_bit(ev, fl, lv_ev, lv_fl)


@pytest.mark.parametrize("arrival,linkkind,policy,mode,ground_truth", [
    ("poisson", "walk", "min_min", "none", False),
    ("poisson", "walk", "min_min", "none", True),
    ("trace", "twostate", "heft", "none", True),
    ("mmpp", "diurnal", "min_min", "planner", False),
    ("diurnal", "fixed", "heft", "planner", True),
    ("trace", "walk", "min_min", "decide", False),
    ("poisson", "diurnal", "heft", "decide", True),
])
def test_fleet_equivalence_pins(arrival, linkkind, policy, mode,
                                ground_truth, cnn_layers):
    ev, fl, lv_ev, lv_fl = run_both(
        n_tasks=13, n_nodes=4, arrival=arrival, linkkind=linkkind,
        policy=policy, mode=mode, ground_truth=ground_truth, seed=5,
        cnn_layers=cnn_layers)
    assert_bit_for_bit(ev, fl, lv_ev, lv_fl)


@pytest.mark.slow
@pytest.mark.parametrize("policy,ground_truth", [
    ("min_min", False), ("min_min", True),
    ("heft", False), ("heft", True),
])
def test_fleet_scan_path_equivalence(policy, ground_truth, cnn_layers,
                                     monkeypatch):
    """Long singleton runs route through the jitted lax.scan lowering
    (small-config suites never reach ``_SCAN_MIN``); pin that the scan
    path actually engages and stays bit-for-bit with the host loop."""
    from repro.sim import fleet as fleet_mod
    calls = []
    real = fleet_mod._place_singleton_run

    def counting(*a, **k):
        res = real(*a, **k)
        calls.append(len(res[0]))           # placements the scan made
        return res

    monkeypatch.setattr(fleet_mod, "_place_singleton_run", counting)
    ev, fl, lv_ev, lv_fl = run_both(
        n_tasks=1500, n_nodes=8, arrival="poisson", linkkind="walk",
        policy=policy, mode="none", ground_truth=ground_truth, seed=11,
        cnn_layers=cnn_layers, dt=1.0)
    assert calls and all(calls)           # scan lowering really ran
    assert_bit_for_bit(ev, fl, lv_ev, lv_fl)


def test_fleet_tick_collisions():
    """The orderings that only bite on exact ties: arrivals landing
    exactly on link ticks (they pop before the tick — lower seq), and a
    completion landing exactly on a tick (it keeps one extra tick alive
    iff it arrived after the previous tick)."""
    tasks = [sch.Task(f"t{i}", flops=1e11 * (i + 1), input_bytes=1e6)
             for i in range(6)]

    def links():
        return sim.ClusterLinks.random_walk([4e7] * 3, sigma=0.5, seed=3)

    def on_tick(task, spec, etc, start):   # realised finish on the grid
        return float(np.ceil(start + etc) - start)

    for arr, kw in [([0.0, 1.0, 1.0, 2.0, 3.0, 3.0], {}),
                    ([0.0, 0.3, 1.0, 1.7, 2.0, 2.4],
                     dict(service_time_fn=on_tick))]:
        ev = sim.simulate_stream(tasks, arr, make_nodes(3), links=links(),
                                 link_update_dt=1.0, **kw)
        fl = sim.simulate_stream(tasks, arr, make_nodes(3), links=links(),
                                 link_update_dt=1.0, engine="fleet", **kw)
        assert_bit_for_bit(ev, fl)


def test_fleet_edge_configs(cnn_layers):
    """Empty runs, single static task, duplicate task objects in one
    batch, drift disabled (dt=0), callable split_layers."""
    t = sch.Task("x", flops=2e11, input_bytes=5e6)
    tasks = [sch.Task(f"t{i}", flops=1e11 * (i + 1), input_bytes=1e6)
             for i in range(4)]
    cases = [
        dict(tasks=[], arrivals=[], nodes=make_nodes(2)),
        dict(tasks=[], arrivals=[], nodes=make_nodes(3),
             links=lambda: sim.ClusterLinks.random_walk([4e7] * 3,
                                                        seed=1)),
        dict(tasks=[t], arrivals=[0.0], nodes=make_nodes(2)),
        dict(tasks=[t, t, t], arrivals=[0.5, 0.5, 0.5],
             nodes=make_nodes(3),
             links=lambda: sim.ClusterLinks.random_walk([4e7] * 3,
                                                        seed=2)),
        dict(tasks=tasks, arrivals=[0.0, 0.5, 1.0, 1.5],
             nodes=make_nodes(2), link_update_dt=0.0,
             links=lambda: sim.ClusterLinks.random_walk([4e7] * 2,
                                                        seed=5)),
        dict(tasks=tasks, arrivals=[0.0, 0.5, 1.0, 1.5],
             nodes=make_nodes(2),
             split_env=lambda: make_env("walk", 9),
             split_layers=lambda task: cnn_layers),
    ]
    for case in cases:
        tels = []
        for engine in ("event", "fleet"):
            kw = {k: (v() if k in ("links", "split_env") else v)
                  for k, v in case.items()}
            tels.append(sim.simulate_stream(engine=engine, **kw))
        assert_bit_for_bit(*tels)


def test_fleet_rejects_sequential_features(cnn_layers):
    tasks = make_tasks(3)
    arr = [0.0, 0.1, 0.2]
    for kw, msg in [(dict(oracle=object()), "oracle"),
                    (dict(rebalance=True), "rebalance"),
                    (dict(cost=object()), "cost")]:
        with pytest.raises(ValueError, match=msg):
            sim.simulate_fleet(tasks, arr, make_nodes(2), **kw)

    class NoBatchPlanner:                  # lacks admit_batch
        def admit(self, *a, **k):
            pass

    with pytest.raises(ValueError, match="admit_batch"):
        sim.simulate_fleet(tasks, arr, make_nodes(2),
                           split_planner=NoBatchPlanner(),
                           split_env=make_env("fixed", 0),
                           split_layers=cnn_layers)
    with pytest.raises(ValueError, match="split_cost"):
        sim.simulate_stream(tasks, arr, make_nodes(2),
                            split_planner=sim.ParetoStreamScheduler(),
                            split_env=make_env("fixed", 0),
                            split_layers=cnn_layers, split_cost=object())
    with pytest.raises(ValueError, match="engine"):
        sim.simulate_stream(tasks, arr, make_nodes(2), engine="warp")


# --------------------------------------------------------------------------
# satellite: step_batch == n scalar steps, bit for bit
# --------------------------------------------------------------------------
@pytest.mark.parametrize("kind", ["fixed", "walk", "twostate", "diurnal"])
@pytest.mark.parametrize("dt", [0.25, 1.0])
def test_step_batch_matches_scalar_steps(kind, dt):
    a = make_link_process(kind, seed=11)
    b = make_link_process(kind, seed=11)
    scalar = np.asarray([a.step(dt) for _ in range(40)])
    batch = b.step_batch(dt, 40)
    np.testing.assert_array_equal(scalar, batch)
    # continuation: the end states agree too (next steps identical)
    assert a.step(dt) == b.step(dt)
    # chunked == one-shot
    c, d = make_link_process(kind, 11), make_link_process(kind, 11)
    np.testing.assert_array_equal(
        np.concatenate([c.step_batch(dt, 7), c.step_batch(dt, 13)]),
        d.step_batch(dt, 20))
    assert d.step_batch(dt, 0).shape == (0,)


def test_random_walk_step_batch_clipped():
    """Near the clip bounds the log-space cumsum prefix is invalid; the
    batched path must replay the same draws sequentially."""
    a = sim.RandomWalkLink(1.1e6, sigma=2.0, seed=3, min_bw=1e6,
                           max_bw=2e6)
    b = sim.RandomWalkLink(1.1e6, sigma=2.0, seed=3, min_bw=1e6,
                           max_bw=2e6)
    scalar = np.asarray([a.step(0.5) for _ in range(64)])
    np.testing.assert_array_equal(scalar, b.step_batch(0.5, 64))
    # the clip lives in log space: exp(log(bound)) may round one ulp out
    assert scalar.max() <= 2e6 * (1 + 1e-12)
    assert scalar.min() >= 1e6 * (1 - 1e-12)
    assert (scalar == scalar.min()).sum() > 1     # clipping engaged


def test_cluster_links_step_batch():
    a = make_links("walk", 3, seed=7)
    b = make_links("walk", 3, seed=7)
    scalar = np.stack([a.step(0.5) for _ in range(20)])
    np.testing.assert_array_equal(scalar, b.step_batch(0.5, 20))


# --------------------------------------------------------------------------
# satellite: EventQueue.push_batch FIFO semantics
# --------------------------------------------------------------------------
def test_push_batch_fifo_matches_push():
    """Bulk heapify must pop identically to n pushes: time order with
    FIFO ties, interleaved correctly with pushes before and after."""
    qa, qb = sim.EventQueue(), sim.EventQueue()
    for q in (qa, qb):
        q.push(1.0, "before", "x")
    times = [2.0, 1.0, 1.0, 0.5, 2.0, 1.0]
    payloads = list(range(6))
    for t, p in zip(times, payloads):
        qa.push(t, "batch", p)
    qb.push_batch(times, "batch", payloads)
    for q in (qa, qb):
        q.push(1.0, "after", "y")

    def drain(q):
        out = []
        while q:
            e = q.pop()
            out.append((e.time, e.kind, e.payload))
        return out

    popped = drain(qb)
    assert popped == drain(qa)           # bulk heapify == n sift-ups
    assert popped == [
        (0.5, "batch", 3), (1.0, "before", "x"), (1.0, "batch", 1),
        (1.0, "batch", 2), (1.0, "batch", 5), (1.0, "after", "y"),
        (2.0, "batch", 0), (2.0, "batch", 4)]


def test_push_batch_validates_lengths():
    q = sim.EventQueue()
    with pytest.raises(ValueError, match="payloads"):
        q.push_batch([1.0, 2.0], "x", [None])
    assert q.push_batch([], "x") == [] and not q


# --------------------------------------------------------------------------
# satellite: DriftingEnv.snapshot caching (build counts pinned)
# --------------------------------------------------------------------------
def test_snapshot_caches_until_link_moves(monkeypatch):
    import repro.sim.state as state
    calls = {"n": 0}
    real = state.make_envs

    def counting(*a, **k):
        calls["n"] += 1
        return real(*a, **k)

    monkeypatch.setattr(state, "make_envs", counting)
    env = sim.DriftingEnv(get_device("jetson-orin-nano"),
                          get_device("edge-server-a100"),
                          sim.FixedLink(60e6), input_bytes=2e6)
    first = env.snapshot()
    for _ in range(10):                  # static link: built exactly once
        assert env.snapshot() is first
    assert calls["n"] == 1
    env.snapshot(5e6)                    # new input size: one more build
    assert calls["n"] == 2
    assert env.snapshot(5e6) is not first and calls["n"] == 2
    env.step(1.0)                        # FixedLink: value unchanged
    assert env.snapshot() is first and calls["n"] == 2

    env.link = sim.RandomWalkLink(60e6, sigma=0.5, seed=1)
    env.step(1.0)                        # link moved: cache invalidated
    env.snapshot()
    assert calls["n"] == 3
    env.snapshot(5e6)
    assert calls["n"] == 4


# --------------------------------------------------------------------------
# satellite: telemetry column batches == per-record completes
# --------------------------------------------------------------------------
def test_complete_arrays_matches_completes():
    a, b = sim.Telemetry(), sim.Telemetry()
    names = ["u", "v", "w"]
    cols = dict(arrived_s=[0.0, 0.1, 0.2], started_s=[0.0, 0.2, 0.4],
                finished_s=[1.0, 0.9, 1.1], node=["n0", "n1", "n0"],
                node_id=[0, 1, 0], deadline_s=[None, 1.0, 0.5],
                energy_j=[5.0, 4.0, 3.0], split=[None, 3, 2],
                switches=[0, 1, 2])
    for k in range(3):
        a.complete(sim.TaskRecord(
            name=names[k], **{key: v[k] for key, v in cols.items()
                              if key not in ("switches",)},
            switches=cols["switches"][k]))
    b.complete_arrays(names, **cols)
    assert len(b) == 3                   # pending counts before build
    assert [rec_tuple(r) for r in a.records] \
        == [rec_tuple(r) for r in b.records]
    assert a.summary() == b.summary()
    with pytest.raises(ValueError, match="node_id"):
        b.complete_arrays(["x"], [0.0], [0.0], [1.0], node=["n"],
                          node_id=[], deadline_s=[None], energy_j=[1.0])


# --------------------------------------------------------------------------
# satellite: env-axis padding + sharded decide
# --------------------------------------------------------------------------
def test_pad_envs():
    env = make_env("fixed", 0)
    envs = env.snapshot([1e6, 2e6, 3e6])
    padded, e = dec.pad_envs(envs, 4)
    assert (len(padded), e) == (4, 3)
    np.testing.assert_array_equal(padded.input_bytes,
                                  [1e6, 2e6, 3e6, 3e6])  # repeats last
    same, e2 = dec.pad_envs(envs, 3)
    assert same is envs and e2 == 3
    with pytest.raises(ValueError):
        dec.pad_envs(envs, 0)


def test_decide_all_sharded_single_device(cnn_layers):
    """On one device the helper must fall back to the jit path and stay
    bit-for-bit with the numpy reference."""
    env = make_env("walk", 5)
    envs = env.snapshot(np.linspace(1e5, 8e6, 5))
    ref = dec.decide_all(cnn_layers, envs)
    out = sim.decide_all_sharded(cnn_layers, envs)
    for f in ("splits", "total_time_s", "device_time_s",
              "transfer_time_s", "edge_time_s"):
        np.testing.assert_array_equal(np.asarray(getattr(ref, f)),
                                      np.asarray(getattr(out, f)))


_SHARDED_SCRIPT = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np
from repro import sim
from repro.core import decisions as dec
from repro.core import offload as off
from repro.core.workloads import WorkloadConfig
from repro.hw import get_device
import jax
assert jax.device_count() == 8, jax.device_count()
layers = off.workload_layer_costs(WorkloadConfig(
    "cnn", 2, epochs=5, optimiser="adam", lr=1e-3, batch_size=32))
env = sim.DriftingEnv(get_device("jetson-orin-nano"),
                      get_device("edge-server-a100"),
                      sim.RandomWalkLink(60e6, sigma=0.5, seed=5))
envs = env.snapshot(np.linspace(1e5, 8e6, 13))   # 13: forces pad + trim
ref = dec.decide_all(layers, envs)
out = sim.decide_all_sharded(layers, envs)
for f in ("splits", "total_time_s", "device_time_s", "transfer_time_s",
          "edge_time_s"):
    a, b = np.asarray(getattr(ref, f)), np.asarray(getattr(out, f))
    assert a.shape == b.shape and (a == b).all(), f
from repro.launch.mesh import make_debug_mesh
out2 = sim.decide_all_sharded(layers, envs, mesh=make_debug_mesh(8))
assert (np.asarray(out2.splits) == np.asarray(ref.splits)).all()
print("SHARDED_OK")
"""


@pytest.mark.slow
def test_decide_all_sharded_eight_devices():
    """shard_map over an 8-host-device mesh, non-divisible env axis:
    still bit-for-bit with the numpy reference (subprocess because
    XLA_FLAGS must be set before any jax import)."""
    r = subprocess.run(
        [sys.executable, "-c", _SHARDED_SCRIPT], capture_output=True,
        text=True, cwd=str(Path(__file__).resolve().parent.parent),
        env={**__import__("os").environ, "JAX_PLATFORMS": "cpu",
             "PYTHONPATH": str(Path(__file__).resolve().parent.parent
                               / "src")})
    assert r.returncode == 0, r.stderr[-2000:]
    assert "SHARDED_OK" in r.stdout


@pytest.mark.slow
def test_fleet_jax_split_backend_equivalence(cnn_layers):
    """decide-at-admission under backend='jax': both engines agree with
    each other and with the numpy backend."""
    ref = None
    for backend in ("numpy", "jax"):
        ev, fl, *_ = run_both(
            n_tasks=10, n_nodes=3, arrival="trace", linkkind="walk",
            policy="min_min", mode="decide", ground_truth=False, seed=2,
            cnn_layers=cnn_layers, split_backend=backend)
        assert_bit_for_bit(ev, fl)
        recs = [rec_tuple(r) for r in ev.records]
        if ref is None:
            ref = recs
        else:
            assert recs == ref
