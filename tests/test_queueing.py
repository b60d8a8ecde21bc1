"""repro.sim.queueing test lanes.

Four layers of pins:

  * units — ``ServerPool`` FIFO semantics (first-index tie-break,
    realised busy state, infinite capacity), ``NodePools``'s
    incrementally-maintained ``avail`` against the full recompute, and
    the closed forms (Erlang-C, M/M/1, M/M/c, RTT mean/quantile/CVaR
    against Monte Carlo);
  * RNG hygiene — int seeds replay the historical ``default_rng(int)``
    streams bit-for-bit, ``SeedSequence(k)`` equals ``k``, spawned
    children are independent;
  * regression — capacity=1 pools with no RTT reproduce the historical
    believed-queue runs bit-for-bit on both engines (hypothesis sweep),
    and zero-contention capacity=∞ runs match too;
  * validation (slow) — simulated M/M/1 / M/M/c mean sojourn within
    confidence bounds of the Erlang-C prediction at ρ ∈ {0.3, 0.7, 0.9}.

The tail-aware cost stack (``CompositeCost(tail=...)``,
``QueueAwareCost``) is pinned numpy ≡ jax bit-for-bit and Pallas-close,
mirroring tests/test_decide_split.py.
"""
import numpy as np
import pytest
from hypothesis_shim import given, settings, st

from repro.core import costs as co
from repro.core import decisions as dec
from repro.core import offload as off
from repro.core import scheduler as sch
from repro.hw import EDGE_DEVICES, get_device
from repro.sim import (ClusterLinks, LognormalRTT, NodePools,
                       ParetoStreamScheduler, RandomWalkLink, ServerPool,
                       WeibullRTT, erlang_c, mm1_sojourn, mmc_sojourn,
                       poisson_arrivals, simulate_stream, spawn_streams)
from repro.sim.state import DriftingEnv

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


def record_rows(tel):
    return [(r.name, r.arrived_s, r.started_s, r.finished_s, r.node,
             r.node_id, r.energy_j, r.transfer_s, r.split, r.switches)
            for r in tel.records]


# --------------------------------------------------------------------------
# ServerPool / NodePools units
# --------------------------------------------------------------------------
def test_pool_capacity1_is_scalar_avail():
    pool = ServerPool(1)
    s0, f0 = pool.admit(0.0, 2.0)
    assert (s0, f0) == (0.0, 2.0)
    s1, f1 = pool.admit(1.0, 3.0)       # arrives while busy: waits
    assert (s1, f1) == (2.0, 5.0)
    assert pool.wait(3.0) == 2.0
    assert pool.wait(6.0) == 0.0
    assert pool.next_free() == 5.0


def test_pool_fifo_tie_break_first_index():
    pool = ServerPool(3)
    for _ in range(3):                   # all servers free at 0: use #0..2
        pool.admit(0.0, 1.0)
    assert np.array_equal(pool.busy, [1.0, 1.0, 1.0])
    s, f = pool.admit(0.5, 1.0)          # all free at 1.0: first index wins
    assert (s, f) == (1.0, 2.0)
    assert np.array_equal(pool.busy, [2.0, 1.0, 1.0])


def test_pool_multiserver_wait():
    pool = ServerPool(2)
    pool.admit(0.0, 4.0)
    pool.admit(0.0, 2.0)
    # both busy; earliest frees at 2.0
    assert pool.wait(1.0) == 1.0
    assert pool.queue_len(1.0) == 2
    s, f = pool.admit(1.0, 1.0)
    assert (s, f) == (2.0, 3.0)


def test_pool_infinite_capacity_never_waits():
    pool = ServerPool(None)
    for k in range(3):
        s, f = pool.admit(float(k) * 0.1, 5.0)
        assert s == float(k) * 0.1       # starts immediately, no wait
    assert pool.wait(0.3) == 0.0
    assert pool.queue_len(0.25) == 3     # three in service, none done
    assert pool.queue_len(6.0) == 0


def test_pool_rejects_bad_capacity():
    with pytest.raises(ValueError):
        ServerPool(0)


def test_pool_utilisation_and_queue_area():
    pool = ServerPool(1)
    pool.admit(0.0, 1.0)                 # busy [0, 1]
    assert pool.utilisation(2.0) == pytest.approx(0.5)
    pool2 = ServerPool(1)
    pool2.admit(0.0, 2.0)
    pool2.admit(0.0, 2.0)                # waits 2
    pool2.admit(0.0, 2.0)                # waits 4
    # Little's law: total wait 6 over a 6s busy period
    assert pool2.mean_queue_len(6.0) == pytest.approx(1.0)


def test_nodepools_incremental_avail_matches_recompute():
    rng = np.random.default_rng(0)
    pools = NodePools([ServerPool(int(c)) for c in rng.integers(1, 4, 6)])
    for _ in range(200):
        j = int(rng.integers(6))
        pools.admit(j, float(rng.uniform(0, 50)),
                    float(rng.uniform(0.1, 3.0)))
        assert np.array_equal(pools.avail, pools.recompute_avail())


def test_nodepools_validations():
    pools = NodePools.uniform(2, 1)
    nodes = make_nodes(3)
    with pytest.raises(ValueError, match="2 pools for 3 nodes"):
        simulate_stream(make_tasks(2), [0.0, 1.0], nodes, pools=pools)
    with pytest.raises(ValueError, match="rebalance"):
        simulate_stream(make_tasks(2), [0.0, 1.0], make_nodes(2),
                        pools=pools, rebalance=True)


# --------------------------------------------------------------------------
# closed forms
# --------------------------------------------------------------------------
def test_erlang_c_known_values():
    # c=1: P(wait) = rho
    assert erlang_c(1, 0.5) == pytest.approx(0.5)
    assert erlang_c(1, 0.0) == 0.0
    # c=2, a=1 (rho=0.5): C = 1/3
    assert erlang_c(2, 1.0) == pytest.approx(1.0 / 3.0)
    with pytest.raises(ValueError):
        erlang_c(2, 2.0)                 # unstable


def test_mm1_mmc_consistency():
    assert mm1_sojourn(0.5, 1.0) == pytest.approx(2.0)
    # M/M/1 is M/M/c at c=1
    assert mmc_sojourn(0.5, 1.0, 1) == pytest.approx(mm1_sojourn(0.5, 1.0))
    with pytest.raises(ValueError):
        mm1_sojourn(1.0, 1.0)


@pytest.mark.parametrize("proc", [WeibullRTT(shape=0.7, scale=0.02, seed=5),
                                  LognormalRTT(mu=-4.0, sigma=1.2, seed=5)])
def test_rtt_closed_forms_match_monte_carlo(proc):
    x = proc.sample(400_000)
    assert proc.mean() == pytest.approx(float(x.mean()), rel=0.05)
    assert proc.percentile(0.99) == pytest.approx(
        float(np.percentile(x, 99)), rel=0.05)
    var = np.percentile(x, 99)
    assert proc.cvar(0.99) == pytest.approx(
        float(x[x >= var].mean()), rel=0.10)
    # tail_stat dispatch
    assert proc.tail_stat("p99", 0.5) == proc.percentile(0.99)
    assert proc.tail_stat("cvar", 0.95) == proc.cvar(0.95)
    with pytest.raises(ValueError):
        proc.tail_stat("p50", 0.5)


def test_rtt_heavy_tail_orders():
    w = WeibullRTT(shape=0.6, scale=0.01, seed=0)
    assert w.mean() < w.percentile(0.99) < w.cvar(0.99)


# --------------------------------------------------------------------------
# RNG hygiene
# --------------------------------------------------------------------------
def test_int_seed_replays_historical_stream():
    # default_rng(k) builds SeedSequence(k) internally: accepting
    # SeedSequence seeds must not change what an int seed produces
    a = poisson_arrivals(5.0, n=64, seed=7)
    b = poisson_arrivals(5.0, n=64, seed=np.random.SeedSequence(7))
    assert np.array_equal(a, b)
    w1 = WeibullRTT(seed=3).sample(32)
    w2 = WeibullRTT(seed=np.random.SeedSequence(3)).sample(32)
    assert np.array_equal(w1, w2)


def test_spawn_streams_independent():
    kids = spawn_streams(42, 3)
    assert len(kids) == 3
    draws = [np.random.default_rng(k).uniform(size=8) for k in kids]
    assert not np.array_equal(draws[0], draws[1])
    # deterministic: spawning again yields the same children
    again = [np.random.default_rng(k).uniform(size=8)
             for k in spawn_streams(42, 3)]
    assert all(np.array_equal(a, b) for a, b in zip(draws, again))


def test_cluster_links_seedsequence_spawn():
    base = [40e6, 55e6, 70e6]
    # int seed: historical per-node seed+j streams, unchanged
    a = ClusterLinks.random_walk(base, sigma=0.4, seed=2)
    b = ClusterLinks([RandomWalkLink(bw, sigma=0.4, seed=2 + j)
                      for j, bw in enumerate(base)])
    for _ in range(5):
        assert np.array_equal(a.step(0.5), b.step(0.5))
    # SeedSequence seed: each link gets an independent spawned child
    c = ClusterLinks.random_walk(base, sigma=0.4,
                                 seed=np.random.SeedSequence(2))
    vals = c.step(0.5)
    assert vals.shape == (3,)
    assert not np.array_equal(vals, a.values())


def test_run_seed_spawn_keeps_processes_independent():
    arr_ss, rtt_ss = spawn_streams(123, 2)
    arr = poisson_arrivals(5.0, n=32, seed=arr_ss)
    rtt = WeibullRTT(seed=rtt_ss)
    # adding the RTT process does not perturb the arrival stream
    assert np.array_equal(arr, poisson_arrivals(5.0, n=32,
                                                seed=spawn_streams(123,
                                                                   2)[0]))
    assert rtt.sample(4).shape == (4,)


# --------------------------------------------------------------------------
# regression: pools thread through both engines without changing history
# --------------------------------------------------------------------------
@pytest.mark.parametrize("engine", ["event", "fleet"])
@pytest.mark.parametrize("policy", ["min_min", "heft"])
def test_capacity1_bit_for_bit_with_historical(engine, policy):
    tasks = make_tasks(40, seed=11, deadlines=True)
    rng = np.random.default_rng(1)
    arrivals = np.sort(rng.uniform(0, 3.0, len(tasks)))
    t0 = simulate_stream(tasks, arrivals, make_nodes(3), policy=policy,
                         engine=engine)
    t1 = simulate_stream(tasks, arrivals, make_nodes(3), policy=policy,
                         pools=NodePools.uniform(3, 1), engine=engine)
    assert record_rows(t0) == record_rows(t1)


@settings(max_examples=20, deadline=None)
@given(st.integers(0, 2**32 - 1), st.integers(2, 12),
       st.integers(2, 4), st.booleans())
def test_zero_contention_infinite_capacity_bit_for_bit(
        seed, n_tasks, n_nodes, heft):
    """Zero-contention runs (arrivals spaced past every service time)
    are identical under capacity=∞ pools, capacity=1 pools, and the
    historical believed queue — on both engines."""
    rng = np.random.default_rng(seed)
    tasks = [sch.Task(f"t{i}", flops=float(rng.uniform(1e8, 5e9)),
                      input_bytes=float(rng.uniform(1e3, 1e5)))
             for i in range(n_tasks)]
    nodes = make_nodes(n_nodes)
    # worst-case service: slowest node, then space arrivals past it
    worst = max(sch.Node(n.spec).exec_time(t)
                for t in tasks for n in nodes)
    arrivals = np.arange(n_tasks, dtype=np.float64) * (worst * 1.01)
    policy = "heft" if heft else "min_min"
    base = [record_rows(simulate_stream(
        tasks, arrivals, nodes, policy=policy, engine=e))
        for e in ("event", "fleet")]
    assert base[0] == base[1]
    for cap in (None, 1):
        for e, ref in zip(("event", "fleet"), base):
            got = record_rows(simulate_stream(
                tasks, arrivals, nodes, policy=policy,
                pools=NodePools.uniform(n_nodes, cap), engine=e))
            assert got == ref


@settings(max_examples=15, deadline=None)
@given(st.integers(0, 2**32 - 1), st.integers(1, 30), st.integers(2, 4),
       st.sampled_from(["min_min", "heft"]),
       st.sampled_from([1, 2, None]), st.booleans())
def test_event_fleet_equivalent_under_contention(
        seed, n_tasks, n_nodes, policy, capacity, with_rtt):
    rng = np.random.default_rng(seed)
    tasks = make_tasks(n_tasks, seed=seed % 1000, deadlines=True)
    arrivals = np.sort(rng.uniform(0, 2.0, n_tasks))
    rows = []
    for engine in ("event", "fleet"):
        rtt = WeibullRTT(shape=0.7, scale=0.01, seed=seed % 97) \
            if with_rtt else None
        rows.append(record_rows(simulate_stream(
            tasks, arrivals, make_nodes(n_nodes), policy=policy,
            pools=NodePools.uniform(n_nodes, capacity), rtt=rtt,
            engine=engine)))
    assert rows[0] == rows[1]


def test_contention_inflates_sojourn_and_telemetry():
    tasks = make_tasks(60, seed=2, deadlines=True)
    arrivals = np.zeros(len(tasks))          # all at once: heavy queueing
    tel = simulate_stream(tasks, arrivals, make_nodes(2),
                          pools=NodePools.uniform(2, 1))
    s = tel.summary()
    assert s["p99_wait_s"] > 0.0
    assert s["mean_wait_s"] > 0.0
    assert s["mean_queue_len"] > 0.0
    assert tel.cvar(0.95) >= s["p99_wait_s"] * 0.0  # defined, finite
    # per-record breakdown: sojourn == wait + service + transfer
    for r in tel.records:
        assert r.sojourn_s == pytest.approx(
            r.wait_s + r.service_s + r.transfer_s)
    # per-node queue lengths are exported
    assert sum(tel.queue_lens().values()) == pytest.approx(
        s["mean_queue_len"])


def test_rtt_recorded_as_transfer():
    tasks = make_tasks(10, seed=4)
    arrivals = np.linspace(0, 5, 10)
    rtt = WeibullRTT(seed=9)
    tel = simulate_stream(tasks, arrivals, make_nodes(2),
                          pools=NodePools.uniform(2, 1), rtt=rtt)
    assert all(r.transfer_s > 0.0 for r in tel.records)
    # the delay really pushed completions: finish > start + service
    assert all(r.finished_s > r.started_s for r in tel.records)


def test_saturation_hook_fires_and_fleet_rejects():
    layers = [off.LayerCost(f"l{i}", flops=2e8 * (i + 1),
                            act_bytes=1e5 * (i + 1)) for i in range(5)]
    env = DriftingEnv(get_device("jetson-orin-nano"),
                      get_device("edge-server-a100"),
                      RandomWalkLink(30e6, sigma=0.3, seed=4),
                      link_latency_s=0.005)
    tasks = make_tasks(60, seed=1, deadlines=True)
    arrivals = np.sort(np.random.default_rng(0).uniform(0, 0.4, 60))
    tel = simulate_stream(tasks, arrivals, make_nodes(3),
                          split_planner=ParetoStreamScheduler(),
                          split_env=env, split_layers=layers,
                          link_update_dt=0.5,
                          pools=NodePools.uniform(3, 1),
                          saturation_threshold=0.5)
    assert tel.summary().get("split_saturation_repicks", 0) >= 1
    with pytest.raises(ValueError, match="saturation_threshold"):
        simulate_stream(tasks, arrivals, make_nodes(3),
                        split_planner=ParetoStreamScheduler(),
                        split_env=env, split_layers=layers,
                        pools=NodePools.uniform(3, 1),
                        saturation_threshold=0.5, engine="fleet")
    with pytest.raises(ValueError, match="saturation_threshold"):
        simulate_stream(tasks, arrivals, make_nodes(3),
                        pools=NodePools.uniform(3, 1),
                        saturation_threshold=0.5)   # no planner


# --------------------------------------------------------------------------
# tail-aware cost stack: numpy == jax bit-for-bit, pallas close
# --------------------------------------------------------------------------
def rand_layers(rng, n):
    return [off.LayerCost(f"l{i}", flops=float(rng.uniform(1e6, 1e12)),
                          act_bytes=float(rng.uniform(1e2, 1e8)))
            for i in range(n)]


def rand_envs(rng, n):
    return dec.make_envs(
        [SPECS[int(rng.integers(len(SPECS)))] for _ in range(n)],
        SPECS[int(rng.integers(len(SPECS)))],
        link_bw=rng.uniform(1e4, 1e10, n),
        link_latency_s=rng.uniform(0.0, 0.05, n),
        input_bytes=rng.uniform(0.0, 1e7, n))


def tail_cost(tail, wait=0.0):
    return co.CompositeCost(
        weights={"latency_s": 1.0, "energy_j": 0.05, "price": 1.0,
                 "tail_latency_s": 0.5},
        price_per_edge_s=0.1, price_per_gb=0.01, deadline_s=0.05,
        tail=tail, tail_alpha=0.95,
        rtt=WeibullRTT(shape=0.7, scale=0.02, seed=0))


def assert_pallas_close(layers, envs, cost):
    """The Pallas plan against numpy's at the f32 kernel's tolerance:
    the same splits, and every f64 re-evaluated field close."""
    ref = dec.decide_all(layers, envs, cost=cost, backend="numpy")
    got = dec.decide_all(layers, envs, cost=cost, backend="pallas")
    assert np.array_equal(ref.splits, got.splits)
    assert ref.objectives == got.objectives
    for f in ("total_time_s", "device_time_s", "transfer_time_s",
              "edge_time_s", "components", "scalar_cost"):
        np.testing.assert_allclose(getattr(got, f), getattr(ref, f),
                                   rtol=1e-5, atol=1e-7, err_msg=f)


def test_tail_objective_grows_component_column():
    cost = tail_cost("p99")
    assert cost.objectives == ("latency_s", "energy_j", "price",
                               "deadline_slack_s", "tail_latency_s")
    # the class default is untouched
    assert co.CompositeCost().objectives == ("latency_s", "energy_j",
                                             "price", "deadline_slack_s")
    rng = np.random.default_rng(0)
    layers, envs = rand_layers(rng, 6), rand_envs(rng, 4)
    plan = dec.decide_all(layers, envs, cost=cost, backend="numpy")
    assert plan.components.shape == (4, 5)
    assert cost.tail_excess_s() > 0.0
    # tail excess is charged on offloading splits only: the last
    # column (no offload) carries plain latency
    comp = np.asarray(cost.components(layers, envs))
    assert np.array_equal(comp[..., :-1, 4],
                          comp[..., :-1, 0] + cost.tail_excess_s())
    assert np.array_equal(comp[..., -1, 4], comp[..., -1, 0])


def test_tail_requires_rtt():
    with pytest.raises(ValueError, match="rtt"):
        co.CompositeCost(tail="p99")
    with pytest.raises(ValueError, match="tail"):
        co.CompositeCost(tail="p42", rtt=WeibullRTT(seed=0))


@pytest.mark.parametrize("tail", ["p99", "cvar"])
def test_tail_cost_numpy_pallas_close(tail):
    rng = np.random.default_rng(17)
    layers, envs = rand_layers(rng, 12), rand_envs(rng, 9)
    assert_pallas_close(layers, envs, tail_cost(tail))


def test_queue_aware_cost_bumps_latency_only():
    rng = np.random.default_rng(5)
    layers, envs = rand_layers(rng, 8), rand_envs(rng, 5)
    base = co.CompositeCost(weights={"latency_s": 1.0, "energy_j": 0.1},
                            deadline_s=0.05)
    qa = co.QueueAwareCost(base=base, wait_s=0.25)
    c0 = np.asarray(base.components(layers, envs))
    c1 = np.asarray(qa.components(layers, envs))
    # latency column: +wait on offloading splits, last split untouched
    assert np.array_equal(c1[..., :-1, 0], c0[..., :-1, 0] + 0.25)
    assert np.array_equal(c1[..., -1, 0], c0[..., -1, 0])
    # every other objective untouched
    assert np.array_equal(c1[..., 1:], c0[..., 1:])


def test_queue_aware_cost_live_pool_state():
    pool = ServerPool(1)
    pool.admit(0.0, 3.0)                 # busy until 3.0
    qa = co.QueueAwareCost(base=co.AnalyticCost(), edge_pool=pool,
                           rtt=LognormalRTT(mu=-5.0, sigma=0.5, seed=0))
    qa.set_now(1.0)
    assert qa._edge_wait() == pytest.approx(2.0 + qa.rtt.mean())
    qa.set_now(5.0)                      # pool drained
    assert qa._edge_wait() == pytest.approx(qa.rtt.mean())


@pytest.mark.parametrize("tail", [None, "p99"])
def test_queue_aware_cost_numpy_pallas_close(tail):
    rng = np.random.default_rng(23)
    layers, envs = rand_layers(rng, 10), rand_envs(rng, 7)
    base = tail_cost(tail) if tail else co.CompositeCost(
        weights={"latency_s": 1.0, "energy_j": 0.05, "price": 1.0},
        price_per_edge_s=0.1, price_per_gb=0.01, deadline_s=0.05)
    assert_pallas_close(layers, envs,
                        co.QueueAwareCost(base=base, wait_s=0.1))


def test_queue_aware_cost_pallas_close():
    rng = np.random.default_rng(31)
    layers, envs = rand_layers(rng, 9), rand_envs(rng, 6)
    assert_pallas_close(layers, envs,
                        co.QueueAwareCost(base=tail_cost("p99"),
                                          wait_s=0.05))


def test_queue_aware_task_matrix_adds_node_waits():
    pools = NodePools.uniform(3, 1)
    pools.admit(1, 0.0, 4.0)             # node 1 backlogged
    qa = co.QueueAwareCost(base=co.AnalyticCost(), pools=pools)
    qa.set_now(1.0)
    tasks = make_tasks(4, seed=0)
    nodes = make_nodes(3)
    base_etc = sch.etc_matrix(tasks, nodes, cost=co.AnalyticCost())
    etc = sch.etc_matrix(tasks, nodes, cost=qa)
    extra = np.asarray(etc) - np.asarray(base_etc)
    assert np.allclose(extra[:, 1], 3.0)       # wait at node 1
    assert np.allclose(extra[:, [0, 2]], 0.0)


# --------------------------------------------------------------------------
# slow validation: M/M/1 and M/M/c against the closed forms
# --------------------------------------------------------------------------
def _sim_mmc_pool(lam, mu, c, n, seed):
    arr_ss, svc_ss = spawn_streams(seed, 2)
    arr = np.cumsum(np.random.default_rng(arr_ss).exponential(1.0 / lam,
                                                              n))
    svc = np.random.default_rng(svc_ss).exponential(1.0 / mu, n)
    pool = ServerPool(c)
    soj = np.empty(n)
    for i in range(n):
        start, fin = pool.admit(arr[i], svc[i])
        soj[i] = fin - arr[i]
        assert start >= arr[i]
    return float(soj.mean())


@pytest.mark.slow
@pytest.mark.parametrize("rho,tol", [(0.3, 0.05), (0.7, 0.05),
                                     (0.9, 0.12)])
@pytest.mark.parametrize("c", [1, 3])
def test_mmc_sojourn_matches_erlang_c(rho, tol, c):
    mu = 1.0
    lam = rho * c * mu
    want = mm1_sojourn(lam, mu) if c == 1 else mmc_sojourn(lam, mu, c)
    got = _sim_mmc_pool(lam, mu, c, 40_000, seed=0)
    assert got == pytest.approx(want, rel=tol)


@pytest.mark.slow
def test_mm1_through_simulator():
    """End-to-end M/M/1: one node, Poisson arrivals, exponential ground
    truth service — the recorded sojourns match 1/(mu - lambda)."""
    mu, rho = 2.0, 0.7
    lam = rho * mu
    n = 12_000
    arr_ss, svc_ss = spawn_streams(7, 2)
    arrivals = poisson_arrivals(lam, n=n, seed=arr_ss)
    svc_rng = np.random.default_rng(svc_ss)

    def service(task, spec, etc_s, start_s):
        return float(svc_rng.exponential(1.0 / mu))

    tasks = [sch.Task(f"t{i}", flops=1e9, input_bytes=0.0)
             for i in range(n)]
    tel = simulate_stream(tasks, arrivals, make_nodes(1),
                          pools=NodePools.uniform(1, 1),
                          service_time_fn=service)
    soj = np.asarray([r.sojourn_s for r in tel.records])
    assert float(soj.mean()) == pytest.approx(mm1_sojourn(lam, mu),
                                              rel=0.08)
    # wait + service decomposition holds for every record
    s = tel.summary()
    assert s["mean_wait_s"] == pytest.approx(
        float(soj.mean()) - 1.0 / mu, rel=0.12)
