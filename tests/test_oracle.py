"""repro.oracle: predictor lowering, online learning, registry.

Pins the tentpole guarantees of the oracle subsystem:

  * lowered GBT inference is *bit-for-bit* with the host ensemble (jax)
    and within f32 tolerance (Pallas kernel);
  * ``decide_all(cost=PredictorCost(...), backend="pallas")`` chooses
    the same splits as the numpy backend on fixed seeds, within f32
    tolerance, and does not raise;
  * the ``OnlineOracle`` stays *exactly* transparent in a drift-free
    streaming run (placements bit-for-bit vs the oracle-free path), and
    detects + refits away injected drift;
  * the registry versions snapshots atomically and round-trips from
    disk.
"""
import dataclasses
import os

import numpy as np
import pytest

from repro.core import costs as co
from repro.core import decisions as dec
from repro.core import offload as off
from repro.core import scheduler as sch
from repro.core.predictors import (GBTRegressor, MLPRegressor,
                                   MultiTargetGBT, RidgeRegressor)
from repro.hw import EDGE_DEVICES, get_device
from repro.kernels.tree_predict import ops as tp_ops
from repro.kernels.tree_predict import ref as tp_ref
from repro.oracle import (OnlineOracle, OracleCost, PageHinkley,
                          PredictorRegistry, lower_predictor)
from repro.sim import simulate_stream

DEVICE, EDGE = get_device("pi5-arm"), get_device("edge-server-a100")
SPECS = list(EDGE_DEVICES.values())


def rand_layers(rng, n, act=1e4):
    return [off.LayerCost(f"l{i}",
                          flops=float(rng.uniform(1e8, 1e11)),
                          act_bytes=float(rng.uniform(1e3, 1e7))
                          if act is None else act)
            for i in range(n)]


def layer_training_set(layers):
    feats, ys = [], []
    for spec in SPECS:
        feats.append(co.default_layer_features(layers, spec))
        ys.append([off.layer_time(lc.flops, spec) for lc in layers])
    return np.concatenate(feats), np.concatenate(ys)


@pytest.fixture(scope="module")
def fitted():
    """One small fitted model per family over layer/hardware features."""
    rng = np.random.default_rng(0)
    x, y = layer_training_set(rand_layers(rng, 24, act=None))
    return {
        "gbt": GBTRegressor(n_trees=25, max_depth=4, subsample=0.9,
                            seed=1).fit(x, y),
        "ridge": RidgeRegressor().fit(x, y),
        "mlp": MLPRegressor(hidden=(24, 12), epochs=15).fit(x, y),
    }


# --------------------------------------------------------------------------
# tree_predict: flattened inference vs the host ensemble
# --------------------------------------------------------------------------
def test_flattened_ref_bit_for_bit(fitted):
    rng = np.random.default_rng(1)
    x, _ = layer_training_set(rand_layers(rng, 17, act=None))
    arrays = tp_ref.flatten_gbt(fitted["gbt"])
    assert np.array_equal(fitted["gbt"].predict(x),
                          tp_ref.predict_ref(x, arrays))


def test_tree_predict_jax_bit_for_bit(fitted):
    rng = np.random.default_rng(2)
    x, _ = layer_training_set(rand_layers(rng, 31, act=None))
    arrays = tp_ref.flatten_gbt(fitted["gbt"])
    assert np.array_equal(fitted["gbt"].predict(x),
                          tp_ops.predict_trees(x, arrays, backend="jax"))


def test_tree_predict_pallas_tolerance(fitted):
    rng = np.random.default_rng(3)
    x, _ = layer_training_set(rand_layers(rng, 40, act=None))
    host = fitted["gbt"].predict(x)
    got = tp_ops.predict_trees(x, arrays=tp_ref.flatten_gbt(fitted["gbt"]),
                               backend="pallas")
    np.testing.assert_allclose(got, host, rtol=1e-4, atol=1e-7)


def test_tree_predict_degenerate(fitted):
    arrays = tp_ref.flatten_gbt(fitted["gbt"])
    for backend in ("jax", "pallas"):
        out = tp_ops.predict_trees(np.zeros((0, 7), np.float32), arrays,
                                   backend=backend)
        assert out.shape == (0,)


def test_unflatten_round_trip(fitted):
    arrays = tp_ref.flatten_gbt(fitted["gbt"])
    trees = tp_ref.unflatten_gbt(arrays)
    clone = dataclasses.replace(fitted["gbt"])
    clone.edges_, clone.base_, clone.trees_ = (arrays.edges,
                                               arrays.base, trees)
    rng = np.random.default_rng(4)
    x, _ = layer_training_set(rand_layers(rng, 9, act=None))
    assert np.array_equal(fitted["gbt"].predict(x), clone.predict(x))


# --------------------------------------------------------------------------
# lower_predictor: every family, plus the rejection boundary
# --------------------------------------------------------------------------
def test_lowered_predict_matches_host(fitted):
    rng = np.random.default_rng(5)
    x, _ = layer_training_set(rand_layers(rng, 21, act=None))
    for name, model in fitted.items():
        host = np.asarray(model.predict(x), np.float64)
        got = np.asarray(lower_predictor(model).predict(x), np.float64)
        if host.ndim == 2:
            host = host[:, 0]
        if got.ndim == 2:
            got = got[:, 0]
        if name == "gbt":
            assert np.array_equal(host, got), name
        else:
            np.testing.assert_allclose(got, host, rtol=1e-5, atol=1e-12,
                                       err_msg=name)


def test_lowered_multi_target():
    rng = np.random.default_rng(6)
    x = rng.normal(size=(200, 5)).astype(np.float32)
    y = np.stack([x[:, 0], x[:, 1] * 2.0], axis=1)
    m = MultiTargetGBT(n_trees=10, max_depth=3).fit(x, y)
    got = lower_predictor(m).predict(x)
    assert got.shape == (200, 2)
    assert np.array_equal(m.predict(x), got)


def test_lower_predictor_rejects_host_models():
    class Host:
        def predict(self, x):
            return np.zeros(len(x))

    with pytest.raises(TypeError, match="host-side"):
        lower_predictor(Host())


# --------------------------------------------------------------------------
# predictor-driven decide_all on the accelerator backends
# --------------------------------------------------------------------------
def decide_fixture(rng, n_layers=24, n_envs=96):
    layers = rand_layers(rng, n_layers, act=None)
    envs = dec.make_envs(DEVICE, EDGE,
                         link_bw=np.geomspace(1e5, 1e10, n_envs),
                         input_bytes=1e5)
    return layers, envs


@pytest.mark.parametrize("family,seed", [
    *(pytest.param(f, 8, id=f) for f in ("gbt", "ridge", "mlp")),
    *(pytest.param(f, 7, id=f"{f}-seed7") for f in ("gbt", "ridge", "mlp"))])
def test_predictor_decide_pallas_tolerance(fitted, family, seed):
    rng = np.random.default_rng(seed)
    layers, envs = decide_fixture(rng)
    model = fitted[family]
    ref = dec.decide_all(layers, envs,
                         cost=co.PredictorCost(model, DEVICE, EDGE))
    got = dec.decide_all(layers, envs,
                         cost=co.PredictorCost(model, DEVICE, EDGE),
                         backend="pallas")
    # f32 argmin may flip at a genuine near-tie: compare achieved cost
    assert np.all(got.total_time_s <= ref.total_time_s * (1 + 1e-4)
                  + 1e-12)
    assert np.array_equal(ref.splits, got.splits)   # holds on these seeds
    np.testing.assert_allclose(got.total_time_s, ref.total_time_s,
                               rtol=1e-4, atol=1e-12)


def test_composite_over_predictor_decides_on_accel(fitted):
    rng = np.random.default_rng(9)
    layers, envs = decide_fixture(rng, n_envs=64)

    def cost():
        return co.CompositeCost(
            base=co.PredictorCost(fitted["gbt"], DEVICE, EDGE),
            weights={"latency_s": 1.0, "energy_j": 0.05, "price": 1.0},
            price_per_edge_s=0.1, price_per_gb=0.01, deadline_s=0.05)

    ref = dec.decide_all(layers, envs, cost=cost())
    pal = dec.decide_all(layers, envs, cost=cost(), backend="pallas")
    assert np.array_equal(ref.splits, pal.splits)
    np.testing.assert_allclose(pal.components, ref.components,
                               rtol=1e-4, atol=1e-12)
    np.testing.assert_allclose(pal.scalar_cost, ref.scalar_cost,
                               rtol=1e-4, atol=1e-12)


@pytest.mark.parametrize("backend", ["numpy", "pallas"])
@pytest.mark.parametrize("n_layers,n_envs", [(0, 4), (3, 0), (0, 0)])
def test_predictor_decide_degenerate(fitted, backend, n_layers, n_envs):
    rng = np.random.default_rng(10)
    layers = rand_layers(rng, n_layers, act=None)
    envs = dec.make_envs(DEVICE, EDGE,
                         link_bw=rng.uniform(1e5, 1e9, max(n_envs, 1))
                         [:n_envs] if n_envs else np.zeros(0),
                         input_bytes=1e4) if n_envs else \
        dec.EnvArrays(*[np.zeros(0)] * 7)
    plan = dec.decide_all(layers, envs,
                          cost=co.PredictorCost(fitted["gbt"], DEVICE,
                                                EDGE), backend=backend)
    assert len(plan) == n_envs


def test_sweep_links_predictor_backend(fitted):
    rng = np.random.default_rng(11)
    layers = rand_layers(rng, 12, act=None)
    env = off.OffloadEnv(DEVICE, EDGE, link_bw=1e8, input_bytes=1e5)
    bws = np.geomspace(1e5, 1e9, 32)
    ref = dec.sweep_links(layers, env, bws,
                          cost=co.PredictorCost(fitted["gbt"], DEVICE,
                                                EDGE))
    got = dec.sweep_links(layers, env, bws,
                          cost=co.PredictorCost(fitted["gbt"], DEVICE,
                                                EDGE), backend="pallas")
    assert np.array_equal(ref.splits, got.splits)
    np.testing.assert_allclose(got.total_time_s, ref.total_time_s,
                               rtol=1e-4, atol=1e-12)


# --------------------------------------------------------------------------
# PageHinkley detector
# --------------------------------------------------------------------------
def test_page_hinkley_fires_on_mean_shift_only():
    rng = np.random.default_rng(12)
    ph = PageHinkley()
    for _ in range(400):                 # stationary: no false alarm
        assert not ph.update(rng.normal(0.0, 0.1))
    fired_at = None
    for i in range(200):                 # +8 sigma shift: fires fast
        if ph.update(rng.normal(0.8, 0.1)):
            fired_at = i
            break
    assert fired_at is not None and fired_at < 50


def test_page_hinkley_two_sided():
    rng = np.random.default_rng(13)
    ph = PageHinkley()
    for _ in range(200):
        ph.update(rng.normal(0.0, 0.1))
    assert any(ph.update(rng.normal(-0.8, 0.1)) for _ in range(200))


def test_page_hinkley_reset():
    rng = np.random.default_rng(14)
    ph = PageHinkley()
    for _ in range(100):
        ph.update(rng.normal(0.0, 0.1))
    ph.reset()
    assert ph.n == 0 and ph.std == 0.0
    for _ in range(ph.min_samples - 1):
        assert not ph.update(rng.normal(5.0, 0.1))


# --------------------------------------------------------------------------
# OnlineOracle: transparency, correction, drift -> refit
# --------------------------------------------------------------------------
def sim_fixture(rng, n_tasks=40):
    nodes = [sch.Node(SPECS[j % len(SPECS)]) for j in range(4)]
    tasks = [sch.Task(f"t{i}", flops=float(rng.uniform(1e9, 5e11)),
                      input_bytes=float(rng.uniform(1e4, 1e6)))
             for i in range(n_tasks)]
    arrivals = np.sort(rng.uniform(0.0, 10.0, n_tasks))
    return tasks, arrivals, nodes


def test_oracle_stream_bit_for_bit_when_static(fitted):
    """Acceptance pin: static environment + no drift -> the oracle path
    places every task exactly like the oracle-free PredictorCost path."""
    rng = np.random.default_rng(15)
    tasks, arrivals, nodes = sim_fixture(rng)
    plain = simulate_stream(tasks, arrivals, nodes,
                            cost=co.PredictorCost(fitted["gbt"], DEVICE,
                                                  EDGE))
    oracle = OnlineOracle(fitted["gbt"], DEVICE, EDGE)
    with_oracle = simulate_stream(tasks, arrivals, nodes, oracle=oracle)
    assert len(plain.records) == len(with_oracle.records) == len(tasks)
    for a, b in zip(plain.records, with_oracle.records):
        assert (a.name, a.node, a.node_id) == (b.name, b.node, b.node_id)
        assert a.started_s == b.started_s
        assert a.finished_s == b.finished_s
    assert oracle.refits == 0 and oracle.drift_triggers == 0
    assert oracle.gain == 1.0 and oracle.bias == 0.0
    assert oracle.observations == len(tasks)
    s = with_oracle.summary()
    assert s["oracle_observations"] == len(tasks)
    assert s["oracle_nrmse"] < 1e-9     # deadband-level float noise only


def test_oracle_cost_is_predictor_cost_bitwise(fitted):
    rng = np.random.default_rng(16)
    layers, envs = decide_fixture(rng, n_envs=16)
    oracle = OnlineOracle(fitted["gbt"], DEVICE, EDGE)
    cost = oracle.cost_model()
    assert isinstance(cost, OracleCost)
    a = dec.decide_all(layers, envs,
                       cost=co.PredictorCost(fitted["gbt"], DEVICE, EDGE))
    b = dec.decide_all(layers, envs, cost=cost)
    assert np.array_equal(a.splits, b.splits)
    assert np.array_equal(a.total_time_s, b.total_time_s)


def test_oracle_rejects_cost_and_oracle_together(fitted):
    rng = np.random.default_rng(17)
    tasks, arrivals, nodes = sim_fixture(rng, 4)
    with pytest.raises(ValueError, match="oracle"):
        simulate_stream(tasks, arrivals, nodes, cost=co.AnalyticCost(),
                        oracle=OnlineOracle(fitted["gbt"], DEVICE, EDGE))


def test_gain_correction_tracks_uniform_slowdown(fitted):
    """Realised times uniformly 2x predictions: the EWMA gain converges
    toward 2 and the corrected predictions converge to realised."""
    oracle = OnlineOracle(fitted["gbt"], DEVICE, EDGE,
                          refit_on_drift=False)
    rng = np.random.default_rng(18)
    feats, _ = layer_training_set(rand_layers(rng, 8, act=None))
    for i in range(120):
        f = feats[i % len(feats)]
        pred_raw = oracle.predict_one(f)
        oracle.observe(f, realised_s=2.0 * pred_raw / oracle.gain
                       if oracle.gain else pred_raw)
    # realised was generated as 2x the *uncorrected* model prediction
    assert abs(oracle.gain - 2.0) < 0.15


def test_oracle_drift_triggers_refit_and_recovers():
    """Structured drift (a subset of devices slows) degrades rolling
    nRMSE; the Page–Hinkley trigger + fresh-window refit recovers it.
    ``correction="none"`` isolates the refit loop (the affine correction
    has its own pin above)."""
    rng = np.random.default_rng(19)
    x, y = layer_training_set(rand_layers(rng, 48, act=None))
    gbt = GBTRegressor(n_trees=30, max_depth=5).fit(x, y)
    oracle = OnlineOracle(gbt, DEVICE, EDGE, window=256, min_refit=120,
                          correction="none")

    def realised(spec, flops, drifted):
        t = off.layer_time(flops, spec)
        if drifted and spec.tdp_watts in (12, 15):   # pi5 + jetson slow
            t *= 3.0
        return t

    track = []
    for step in range(800):
        spec = SPECS[int(rng.integers(len(SPECS)))]
        flops = float(rng.uniform(1e8, 1e11))
        lc = off.LayerCost("q", flops=flops, act_bytes=0.0)
        f = oracle.feature_fn([lc], spec)[0]
        oracle.observe(f, realised(spec, flops, drifted=step >= 250))
        track.append(oracle.rolling_nrmse())
    assert oracle.drift_triggers >= 1
    assert oracle.refits >= 1
    assert oracle.version >= 1
    peak = max(track[250:])
    recovered = float(np.mean(track[-50:]))
    assert recovered < 0.5 * peak, (recovered, peak)


def test_refit_requires_observations(fitted):
    oracle = OnlineOracle(fitted["gbt"], DEVICE, EDGE)
    with pytest.raises(ValueError, match="refit"):
        oracle.refit()


def test_multi_target_refit_only_served_column():
    """A MultiTargetGBT refit replaces only the served target's
    ensemble; the other target keeps predicting, and serving a non-zero
    column still works after the refit."""
    rng = np.random.default_rng(30)
    x = rng.normal(size=(240, 5)).astype(np.float32)
    y = np.stack([x[:, 0], 2.0 * x[:, 1]], axis=1)
    m = MultiTargetGBT(n_trees=8, max_depth=3).fit(x, y)
    oracle = OnlineOracle(m, DEVICE, EDGE, target_index=1)
    before = m.predict(x)
    for i in range(64):
        oracle.observe(x[i], float(y[i, 1]) * 3.0)
    oracle.refit()
    after = oracle.model.predict(x)
    assert after.shape == before.shape == (240, 2)
    # column 0 untouched, column 1 re-learned on the 3x targets
    assert np.array_equal(after[:, 0], before[:, 0])
    assert not np.array_equal(after[:, 1], before[:, 1])
    oracle.predict_one(x[0])             # serving path stays alive


def test_single_target_refit_rejects_nonzero_index(fitted):
    oracle = OnlineOracle(fitted["ridge"], DEVICE, EDGE, target_index=1)
    rng = np.random.default_rng(31)
    feats, _ = layer_training_set(rand_layers(rng, 4, act=None))
    for f in feats[:8]:
        oracle.observe(f, 1.0, predicted_s=1.0)
    with pytest.raises(TypeError, match="target_index"):
        oracle.refit()


def test_sim_service_time_fn_drives_real_drift():
    """With a ground-truth service model that disagrees with the
    predictor on one device class, the oracle sees genuine residuals
    through simulate_stream completions and closes the loop in-sim."""
    rng = np.random.default_rng(32)
    x, y = layer_training_set(rand_layers(rng, 48, act=None))
    gbt = GBTRegressor(n_trees=30, max_depth=5).fit(x, y)
    nodes = [sch.Node(s) for s in SPECS]
    tasks = [sch.Task(f"t{i}", flops=float(rng.uniform(1e8, 1e11)),
                      input_bytes=0.0) for i in range(400)]
    arrivals = np.sort(rng.uniform(0.0, 400.0, len(tasks)))

    def ground_truth(task, spec, etc_s, start_s):
        # pi5 + jetson silently slow down 3x a third of the way in
        slow = 3.0 if start_s >= 130.0 and spec.tdp_watts in (12, 15) \
            else 1.0
        return slow * off.layer_time(task.flops, spec)

    oracle = OnlineOracle(gbt, DEVICE, EDGE, window=256, min_refit=64,
                          correction="none")
    out = simulate_stream(tasks, arrivals, nodes, oracle=oracle,
                          service_time_fn=ground_truth)
    s = out.summary()
    assert s["oracle_observations"] == len(tasks)
    assert oracle.drift_triggers >= 1      # detected through the sim
    assert oracle.refits >= 1              # and refit through the sim
    # realised (not believed) times land in telemetry
    slowed = [r for r in out.records
              if r.node in ("pi5-arm", "jetson-orin-nano")]
    assert slowed, "fixture must exercise the slowed nodes"


def test_oracle_cost_picks_up_refit(fitted):
    """After a refit the same OracleCost instance serves the new
    version (caches flushed on version change)."""
    rng = np.random.default_rng(20)
    x, y = layer_training_set(rand_layers(rng, 16, act=None))
    oracle = OnlineOracle(fitted["gbt"], DEVICE, EDGE)
    cost = oracle.cost_model()
    layers = rand_layers(rng, 6, act=None)
    t0 = cost.layer_times(layers)
    for i in range(40):
        oracle.observe(x[i % len(x)], float(y[i % len(y)]) * 4.0)
    oracle.refit()
    assert oracle.version == 1
    t1 = cost.layer_times(layers)
    assert cost.model is oracle.model
    assert not np.array_equal(t0[0], t1[0])


# --------------------------------------------------------------------------
# registry
# --------------------------------------------------------------------------
def test_registry_versions_and_rollback(fitted):
    reg = PredictorRegistry(keep=2)
    assert reg.version == -1
    with pytest.raises(LookupError):
        reg.current()
    v0 = reg.publish(fitted["ridge"], tag="a")
    v1 = reg.publish(fitted["gbt"], tag="b")
    assert (v0, v1, reg.version) == (0, 1, 1)
    assert reg.current().model is fitted["gbt"]
    assert reg.get(0).model is fitted["ridge"]
    reg.rollback(0)
    assert reg.version == 0 and reg.current().model is fitted["ridge"]
    # version numbers are never re-minted: publishing after a rollback
    # gets a fresh number instead of overwriting the rolled-past v1
    assert reg.publish(fitted["mlp"]) == 2
    assert reg.get(1).model is fitted["gbt"]


def test_registry_keep_bound(fitted):
    reg = PredictorRegistry(keep=2)
    for _ in range(4):
        reg.publish(fitted["ridge"])
    with pytest.raises(LookupError):
        reg.get(0)
    assert reg.get(3).model is fitted["ridge"]


def test_registry_persistence_round_trip(tmp_path, fitted):
    root = os.path.join(str(tmp_path), "reg")
    reg = PredictorRegistry(root=root)
    reg.publish(fitted["ridge"], tag="first")
    reg.publish(fitted["gbt"], tag="second")
    assert os.path.exists(os.path.join(root, "CURRENT.json"))
    rng = np.random.default_rng(21)
    x, _ = layer_training_set(rand_layers(rng, 7, act=None))
    loaded = PredictorRegistry.load(root)
    assert loaded.version == 1
    assert np.array_equal(loaded.current().model.predict(x),
                          fitted["gbt"].predict(x))
    # older versions remain addressable from disk
    old = loaded.get(0).model
    np.testing.assert_allclose(np.asarray(old.predict(x), np.float64),
                               np.asarray(fitted["ridge"].predict(x),
                                          np.float64))
