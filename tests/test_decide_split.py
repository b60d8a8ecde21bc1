"""The Pallas decide path and the sharded decide vs the host decision core.

Pins the fused Pallas kernel within f32 tolerance of the numpy path
(plus a near-optimality bound, so a last-ulp argmin flip at a genuine
tie cannot flake the suite), and ``decide_all_sharded`` on a one-device
mesh bit-for-bit (f64) to it.  Also covers the degenerate shapes every
path must accept (empty layer chain, zero environments) and the cost
models and backends that must *not* lower.
"""
import numpy as np
import pytest
from hypothesis_shim import given, settings, st

from repro.core import costs as co
from repro.core import decisions as dec
from repro.core import offload as off
from repro.hw import EDGE_DEVICES, get_device
from repro.kernels.decide_split import kernel as dk
from repro.kernels.decide_split.ops import BLOCK_E, BLOCK_S
from repro.kernels.decide_split.ref import decide_ref, latency_matrix_ref
from repro.sim import decide_all_sharded

PLAN_FIELDS = ("splits", "total_time_s", "device_time_s",
               "transfer_time_s", "edge_time_s")


def rand_layers(rng, n):
    return [off.LayerCost(f"l{i}",
                          flops=float(rng.uniform(1e6, 1e12)),
                          act_bytes=float(rng.uniform(1e2, 1e8)))
            for i in range(n)]


def rand_envs(rng, n):
    specs = list(EDGE_DEVICES.values())
    return dec.make_envs(
        [specs[int(rng.integers(len(specs)))] for _ in range(max(n, 1))][:n]
        or [specs[0]],
        specs[int(rng.integers(len(specs)))],
        link_bw=rng.uniform(1e4, 1e10, max(n, 1))[:n],
        link_latency_s=rng.uniform(0.0, 0.05, max(n, 1))[:n],
        input_bytes=rng.uniform(0.0, 1e7, max(n, 1))[:n]) \
        if n else dec.EnvArrays(*[np.zeros(0)] * 7)


def composite():
    return co.CompositeCost(
        weights={"latency_s": 1.0, "energy_j": 0.05, "price": 1.0},
        price_per_edge_s=0.1, price_per_gb=0.01, deadline_s=0.05)


def assert_plans_equal(a, b):
    for f in PLAN_FIELDS:
        assert np.array_equal(getattr(a, f), getattr(b, f)), f
    assert a.objectives == b.objectives
    for f in ("components", "scalar_cost"):
        x, y = getattr(a, f), getattr(b, f)
        assert (x is None) == (y is None), f
        if x is not None:
            assert np.array_equal(x, y), f


def test_sweep_links_backend_passthrough():
    rng = np.random.default_rng(6)
    layers = rand_layers(rng, 8)
    env = off.OffloadEnv(get_device("pi5-arm"),
                         get_device("edge-server-a100"),
                         link_bw=1e8, input_bytes=1e5)
    bws = np.geomspace(1e5, 1e9, 16)
    ref = dec.sweep_links(layers, env, bws)
    got = dec.sweep_links(layers, env, bws, backend="pallas")
    assert np.array_equal(ref.splits, got.splits)
    for f in PLAN_FIELDS[1:]:
        np.testing.assert_allclose(getattr(got, f), getattr(ref, f),
                                   rtol=1e-5, atol=1e-12, err_msg=f)


# --------------------------------------------------------------------------
# Pallas kernel: within f32 tolerance, chosen splits near-optimal
# --------------------------------------------------------------------------
def assert_pallas_close(layers, envs, efficiency=off.DEFAULT_EFFICIENCY,
                        *, cost=None, rtol=1e-5):
    ref = decide_ref(layers, envs, efficiency, cost=cost)
    got = dec.decide_all(layers, envs, efficiency, cost=cost,
                         backend="pallas")
    # the split the kernel picked, re-costed exactly in f64, must be
    # within f32-argmin distance of the true optimum...
    ranked_ref = ref.scalar_cost if ref.scalar_cost is not None \
        else ref.total_time_s
    ranked_got = got.scalar_cost if got.scalar_cost is not None \
        else got.total_time_s
    assert np.all(ranked_got <= ranked_ref * (1 + 1e-4) + 1e-12)
    # ...and the plan's own breakdown must be internally consistent
    np.testing.assert_allclose(
        got.device_time_s + got.transfer_time_s + got.edge_time_s,
        got.total_time_s, rtol=1e-9, atol=1e-15)
    # on fixed seeds the argmin agrees outright
    assert np.array_equal(ref.splits, got.splits)
    for f in PLAN_FIELDS[1:]:
        np.testing.assert_allclose(getattr(got, f), getattr(ref, f),
                                   rtol=rtol, atol=1e-12, err_msg=f)


@pytest.mark.parametrize("seed,max_layers,max_envs", [
    *(pytest.param(10 + t, 40, 64, id=str(t)) for t in range(4)),
    *(pytest.param(t, 24, 48, id=f"seed{t}") for t in range(8))])
def test_pallas_decide_close(seed, max_layers, max_envs):
    rng = np.random.default_rng(seed)
    assert_pallas_close(rand_layers(rng, int(rng.integers(1, max_layers))),
                        rand_envs(rng, int(rng.integers(1, max_envs))))


def test_pallas_custom_efficiency_close():
    rng = np.random.default_rng(4)
    assert_pallas_close(rand_layers(rng, 9), rand_envs(rng, 12), 0.71)


@pytest.mark.parametrize("make_cost", [co.AnalyticCost,
                                       lambda: co.AnalyticCost(0.5),
                                       composite],
                         ids=["analytic", "analytic_eff", "composite"])
def test_pallas_cost_models_close(make_cost):
    rng = np.random.default_rng(5)
    assert_pallas_close(rand_layers(rng, 14), rand_envs(rng, 20),
                        cost=make_cost())


def test_pallas_composite_close():
    rng = np.random.default_rng(20)
    layers = rand_layers(rng, 12)
    envs = rand_envs(rng, 24)
    assert_pallas_close(layers, envs, cost=composite())
    ref = decide_ref(layers, envs, cost=composite())
    got = dec.decide_all(layers, envs, cost=composite(), backend="pallas")
    np.testing.assert_allclose(got.components, ref.components,
                               rtol=1e-5, atol=1e-12)


def test_pallas_multi_block_sweep():
    """Splits beyond one 128-lane block: the running argmin must carry
    across split blocks (and env padding must not leak into outputs)."""
    rng = np.random.default_rng(21)
    layers = rand_layers(rng, 300)               # 301 splits -> 3 blocks
    envs = rand_envs(rng, 13)                    # pads to block_e
    assert_pallas_close(layers, envs)


# --------------------------------------------------------------------------
# the kernel itself: bit for bit with its formulas in np.float32
# --------------------------------------------------------------------------
def kernel_operands(n_envs, n_splits, objective, seed, ties=False):
    """Split rows and env columns spanning orders of magnitude, so that
    the optimum falls on every kind of split.  With ``ties`` each pair of
    splits ``(2k, 2k + 1)`` below ``L`` costs the same to the bit.

    Off the TPU the kernel runs through XLA's CPU compiler, which turns a
    division by a constant into a product and fuses ``a * b + c`` into
    one rounding.  So every factor of a product is a power of two (the
    weights, prices, energy rates) and every byte count a whole GB times
    one (``ship / 1e9`` exact): each product is then exact, and both
    roundings of each formula agree with numpy's."""
    rng = np.random.default_rng(seed)

    def log_uniform(lo, hi, n=n_envs):
        return 10.0 ** rng.uniform(lo, hi, n)
    # device layer times rising, edge layer times falling: interior optima
    dcum = np.cumsum(np.sort(log_uniform(-4, -1, n_splits)))
    ecum = np.cumsum(np.sort(log_uniform(-5, -2, n_splits))[::-1])
    def gb_pow2(n):
        return 1e9 * 2.0 ** rng.integers(-20, -3, n)
    bvec, inp = gb_pow2(n_splits), gb_pow2(n_envs)
    if ties:
        dcum, ecum = (np.repeat(x[:(n_splits + 1) // 2], 2)[:n_splits]
                      for x in (dcum, ecum))
        bvec[:], inp[:] = 1e9 / 2 ** 14, 1e9 / 2 ** 14
    dcum[0] = ecum[0] = bvec[0] = bvec[-1] = 0.0
    envs = (log_uniform(-1, 1), log_uniform(-2, 0), log_uniform(4, 10),
            rng.uniform(0.0, 0.05, n_envs), inp,
            2.0 ** rng.integers(0, 4, n_envs), 2.0 ** rng.integers(3, 9, n_envs))
    params = {"latency": {},
              "composite": dict(radio_watts=2.0, price_per_edge_s=2 ** -3,
                                price_per_gb=2 ** -7, deadline_s=0.05)}
    params["queue"] = dict(params["composite"], queue_wait_s=0.003,
                           tail_excess_s=0.002, tail_weight=0.5)
    weights = (1.0, 0.0, 0.0, 0.0) if objective == "latency" \
        else (1.0, 2 ** -4, 1.0, 0.5)
    spec = dk.pack_spec(weights, edge_total=float(np.float32(ecum[-1])),
                        **params[objective])
    return [np.asarray(x, np.float32)
            for x in (dcum, ecum, bvec, *envs)] + [spec]


def kernel_in_numpy(dcum, ecum, bvec, dev_div, edge_div, bw, lat, inp,
                    dev_w, edge_w, spec):
    """The kernel's cost of every split, one split at a time in its own
    operation order, and a strict-``<`` running argmin: ties keep the
    earlier split, as ``np.argmin`` does."""
    f32, zero = np.float32, np.float32(0.0)
    n = dcum.shape[0]
    best = np.full(dev_div.shape, np.inf, f32)
    idx = np.zeros(dev_div.shape, np.int32)
    bw1 = np.maximum(bw, f32(1.0))
    for s in range(n):
        last = s == n - 1
        dev_t = dcum[s] / dev_div
        edge_t = (spec[dk.SPEC_ETOT] - ecum[s]) / edge_div
        ship = np.zeros_like(inp) if last else inp if s == 0 \
            else np.full_like(inp, bvec[s])
        xfer = np.zeros_like(lat) if last else lat + ship / bw1
        total = dev_t + xfer + edge_t
        energy = dev_t * dev_w + xfer * spec[dk.SPEC_RADIO] \
            + edge_t * edge_w
        price = edge_t * spec[dk.SPEC_PPS] \
            + ship / f32(1e9) * spec[dk.SPEC_PPG]
        slack = np.maximum(total - spec[dk.SPEC_DEADLINE], zero)
        lat_col = total + (zero if last else spec[dk.SPEC_WAIT])
        scal = spec[dk.SPEC_W0] * lat_col + spec[dk.SPEC_W1] * energy \
            + spec[dk.SPEC_W2] * price + spec[dk.SPEC_W3] * slack
        scal = scal + spec[dk.SPEC_W4] * (
            total + (zero if last else spec[dk.SPEC_TEXC]))
        better = scal < best
        best = np.where(better, scal, best)
        idx = np.where(better, np.int32(s), idx)
    return idx, best


@pytest.mark.parametrize("n_envs,n_splits,objective", [
    (1, 33, "queue"), (127, 2, "composite"), (129, 3, "queue"),
    (1023, 301, "composite"), (1025, 129, "queue"), (1025, 33, "latency"),
    (BLOCK_E + 1, 33, "composite")])
def test_kernel_bit_for_bit_with_its_formulas(n_envs, n_splits, objective):
    ops = kernel_operands(n_envs, n_splits, objective, n_envs + n_splits)
    got = dk.decide_split_kernel(*ops, block_e=BLOCK_E, block_s=BLOCK_S,
                                 interpret=True)
    want = kernel_in_numpy(*ops)
    assert [g.shape for g in got] == [(n_envs,)] * 2
    assert np.array_equal(np.asarray(got[0]), want[0])
    assert np.array_equal(np.asarray(got[1]), want[1])


@pytest.mark.parametrize("objective", ["latency", "queue"])
def test_kernel_ties_keep_the_earlier_split(objective):
    """Splits ``2k`` and ``2k + 1`` cost the same to the bit; the kernel
    returns the even one, and ``L`` (33 splits: the lone last one)."""
    ops = kernel_operands(1500, 33, objective, 9, ties=True)
    split, cost = dk.decide_split_kernel(*ops, block_e=BLOCK_E,
                                         block_s=BLOCK_S, interpret=True)
    split = np.asarray(split)
    want = kernel_in_numpy(*ops)
    assert np.array_equal(split, want[0])
    assert np.array_equal(np.asarray(cost), want[1])
    assert np.all(split % 2 == 0) and len(np.unique(split)) > 2


# --------------------------------------------------------------------------
# the sharded decide on a one-device mesh: bit-for-bit with numpy (f64)
# --------------------------------------------------------------------------
def one_device_mesh():
    import jax
    return jax.make_mesh((1,), ("env",), devices=jax.devices()[:1])


def test_sharded_one_device_bit_for_bit():
    rng = np.random.default_rng(3)
    layers = rand_layers(rng, 19)
    envs = rand_envs(rng, 31)
    assert_plans_equal(decide_ref(layers, envs),
                       decide_all_sharded(layers, envs,
                                          mesh=one_device_mesh()))


# --------------------------------------------------------------------------
# degenerate shapes: every path, every entry point
# --------------------------------------------------------------------------
@pytest.mark.parametrize("n_layers,n_envs", [(0, 7), (5, 0), (0, 0)])
def test_transfer_and_latency_degenerate(n_layers, n_envs):
    rng = np.random.default_rng(30)
    layers = rand_layers(rng, n_layers)
    envs = rand_envs(rng, n_envs)
    tb = dec.transfer_bytes(layers, envs)
    assert tb.shape == (n_envs, n_layers + 1)
    assert np.all(tb[:, -1] == 0.0)              # split == L ships nothing
    lat = dec.latency_matrix(layers, envs)
    assert lat.shape == (n_envs, n_layers + 1)
    if n_layers == 0 and n_envs:                 # L == 0: only split 0 == L
        assert np.array_equal(tb, np.zeros((n_envs, 1)))
        assert np.array_equal(lat, np.zeros((n_envs, 1)))


@pytest.mark.parametrize("n_layers,n_envs", [(0, 7), (5, 0), (0, 0)])
def test_sharded_decide_degenerate(n_layers, n_envs):
    rng = np.random.default_rng(30)
    layers = rand_layers(rng, n_layers)
    envs = rand_envs(rng, n_envs)
    assert_plans_equal(decide_ref(layers, envs),
                       decide_all_sharded(layers, envs,
                                          mesh=one_device_mesh()))


@pytest.mark.parametrize("backend", ["numpy", "pallas"])
@pytest.mark.parametrize("n_layers,n_envs", [(0, 7), (5, 0), (0, 0)])
def test_decide_all_degenerate(backend, n_layers, n_envs):
    rng = np.random.default_rng(31)
    layers = rand_layers(rng, n_layers)
    envs = rand_envs(rng, n_envs)
    plan = dec.decide_all(layers, envs, backend=backend)
    assert len(plan) == n_envs
    assert plan.splits.shape == plan.total_time_s.shape == (n_envs,)
    if n_layers == 0:                            # split 0 is also split L
        assert np.all(plan.splits == 0)
        assert np.all(plan.total_time_s == 0.0)


@pytest.mark.parametrize("backend", ["numpy", "pallas"])
@pytest.mark.parametrize("n_layers,n_envs", [(0, 4), (3, 0)])
def test_decide_all_degenerate_composite(backend, n_layers, n_envs):
    rng = np.random.default_rng(32)
    plan = dec.decide_all(rand_layers(rng, n_layers),
                          rand_envs(rng, n_envs), cost=composite(),
                          backend=backend)
    assert len(plan) == n_envs
    assert plan.components.shape == (n_envs, 4)


# --------------------------------------------------------------------------
# lowering boundaries (PredictorCost over a *lowerable* regressor now
# lowers — see tests/test_oracle.py; only host-only models are rejected)
# --------------------------------------------------------------------------
class _HostModel:
    def predict(self, x):
        return np.zeros(len(x))


def test_host_only_predictor_rejected_on_accelerator():
    rng = np.random.default_rng(40)
    cost = co.PredictorCost(_HostModel(), get_device("pi5-arm"),
                            get_device("edge-server-a100"))
    with pytest.raises(TypeError, match="host-side"):
        dec.decide_all(rand_layers(rng, 4), rand_envs(rng, 3), cost=cost,
                       backend="pallas")


def test_composite_over_host_only_base_rejected():
    cost = co.CompositeCost(base=co.PredictorCost(
        _HostModel(), get_device("pi5-arm"),
        get_device("edge-server-a100")))
    rng = np.random.default_rng(41)
    with pytest.raises(TypeError, match="host-side"):
        dec.decide_all(rand_layers(rng, 4), rand_envs(rng, 3), cost=cost,
                       backend="pallas")


@pytest.mark.parametrize("backend", ["tpu", "jax"])
def test_unknown_backend_rejected(backend):
    rng = np.random.default_rng(42)
    with pytest.raises(ValueError, match="'numpy' or 'pallas'"):
        dec.decide_all(rand_layers(rng, 2), rand_envs(rng, 2),
                       backend=backend)


def test_efficiency_cost_conflict_guard_on_accelerator():
    rng = np.random.default_rng(43)
    with pytest.raises(ValueError, match="efficiency"):
        dec.decide_all(rand_layers(rng, 2), rand_envs(rng, 2), 0.5,
                       cost=co.AnalyticCost(), backend="pallas")


# --------------------------------------------------------------------------
# hypothesis: backend equivalence over random env grids
# --------------------------------------------------------------------------
@pytest.mark.slow
@settings(max_examples=10, deadline=None)
@given(st.integers(0, 10_000), st.integers(1, 20), st.integers(1, 16))
def test_pallas_equivalence_property(seed, n_layers, n_envs):
    rng = np.random.default_rng(seed)
    layers = rand_layers(rng, n_layers)
    envs = rand_envs(rng, n_envs)
    ref = decide_ref(layers, envs)
    got = dec.decide_all(layers, envs, backend="pallas")
    # f32 argmin may legitimately flip at near-ties, so compare the
    # achieved cost, not the index
    np.testing.assert_allclose(
        latency_matrix_ref(layers, envs)[np.arange(n_envs), got.splits],
        ref.total_time_s, rtol=1e-4, atol=1e-12)
