"""Each Pallas kernel is built once per static signature.

``decide_split_kernel`` and ``tree_predict_kernel`` run their pads,
``pallas_call`` and slices as one jitted program keyed on the operands'
shapes and the static arguments (``block_e``/``block_s``/``interpret``;
``levels``/``n_trees``/``thr_bits``/``feat_bits``/``blk``/``interpret``).
On the CPU, in interpret mode: a second call at one shape lowers nothing,
a new static signature lowers exactly once, the outputs are the bits of
the same body run op by op, and a fault planted on the public kernel
function after the cache is warm still reaches the answer, since the
callers look the function up at each call.
"""
import contextlib
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import costs as co
from repro.core import decisions as dec
from repro.core import offload as off
from repro.core.predictors.gbt import GBTRegressor
from repro.hw import get_device
from repro.kernels.decide_split.kernel import decide_split_kernel, pack_spec
from repro.kernels.tree_predict import ops as tree_ops
from repro.kernels.tree_predict.kernel import tree_predict_kernel
from repro.oracle.lowered import lower_predictor

BENCH = Path(__file__).resolve().parents[1] / "bench"
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))
import faults  # noqa: E402

DEVICE, EDGE = get_device("pi5-arm"), get_device("edge-server-a100")
#: JAX's event for one jaxpr lowered to an MLIR module
LOWERED = "/jax/core/compile/jaxpr_to_mlir_module_duration"


@contextlib.contextmanager
def lowerings():
    """A list that collects one entry per lowering made inside."""
    seen = []

    def listen(event, duration_secs, **kwargs):
        if event == LOWERED:
            seen.append(event)

    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        yield seen
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)


@pytest.fixture(scope="module")
def gbt():
    rng = np.random.default_rng(0)
    layers = [off.LayerCost(f"l{i}", flops=float(rng.uniform(1e8, 1e11)),
                            act_bytes=float(rng.uniform(1e3, 1e7)))
              for i in range(24)]
    x = np.concatenate([co.default_layer_features(layers, s)
                        for s in (DEVICE, EDGE)])
    y = np.concatenate([[off.layer_time(lc.flops, s) for lc in layers]
                        for s in (DEVICE, EDGE)])
    return GBTRegressor(n_trees=12, max_depth=4, seed=1).fit(x, y)


def decide_operands(n_envs, n_splits):
    rng = np.random.default_rng(0)
    rows = [np.cumsum(rng.uniform(0.0, 1.0, n_splits)) for _ in range(3)]
    envs = [rng.uniform(0.5, 2.0, n_envs) for _ in range(7)]
    spec = pack_spec((1.0, 0.0, 0.0, 0.0), edge_total=float(rows[1][-1]))
    return [jnp.asarray(a, jnp.float32) for a in (*rows, *envs, spec)]


def tree_operands(gbt, n_rows):
    arrays, = lower_predictor(gbt).arrays
    layout = tree_ops.level_layout(arrays)
    x = np.random.default_rng(0).uniform(
        0.0, 1.0, (n_rows, arrays.edges.shape[0])).astype(np.float32)
    codes = tree_ops._bin_codes(jnp.asarray(x), jnp.asarray(arrays.edges))
    static = dict(levels=layout.levels, n_trees=layout.n_trees,
                  thr_bits=layout.thr_bits, feat_bits=layout.feat_bits)
    return (codes, jnp.asarray(layout.node), jnp.asarray(layout.value)), \
        static


def decide_call(gbt, shape, **static):
    """The kernel call on operands made beforehand, as a thunk."""
    args = decide_operands(*shape)
    static = {"block_e": 64, "block_s": 16, **static}
    return lambda: decide_split_kernel(*args, **static, interpret=True)


def tree_call(gbt, shape, **static):
    args, layout = tree_operands(gbt, shape[0])
    static = {**layout, "blk": 256, **static}
    return lambda: tree_predict_kernel(*args, **static, interpret=True)


def all_levels_flagged(gbt):
    """The layout's ``levels`` with every level marked as holding splits
    and leaves: a static signature of its own."""
    _, layout = tree_operands(gbt, 1)
    return {"levels": tuple((lo, width, True, True)
                            for lo, width, _, _ in layout["levels"])}


#: per kernel: the call, two shapes (the first padded up to whole blocks,
#: the second smaller than one block), and another static signature
KERNELS = {
    "decide": (decide_call, ((300, 37), (40, 5)),
               lambda gbt: {"block_e": 32}),
    "tree": (tree_call, ((600,), (136,)), all_levels_flagged),
}


def bits(outs):
    return [np.asarray(o).view(np.uint32) for o in outs]


@pytest.mark.parametrize("kernel", sorted(KERNELS))
def test_a_second_call_at_one_shape_lowers_nothing(kernel, gbt):
    make, (shape, _), _ = KERNELS[kernel]
    call = make(gbt, shape)
    jax.block_until_ready(call())
    with lowerings() as seen:
        jax.block_until_ready(call())
    assert seen == []


@pytest.mark.parametrize("kernel", sorted(KERNELS))
def test_a_new_static_signature_lowers_once(kernel, gbt):
    make, (shape, _), other = KERNELS[kernel]
    first, second = make(gbt, shape), make(gbt, shape, **other(gbt))
    jax.clear_caches()
    for call in (first, second):
        with lowerings() as seen:
            jax.block_until_ready(call())
        assert len(seen) == 1
    with lowerings() as seen:
        jax.block_until_ready(second())
    assert seen == []


@pytest.mark.parametrize("at", [0, 1])
@pytest.mark.parametrize("kernel", sorted(KERNELS))
def test_outputs_are_the_bits_of_the_body_run_op_by_op(kernel, at, gbt):
    make, shapes, _ = KERNELS[kernel]
    call = make(gbt, shapes[at])
    got = jax.tree.leaves(call())
    with jax.disable_jit():
        want = jax.tree.leaves(call())
    assert [g.shape for g in got] == [w.shape for w in want]
    for g, w in zip(bits(got), bits(want)):
        np.testing.assert_array_equal(g, w)


def sweep_answer(gbt):
    envs = dec.make_envs(DEVICE, EDGE, link_bw=np.geomspace(1e5, 1e10, 96),
                         input_bytes=1e5)
    layers = [off.LayerCost(f"l{i}", flops=1e8 * (i + 1),
                            act_bytes=1e4 * (9 - i)) for i in range(9)]
    plan = dec.decide_all(layers, envs, backend="pallas",
                          cost=co.PredictorCost(gbt, DEVICE, EDGE))
    return plan.splits


def catalog_answer(gbt):
    x = np.random.default_rng(5).uniform(
        0.0, 1.0, (200, gbt.edges_.shape[0]))
    return lower_predictor(gbt).predict(x, backend="pallas")


@pytest.mark.parametrize("cell, answer", [
    ("edge-metro.sweep", sweep_answer),
    ("edge-metro.catalog", catalog_answer),
])
def test_a_fault_planted_after_warm_up_still_changes_the_answer(
        cell, answer, gbt, monkeypatch):
    sound = answer(gbt)
    np.testing.assert_array_equal(answer(gbt), sound)   # the cache is warm
    faults.plant(cell, "altered", monkeypatch.setattr)
    assert not np.array_equal(answer(gbt), sound)
