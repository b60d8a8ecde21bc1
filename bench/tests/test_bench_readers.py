"""Each per-layer metric reader on a record made by hand: the value it
derives, and nothing (never 0) where its cell gave it nothing."""
from __future__ import annotations

import pytest

import small_cells
from common import load_json, load_module

SPEC = load_json(small_cells.ROOT / "BENCHMARK.json")
EDGE = load_json(small_cells.BENCH / "configs" / "edge-metro.json")
DECIDE = ("%tpu_custom_call.1 = (s32[1048576,1]{1,0}, f32[1048576,1]{1,0}) "
          "custom-call(...)")
TREE = "%tpu_custom_call.1 = f32[262144,1]{1,0} custom-call(...)"


def reader(name):
    return load_module(small_cells.BENCH / "metrics" / f"{name}.py")


def record(per_op, busy=1.0, window=4.0, **counters):
    return {"cfg": EDGE, "peak": small_cells.PEAK, "traced_s": 2.0,
            "counters": counters,
            "trace": {"window_s": window, "busy_s": busy,
                      "per_op": per_op}}


def test_every_per_layer_metric_has_a_reader():
    for m in SPEC["per_layer"]:
        assert callable(reader(m["name"]).read)


def test_decide_split_roofline():
    rec = record({DECIDE: 0.1, "%copy": 5.0}, users=1 << 20,
                 traced_splits=[33, 33])
    want = 2 * (36 * 2**20 + 3 * 33 * 4) / 819e9 / 0.1 * 100
    assert reader("decide_split_roofline").read(rec) == pytest.approx(want)
    assert reader("decide_split_roofline").read(
        record({"%copy": 1.0}, users=1, traced_splits=[3])) is None


def test_tree_predict_roofline_and_predict_mfu():
    rec = record({TREE: 2.0}, rows_per_call=2**18, traced_calls=3)
    b = 2**18 * 7 * 4 + 5 * 100 * 1427 * 4 + 2**18 * 4
    assert reader("tree_predict_roofline").read(rec) == pytest.approx(
        3 * b / 819e9 / 2.0 * 100)
    assert reader("predict.mfu").read(rec) == pytest.approx(
        3 * b / 819e9 / 2.0 * 100)
    assert reader("tree_predict_roofline").read(
        record({DECIDE: 1.0}, rows_per_call=8, traced_calls=1)) is None


@pytest.mark.parametrize("name", ["device_idle.plan", "device_idle.catalog",
                                  "device_idle.serve"])
def test_device_idle(name):
    assert reader(name).read(record({}, busy=1.0, window=4.0)) == 75.0
    assert reader(name).read({"trace": None}) is None


def test_serve_metrics():
    rec = record({}, traced_steps=100, traced_flops=197e12)
    assert reader("serve.decode_step_ms").read(rec) == pytest.approx(20.0)
    assert reader("serve.mfu").read(rec) == pytest.approx(50.0)
    assert reader("serve.mfu").read(record({}, traced_steps=0,
                                           traced_flops=0.0)) is None
