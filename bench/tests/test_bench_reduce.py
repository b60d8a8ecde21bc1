"""The trace reduction: busy union, idle gaps named by host activity,
device time per operation; and the loader on a trace recorded here."""
from __future__ import annotations

import small_cells  # noqa: F401  (puts the benchmark on the path)
import trace_reduce as tr


def events():
    # one device: ops [0,10] a, [5,20] b, [30,40] a, and one op outside
    # the window; host: the window [0,50], a call [0,25], prep [20,35]
    return tr.Events(
        device={"/device:TPU:0": [("a", 0, 10), ("b", 5, 20), ("a", 30, 40),
                                  ("c", 60, 70)]},
        host=[("window", 0, 50), ("call", 0, 25), ("prep", 20, 35)])


def test_busy_is_the_union_of_op_intervals():
    r = tr.reduce(events())
    assert abs(r["window_s"] - 50e-9) < 1e-18
    assert abs(r["busy_s"] - 30e-9) < 1e-18        # [0,20] and [30,40]


def test_time_per_operation_sums_its_events_inside_the_window():
    r = tr.reduce(events())
    assert abs(r["per_op"]["a"] - 20e-9) < 1e-18
    assert abs(r["per_op"]["b"] - 15e-9) < 1e-18
    assert "c" not in r["per_op"]
    assert r["device_ops"][0][0] == "a"


def test_idle_gaps_are_named_by_the_innermost_host_span():
    r = tr.reduce(events())
    names = sorted(n for n, _ in r["idle_gaps"])
    # [20,30] falls in prep (the later-starting span over its middle);
    # [40,50] in no call
    assert names == ["outside calls", "prep"]
    assert all(abs(d - 10e-9) < 1e-18 for _, d in r["idle_gaps"])


def test_busy_is_averaged_over_devices():
    ev = events()
    ev.device["/device:TPU:1"] = [("a", 0, 50)]
    assert abs(tr.reduce(ev)["busy_s"] - 40e-9) < 1e-18


def test_ops_spanning_the_window_edge_are_clipped():
    ev = tr.Events({"/device:TPU:0": [("x", -10, 10), ("x", 45, 80)]},
                   [("window", 0, 50)])
    assert abs(tr.reduce(ev)["busy_s"] - 15e-9) < 1e-18


def test_op_seconds_matches_by_name():
    r = tr.reduce(events())
    assert abs(tr.op_seconds(r, lambda n: n == "b") - 15e-9) < 1e-18
    assert tr.op_seconds(r, lambda n: n == "zz") is None


def test_loader_reads_the_benchmark_spans_of_a_recorded_trace(tmp_path):
    import jax
    import jax.numpy as jnp
    from common import Spans, TraceWindow
    spans = Spans()
    tw = TraceWindow(True, str(tmp_path), 0.0, 10.0, spans)
    tw.poll(0.0)
    for _ in range(3):
        with spans("call"):
            (jnp.ones((64, 64)) @ jnp.ones((64, 64))).block_until_ready()
    tw.stop()
    ev = tr.load(str(tmp_path))
    names = [n for n, _, _ in ev.host]
    assert names.count("call") == 3 and names.count("window") == 1
    r = tr.reduce(ev)
    assert r["window_s"] > 0
    if jax.default_backend() != "tpu":
        assert ev.device == {} and r["busy_s"] == 0.0
