"""Program spans on the profiler's clock (``repro.obs.region``) and the
device-idle attribution that reads them (``repro.obs.analyze.idle``).

A CPU profile of the Pallas decide and tree-predict paths (interpret
mode) shows each phase span nested in its call span, with the first
call's compile events inside the kernel span and none under a second
identical call's; the attribution, on traces made by hand,
names idle gaps by the innermost span and splits each span's idle time
into compile and the rest; and the spans perturb nothing: decisions,
predictions and served tokens are bit-identical with a live ``Tracer``
and under a profiler trace.
"""
import contextlib
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.core import costs as co
from repro.core import decisions as dec
from repro.core import offload as off
from repro.core.predictors.gbt import GBTRegressor
from repro.hw import get_device
from repro.obs import NULL_TRACER, Tracer, region, validate_chrome
from repro.obs.analyze import idle
from repro.obs.analyze.cli import main as analyze_main
from repro.oracle.lowered import lower_predictor

DEVICE, EDGE = get_device("pi5-arm"), get_device("edge-server-a100")


@contextlib.contextmanager
def profiled(trace_dir):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


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


def layers_of(n):
    return [off.LayerCost(f"l{i}", flops=1e8 * (i + 1),
                          act_bytes=1e4 * (n - i)) for i in range(n)]


def envs_of(n):
    return dec.make_envs(DEVICE, EDGE, link_bw=np.geomspace(1e5, 1e10, n),
                         input_bytes=1e5)


# --------------------------------------------------------------------------
# the region API
# --------------------------------------------------------------------------
def test_null_region_records_nothing():
    assert region.__self__ is NULL_TRACER
    with region("decide", "call"):
        with NULL_TRACER.region("serve", "admit", tid=3, args={"slot": 1},
                                now=lambda: 1.0):
            pass
    assert NULL_TRACER.last() == []


def test_live_region_records_nested_spans_on_the_callers_clock():
    clock = iter(range(100))
    tracer = Tracer()
    args = {"slot": 2}
    with tracer.region("serve", "admit", tid=7, args=args,
                       now=lambda: float(next(clock))):
        plan_args = {}
        with tracer.region("serve", "plan", tid=7, args=plan_args,
                           now=lambda: float(next(clock))):
            plan_args["split"] = 4      # read when the region closes
    spans = {s.name: s for s in tracer.all_spans()}
    assert (spans["admit"].t0, spans["admit"].t1) == (0.0, 3.0)
    assert (spans["plan"].t0, spans["plan"].t1) == (1.0, 2.0)
    assert spans["plan"].args == {"split": 4}
    assert spans["admit"].args == {"slot": 2}
    assert {s.track for s in spans.values()} == {"serve"}
    assert validate_chrome(tracer.export_chrome(None))["n_spans"] == 2


def test_live_region_needs_the_callers_clock():
    with pytest.raises(ValueError, match="now="):
        Tracer().region("serve", "admit")


def test_importing_obs_pulls_in_no_jax():
    code = ("import sys, repro.obs, repro.obs.analyze.idle; "
            "sys.exit('jax' in sys.modules)")
    assert subprocess.run([sys.executable, "-c", code]).returncode == 0


# --------------------------------------------------------------------------
# a CPU profile: phase spans nest in their call, compiles in the kernel
# --------------------------------------------------------------------------
def run_decide(gbt):
    envs = envs_of(72)
    return dec.decide_all(layers_of(7), envs, backend="pallas",
                          cost=co.PredictorCost(gbt, DEVICE, EDGE))


def run_predict(gbt):
    n_features = gbt.edges_.shape[0]
    x = np.random.default_rng(5).uniform(0.0, 1.0, (136, n_features))
    return lower_predictor(gbt).predict(x, backend="pallas")


PHASES = {
    "decide": (run_decide, "decide/call",
               ["decide/prep", "decide/h2d", "decide/kernel",
                "decide/sync", "decide/reeval", "predict/call"],
               "decide/kernel"),
    "predict": (run_predict, "predict/call",
                ["predict/bin", "predict/kernel", "predict/sync"],
                "predict/kernel"),
}


def inside(inner, outer) -> bool:
    return (inner[3] == outer[3] and outer[1] <= inner[1]
            and inner[2] <= outer[2])


@pytest.mark.parametrize("path", sorted(PHASES))
def test_profile_nests_phases_in_the_call(path, gbt, tmp_path):
    run, call, phases, kernel = PHASES[path]
    jax.clear_caches()                  # the first call builds the kernel
    first, second = tmp_path / "first", tmp_path / "second"
    for trace_dir in (first, second):
        with profiled(trace_dir):
            run(gbt)
    trace = idle.load(str(first))
    calls = [s for s in trace.spans if s[0] == call]
    assert len(calls) == 1
    for name in phases:
        mine = [s for s in trace.spans if s[0] == name]
        assert mine, f"no {name} span"
        assert all(inside(s, calls[0]) for s in mine), name
    kernels = [s for s in trace.spans if s[0] == kernel]
    lowerings = [c for c in trace.compiles if c[0] == idle.LOWERING]
    assert any(inside(c, k) for c in lowerings for k in kernels)
    rep = idle.attribute(trace)
    assert rep["devices"] == 0                      # no device on a CPU
    assert rep["program"][kernel]["compiles"] >= 1
    assert rep["program"][call]["compiles"] >= \
        rep["program"][kernel]["compiles"]
    assert analyze_main(["idle", str(first)]) == 0
    # the same call again dispatches the kernel built by the first
    again = idle.attribute(idle.load(str(second)))
    assert again["program"][kernel]["count"] == 1
    assert again["program"][kernel]["compiles"] == 0


def test_profile_records_make_envs(tmp_path):
    with profiled(tmp_path):
        envs_of(8)
    assert [s[0] for s in idle.load(str(tmp_path)).spans] == \
        ["decide/envs"]


# --------------------------------------------------------------------------
# the attribution, on traces made by hand
# --------------------------------------------------------------------------
def hand_trace(*, compiles=True, spans=True, compile_line=1):
    """One device, busy [0,10] [30,40] [90,100] of the window [0,100];
    a call [0,60] holding a kernel [10,30] (lowering [12,20], backend
    compile [20,28]) and a re-evaluation [40,60]."""
    ops = [("a", 0, 10), ("b", 30, 40), ("c", 90, 100), ("d", 120, 130)]
    sp = [("decide/call", 0, 60, 1), ("decide/kernel", 10, 30, 1),
          ("decide/reeval", 40, 60, 1)] if spans else []
    cc = [("lower_sharding_computation", 12, 20, compile_line),
          ("backend_compile_and_load", 20, 28, compile_line)] \
        if compiles else []
    return idle.ProfilerTrace({"/device:TPU:0": ops}, sp, cc, (0, 100))


def ns(x):
    return pytest.approx(x * 1e-9, abs=1e-18)


def test_gaps_are_named_by_the_innermost_span():
    rep = idle.attribute(hand_trace())
    assert rep["devices"] == 1
    assert rep["window_s"] == ns(100) and rep["busy_s"] == ns(30)
    assert rep["idle_gaps"] == [["outside spans", ns(50)],
                                ["decide/kernel/compile", ns(20)]]
    assert rep["idle_by_span"] == {"outside spans": ns(50),
                                   "decide/kernel/compile": ns(20)}


@pytest.mark.parametrize("case", ["compiles", "no compiles",
                                  "compiles on another line"])
def test_span_idle_splits_out_compile(case):
    rep = idle.attribute(hand_trace(compiles=case != "no compiles",
                                    compile_line=2 if "another" in case
                                    else 1))
    p = rep["program"]
    assert p["decide/call"]["count"] == 1
    assert p["decide/call"]["seconds"] == ns(60)
    assert p["decide/call"]["idle_s"] == ns(40)     # [10,30] and [40,60]
    assert p["decide/kernel"]["idle_s"] == ns(20)
    assert p["decide/reeval"]["idle_s"] == ns(20)
    counted = case == "compiles"
    for name in ("decide/call", "decide/kernel"):
        assert p[name]["compiles"] == (1 if counted else 0)
        assert p[name]["compile_s"] == ns(16 if counted else 0)
        assert p[name]["compile_idle_s"] == ns(16 if counted else 0)
    assert p["decide/reeval"]["compiles"] == 0
    assert p["decide/reeval"]["compile_idle_s"] == 0.0
    if not counted:
        assert ["decide/kernel", ns(20)] in rep["idle_gaps"]


def test_no_spans_reads_an_empty_program():
    rep = idle.attribute(hand_trace(spans=False))
    assert rep["program"] == {}
    assert {n for n, _ in rep["idle_gaps"]} == {"outside spans", "compile"}
    with pytest.raises(ValueError, match="no program span"):
        idle.attribute(idle.ProfilerTrace({}, [], []))


def test_default_window_is_the_extent_of_the_spans():
    trace = hand_trace()
    trace.window = None
    rep = idle.attribute(trace)
    assert rep["window_s"] == ns(60) and rep["busy_s"] == ns(20)


def test_idle_is_averaged_over_devices():
    trace = hand_trace()
    trace.device["/device:TPU:1"] = [("a", 0, 100)]
    rep = idle.attribute(trace)
    assert rep["busy_s"] == ns(65)
    assert rep["program"]["decide/call"]["idle_s"] == ns(20)


# --------------------------------------------------------------------------
# zero perturbation: the same answers under a profiler trace
# --------------------------------------------------------------------------
def answers(path, backend, gbt):
    if path == "decide":
        plan = dec.decide_all(layers_of(5), envs_of(40), backend=backend,
                              cost=co.PredictorCost(gbt, DEVICE, EDGE))
        return (plan.splits, plan.total_time_s, plan.device_time_s,
                plan.transfer_time_s, plan.edge_time_s)
    x = np.random.default_rng(6).uniform(0.0, 1.0, (40, gbt.edges_.shape[0]))
    return (lower_predictor(gbt).predict(x, backend=backend),)


@pytest.mark.parametrize("path,backend", [
    ("decide", "numpy"), ("decide", "pallas"),
    ("predict", "jax"), ("predict", "pallas")])
def test_outputs_bit_identical_under_a_profiler_trace(path, backend, gbt,
                                                      tmp_path):
    plain = answers(path, backend, gbt)
    with profiled(tmp_path):
        traced = answers(path, backend, gbt)
    for a, b in zip(plain, traced):
        assert np.asarray(a).dtype == np.asarray(b).dtype
        assert np.array_equal(a, b)


def test_served_tokens_bit_identical_traced(tmp_path):
    from repro.configs import reduced_config
    from repro.serve import Request
    from repro.serve.continuous import ContinuousBatchEngine
    cfg = reduced_config("qwen3-1.7b").replace(dtype="float32")
    lens = (5, 9, 7)

    def serve(obs):
        eng = ContinuousBatchEngine(cfg, slots=2, max_len=48, seed=3,
                                    cost=co.AnalyticCost(), obs=obs)
        rng = np.random.default_rng(7)
        reqs = [Request(rid=i, prompt=rng.integers(
            0, cfg.vocab_size, size=n, dtype=np.int32), max_new_tokens=4,
            arrived_at=i * 0.01) for i, n in enumerate(lens)]
        done = eng.serve(reqs)
        return {r.rid: (r.output.tolist(), r.offload.split,
                        r.admitted_at) for r in done}

    plain = serve(None)
    tracer = Tracer()
    with profiled(tmp_path):
        traced = serve(tracer)
    assert traced == plain
    spans = tracer.all_spans()
    admits = [s for s in spans if (s.track, s.name) == ("serve", "admit")]
    plans = [s for s in spans if (s.track, s.name) == ("serve", "plan")]
    assert sorted(s.tid for s in admits) == [0, 1, 2]
    assert all(set(s.args) == {"slot"} for s in admits)
    assert {s.tid: s.args["split"] for s in plans} == \
        {rid: v[1] for rid, v in plain.items()}
    decodes = [s for s in spans if (s.track, s.name) == ("serve", "decode")]
    prof = idle.load(str(tmp_path))
    names = [s[0] for s in prof.spans]
    assert names.count("serve/admit") == len(lens)
    assert names.count("serve/decode") == len(decodes) > 0
    for phase in ("plan", "prefill", "splice"):
        mine = [s for s in prof.spans if s[0] == f"serve/{phase}"]
        assert len(mine) == len(lens)
        assert all(any(inside(s, a) for a in prof.spans
                       if a[0] == "serve/admit") for s in mine)
