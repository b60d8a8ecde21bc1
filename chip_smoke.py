#!/usr/bin/env python3
"""Chip smoke: the system's main path, profile -> predict -> decide ->
serve, once on a TPU through the repository's own entry points, each
phase checked against its reference.

    python chip_smoke.py               # one chip: every phase
    python chip_smoke.py --four-chips  # four chips: the sharded decide only

Phases, all in this one process (nothing here starts a child process):

  profile  Table-I workloads measured by ``profile_workload``; each
           record must name the chip it ran on.
  predict  a GBT layer-time predictor, lowered to node arrays and run by
           the Pallas tree kernel at 65536 rows, against
           ``GBTRegressor.predict`` on the host.
  decide   ``decide_all`` with that predictor as its cost, 16384 envs by
           L = 64 and L = 1024, on ``backend="pallas"`` and ``"jax"``,
           against ``backend="numpy"``.
  fleet    ``simulate_stream(engine="fleet")`` over a seeded diurnal +
           MMPP day whose arrivals all reach the jitted placement scan,
           against ``engine="event"``.
  serve    ``ContinuousBatchEngine`` at the full width of qwen3-1.7b in
           bf16, re-planning every admission on the Pallas decide
           kernel; its greedy tokens against the teacher-forced argmax
           of ``transformer.forward``.

``--four-chips`` runs ``decide_all_sharded`` over a (2, 2) mesh against
the one-chip ``backend="jax"`` result and numpy, and nothing else.

Each phase prints one JSON line with its checks, its wall time, its
compile time (trace + lower + compile, summed from ``jax.monitoring``)
and the device's ``peak_bytes_in_use`` so far.  The last line is
``{"ok": true, "device": {...}}``.  Without a TPU, or without the
repository beside it, the script exits non-zero before any phase and
prints no result; a failed check raises and exits non-zero.
"""
from __future__ import annotations

import argparse
import functools
import json
import sys
import time
from pathlib import Path

import numpy as np

SRC = Path(__file__).resolve().parent / "src"
if str(SRC) not in sys.path:
    sys.path.insert(0, str(SRC))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro import sim  # noqa: E402
from repro.core import costs as co  # noqa: E402
from repro.core import decisions as dec  # noqa: E402
from repro.core import offload as off  # noqa: E402
from repro.core import scheduler as sch  # noqa: E402
from repro.core.predictors import GBTRegressor  # noqa: E402
from repro.core.profiler import profile_workload  # noqa: E402
from repro.core.workloads import WorkloadConfig  # noqa: E402
from repro.hw import (ALL_DEVICES, EDGE_DEVICES, get_device,  # noqa: E402
                      local_device_spec)
from repro.oracle import lower_predictor  # noqa: E402
from repro.x64 import x64  # noqa: E402

SEED = 0
#: the decide sweep's device/edge pair (the oracle tests' pair)
DEVICE, EDGE = "pi5-arm", "edge-server-a100"
#: f32 Pallas kernels against the f64 host: the tests' pins (the tree
#: kernel's absolute term is widened to its f32 bound, phase_predict)
PALLAS_RTOL, PALLAS_ATOL = 1e-4, 1e-7
#: f64 on a chip without native f64 (XLA emulates it): results must be
#: bitwise equal to numpy or, failing that, within this relative error
F64_RTOL = 1e-9
#: greedy-token check: a position whose reference top-2 logit gap is at
#: most this many bf16 ulps of the top logit is a near-tie, exempt
NEAR_TIE_ULPS = 8

PROFILE_WORKLOADS = (
    WorkloadConfig("cnn", 0, epochs=5, optimiser="adam", lr=1e-3,
                   batch_size=64),
    WorkloadConfig("cnn", 2, epochs=5, optimiser="sgd", lr=1e-2,
                   batch_size=64),
    WorkloadConfig("mlp", 2, epochs=5, optimiser="rmsprop", lr=1e-3,
                   batch_size=128),
)


class SmokeFailure(RuntimeError):
    """A phase's result disagreed with its reference."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


class CompileClock:
    """Seconds JAX spent tracing, lowering and compiling, summed."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if event in self.EVENTS:
            self.seconds += duration


def run_phase(name: str, fn, clock: CompileClock) -> dict:
    """Run one phase; print its JSON line; return its result."""
    c0, t0 = clock.seconds, time.perf_counter()
    out = fn()
    line = {"phase": name, "ok": True,
            "wall_s": time.perf_counter() - t0,
            "compile_s": clock.seconds - c0,
            "peak_bytes_in_use": (jax.devices()[0].memory_stats()
                                  or {}).get("peak_bytes_in_use"),
            **out}
    print(json.dumps(line, default=float), flush=True)
    return out


def assert_compiled(fn, *shapes, **static) -> None:
    """The kernel call as the path makes it (``interpret`` left to its
    default) compiles to a Mosaic custom call, not the interpreter."""
    text = jax.jit(functools.partial(fn, **static)).lower(
        *shapes).compile().as_text()
    check("tpu_custom_call" in text,
          f"{fn.__name__} did not compile to a tpu_custom_call")


def random_layers(rng, n: int) -> list:
    return [off.LayerCost(f"l{i}", flops=float(rng.uniform(1e8, 1e11)),
                          act_bytes=float(rng.uniform(1e3, 1e7)))
            for i in range(n)]


def layer_rows(layers) -> tuple[np.ndarray, np.ndarray]:
    """Per-layer feature rows over every known device, with the
    roofline layer time as the target."""
    specs = list(ALL_DEVICES.values())
    x = np.concatenate([co.default_layer_features(layers, s)
                        for s in specs])
    y = np.concatenate([[off.layer_time(lc.flops, s) for lc in layers]
                        for s in specs])
    return x, y


def fit_predictor(n_layers: int, n_trees: int, max_depth: int
                  ) -> GBTRegressor:
    x, y = layer_rows(random_layers(np.random.default_rng(SEED), n_layers))
    return GBTRegressor(n_trees=n_trees, max_depth=max_depth,
                        subsample=0.9, seed=SEED).fit(x, y)


# --------------------------------------------------------------------------
# phases
# --------------------------------------------------------------------------
def phase_profile(workloads, max_steps: int) -> dict:
    spec = local_device_spec()
    recs = [profile_workload(wc, measure=True, max_steps=max_steps,
                             seed=SEED + i)
            for i, wc in enumerate(workloads)]
    for r in recs:
        check(r.device == spec.name and r.hardware == spec.as_features(),
              f"{r.label} names {r.device!r}, ran on {spec.name!r}")
        check(r.flops_per_step > 0 and r.step_time_s > 0
              and np.isfinite(r.total_time_s) and np.isfinite(r.final_loss),
              f"{r.label}: degenerate profile")
    return {"device": spec.name,
            "records": [{"label": r.label, "step_time_s": r.step_time_s,
                         "flops_per_step": r.flops_per_step,
                         "final_loss": r.final_loss} for r in recs]}


def phase_predict(gbt: GBTRegressor, n_rows: int, on_chip: bool) -> dict:
    n_specs = len(ALL_DEVICES)
    layers = random_layers(np.random.default_rng(SEED + 1),
                           -(-n_rows // n_specs))
    x = layer_rows(layers)[0][:n_rows]
    host = gbt.predict(x)
    lowered = lower_predictor(gbt)
    a = lowered.arrays[0]
    got = lowered.predict(x, backend="pallas")
    # the kernel sums T leaf values in f32: its error scales with the
    # summands, not with a result that cancels to near zero, so the
    # absolute term is the f32 bound T * 2**-24 * sum_t max|v_t|
    atol = a.n_trees * 2.0 ** -24 * np.abs(
        a.learning_rate * a.value).max(axis=1).sum()
    np.testing.assert_allclose(got, host, rtol=PALLAS_RTOL, atol=atol)
    err = np.abs(got - host)
    f64 = lowered.predict(x, backend="jax")
    np.testing.assert_allclose(f64, host, rtol=F64_RTOL, atol=1e-15)
    if on_chip:
        from repro.kernels.tree_predict.kernel import tree_predict_kernel
        nodes = [jax.ShapeDtypeStruct(a.feature.shape, jnp.int32)] * 4
        assert_compiled(
            tree_predict_kernel,
            jax.ShapeDtypeStruct((n_rows, x.shape[1]), jnp.int32), *nodes,
            jax.ShapeDtypeStruct(a.feature.shape, jnp.float32),
            max_depth=a.max_depth)
    return {"rows": n_rows, "trees": a.n_trees, "max_nodes": a.max_nodes,
            "pallas_atol": atol, "pallas_max_abs_err": float(err.max()),
            "rows_beyond_test_pin": int(np.sum(
                err > PALLAS_RTOL * np.abs(host) + PALLAS_ATOL)),
            "jax_f64_bitwise": bool(np.array_equal(f64, host))}


def _compare_plans(ref, got, rtol: float, atol: float) -> dict:
    """Splits agree except at near-ties, where the split ``got`` chose
    costs at most ``rtol`` more than the optimum; totals within ``rtol``."""
    np.testing.assert_allclose(got.total_time_s, ref.total_time_s,
                               rtol=rtol, atol=atol)
    return {"near_ties": int(np.sum(ref.splits != got.splits)),
            "bitwise": bool(np.array_equal(ref.splits, got.splits)
                            and np.array_equal(ref.total_time_s,
                                               got.total_time_s))}


def phase_decide(gbt: GBTRegressor, n_envs: int, layer_counts,
                 on_chip: bool) -> dict:
    device, edge = get_device(DEVICE), get_device(EDGE)
    envs = dec.make_envs(device, edge,
                         link_bw=np.geomspace(1e5, 1e10, n_envs),
                         input_bytes=1e5)
    rng = np.random.default_rng(SEED + 2)
    out = {"envs": n_envs}
    for n_layers in layer_counts:
        layers = random_layers(rng, n_layers)
        plans, walls = {}, {}
        for backend in ("numpy", "pallas", "jax"):
            t0 = time.perf_counter()
            plans[backend] = dec.decide_all(
                layers, envs, cost=co.PredictorCost(gbt, device, edge),
                backend=backend)
            walls[backend] = time.perf_counter() - t0
        ref = plans["numpy"]
        out[f"L{n_layers}"] = {
            "pallas": _compare_plans(ref, plans["pallas"], PALLAS_RTOL,
                                     1e-12),
            "jax": _compare_plans(ref, plans["jax"], F64_RTOL, 0.0),
            "backend_wall_s": walls}
        if on_chip:
            from repro.kernels.decide_split.kernel import (
                SPEC_LEN, decide_split_kernel)
            f32 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.float32)
            assert_compiled(decide_split_kernel,
                            *[f32((n_layers + 1,))] * 3,
                            *[f32((n_envs,))] * 7, f32((SPEC_LEN,)),
                            block_e=256, block_s=128)
    return out


def fleet_day(n_nodes: int, horizon_s: float, seed: int):
    """Tasks, arrivals, nodes and link factory of one seeded day: a
    diurnal tide with MMPP bursts on top (distinct arrival instants, so
    every batch is a singleton and the whole day is one scan run)."""
    arrivals = np.sort(np.concatenate([
        sim.diurnal_arrivals(0.1, horizon=horizon_s, amplitude=0.8,
                             period_s=horizon_s, seed=seed),
        sim.mmpp_arrivals([0.0, 1.0], [horizon_s / 16, horizon_s / 144],
                          horizon=horizon_s, seed=seed + 1)]))
    rng = np.random.default_rng(seed + 2)
    tasks = [sch.Task(f"t{i}", flops=float(rng.uniform(1e9, 5e11)),
                      input_bytes=float(rng.uniform(1e4, 1e7)))
             for i in range(len(arrivals))]
    specs = list(EDGE_DEVICES.values())
    nodes = [sch.Node(specs[j % len(specs)]) for j in range(n_nodes)]

    def links():
        return sim.ClusterLinks([
            sim.DiurnalLink(20e6 + 2e6 * j, amplitude=0.6,
                            period_s=horizon_s, noise_sigma=0.2,
                            seed=seed + 100 + j) for j in range(n_nodes)])
    return tasks, arrivals, nodes, links


def phase_fleet(n_nodes: int, horizon_s: float) -> dict:
    from repro.sim import fleet
    tasks, arrivals, nodes, links = fleet_day(n_nodes, horizon_s, SEED)
    check(len(np.unique(arrivals)) == len(arrivals) >= fleet._SCAN_MIN,
          "the day must be one singleton run of at least _SCAN_MIN")
    tels, walls = {}, {}
    for engine in ("event", "fleet"):
        t0 = time.perf_counter()
        tels[engine] = sim.simulate_stream(
            tasks, arrivals, nodes, policy="min_min", links=links(),
            link_update_dt=horizon_s / 288, engine=engine)
        walls[engine] = time.perf_counter() - t0
    check(n_nodes in fleet._SCAN_FNS, "the jitted placement scan never ran")
    ev, fl = tels["event"].records, tels["fleet"].records
    check(len(ev) == len(fl) == len(tasks), "tasks lost")
    check([(r.name, r.node_id) for r in ev] == [(r.name, r.node_id)
                                                for r in fl],
          "fleet and event engines placed tasks differently")
    times = np.asarray([(r.started_s, r.finished_s) for r in ev])
    times_fl = np.asarray([(r.started_s, r.finished_s) for r in fl])
    np.testing.assert_allclose(times_fl, times, rtol=F64_RTOL, atol=0.0)
    return {"tasks": len(tasks), "nodes": n_nodes,
            "bitwise": bool(np.array_equal(times, times_fl)),
            "engine_wall_s": walls}


def teacher_top2(cfg):
    """Jitted ``(values, indices)`` of the top-2 logits at every position
    of a teacher-forced ``transformer.forward``."""
    from repro.models import transformer

    def fn(params, tokens):
        logits, _ = transformer.forward(params, {"tokens": tokens}, cfg,
                                        impl="naive")
        return jax.lax.top_k(logits.astype(jnp.float32), 2)
    return jax.jit(fn)


def near_tie(gap: float, top: float) -> bool:
    """``gap`` within ``NEAR_TIE_ULPS`` bf16 ulps (8 significant bits)
    of the top logit."""
    ulp = 2.0 ** (np.floor(np.log2(max(abs(top), 2.0 ** -14))) - 7)
    return gap <= NEAR_TIE_ULPS * ulp


def phase_serve(cfg, gbt: GBTRegressor, *, slots: int, prompt_len: int,
                max_new, on_chip: bool) -> dict:
    from repro.serve import ContinuousBatchEngine, Request
    cost = co.PredictorCost(gbt, get_device("jetson-orin-nano"),
                            get_device("edge-server-a100"))
    engine = ContinuousBatchEngine(
        cfg, slots=slots, max_len=prompt_len + max(max_new) + 8, seed=SEED,
        cost=cost, decision_backend="pallas")
    rng = np.random.default_rng(SEED + 3)
    reqs = [Request(rid=i, prompt=rng.integers(0, cfg.vocab_size,
                                               size=prompt_len,
                                               dtype=np.int32),
                    max_new_tokens=n, arrived_at=i * 1e-3)
            for i, n in enumerate(max_new)]
    t0 = time.perf_counter()
    done = engine.serve(reqs)
    serve_s = time.perf_counter() - t0
    check(sorted(r.rid for r in done) == list(range(len(reqs))),
          "not every request completed")
    check(all(r._written == r.max_new_tokens and r.offload is not None
              for r in reqs), "a request stopped short or was not planned")
    check(engine.replans == len(reqs), "an admission skipped re-planning")

    seq_len = prompt_len + max(max_new) - 1
    tokens = np.zeros((len(reqs), seq_len), np.int32)
    for i, r in enumerate(reqs):
        seq = np.concatenate([r.prompt, r.output[:-1]])
        tokens[i, :len(seq)] = seq        # causal: the tail padding is unseen
    vals, idx = (np.asarray(a) for a in
                 teacher_top2(cfg)(engine.params, jnp.asarray(tokens)))
    positions = exempt = 0
    exempt_gaps = []
    for i, r in enumerate(reqs):
        for k, tok in enumerate(r.output):
            p = prompt_len - 1 + k
            positions += 1
            if idx[i, p, 0] == tok:
                continue
            gap = float(vals[i, p, 0] - vals[i, p, 1])
            check(near_tie(gap, float(vals[i, p, 0])),
                  f"request {r.rid} token {k}: engine {tok}, reference "
                  f"{idx[i, p, 0]} with top-2 gap {gap}")
            exempt += 1
            exempt_gaps.append(gap)
    if on_chip:
        from repro.kernels.decide_split.kernel import (SPEC_LEN,
                                                       decide_split_kernel)
        f32 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.float32)
        n_split = cfg.num_layers + 1
        assert_compiled(decide_split_kernel, *[f32((n_split,))] * 3,
                        *[f32((1,))] * 7, f32((SPEC_LEN,)),
                        block_e=256, block_s=128)
    return {"arch": cfg.name, "dtype": cfg.dtype, "requests": len(reqs),
            "slots": slots, "prompt_len": prompt_len,
            "tokens_out": engine.tokens_out, "decode_steps": engine.steps,
            "serve_s": serve_s, "positions": positions,
            "near_tie_exempt": exempt, "exempt_gaps": exempt_gaps}


def phase_sharded(n_envs: int, n_layers: int, n_devices: int) -> dict:
    """``decide_all_sharded`` over a (2, n/2) mesh against the one-chip
    jax backend and numpy; the compiled program's env arguments and
    results must be split over every device of the mesh."""
    from repro.launch.mesh import make_debug_mesh
    from repro.sim.fleet import sharded_latency_fn
    mesh = make_debug_mesh(n_devices)
    rng = np.random.default_rng(SEED + 4)
    layers = random_layers(rng, n_layers)
    envs = dec.make_envs(get_device(DEVICE), get_device(EDGE),
                         link_bw=np.geomspace(1e5, 1e10, n_envs),
                         input_bytes=1e5)
    check(n_envs % n_devices != 0, "the env count must force pad and trim")
    ref = dec.decide_all(layers, envs)
    one = dec.decide_all(layers, envs, backend="jax")
    got = sim.decide_all_sharded(layers, envs, mesh=mesh)
    n_pad = -(-n_envs // n_devices) * n_devices
    with x64():
        row = jax.ShapeDtypeStruct((n_layers,), jnp.float64)
        env = jax.ShapeDtypeStruct((n_pad,), jnp.float64)
        compiled = sharded_latency_fn(mesh).lower(
            row, row, *[env] * 5).compile()
    in_sh = compiled.input_shardings[0][2:]
    out_sh = compiled.output_shardings
    for s in (*in_sh, *out_sh):
        check(len(s.device_set) == n_devices
              and s.shard_shape((n_pad,)) == (n_pad // n_devices,),
              f"env axis not split over {n_devices} devices: {s}")
    one_vs_ref = _compare_plans(ref, one, F64_RTOL, 0.0)
    sharded_vs_ref = _compare_plans(ref, got, F64_RTOL, 0.0)
    check(np.array_equal(one.splits, got.splits),
          "sharded and one-chip splits differ")
    return {"envs": n_envs, "padded": n_pad, "layers": n_layers,
            "mesh": dict(mesh.shape), "jax_vs_numpy": one_vs_ref,
            "sharded_vs_numpy": sharded_vs_ref,
            "sharded_vs_jax_bitwise": bool(
                np.array_equal(one.total_time_s, got.total_time_s))}


# --------------------------------------------------------------------------
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded decide over four chips")
    args = ap.parse_args(argv)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX's first device is "
              f"{devices[0].platform!r}", file=sys.stderr)
        return 1
    from repro.launch.cache import use_compile_cache
    print(json.dumps({"compile_cache": use_compile_cache(),
                      "jax": jax.__version__}), flush=True)
    clock = CompileClock()
    if args.four_chips:
        check(len(devices) == 4, f"--four-chips needs 4 devices, "
              f"JAX sees {len(devices)}")
        run_phase("sharded_decide",
                  lambda: phase_sharded(16386, 64, 4), clock)
    else:
        from repro.configs import get_config
        run_phase("profile",
                  lambda: phase_profile(PROFILE_WORKLOADS, 16), clock)
        gbt = fit_predictor(1000, n_trees=100, max_depth=7)
        run_phase("predict", lambda: phase_predict(gbt, 65536, True), clock)
        run_phase("decide",
                  lambda: phase_decide(gbt, 16384, (64, 1024), True), clock)
        run_phase("fleet", lambda: phase_fleet(64, 86400.0), clock)
        run_phase("serve", lambda: phase_serve(
            get_config("qwen3-1.7b"), gbt, slots=4, prompt_len=256,
            max_new=(16, 32, 24, 20, 28, 12), on_chip=True), clock)
    dev = devices[0]
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
