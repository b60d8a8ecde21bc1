"""repro.obs test lanes.

The tentpole contract is **zero perturbation**: a run with
``obs=Tracer()`` is bit-for-bit identical to the untraced run — records,
summary, counters, and end-of-run link state — in *both* engines, and
the two engines' exports describe the same trace.  The satellites ride
along: a hypothesis property that any set of task lifecycles exports a
well-formed Chrome trace (every ``B`` matched by a LIFO ``E``, children
nested, timestamps monotone per track), the validator's negative cases,
the deferred slab-ingestion paths, the flight recorder, the metrics
registry / Prometheus exposition, and the ``Telemetry`` bridges
(``registry()`` / ``to_prometheus()``, CVaR in ``summary()``).
"""
import json

import numpy as np
import pytest
from hypothesis_shim import given, settings, st

from repro import sim
from repro.core import offload as off
from repro.core import scheduler as sch
from repro.core.workloads import WorkloadConfig
from repro.hw import EDGE_DEVICES, get_device
from repro.obs import (LATENCY_BOUNDARIES, MetricsRegistry, NULL_TRACER,
                       Histogram, NullTracer, Tracer, validate_chrome)

SPECS = list(EDGE_DEVICES.values())


def make_tasks(n, seed=3):
    rng = np.random.default_rng(seed)
    return [sch.Task(f"t{i}", flops=float(rng.uniform(1e9, 5e11)),
                     input_bytes=float(rng.uniform(1e4, 1e7)),
                     deadline_s=float(rng.uniform(0.02, 2.0)))
            for i in range(n)]


def make_nodes(n):
    return [sch.Node(SPECS[j % len(SPECS)]) for j in range(n)]


@pytest.fixture(scope="module")
def cnn_layers():
    wc = WorkloadConfig("cnn", 2, epochs=5, optimiser="adam", lr=1e-3,
                        batch_size=32)
    return off.workload_layer_costs(wc)


def run_stream(engine, obs, cfg, cnn_layers, *, n_tasks=24, n_nodes=3,
               seed=5):
    """One simulate_stream pass (fresh stateful processes every call)
    -> (Telemetry, end-of-run link bandwidths)."""
    tasks = make_tasks(n_tasks, seed=seed)
    arrivals = sim.poisson_arrivals(8.0, n=n_tasks, seed=seed)
    links = sim.ClusterLinks.random_walk(
        [40e6 + 5e6 * j for j in range(n_nodes)], sigma=0.4,
        seed=seed + 100)
    kw = {}
    if cfg == "links_planner":
        kw["split_planner"] = sim.ParetoStreamScheduler()
        kw["split_env"] = sim.DriftingEnv(
            get_device("jetson-orin-nano"),
            get_device("edge-server-a100"),
            sim.TwoStateLink(80e6, 8e6, seed=seed + 7),
            input_bytes=2e6)
        kw["split_layers"] = cnn_layers
    elif cfg == "pools_rtt":
        kw["pools"] = sim.NodePools.uniform(n_nodes, 2)
        kw["rtt"] = sim.WeibullRTT(shape=0.7, scale=0.01, seed=seed + 9)
    else:
        raise ValueError(cfg)
    tel = sim.simulate_stream(tasks, arrivals, make_nodes(n_nodes),
                              policy="min_min", links=links,
                              link_update_dt=0.5, engine=engine,
                              obs=obs, **kw)
    return tel, links.values()


def rec_tuple(r):
    return (r.name, r.arrived_s, r.started_s, r.finished_s, r.node,
            r.node_id, r.deadline_s, r.energy_j, r.split, r.switches)


# --------------------------------------------------------------------------
# tentpole: tracing perturbs nothing, in either engine
# --------------------------------------------------------------------------
@pytest.mark.parametrize("engine", ["event", "fleet"])
@pytest.mark.parametrize("cfg", ["links_planner", "pools_rtt"])
def test_tracing_zero_perturbation(engine, cfg, cnn_layers):
    """obs=Tracer() leaves records, summary, counters, and the drift
    processes' end state bit-for-bit identical to the untraced run —
    and the trace it collected exports clean."""
    tel_off, links_off = run_stream(engine, None, cfg, cnn_layers)
    tracer = Tracer()
    tel_on, links_on = run_stream(engine, tracer, cfg, cnn_layers)
    assert [rec_tuple(r) for r in tel_on.records] \
        == [rec_tuple(r) for r in tel_off.records]
    assert tel_on.summary() == tel_off.summary()
    assert tel_on.counters == tel_off.counters
    np.testing.assert_array_equal(links_on, links_off)
    stats = validate_chrome(tracer.export_chrome(None))
    # every completed task contributes at least sojourn + service
    assert stats["n_spans"] >= 2 * len(tel_on.records)
    assert stats["n_instants"] >= 1                    # replans at least


@pytest.mark.parametrize("cfg", ["links_planner", "pools_rtt"])
def test_traced_event_fleet_equivalence(cfg, cnn_layers):
    """With tracing ON, the event ≡ fleet equivalence still holds, and
    the two engines' traces describe the same run: identical validator
    stats (the fleet's deferred slab ingestion materialises to the same
    spans and instants the event loop emitted one by one)."""
    stats, tels = [], []
    for engine in ("event", "fleet"):
        tracer = Tracer()
        tel, _ = run_stream(engine, tracer, cfg, cnn_layers)
        tels.append(tel)
        stats.append(validate_chrome(tracer.export_chrome(None)))
    assert [rec_tuple(r) for r in tels[0].records] \
        == [rec_tuple(r) for r in tels[1].records]
    assert stats[0] == stats[1]


def test_example_trace_file_roundtrip(tmp_path, cnn_layers):
    """export_chrome(path) writes Perfetto-loadable JSON: traceEvents +
    displayTimeUnit, process_name metadata per track, and the file
    re-validates from disk."""
    tracer = Tracer()
    run_stream("event", tracer, "links_planner", cnn_layers)
    path = str(tmp_path / "trace.json")
    trace = tracer.export_chrome(path)
    assert trace["displayTimeUnit"] == "ms"
    with open(path) as f:
        on_disk = json.load(f)
    assert on_disk == json.loads(json.dumps(trace))    # serialisable
    assert validate_chrome(path) == validate_chrome(trace)
    meta = [e for e in on_disk["traceEvents"] if e["ph"] == "M"]
    assert {e["name"] for e in meta} == {"process_name"}
    names = {e["args"]["name"] for e in meta}
    assert "scheduler" in names
    assert any("@" in n for n in names)          # per-node task tracks


# --------------------------------------------------------------------------
# property: any set of task lifecycles exports well-formed
# --------------------------------------------------------------------------
@given(data=st.data())
@settings(max_examples=60, deadline=None)
def test_exported_lifecycles_well_formed(data):
    """Random lifecycles (arbitrary tracks, waits, services, transfers,
    including zero-length phases) plus out-of-order instants always
    export with every B matched by a LIFO E, children nested inside
    parents, and per-track monotone timestamps."""
    pos = st.floats(0.0, 50.0, allow_nan=False, allow_infinity=False)
    tracer = Tracer()
    n = data.draw(st.integers(1, 30), label="n_tasks")
    for i in range(n):
        track = f"node@{data.draw(st.integers(0, 3))}"
        arrived = data.draw(pos, label=f"arrived{i}")
        wait = data.draw(pos, label=f"wait{i}")
        service = data.draw(pos, label=f"service{i}")
        transfer = data.draw(pos, label=f"transfer{i}")
        tracer.task_spans(track, i, f"t{i}", arrived, arrived + wait,
                          arrived + wait + service + transfer,
                          transfer_s=transfer)
    for k in range(data.draw(st.integers(0, 8), label="n_instants")):
        tracer.instant("scheduler", "replan",
                       data.draw(pos, label=f"ts{k}"))
    stats = validate_chrome(tracer.export_chrome(None))
    assert stats["n_spans"] == len(tracer.all_spans())
    assert stats["n_instants"] == len(tracer.all_instants())


@pytest.mark.parametrize("arrived,wait,service,transfer", [
    (0.0, 14.792538958709294, 0.0, 50.0),     # hypothesis's find
    (0.1, 0.2, 0.0, 0.7),
    (1.0, 0.0, 2.0, 3.0),
])
def test_task_spans_service_never_ends_before_it_starts(arrived, wait,
                                                        service, transfer):
    """``finished - transfer`` can round below ``started`` when the
    service time is 0: the service end is clamped to ``started`` and the
    transfer starts there."""
    tracer = Tracer()
    started = arrived + wait
    finished = started + service + transfer
    tracer.task_spans("node@0", 0, "t0", arrived, started, finished,
                      transfer_s=transfer)
    spans = {s.name: s for s in tracer.all_spans()}
    assert spans["service"].t0 == started
    assert spans["service"].t1 >= started
    assert spans["transfer"].t0 == spans["service"].t1
    assert spans["transfer"].t1 == finished
    validate_chrome(tracer.export_chrome(None))


def test_slab_ingestion_matches_per_event_path():
    """span_arrays / instant_arrays are exactly n deferred task_spans /
    instant calls in column order."""
    cols = dict(
        tracks=["a@0", "b@1", "a@0"], tids=np.array([0, 1, 2]),
        names=["t0", "t1", "t2"],
        arrived_s=np.array([0.0, 0.5, 1.0]),
        started_s=np.array([0.1, 0.5, 1.4]),
        finished_s=np.array([0.9, 0.8, 2.0]))
    batched = Tracer()
    batched.span_arrays(**cols, transfer_s=np.array([0.1, 0.0, 0.2]))
    batched.instant_arrays("scheduler", "replan",
                           np.array([0.0, 0.5]),
                           args_cols={"batch": np.array([2, 1])})
    loop = Tracer()
    for k in range(3):
        loop.task_spans(cols["tracks"][k], int(cols["tids"][k]),
                        cols["names"][k], cols["arrived_s"][k],
                        cols["started_s"][k], cols["finished_s"][k],
                        transfer_s=[0.1, 0.0, 0.2][k])
    for ts, b in ((0.0, 2), (0.5, 1)):
        loop.instant("scheduler", "replan", ts, args={"batch": b})
    # __len__ counts ingested rows while pending (3 lifecycles + 2
    # instants), materialised events once read
    assert len(batched) == 5
    assert batched._pending and not loop._pending
    assert batched.all_spans() == loop.all_spans()
    assert batched.all_instants() == loop.all_instants()
    assert len(batched) == len(loop)


def test_tracer_rejects_malformed_input():
    tracer = Tracer()
    with pytest.raises(ValueError, match="ends before it starts"):
        tracer.span("n", "bad", 2.0, 1.0)
    with pytest.raises(ValueError, match="column started_s"):
        tracer.span_arrays(["a"], [0], ["t0"], [0.0], [0.1, 0.2], [1.0])
    with pytest.raises(ValueError, match="args column"):
        tracer.instant_arrays("s", "replan", [0.0, 1.0],
                              args_cols={"batch": [1]})


def test_export_rejects_partial_overlap():
    tracer = Tracer()
    tracer.span("n", "a", 0.0, 2.0)
    tracer.span("n", "b", 1.0, 3.0)      # same (track, tid): not nested
    with pytest.raises(ValueError, match="partially overlap"):
        tracer.export_chrome(None)


def test_flight_recorder_ring():
    tracer = Tracer(ring=8)
    for k in range(20):
        tracer.instant("s", f"e{k}", float(k))
    assert [e.name for e in tracer.last(64)] \
        == [f"e{k}" for k in range(12, 20)]
    assert [e.name for e in tracer.last(3)] == ["e17", "e18", "e19"]
    assert tracer.last(0) == []


def test_null_tracer_is_inert():
    assert NULL_TRACER.enabled is False
    assert isinstance(NULL_TRACER, NullTracer)
    NULL_TRACER.span("n", "a", 0.0, 1.0)
    NULL_TRACER.instant("n", "a", 0.0)
    NULL_TRACER.task_spans("n", 0, "t", 0.0, 0.0, 1.0)
    NULL_TRACER.span_arrays([], [], [], [], [], [])
    NULL_TRACER.instant_arrays("n", "a", [])
    assert NULL_TRACER.last() == []
    with pytest.raises(ValueError, match="no-op tracer"):
        NULL_TRACER.export_chrome("/tmp/nope.json")


# --------------------------------------------------------------------------
# validator negatives: each well-formedness clause actually bites
# --------------------------------------------------------------------------
def _ev(ph, name, ts, pid=0, tid=0):
    return {"name": name, "ph": ph, "pid": pid, "tid": tid, "ts": ts}


@pytest.mark.parametrize("events,match", [
    ([_ev("E", "a", 1.0)], "no open 'B'"),
    ([_ev("B", "a", 0.0), _ev("B", "b", 1.0), _ev("E", "a", 2.0)],
     "close LIFO"),
    ([_ev("B", "a", 2.0), _ev("E", "a", 1.0)], "backwards"),
    ([_ev("B", "a", 0.0)], "unmatched 'B'"),
    ([_ev("X", "a", 0.0)], "unknown phase"),
    ([_ev("i", "a", 2.0), _ev("i", "b", 1.0)], "backwards"),
])
def test_validator_negatives(events, match):
    with pytest.raises(ValueError, match=match):
        validate_chrome(events)


def test_validator_accepts_nested_and_counts():
    events = [_ev("B", "sojourn", 0.0), _ev("B", "service", 1.0),
              _ev("E", "service", 2.0), _ev("i", "replan", 2.5),
              _ev("E", "sojourn", 3.0),
              _ev("B", "other", 0.0, pid=1)] + [_ev("E", "other", 1.0,
                                                    pid=1)]
    assert validate_chrome(events) == {"n_events": 7, "n_spans": 3,
                                       "n_instants": 1, "n_tracks": 2}


# --------------------------------------------------------------------------
# metrics registry + Prometheus exposition
# --------------------------------------------------------------------------
def test_registry_get_or_create_and_mismatches():
    reg = MetricsRegistry()
    c = reg.counter("req_total", help="requests")
    assert reg.counter("req_total") is c                 # idempotent
    with pytest.raises(TypeError, match="already registered"):
        reg.gauge("req_total")
    reg.histogram("lat", boundaries=(1.0, 2.0))
    with pytest.raises(ValueError, match="boundaries"):
        reg.histogram("lat", boundaries=(1.0, 3.0))
    with pytest.raises(ValueError, match="invalid metric name"):
        reg.counter("bad name!")
    with pytest.raises(ValueError, match="cannot decrease"):
        c.inc(-1)
    with pytest.raises(ValueError, match="strictly increasing"):
        Histogram("h", boundaries=(1.0, 1.0, 2.0))


def test_histogram_buckets_and_percentile():
    h = Histogram("lat_seconds", boundaries=(1.0, 2.0, 5.0))
    h.observe_many([0.5, 1.0, 1.5, 10.0])                # le semantics
    assert h.counts.tolist() == [2, 1, 0, 1]
    assert h.count == 4 and h.sum == pytest.approx(13.0)
    exp = h.expose()
    assert 'lat_seconds_bucket{le="1"} 2' in exp
    assert 'lat_seconds_bucket{le="2"} 3' in exp
    assert 'lat_seconds_bucket{le="+Inf"} 4' in exp
    assert h.percentile_bound(0.5) == 1.0
    # the +Inf bucket answers with the exact observed max, never inf
    assert h.percentile_bound(1.0) == 10.0
    assert h.observed_max == 10.0
    # q below the observed mass clamps to the first observation's bucket
    assert h.percentile_bound(0.0) == 1.0
    # same-boundary merge sums counts and keeps the max
    h2 = Histogram("lat_seconds", boundaries=(1.0, 2.0, 5.0))
    h2.observe_many([3.0, 20.0])
    h.merge(h2)
    assert h.counts.tolist() == [2, 1, 1, 2]
    assert h.count == 6 and h.percentile_bound(1.0) == 20.0
    with pytest.raises(ValueError, match="identical boundaries"):
        h.merge(Histogram("other", boundaries=(1.0, 2.0)))


def test_prometheus_text_and_rows(tmp_path):
    reg = MetricsRegistry()
    reg.counter("tasks_total", help="done").inc(3)
    reg.gauge("energy_joules").set(1.5)
    reg.histogram("sojourn_seconds",
                  boundaries=LATENCY_BOUNDARIES).observe_many(
                      [0.002, 0.3, 4.0])
    text = reg.to_prometheus()
    assert "# HELP tasks_total done" in text
    assert "# TYPE tasks_total counter" in text
    assert "# TYPE sojourn_seconds histogram" in text
    assert "tasks_total 3" in text
    assert "energy_joules 1.5" in text
    assert "sojourn_seconds_count 3" in text
    rows = reg.to_rows("m")
    assert rows[0] == {"name": "m", "energy_joules": 1.5,
                       "tasks_total": 3.0}
    assert rows[1]["name"] == "m_hist_sojourn_seconds"
    assert sum(rows[1]["counts"]) == 3
    path = str(tmp_path / "metrics.json")
    reg.save(path, "m")
    with open(path) as f:
        assert json.load(f) == json.loads(json.dumps(rows))


# --------------------------------------------------------------------------
# Telemetry bridges: registry()/to_prometheus(), cvar95 in summary()
# --------------------------------------------------------------------------
def test_telemetry_registry_bridge(cnn_layers):
    tel, _ = run_stream("event", None, "pools_rtt", cnn_layers,
                        n_tasks=30)
    reg = tel.registry()
    assert reg.get("sim_tasks_completed_total").value == len(tel.records)
    assert reg.get("sim_task_sojourn_seconds").count == len(tel.records)
    for key in tel.counters:
        assert reg.get(f"sim_{key}_total").value == tel.counters[key]
    text = tel.to_prometheus()
    assert "# TYPE sim_tasks_completed_total counter" in text
    assert "sim_task_wait_seconds_bucket" in text

    s = tel.summary()
    assert "cvar95_completion_s" in s
    assert np.isfinite(s["cvar95_completion_s"])
    # CVaR(0.95) is the mean of the worst 5% completions: at least p50
    assert s["cvar95_completion_s"] >= s["p50_completion_s"]
    # to_rows leads with the summary row, then one row per node
    rows = tel.to_rows()
    assert rows[0]["cvar95_completion_s"] == s["cvar95_completion_s"]
    assert len(rows) == 1 + len(tel.utilisation())
    for row in rows[1:]:
        assert {"name", "utilisation", "mean_queue_len"} <= set(row)


# --------------------------------------------------------------------------
# serving engines: wall-clock spans (tier-1 lane — model forward passes)
# --------------------------------------------------------------------------
@pytest.mark.slow
def test_serve_engines_emit_spans():
    from repro.configs import reduced_config
    from repro.serve import Request, ServeEngine
    from repro.serve.continuous import ContinuousBatchEngine
    cfg = reduced_config("qwen3-1.7b").replace(dtype="float32")

    tracer = Tracer()
    engine = ServeEngine(cfg, batch_size=2, max_len=48, obs=tracer)
    prompts = np.tile(np.arange(8, dtype=np.int32)[None], (2, 1))
    engine.generate_batch(prompts, 5)
    spans = tracer.all_spans()
    assert [s.name for s in spans] == ["prefill", "decode"]
    assert all(s.track == "serve_engine" for s in spans)
    assert [i.name for i in tracer.all_instants()] == ["first_token"]
    validate_chrome(tracer.export_chrome(None))

    ctracer = Tracer()
    ceng = ContinuousBatchEngine(cfg, slots=2, max_len=48, seed=3,
                                 obs=ctracer)
    rng = np.random.default_rng(7)
    reqs = [Request(rid=i, prompt=rng.integers(0, cfg.vocab_size, size=n,
                                               dtype=np.int32),
                    max_new_tokens=4, arrived_at=i * 0.01)
            for i, n in enumerate((5, 9, 7))]
    done = ceng.serve(reqs)
    sojourns = [s for s in ctracer.all_spans() if s.name == "sojourn"]
    assert len(sojourns) == len(done)
    admits = [s for s in ctracer.all_spans()
              if (s.track, s.name) == ("serve", "admit")]
    assert sorted(s.tid for s in admits) == [r.rid for r in reqs]
    assert all(s.args == {"slot": s.args["slot"]} for s in admits)
    assert ctracer.all_instants() == []
    validate_chrome(ctracer.export_chrome(None))
