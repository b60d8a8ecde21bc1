#!/usr/bin/env python3
"""Chip benchmark: one cell of ``BENCHMARK.json`` per process.

  python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything is found by name.  The cell (``BENCHMARK.json`` ``workloads``)
names a configuration and a traffic mix.  The configuration is
``configs/<config>.json`` (its sizes) beside ``configs/<config>.py``
(its builder from the seed and its plain reference).  The traffic mix is
``traffic/<traffic>.json``, whose ``driver`` names the general generator
in ``drivers/<driver>.py`` that reads it.  Each per-layer metric is read
by ``metrics/<metric>.py``, and the limits of the cell's correctness
check are in ``limits/<cell>.json``.

The run finds the chip (no TPU, no result: exit code 3), builds the
cell from ``--seed`` and warms up its shapes (set-up), measures for
``--seconds``, checks the window's answers against the reference, and
prints one JSON object as its last line of standard output.  With
``--trace 1`` a profiler trace of the window gives the per-layer
metrics in place of the end-to-end ones.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))

from common import (CompileClock, Spans, TraceWindow, load_json,  # noqa: E402
                    load_module)


class Cell:
    """What one run knows about its cell."""

    def __init__(self, spec: dict, name: str, root: Path = ROOT):
        cells = {w["name"]: w for w in spec["workloads"]}
        if name not in cells:
            raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
        self.name = name
        self.entry = cells[name]
        configs = {c["name"]: c for c in spec["configs"]}
        self.config_entry = configs[self.entry["config"]]
        cfg_file = root / self.config_entry["file"]
        self.cfg = load_json(cfg_file)
        self.cfg_path = cfg_file.with_suffix(".py")
        self.traffic = load_json(BENCH / "traffic"
                                 / f"{self.entry['traffic']}.json")
        self.limits = load_json(BENCH / "limits" / f"{name}.json")
        self.end_to_end = [m for m in spec["end_to_end"]
                           if name in m.get("workloads", [name])]
        self.per_layer = [m for m in spec["per_layer"]
                          if name in m.get("workloads", [name])]

    def config_module(self):
        return load_module(self.cfg_path)

    def driver(self):
        return load_module(BENCH / "drivers"
                           / f"{self.traffic['driver']}.py")


class Ctx:
    """Handed to the driver: the cell, the seed, the peaks, the host
    spans and the traced sub-window."""

    def __init__(self, cell: Cell, seed: int, peak: dict, spans: Spans,
                 trace: TraceWindow | None = None):
        self.cell, self.seed, self.peak = cell, seed, peak
        self.cfg, self.traffic = cell.cfg, cell.traffic
        self.cfg_mod = cell.config_module()
        self.spans = spans
        self.trace = trace or TraceWindow(False, None, 0.0, 0.0, spans)
        self.tmpdir = tempfile.mkdtemp(prefix="bench-")


def use_compile_cache() -> str:
    """JAX's persistent cache where ``JAX_COMPILATION_CACHE_DIR`` says,
    else at the fixed ``.jax_cache/`` of the checkout; every program is
    kept, however quick its compile, so set-up is steady."""
    import jax
    where = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not where:
        where = str(ROOT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", where)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return where


def per_layer_values(cell: Cell, record: dict) -> dict:
    out = {}
    for m in cell.per_layer:
        reader = load_module(BENCH / "metrics" / f"{m['name']}.py")
        v = reader.read(record)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def checks_of(compared) -> tuple[bool, dict]:
    """``[(name, value, limit)]`` -> (all within, the result's key)."""
    ok = all(v == v and v <= lim for _, v, lim in compared)
    return ok, {n: {"value": v, "limit": lim} for n, v, lim in compared}


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, devices,
             peak: dict, cache_dir: str) -> dict:
    """Set-up, window, check and (with ``trace``) the trace's reading of
    one run on ``devices``; returns the result object.  An earlier line
    of standard output gets set-up split into compile and the rest, the
    compiles counted inside the window, and the driver's own counts."""
    clock = CompileClock()
    spans = Spans()
    tr = cell.traffic
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    tw = TraceWindow(trace, trace_dir, float(tr.get("trace_start_s", 0.0)),
                     min(float(tr.get("trace_s", seconds)), seconds), spans)
    ctx = Ctx(cell, seed, peak, spans, tw)
    driver = cell.driver()
    try:
        state = driver.setup(ctx)
        setup_s = time.perf_counter() - T_START
        setup_compile_s, setup_compiles = clock.seconds, clock.compiles
        c0, s0, n0 = clock.compiles, clock.seconds, clock.traces
        res = driver.window(ctx, state, seconds)
        tw.stop()
        mem = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                  for d in devices)
        print(json.dumps({
            "setup_s": setup_s, "setup_compile_s": setup_compile_s,
            "setup_other_s": setup_s - setup_compile_s,
            "setup_compiles": setup_compiles,
            "window_compiles": clock.compiles - c0,
            "window_compile_s": clock.seconds - s0,
            "window_traces": clock.traces - n0, "compile_cache": cache_dir,
            **res.get("info", {})}), flush=True)
        driver.release(state)
        readings = driver.readings(ctx, state)
    finally:
        shutil.rmtree(ctx.tmpdir, ignore_errors=True)
    for n, v in readings.items():
        if n not in cell.limits:
            print(f"reading {n}: {v!r} (not compared)", file=sys.stderr)
    correct, checks = checks_of([(n, v, cell.limits[n])
                                 for n, v in readings.items()
                                 if n in cell.limits])
    dev = {"platform": devices[0].platform, "kind": devices[0].device_kind,
           "count": len(devices), "memory_peak_bytes": int(mem)}
    out = {"correct": correct, "attempted": int(res["attempted"]),
           "failed": int(res["failed"])}
    if trace:
        import trace_reduce
        reduced = trace_reduce.reduce(trace_reduce.load(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
        record = {"cell": cell.name, "cfg": cell.cfg, "traffic": tr,
                  "peak": peak, "trace": reduced,
                  "counters": res["counters"], "window_s": seconds,
                  "traced_s": tw.t1 - tw.t0}
        out["metrics"] = per_layer_values(cell, record)
        dev.update(busy_s=reduced["busy_s"], window_s=reduced["window_s"])
        out["device"] = dev
        out["breakdown"] = {"device_ops": reduced["device_ops"],
                            "idle_gaps": reduced["idle_gaps"]}
        ops = sorted(reduced["per_op"].items(), key=lambda kv: -kv[1])
        print(json.dumps({"idle_by_host": reduced.get("idle_by_host", {}),
                          "ops": ops[:30]}), flush=True)
    else:
        values = {**res["e2e"], "setup_s": setup_s}
        out["metrics"] = {m["name"]: {"value": float(values[m["name"]]),
                                      "unit": m["unit"]}
                          for m in cell.end_to_end}
        out["device"] = dev
    out["checks"] = checks
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        print(f"bench: the system under test is not beside the benchmark "
              f"({ROOT / 'src' / 'repro'} missing)", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    cell = Cell(load_json(ROOT / "BENCHMARK.json"), args.workload)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"bench: needs a TPU; JAX's first device is "
              f"{devices[0].platform!r}", file=sys.stderr)
        return 3
    if len(devices) < cell.entry["chips"]:
        print(f"bench: {args.workload} needs {cell.entry['chips']} chips, "
              f"JAX sees {len(devices)}", file=sys.stderr)
        return 3
    devices = devices[:cell.entry["chips"]]
    from peaks import peaks
    out = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                   devices, peaks(devices[0].device_kind),
                   use_compile_cache())
    for n, c in out["checks"].items():
        print(f"check {n}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
