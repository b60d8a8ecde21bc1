#!/usr/bin/env python3
"""The serving cell's knee, on the chip: one set-up, then one window at
each offered rate.

    python bench/knee.py --workload minitron-4b.chat --seconds 30 --rates 2,3,4

For each rate: requests offered and completed, completions per second
of the window, latency p50 and p95 (due to completion), and how long
the queue took to drain after the window closed.  The knee is the
highest rate the engine keeps up with: it completes what is offered,
and the drain stays within one long answer.
"""
from __future__ import annotations

import argparse
import json
import sys


import run
from common import Spans, load_json


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=9_000_000_001)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(run.ROOT / "src"))
    import jax
    if jax.devices()[0].platform != "tpu":
        print("knee: needs a TPU", file=sys.stderr)
        return 3
    from peaks import peaks
    run.use_compile_cache()
    cell = run.Cell(load_json(run.ROOT / "BENCHMARK.json"), args.workload)
    driver = cell.driver()
    ctx = run.Ctx(cell, args.seed, peaks(jax.devices()[0].device_kind),
                  Spans())
    st = driver.setup(ctx)
    for rate in (float(r) for r in args.rates.split(",")):
        res = driver.window(ctx, st, args.seconds, rate=rate)
        info = res["info"]
        done_in = sum(1 for r in st.reqs if r.rid in st.finished
                      and st.finished[r.rid] <= args.seconds)
        print(json.dumps({"rate": rate, "offered": info["requests"],
                          "completed_in_window": done_in,
                          "completed_per_s": done_in / args.seconds,
                          "p50_s": info["p50_s"], "p95_s": info["p95_s"],
                          "drain_s": info["drained_s"] - args.seconds,
                          "decode_step_ms": 1e3 * args.seconds
                          / max(info["steps_in_window"], 1)}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
