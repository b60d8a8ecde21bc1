#!/usr/bin/env python3
"""Readings that set a cell's correctness limits, on the chip.

    python bench/controls.py --workload <cell> --seeds 12 --seconds 8

For each seed the cell is built by the driver's own set-up, driven for
``--seconds`` through its timed path, its state released as a run
releases it, and its compared numbers are read twice: from the program's answers, and
from the control, the reference computed one precision below the
configuration's and put in the program's place.  One JSON line per
seed.  The benchmark's own runs never run the control.
"""
from __future__ import annotations

import argparse
import gc
import json
import shutil
import sys
import time

import run
from common import Spans, load_json


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--base", type=int, default=7_000_000_000)
    ap.add_argument("--seconds", type=float, default=8.0)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(run.ROOT / "src"))
    import jax
    if jax.devices()[0].platform != "tpu":
        print("controls: needs a TPU", file=sys.stderr)
        return 3
    from peaks import peaks
    run.use_compile_cache()
    cell = run.Cell(load_json(run.ROOT / "BENCHMARK.json"), args.workload)
    driver = cell.driver()
    for k in range(args.seeds):
        seed = args.base + 1000 * k
        ctx = run.Ctx(cell, seed, peaks(jax.devices()[0].device_kind),
                      Spans())
        t0 = time.perf_counter()
        try:
            st = driver.setup(ctx)
            res = driver.window(ctx, st, args.seconds)
            driver.release(st)
            line = {"seed": seed, "attempted": res["attempted"],
                    "failed": res["failed"],
                    "program": driver.readings(ctx, st),
                    "control": driver.control(ctx, st),
                    "wall_s": time.perf_counter() - t0}
        finally:
            shutil.rmtree(ctx.tmpdir, ignore_errors=True)
        print(json.dumps(line), flush=True)
        del st                  # this seed's state goes before the next's
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
