"""Cells of the benchmark cut to a size the CPU test run can hold, for
the tests of the harness, the controls and the planted faults."""
from __future__ import annotations

import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
for p in (str(BENCH), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import run  # noqa: E402
from common import load_json  # noqa: E402
from peaks import PEAKS  # noqa: E402

PEAK = PEAKS["TPU v5 lite"]
SEED = 2**31 + 977


def small(name: str, spec: dict | None = None) -> run.Cell:
    """The named cell with its sizes cut: 4096 users on links ten times
    slower (the ensemble at its full shape), 4096-row batches through an
    8-tree ensemble of depth 6, days of 1400 tasks on 16 nodes, a served
    model cut to the keys its configuration module's ``small(m)`` returns,
    on 4 slots.

    The full sweep's 2^20 users hold thousands a call whose best split
    is not 0; at the full cell's links 4096 users hold a handful, in one
    app alone, and an answer left at zeros would pass unseen.  The
    slower links give every app but the model's some."""
    cell = run.Cell(spec or load_json(ROOT / "BENCHMARK.json"), name)
    cfg, tr = cell.cfg, cell.traffic
    if tr["driver"] == "sweep":
        cfg["users"] = 4096
        tr["link_bw_median"] /= 10
    if tr["driver"] == "catalog":
        cfg["predictor"].update(n_trees=8, max_depth=6, max_nodes=63,
                                train_layers=200)
        tr.update(rows=4096, check_rows=512)
    if tr["driver"] == "day":
        cfg["fleet"]["nodes"] = 16
        tr.update(days=2, diurnal_tasks=700, burst_tasks=700, check_days=1)
    if tr["driver"] == "serve":
        cfg["model"].update(cell.config_module().small(cfg["model"]))
        cfg["serving"].update(slots=4, max_len=80)
        tr.update(prompt_lens=[8, 16, 32, 64], answer_median=6,
                  answer_min=2, answer_max=15, rate_per_s=4.0,
                  check_requests=4)
    return cell


def run_small(cell: run.Cell, seconds: float = 1.0) -> dict:
    """One run of the cell past the harness's look for a chip."""
    import jax
    return run.run_cell(cell, SEED, seconds, False, jax.devices(), PEAK,
                        "")


def driven(cell: run.Cell, seconds: float = 1.0):
    """``(ctx, state)`` of a cell driven through its window, for reading
    the program's numbers and the control's."""
    from common import Spans
    ctx = run.Ctx(cell, SEED, PEAK, Spans())
    driver = cell.driver()
    st = driver.setup(ctx)
    driver.window(ctx, st, seconds)
    return ctx, st, driver


def failed_limits(cell: run.Cell, readings: dict) -> list[str]:
    return [n for n, v in readings.items()
            if n in cell.limits and not v <= cell.limits[n]]
