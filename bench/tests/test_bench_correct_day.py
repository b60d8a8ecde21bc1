"""edge-metro.day, cut to 16 nodes on the CPU: sound runs are correct;
the control (the reference's placement in float32) fails a limit; a run
with the fleet scan broken underneath reads false."""
from __future__ import annotations

import shutil

import numpy as np
import pytest

import small_cells  # first: puts bench/ on the path
import faults  # noqa: E402

CELL = "edge-metro.day"
# the cell's entries, kept out of BENCHMARK.json until its traffic is
# sized from a stated source
ENTRIES = {
    "workloads": [{"name": CELL, "config": "edge-metro", "traffic": "day",
                   "chips": 1, "why": "what-if fleet days"}],
    "end_to_end": [{"name": "sim_tasks_per_s", "unit": "tasks/s",
                    "better": "higher", "bound": 0.05,
                    "source": "host_clock", "workloads": [CELL]}],
    "per_layer": [
        {"name": "fleet.mfu", "unit": "%", "better": "higher",
         "source": "host_clock", "layer": "fleet engine",
         "moves": "sim_tasks_per_s", "workloads": [CELL]},
        {"name": "device_idle.fleet", "unit": "%", "better": "lower",
         "source": "device_trace", "layer": "device",
         "moves": "sim_tasks_per_s", "workloads": [CELL]}],
}


def day_spec() -> dict:
    from common import load_json
    spec = load_json(small_cells.ROOT / "BENCHMARK.json")
    for key, extra in ENTRIES.items():
        spec[key] = spec[key] + extra
    return spec


def small_day():
    return small_cells.small(CELL, day_spec())


def test_sound_run_is_correct():
    out = small_cells.run_small(small_day())
    assert out["correct"], out["checks"]
    assert out["failed"] == 0
    assert out["metrics"]["sim_tasks_per_s"]["value"] > 0


def test_control_fails_a_limit():
    cell = small_day()
    ctx, st, driver = small_cells.driven(cell)
    try:
        assert not small_cells.failed_limits(cell, driver.readings(ctx, st))
        assert small_cells.failed_limits(cell, driver.control(ctx, st))
    finally:
        shutil.rmtree(ctx.tmpdir, ignore_errors=True)


@pytest.mark.parametrize("fault", sorted(faults.faults_of(CELL,
                                                           day_spec())))
def test_broken_scan_reads_false(monkeypatch, fault):
    faults.plant(CELL, fault, monkeypatch.setattr, day_spec())
    out = small_cells.run_small(small_day())
    assert not out["correct"], out["checks"]


def test_days_hold_the_same_work_whatever_the_seed():
    import common
    cell = small_day()
    driver = cell.driver()
    sizes = [driver.make_day(cell.traffic, 16, r)["arrivals"].shape[0]
             for r in common.seed_streams(12345, 3)]
    assert sizes == [1400] * 3
    day = driver.make_day(cell.traffic, 16, common.seed_streams(7, 1)[0])
    assert np.all(np.diff(day["arrivals"]) > 0)
