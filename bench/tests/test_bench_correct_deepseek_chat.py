"""deepseek-v2-lite.chat, cut to three layers of width 128 on the CPU (the
dense one and two MoE layers holding 8 of 64 experts): sound runs are
correct; the control (float8 weights, bfloat16 planner) reads far above
them; a run with the model step or the planner broken underneath reads
false."""
from __future__ import annotations

import shutil

import pytest

import small_cells  # first: puts bench/ on the path
import faults  # noqa: E402

CELL = "deepseek-v2-lite.chat"


def small():
    """The cut cell, its prompts three lengths as the traffic's three
    shares (512, 1024, 2048 at full size)."""
    cell = small_cells.small(CELL)
    cell.traffic["prompt_lens"] = [16, 32, 64]
    return cell


def test_sound_run_is_correct():
    out = small_cells.run_small(small(), 2.0)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0


def test_control_separates_from_sound_runs():
    # at three layers of width 128 the logit gaps are smaller than at the
    # published widths, so the limit set from chip readings at full size
    # does not apply here; the control still reads far above the program
    cell = small()
    ctx, st, driver = small_cells.driven(cell, 2.0)
    try:
        sound = driver.readings(ctx, st)
        low = driver.control(ctx, st)
        assert not small_cells.failed_limits(cell, sound)
        assert low["token_gap_mean"] > 3 * sound["token_gap_mean"]
        assert low["token_gap_mean"] > 1e-3
    finally:
        shutil.rmtree(ctx.tmpdir, ignore_errors=True)


MODEL_FAULTS = sorted(set(faults.faults_of(CELL)) - {"planner"})


@pytest.mark.parametrize("fault", MODEL_FAULTS)
def test_broken_model_step_reads_false(monkeypatch, fault):
    faults.plant(CELL, fault, monkeypatch.setattr)
    out = small_cells.run_small(small(), 2.0)
    assert not out["correct"], out["checks"]


def test_broken_planner_reads_false(monkeypatch):
    faults.plant(CELL, "planner", monkeypatch.setattr)
    out = small_cells.run_small(small(), 2.0)
    assert not out["correct"], out["checks"]
