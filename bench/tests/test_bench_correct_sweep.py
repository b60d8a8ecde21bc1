"""edge-metro.sweep, cut small on the CPU: sound runs are correct; the
control (the reference in bfloat16 in the program's place) fails a
limit; a run with the decide kernel broken underneath reads false."""
from __future__ import annotations

import shutil

import numpy as np
import pytest

import small_cells  # first: puts bench/ on the path
import faults  # noqa: E402

CELL = "edge-metro.sweep"


def test_sound_run_is_correct():
    out = small_cells.run_small(small_cells.small(CELL))
    assert out["correct"], out["checks"]
    assert out["metrics"]["decisions_per_s"]["value"] > 0


def test_control_fails_a_limit():
    cell = small_cells.small(CELL)
    ctx, st, driver = small_cells.driven(cell)
    try:
        assert not small_cells.failed_limits(cell, driver.readings(ctx, st))
        assert small_cells.failed_limits(cell, driver.control(ctx, st))
    finally:
        shutil.rmtree(ctx.tmpdir, ignore_errors=True)


@pytest.mark.parametrize("fault", sorted(faults.faults_of(CELL)))
def test_broken_kernel_reads_false(monkeypatch, fault):
    faults.plant(CELL, fault, monkeypatch.setattr)
    out = small_cells.run_small(small_cells.small(CELL))
    assert not out["correct"], out["checks"]
    assert np.isfinite(out["metrics"]["decisions_per_s"]["value"])


def test_cut_cell_has_users_whose_best_split_is_not_0():
    # a constant answer (split 0) must be wrong for some users of the
    # first call of the window, or the faults above could pass unseen
    cell = small_cells.small(CELL)
    ctx, st, driver = small_cells.driven(cell)
    try:
        splits = driver.call(ctx, st, 0, 0).splits
        half = len(splits) // 2
        assert np.count_nonzero(splits[half:]) > 0
    finally:
        shutil.rmtree(ctx.tmpdir, ignore_errors=True)
