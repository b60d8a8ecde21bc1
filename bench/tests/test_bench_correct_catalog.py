"""edge-metro.catalog, cut small on the CPU: sound runs are correct; the
control (the reference's tree walk in bfloat16) fails the limit; a run
with the tree kernel broken underneath reads false."""
from __future__ import annotations

import shutil

import pytest

import small_cells  # first: puts bench/ on the path
import faults  # noqa: E402

CELL = "edge-metro.catalog"


def test_sound_run_is_correct():
    out = small_cells.run_small(small_cells.small(CELL))
    assert out["correct"], out["checks"]
    assert out["metrics"]["predictions_per_s"]["value"] > 0


def test_control_fails_the_limit():
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
