"""Profiling-dataset generation (paper §III-A: ">3,000 runs").

Runs the Table-I grid through the profiler and assembles the tabular
regression dataset.  ``max_steps`` truncates each run (per-step time is
measured, total time extrapolated) so a >100-run grid stays tractable on
this host; benchmarks validate the extrapolation on full runs.

Heterogeneity augmentation: each measured record is re-projected onto the
other edge-device specs analytically (scaled by relative roofline), giving
the multi-hardware dataset of the paper's roadmap without owning the
physical devices. Augmented rows are flagged ``measured=False``.
"""
from __future__ import annotations

import dataclasses
import json
import time
from typing import Optional

import numpy as np

from repro.core.features import records_to_dataset
from repro.core.profiler import ProfileRecord, profile_workload
from repro.core.workloads import WorkloadConfig, sample_grid
from repro.hw import EDGE_DEVICES, DeviceSpec, local_device_spec


def generate(n_runs: int = 120, *, max_steps: int = 8, seed: int = 0,
             measure: bool = True, augment_hardware: bool = True,
             verbose: bool = False):
    """Returns (records, TabularDataset)."""
    grid = sample_grid(n_runs, seed=seed)
    base_dev = local_device_spec()
    records: list[ProfileRecord] = []
    t0 = time.time()
    for i, wc in enumerate(grid):
        rec = profile_workload(wc, device=base_dev, measure=measure,
                               max_steps=max_steps, seed=seed + i)
        records.append(rec)
        if verbose and (i + 1) % 20 == 0:
            print(f"[dataset] {i+1}/{len(grid)} runs "
                  f"({time.time()-t0:.0f}s)")
    if augment_hardware:
        records += project_hardware(records, base_dev)
    return records, records_to_dataset(records)


def project_hardware(records: list[ProfileRecord], base: DeviceSpec
                     ) -> list[ProfileRecord]:
    """Analytic re-projection of runs measured on ``base`` onto the
    other edge-device specs."""
    out = []
    for rec in records:
        for name, dev in EDGE_DEVICES.items():
            if name == base.name:
                continue
            # scale times by the inverse compute-throughput ratio, bounded
            # by the memory-bandwidth ratio (roofline projection)
            comp_ratio = base.peak_flops_f32 / dev.peak_flops_f32
            mem_ratio = base.hbm_bw / dev.hbm_bw
            scale = max(comp_ratio, mem_ratio)
            out.append(dataclasses.replace(
                rec,
                label=f"{rec.label}@{name}",
                total_time_s=rec.total_time_s * scale,
                step_time_s=rec.step_time_s * scale,
                hardware=dev.as_features(),
                device=name,
            ))
    return out


def save_records(records: list[ProfileRecord], path: str) -> None:
    with open(path, "w") as f:
        json.dump([dataclasses.asdict(r) for r in records], f)


def load_records(path: str) -> list[ProfileRecord]:
    with open(path) as f:
        return [ProfileRecord(**d) for d in json.load(f)]
