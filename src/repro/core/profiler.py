"""The profiler (paper §II-A): run an AI workload, capture its execution
profile — FLOPs, MACs, memory, wall time, accuracy — on concrete hardware.

Two probe backends:

  * **measured** — actually executes the workload on this host (the paper's
    own method: >3,000 timed runs on a Dell XPS).  Wall-clock is measured,
    FLOPs/MACs/bytes come from XLA ``cost_analysis`` of the jitted step.
  * **analytic** — for TPU-pod-scale workloads that cannot run here:
    lower+compile only (the multi-pod dry-run), with the roofline terms as
    the time estimate.  Same ``ProfileRecord`` schema, so predictors train
    on both.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.workloads import (WorkloadConfig, init_workload_params,
                                  synthetic_image_data, workload_loss)
from repro.hw import DeviceSpec, local_device_spec
from repro.optim import apply_updates, get_optimizer


@dataclasses.dataclass
class ProfileRecord:
    """One profiling run (one row of the paper's dataset)."""
    label: str
    kind: str
    # --- profile targets (paper Fig. 3: FLOPS, MACs, total time) ---
    flops_per_step: float
    macs_per_step: float
    total_time_s: float
    # --- extended targets ---
    step_time_s: float
    peak_bytes: float
    param_count: int
    final_loss: float
    final_acc: float
    # --- inputs (features) ---
    config: dict
    hardware: dict
    device: str = ""            # DeviceSpec.name the run was measured on

    def targets(self, extended: bool = False) -> dict:
        """Prediction targets.  The default three are the paper's Fig. 3
        stack;
        ``extended=True`` additionally surfaces the resource-utilisation
        targets (per-step time and peak memory) so predictors can learn
        *resource use*, not just completion time (paper abstract:
        "execution time and resource utilization")."""
        out = {
            "flops": self.flops_per_step,
            "macs": self.macs_per_step,
            "total_time": self.total_time_s,
        }
        if extended:
            out["step_time"] = self.step_time_s
            out["peak_bytes"] = self.peak_bytes
        return out


def _cost_of(jitted, *args) -> dict:
    from repro.roofline import normalize_cost_analysis
    compiled = jitted.lower(*args).compile()
    cost = normalize_cost_analysis(compiled.cost_analysis())
    mem = compiled.memory_analysis()
    peak = (getattr(mem, "temp_size_in_bytes", 0)
            + getattr(mem, "argument_size_in_bytes", 0))
    return {"flops": float(cost.get("flops", 0.0)),
            "bytes": float(cost.get("bytes accessed", 0.0)),
            "peak_bytes": float(peak)}


def profile_workload(wc: WorkloadConfig, *, device: Optional[DeviceSpec] = None,
                     measure: bool = True, max_steps: int = 0,
                     seed: int = 0) -> ProfileRecord:
    """Train the Table-I workload and record its profile.

    ``device`` defaults to the spec of the device JAX runs on
    (:func:`repro.hw.local_device_spec`), so a record names the hardware
    that produced it.

    ``max_steps`` > 0 truncates the run and extrapolates total time from the
    measured per-step time (the profiling-dataset generator uses this to
    keep >100-run grids tractable; the benchmark validates the
    extrapolation error on full runs).
    """
    device = device or local_device_spec()
    key = jax.random.key(seed)
    params = init_workload_params(wc, key)
    opt = get_optimizer(wc.optimiser, wc.lr)
    opt_state = opt.init(params)
    x, y = synthetic_image_data(wc.dataset_size, seed=seed)

    def train_step(params, opt_state, batch):
        (loss, acc), grads = jax.value_and_grad(
            workload_loss, has_aux=True)(params, batch, wc)
        updates, opt_state = opt.update(grads, opt_state, params)
        return apply_updates(params, updates), opt_state, loss, acc

    jitted = jax.jit(train_step)
    bs = wc.batch_size
    batch0 = {"x": jnp.asarray(x[:bs]), "y": jnp.asarray(y[:bs])}
    cost = _cost_of(jitted, params, opt_state, batch0)

    steps_per_epoch = max(wc.dataset_size // bs, 1)
    planned = wc.epochs * steps_per_epoch
    loss_v = acc_v = float("nan")
    if measure:
        # warmup (compile) excluded from timing
        params, opt_state, *_ = jitted(params, opt_state, batch0)
        jax.block_until_ready(params)
        run_steps = min(planned, max_steps) if max_steps else planned
        t0 = time.perf_counter()
        step = 0
        done = False
        for _ in range(wc.epochs):
            for i in range(steps_per_epoch):
                lo = (i * bs) % wc.dataset_size
                batch = {"x": jnp.asarray(x[lo:lo + bs]),
                         "y": jnp.asarray(y[lo:lo + bs])}
                params, opt_state, loss_v, acc_v = jitted(
                    params, opt_state, batch)
                step += 1
                if step >= run_steps:
                    done = True
                    break
            if done:
                break
        jax.block_until_ready(params)
        elapsed = time.perf_counter() - t0
        step_time = elapsed / max(step, 1)
        total_time = step_time * planned
        loss_v, acc_v = float(loss_v), float(acc_v)
    else:
        # analytic estimate from the roofline of this device
        step_time = max(cost["flops"] / device.peak_flops_f32,
                        cost["bytes"] / device.hbm_bw)
        total_time = step_time * planned

    n_params = sum(int(np.prod(p.shape))
                   for p in jax.tree_util.tree_leaves(params))
    return ProfileRecord(
        label=wc.label(),
        kind=wc.kind,
        flops_per_step=cost["flops"],
        macs_per_step=cost["flops"] / 2.0,
        total_time_s=total_time,
        step_time_s=step_time,
        peak_bytes=cost["peak_bytes"],
        param_count=n_params,
        final_loss=loss_v,
        final_acc=acc_v,
        config=dataclasses.asdict(wc),
        hardware=device.as_features(),
        device=device.name,
    )
