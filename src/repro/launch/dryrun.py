import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run (deliverable e).

Lowers + compiles every (architecture × input-shape) pair on the production
mesh — 16×16 single-pod and 2×16×16 multi-pod — and records
``memory_analysis()`` / ``cost_analysis()`` / collective schedule for the
roofline (deliverable g).  The two os.environ lines above MUST stay the very
first statements: JAX locks the device count on first init.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch gemma-2b \
        --shape train_4k [--multi-pod] [--out EXPERIMENTS/dryrun]
    PYTHONPATH=src python -m repro.launch.dryrun --all --out results/
"""
import argparse
import json
import time
import traceback

import jax

from repro.configs import ARCH_NAMES, INPUT_SHAPES, get_config
from repro.launch.mesh import make_production_mesh
from repro.launch.steps import arch_shape_cfg, assemble
from repro.roofline import analyze, collective_bytes, model_flops_estimate


def _legalization_bytes(hlo: str, arg_specs, mesh, temp_bytes: int = 0) -> int:
    """Estimate bytes of XLA:CPU bf16→f32 legalisation copies.

    Finds ``f32[dims] convert`` results whose dims match a bf16 argument
    leaf under every possible per-device sharding factor (divisors of the
    mesh axis sizes), counting each distinct shape once.
    """
    import itertools
    import re

    import numpy as np

    conv_names = re.findall(
        r"(%[\w.-]+) = f32\[([0-9,]+)\][^ ]* convert\(", hlo)
    conv_count: dict[str, set] = {}
    for name, dims in conv_names:
        conv_count.setdefault(dims, set()).add(name)
    conv_shapes = set(conv_count)
    if not conv_shapes:
        return 0
    axis_sizes = list(mesh.devices.shape)
    factors = {1}
    for r in range(1, len(axis_sizes) + 1):
        for combo in itertools.combinations(axis_sizes, r):
            factors.add(int(np.prod(combo)))
    total = 0
    leaves = jax.tree_util.tree_leaves(arg_specs)
    bf16_shapes: dict[tuple, int] = {}
    for l in leaves:
        if getattr(l, "dtype", None) == jnp_bf16 \
                and np.prod(l.shape) * 2 > 64 * 2**20:
            t = tuple(l.shape)
            bf16_shapes[t] = bf16_shapes.get(t, 0) + 1
    # bf16 TEMPS defined in the HLO itself (e.g. scan carry stacks) whose
    # f32 convert twins are likewise CPU legalisation artefacts
    for dims in set(re.findall(r"= bf16\[([0-9,]+)\]", hlo)):
        shape = tuple(int(d) for d in dims.split(","))
        if np.prod(shape) * 2 > 64 * 2**20:
            bf16_shapes.setdefault(shape, 1)
    # only clearly-dominant long-lived copies qualify (transient per-layer
    # converts share buffers and must not be double-subtracted)
    floor = max(64 * 2**20, int(0.25 * temp_bytes))
    for dims in conv_shapes:
        shape = tuple(int(d) for d in dims.split(","))
        size_f32 = int(np.prod(shape)) * 4
        if size_f32 < floor:
            continue
        for g, n_leaves in bf16_shapes.items():
            if len(g) != len(shape):
                continue
            ratio = 1
            okay = True
            for a, b in zip(g, shape):
                if b == 0 or a % b:
                    okay = False
                    break
                ratio *= a // b
            if okay and ratio in factors:
                # one live copy per matching arg leaf, capped by the number
                # of distinct convert instances in the HLO
                total += size_f32 * min(n_leaves, len(conv_count[dims]))
                break
    if temp_bytes:
        total = min(total, int(0.9 * temp_bytes))
    return total


import jax.numpy as _jnp  # noqa: E402
jnp_bf16 = _jnp.bfloat16


def run_one(arch: str, shape_name: str, *, multi_pod: bool = False,
            seq_shard_cache: bool = False, extra_cfg_kw=None,
            verbose: bool = True) -> dict:
    """Lower+compile one (arch, shape, mesh). Returns the result record."""
    base_cfg = get_config(arch)
    shape = INPUT_SHAPES[shape_name]
    cfg = arch_shape_cfg(base_cfg, shape)
    rec: dict = {"arch": arch, "shape": shape_name,
                 "mesh": "2x16x16" if multi_pod else "16x16"}
    if cfg is None:
        rec["status"] = "skipped"
        rec["reason"] = "principled skip (DESIGN.md §4)"
        return rec

    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.devices.size
    # perf_counter, not time.time: these are durations, and wall-clock
    # steps (NTP slew) would corrupt the lower/compile split
    t0 = time.perf_counter()
    try:
        step = assemble(cfg, shape, mesh, seq_shard_cache=seq_shard_cache,
                        extra_cfg_kw=extra_cfg_kw)
        with mesh:
            lowered = step.jitted.lower(*step.arg_specs)
            t_lower = time.perf_counter() - t0
            compiled = lowered.compile()
            t_compile = time.perf_counter() - t0 - t_lower
            mem = compiled.memory_analysis()
            from repro.roofline import normalize_cost_analysis
            cost = normalize_cost_analysis(compiled.cost_analysis())
            hlo = compiled.as_text()
    except Exception as e:                         # noqa: BLE001
        rec["status"] = "FAILED"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-2000:]
        if verbose:
            print(f"[dryrun] {arch} × {shape_name} FAILED: {rec['error']}")
        return rec

    rec["status"] = "ok"
    rec["sharding"] = step.shard_report.summary()
    rec["lower_s"] = round(t_lower, 1)
    rec["compile_s"] = round(t_compile, 1)
    rec["memory"] = {
        k: int(getattr(mem, k))
        for k in ("temp_size_in_bytes", "argument_size_in_bytes",
                  "output_size_in_bytes", "alias_size_in_bytes",
                  "generated_code_size_in_bytes")
        if hasattr(mem, k)
    }
    args_b = rec["memory"].get("argument_size_in_bytes", 0)
    temp_b = rec["memory"].get("temp_size_in_bytes", 0)
    alias_b = rec["memory"].get("alias_size_in_bytes", 0)
    out_b = rec["memory"].get("output_size_in_bytes", 0)
    per_dev = args_b + temp_b + out_b - alias_b
    rec["bytes_per_device"] = int(per_dev)
    # XLA:CPU legalises bf16 dot operands by materialising f32 copies of
    # big bf16 buffers (caches, stacked weights) — copies that do NOT exist
    # on the TPU target (native bf16 MXU).  Subtract f32 convert results
    # whose shape matches a bf16 input leaf (each counted once); report
    # both raw and TPU-adjusted numbers (convention noted in EXPERIMENTS.md).
    rec["cpu_legalization_bytes"] = int(
        _legalization_bytes(hlo, step.arg_specs, mesh, temp_b))
    adj = per_dev - rec["cpu_legalization_bytes"]
    rec["bytes_per_device_tpu_adjusted"] = int(adj)
    rec["fits_hbm16"] = bool(adj < 16 * 2**30)
    rec["fits_hbm16_raw"] = bool(per_dev < 16 * 2**30)
    mf = model_flops_estimate(cfg, shape)
    roof = analyze(f"{arch}/{shape_name}", cost, hlo, chips=chips,
                   model_flops=mf)
    rec["roofline"] = roof.row()
    rec["collectives"] = collective_bytes(hlo)
    from repro.roofline_hlo import corrected_costs
    cc = corrected_costs(hlo)
    rec["hlo_parsed"] = {"flops": cc["flops"],
                         "bytes_noreuse_bound": cc["bytes"],
                         "cost_analysis_flops": float(cost.get("flops", 0)),
                         "cost_analysis_bytes": float(
                             cost.get("bytes accessed", 0))}
    if verbose:
        print(f"[dryrun] {arch} × {shape_name} ({rec['mesh']}): OK "
              f"compile={t_compile:.0f}s mem/dev={per_dev/2**30:.2f}GiB "
              f"dominant={roof.dominant} "
              f"terms=({roof.compute_s:.2e},{roof.memory_s:.2e},"
              f"{roof.collective_s:.2e})s")
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_NAMES)
    ap.add_argument("--shape", choices=list(INPUT_SHAPES))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--seq-shard-cache", action="store_true",
                    help="sequence-parallel KV cache (perf variant)")
    ap.add_argument("--out", default=None, help="JSON output path")
    args = ap.parse_args()

    from repro.launch.cache import use_compile_cache
    use_compile_cache()
    archs = ARCH_NAMES if (args.all or not args.arch) else [args.arch]
    shapes = list(INPUT_SHAPES) if (args.all or not args.shape) \
        else [args.shape]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]

    results = []
    for mp in meshes:
        for arch in archs:
            for shp in shapes:
                results.append(run_one(arch, shp, multi_pod=mp,
                                       seq_shard_cache=args.seq_shard_cache))
    ok = sum(r["status"] == "ok" for r in results)
    skip = sum(r["status"] == "skipped" for r in results)
    fail = sum(r["status"] == "FAILED" for r in results)
    print(f"[dryrun] done: {ok} ok, {skip} skipped, {fail} failed "
          f"of {len(results)}")
    if args.out:
        with open(args.out, "w") as f:
            json.dump(results, f, indent=1)
        print(f"[dryrun] wrote {args.out}")
    if fail:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
