"""Production training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch qwen3-1.7b \
        --steps 200 [--reduced] [--debug-mesh]

On this CPU host use ``--reduced`` (family-faithful small config); the full
configs are exercised via the dry-run.  ``--debug-mesh`` runs the real
pjit path on a tiny forced-host-device mesh.
"""
import argparse
import os


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-1.7b")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch-size", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--debug-mesh", action="store_true",
                    help="4 forced host devices, (2,2) mesh pjit path")
    ap.add_argument("--ckpt-dir", default="")
    args = ap.parse_args()

    if args.debug_mesh:
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"

    from repro.configs import get_config, reduced_config
    from repro.configs.base import InputShape
    from repro.launch.cache import use_compile_cache
    from repro.train import TrainConfig, train

    use_compile_cache()

    cfg = (reduced_config(args.arch) if args.reduced
           else get_config(args.arch))
    if not args.debug_mesh:
        cfg = cfg.replace(dtype="float32")
    tcfg = TrainConfig(steps=args.steps, batch_size=args.batch_size,
                       seq_len=args.seq_len, lr=args.lr,
                       ckpt_every=50 if args.ckpt_dir else 0,
                       ckpt_dir=args.ckpt_dir or "checkpoints")

    if args.debug_mesh:
        from repro.data.synthetic import train_batch
        from repro.launch.mesh import make_debug_mesh
        from repro.launch.steps import assemble
        mesh = make_debug_mesh(4)
        shape = InputShape("debug", args.seq_len, args.batch_size, "train")
        step = assemble(cfg, shape, mesh, auto_knobs=False)
        with mesh:
            api_params = None
            res = train(cfg, tcfg,
                        jit_step=step.jitted,
                        batch_fn=lambda i: train_batch(
                            cfg, args.batch_size, args.seq_len, seed=i))
    else:
        res = train(cfg, tcfg)
    print(f"[train] {args.arch}: loss {res.losses[0]:.3f} -> "
          f"{res.losses[-1]:.3f} at {res.steps_per_s:.2f} steps/s")


if __name__ == "__main__":
    main()
