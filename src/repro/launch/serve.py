"""Serving launcher: batched requests against a model.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-1.7b \
        --requests 8 --max-new 16 [--reduced]

The full config at its own dtype unless ``--reduced`` is given (the
family-faithful small config in float32, for a CPU host) — the
convention of ``repro.launch.train``.
"""
import argparse

import numpy as np


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-1.7b")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--batch-size", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--reduced", action="store_true")
    args = ap.parse_args()

    from repro.configs import get_config, reduced_config
    from repro.launch.cache import use_compile_cache
    from repro.serve import Request, ServeEngine

    use_compile_cache()
    cfg = (reduced_config(args.arch).replace(dtype="float32")
           if args.reduced else get_config(args.arch))
    engine = ServeEngine(cfg, batch_size=args.batch_size,
                         max_len=args.prompt_len + args.max_new + 8)
    rng = np.random.default_rng(0)
    reqs = [Request(rid=i,
                    prompt=rng.integers(0, cfg.vocab_size,
                                        size=args.prompt_len,
                                        dtype=np.int32),
                    max_new_tokens=args.max_new,
                    # virtual arrival stamps: only their order matters,
                    # and seeded launcher runs stay reproducible
                    arrived_at=i * 1e-3)
            for i in range(args.requests)]
    done = engine.serve(reqs)
    st = engine.stats
    print(f"[serve] {args.arch}: {st.served} requests, "
          f"{st.tokens_out} tokens, {st.tokens_per_s:.1f} tok/s decode, "
          f"prefill {st.prefill_s:.2f}s decode {st.decode_s:.2f}s")
    assert all(r.output is not None for r in done)


if __name__ == "__main__":
    main()
