"""Closed-loop predictor catalog: re-predict batches of (layer, device)
feature rows back to back through the lowered tree ensemble.

Traffic parameters: ``rows`` per call, ``batches`` distinct batches made
from the seed in set-up and cycled, the predict ``backend``,
``check_calls`` calls of the window kept (a seeded uniform sample) and
``check_rows`` rows of each (drawn from the seed) compared with the
reference's plain tree walk.
"""
from __future__ import annotations

import time

import numpy as np

from common import Reservoir, seed_streams


class State:
    pass


def setup(ctx):
    from repro.oracle import lower_predictor
    cfg, tr = ctx.cfg, ctx.traffic
    r_ens, r_rows, r_keep, r_sample = seed_streams(ctx.seed, 4)
    st = State()
    st.ens = ctx.cfg_mod.make_ensemble(cfg, r_ens)
    st.gbt = ctx.cfg_mod.load_program_predictor(st.ens, cfg, ctx.tmpdir)
    st.lowered = lower_predictor(st.gbt)
    st.batches = [ctx.cfg_mod.catalog_rows(cfg, r_rows, tr["rows"])
                  for _ in range(tr["batches"])]
    st.keep = Reservoir(tr["check_calls"], r_keep)
    st.r_sample = r_sample
    call(ctx, st, 0)                     # warm-up: the one shape
    return st


def call(ctx, st, b: int) -> np.ndarray:
    with ctx.spans("predict"):
        return st.lowered.predict(st.batches[b],
                                  backend=ctx.traffic["backend"])


def window(ctx, st, seconds: float) -> dict:
    nb = len(st.batches)
    calls = rows = traced = 0
    t0 = time.perf_counter()
    elapsed = 0.0
    while elapsed < seconds:
        ctx.trace.poll(elapsed)
        b = calls % nb
        traced += ctx.trace.state == "tracing"
        out = call(ctx, st, b)
        elapsed = time.perf_counter() - t0
        calls += 1
        rows += out.shape[0]
        st.keep.offer(lambda: (b, out))
    return {"e2e": {"predictions_per_s": rows / elapsed},
            "counters": {"rows_per_call": ctx.traffic["rows"],
                         "traced_calls": traced},
            "attempted": calls, "failed": 0,
            "info": {"calls": calls, "rows": rows, "window_s": elapsed}}


def release(st) -> None:
    st.lowered = st.gbt = None


def sample(ctx, st):
    """``[(rows, program predictions)]`` of the seeded row sample of each
    kept call (drawn once)."""
    if getattr(st, "sampled", None) is None:
        n = ctx.traffic["check_rows"]
        st.sampled = []
        for b, pred in st.keep.items:
            idx = st.r_sample.choice(pred.shape[0], size=n, replace=False)
            st.sampled.append((st.batches[b][idx], pred[idx]))
    return st.sampled


def error_scale(ens: dict) -> float:
    """Sum over trees of the largest scaled leaf value: what an f32
    accumulation's error is proportional to."""
    return float(np.sum(np.max(np.abs(ens["learning_rate"] * ens["value"]),
                               axis=1)))


def readings(ctx, st, low=None) -> dict:
    """Widest error of a sampled prediction against the reference's plain
    tree walk in f64, over the f32 error scale.  ``low`` (a dtype) puts
    the reference, computed in that precision, in the program's place."""
    scale = error_scale(st.ens)
    err = 0.0
    for x, got in sample(ctx, st):
        ref = ctx.cfg_mod.predict_ref(st.ens, x)
        if low is not None:
            got = ctx.cfg_mod.predict_ref(st.ens, x, low)
        err = max(err, float(np.max(np.abs(got - ref))) / scale)
    return {"predict_err": err}


def control(ctx, st) -> dict:
    """The reference's tree walk accumulated in bfloat16."""
    import ml_dtypes
    return readings(ctx, st, ml_dtypes.bfloat16)
