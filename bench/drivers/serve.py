"""Open-loop serving: Poisson arrivals into the continuous-batching
engine, on the wall clock.

Traffic parameters: ``rate_per_s`` (fixed in the cell), prompt lengths
``prompt_lens`` with probabilities ``prompt_probs``, answers log-normal
(``answer_median``, ``answer_sigma``) clipped to ``answer_min`` ..
``answer_max``, the users' link bandwidth (log-normal,
``link_bw_median``, ``link_bw_sigma``) observed at each admission, and
``check_requests`` served requests compared with the reference.

Every seed serves the same work: the window's ``round(rate * seconds)``
requests take the exponential inter-arrival gaps, answer lengths and
link bandwidths at evenly spaced quantiles, and the prompt lengths in
exact proportion; the seed orders them and draws the prompt tokens.

Whatever depends on the model's shape comes from the configuration's
module (``configs/<config>.py``, the functions ``MODULE_FUNCTIONS``
names), so a served model of any block enters by its own files.

In the window the engine's clock is the wall clock: ``now`` is seconds
since the window opened, ``advance`` (once per decode step) only counts,
and ``advance_to`` sleeps until the next arrival.  Set-up's warm-up runs
on the engine's own virtual clock.  A request's latency is
its completion less the instant it was due.
"""
from __future__ import annotations

import time
from statistics import NormalDist

import numpy as np

from common import (BENCH, jax_seed, load_json, load_module, quantile,
                    rel_gap, seed_streams)


def requests(tr: dict, seconds: float, rng, vocab: int,
             rate: float | None = None) -> list[dict]:
    """The window's requests, due in ``[0, seconds)``, in due order."""
    rate = rate or tr["rate_per_s"]
    n = max(int(round(rate * seconds)), 1)
    u = (np.arange(n) + 0.5) / n
    gaps = rng.permutation(-np.log1p(-u) / rate)
    due = (np.cumsum(gaps) - gaps) * (seconds / gaps.sum())
    probs = np.asarray(tr["prompt_probs"], np.float64)
    counts = np.floor(probs * n).astype(int)
    for i in np.argsort(-(probs * n - counts))[:n - counts.sum()]:
        counts[i] += 1
    plens = rng.permutation(np.repeat(tr["prompt_lens"], counts))
    z = np.asarray([NormalDist().inv_cdf(x) for x in u])
    answers = np.clip(np.round(tr["answer_median"]
                               * np.exp(tr["answer_sigma"] * z)),
                      tr["answer_min"], tr["answer_max"]).astype(int)
    answers = rng.permutation(answers)
    bw = rng.permutation(tr["link_bw_median"]
                         * np.exp(tr["link_bw_sigma"] * z))
    return [{"due": float(due[i]), "prompt_len": int(plens[i]),
             "answer": int(answers[i]), "link_bw": float(bw[i]),
             "prompt": rng.integers(0, vocab, int(plens[i]),
                                    dtype=np.int32)}
            for i in range(n)]


class WallClock:
    """The engine's clock in the window: wall seconds since it opened."""

    def __init__(self, on_step=None):
        self.t0 = time.perf_counter()
        self.on_step = on_step

    @property
    def now(self) -> float:
        return time.perf_counter() - self.t0

    def advance(self, dt: float) -> None:
        if self.on_step is not None:
            self.on_step()

    def advance_to(self, t: float) -> None:
        wait = t - self.now
        if wait > 0:
            time.sleep(wait)


#: what a served configuration's module gives: its weights from the seed
#: (``make_params``), the reference (``forward``, ``logits_at``), the
#: control's weights (``fp8_weights``), the planner's statement of the
#: model's layers (``planner_layers(m, seq) -> (flops[L], act[L])``), the
#: model FLOPs of a prefill (``prefill_flops(m, prompt_len)``) and of a
#: decoded token (``token_flops(m, context, with_head)``)
MODULE_FUNCTIONS = ("make_params", "forward", "logits_at", "fp8_weights",
                    "planner_layers", "prefill_flops", "token_flops")


class State:
    pass


def setup(ctx):
    missing = [f for f in MODULE_FUNCTIONS
               if not callable(getattr(ctx.cfg_mod, f, None))]
    if missing:
        raise AttributeError(f"{ctx.cell.cfg_path.name}: a served "
                             f"configuration's module must define "
                             f"{', '.join(missing)}")
    from repro.configs.base import ModelConfig
    from repro.core import costs as co
    from repro.hw import get_device
    from repro.serve import ContinuousBatchEngine, Request
    cfg, tr = ctx.cfg, ctx.traffic
    sv = cfg["serving"]
    r_ens, r_warm, r_req, r_keep = seed_streams(ctx.seed, 4)
    st = State()
    st.m = cfg["model"]
    st.Request = Request
    # the admission planner: the planner configuration's predictor
    st.pcfg = load_json(BENCH / "configs" / f"{sv['planner_config']}.json")
    st.pmod = load_module(BENCH / "configs"
                          / f"{sv['planner_config']}.py")
    st.ens = st.pmod.make_ensemble(st.pcfg, r_ens)
    gbt = st.pmod.load_program_predictor(st.ens, st.pcfg, ctx.tmpdir)
    dev, edge = get_device(sv["offload_device"]), get_device(
        sv["offload_edge"])
    st.mcfg = ModelConfig(**st.m)
    st.engine = ContinuousBatchEngine(
        st.mcfg, slots=sv["slots"], max_len=sv["max_len"],
        seed=jax_seed(ctx.seed, 1), cost=co.PredictorCost(gbt, dev, edge),
        offload_device=dev, offload_edge=edge,
        decision_backend=sv["decision_backend"])
    # the benchmark's own weights replace the engine's
    st.engine.params = None
    st.params = ctx.cfg_mod.make_params(st.m, jax_seed(ctx.seed, 2))
    st.engine.params = st.params
    st.r_req, st.r_keep = r_req, r_keep
    warm_up(ctx, st, r_warm)
    return st


def warm_up(ctx, st, rng) -> None:
    """Every shape the window uses: each prompt length's prefill, a
    splice into every slot, the decode step, the admission planner."""
    lens = ctx.traffic["prompt_lens"]
    n = max(2 * st.engine.slots, len(lens))
    reqs = [st.Request(rid=i, prompt=rng.integers(
        0, st.m["vocab_size"], lens[i % len(lens)], dtype=np.int32),
        max_new_tokens=2, arrived_at=0.0) for i in range(n)]
    st.engine.link_bw = ctx.traffic["link_bw_median"]
    st.engine.serve(reqs)


def window(ctx, st, seconds: float, rate: float | None = None) -> dict:
    from repro.obs.trace import Tracer
    eng, m, mod = st.engine, st.m, ctx.cfg_mod
    gen = requests(ctx.traffic, seconds, st.r_req, m["vocab_size"], rate)
    reqs = [st.Request(rid=i, prompt=g["prompt"],
                       max_new_tokens=g["answer"], arrived_at=g["due"])
            for i, g in enumerate(gen)]
    obs = Tracer()
    counts = {"admitted": 0, "steps": 0, "traced_steps": 0,
              "traced_flops": 0.0}
    span = [None]

    def observe_link():
        # the engine observes the link once per admission, in due order
        k = counts["admitted"]
        counts["admitted"] += 1
        if ctx.trace.state == "tracing":
            counts["traced_flops"] += mod.prefill_flops(
                m, gen[k]["prompt_len"])
        return gen[k]["link_bw"]

    def on_step():
        now = clock.now
        if now < seconds:
            counts["steps"] += 1
        if ctx.trace.state == "tracing":
            counts["traced_steps"] += 1
            for s in range(eng.slots):
                if eng.slot_req[s] is not None:
                    counts["traced_flops"] += mod.token_flops(
                        m, int(eng.slot_pos[s]) + 1, True)
        if span[0] is not None:
            span[0].__exit__(None, None, None)
            span[0] = None
        ctx.trace.poll(now)
        if ctx.spans.on:
            span[0] = ctx.spans("step")
            span[0].__enter__()

    eng.link_bw = observe_link
    eng.obs = obs
    clock = WallClock(on_step)
    eng.clock = clock
    with ctx.spans("serve"):
        done = eng.serve(reqs)
    drained = clock.now
    if span[0] is not None:
        span[0].__exit__(None, None, None)
    ctx.trace.stop()
    end = {s.tid: s.t1 for s in obs.all_spans() if s.name == "sojourn"}
    lat = [end[r.rid] - r.arrived_at if r.rid in end else np.inf
           for r in reqs]
    failed = sum(1 for v in lat if not np.isfinite(v))
    # how late the engine took each request up after it was due (the
    # wait counts in its latency, which runs from the due instant)
    wait = [r.admitted_at - r.arrived_at for r in done]
    st.done, st.reqs, st.gen, st.finished = done, reqs, gen, end
    return {"e2e": {"serve_latency_p95_s": quantile(lat, 0.95)},
            "counters": counts,
            "attempted": len(reqs), "failed": failed,
            "info": {"requests": len(reqs), "p50_s": quantile(lat, 0.5),
                     "p95_s": quantile(lat, 0.95), "max_s": max(lat),
                     "mean_s": float(np.mean(lat)),
                     "admit_wait_p50_s": quantile(wait, 0.5),
                     "admit_wait_p95_s": quantile(wait, 0.95),
                     "admit_wait_max_s": max(wait),
                     "drained_s": drained,
                     "steps_in_window": counts["steps"]}}


def release(st) -> None:
    """Free the engine's state; the benchmark's weights stay for the
    reference."""
    st.engine.cache = None
    st.engine = None


def check_sample(st, rng, k: int) -> list:
    """The longest served request and ``k - 1`` others, drawn from the
    seed."""
    done = sorted(st.done, key=lambda r: r.rid)
    longest = max(done, key=lambda r: (len(r.prompt) + len(r.output),
                                       -r.rid))
    rest = [r for r in done if r is not longest]
    pick = rng.choice(len(rest), size=min(k - 1, len(rest)), replace=False)
    return [longest] + [rest[i] for i in sorted(pick)]


def batches(sample, max_len: int, answer_max: int, width: int):
    """``(tokens [B, S], rows [B, P], served [B, P], mask [B, P])`` per
    batch of ``width`` requests: prompt plus served tokens but the last,
    the rows whose logits chose each served token."""
    for lo in range(0, len(sample), width):
        part = sample[lo:lo + width]
        tok = np.zeros((width, max_len), np.int32)
        rows = np.zeros((width, answer_max), np.int32)
        served = np.zeros((width, answer_max), np.int32)
        mask = np.zeros((width, answer_max), bool)
        for b, r in enumerate(part):
            seq = np.concatenate([r.prompt, r.output[:-1]])
            tok[b, :len(seq)] = seq
            n, p = len(r.output), len(r.prompt)
            rows[b, :n] = np.arange(p - 1, p - 1 + n)
            served[b, :n] = r.output
            mask[b, :n] = True
        yield tok, rows, served, mask


def token_gaps(ctx, st, sample, quant=None) -> tuple[float, float]:
    """By how much a chosen token's reference logit lies below the
    reference's best, as ``(mean over positions, widest)``: for the
    served tokens (``quant=None``), or for the tokens the control
    (weights through ``quant``) puts first at the same positions."""
    mod, m = ctx.cfg_mod, st.m
    tr = ctx.traffic
    seq_len = ctx.cfg["serving"]["max_len"]
    total = widest = 0.0
    n = 0
    for tok, rows, served, mask in batches(sample, seq_len,
                                           tr["answer_max"],
                                           tr["check_batch"]):
        h, best, best_id = mod.forward(st.params, m, tok, rows)
        if quant is not None:
            _, _, served = mod.forward(st.params, m, tok, rows, quant)
        got = mod.logits_at(st.params, h, served)
        gap = np.where(mask & (served != best_id),
                       np.maximum(best - got, 0.0), 0.0)
        total += float(gap.sum())
        n += int(mask.sum())
        widest = max(widest, float(gap.max()))
    return total / n, widest


def plan_gaps(ctx, st, low=None, xp=np) -> tuple[float, float]:
    """Over the window's admissions: the widest relative gap between the
    reference cost of the program's split and the reference's best, and
    the widest relative distance of the program's reported latency from
    that best.  ``low`` (a dtype, with ``xp``) puts the reference,
    computed in that precision, in the planner's place."""
    pm, m = st.pmod, st.m
    sv = ctx.cfg["serving"]
    pcfg = dict(st.pcfg, device=sv["offload_device"],
                edge=sv["offload_edge"])
    gap = err = 0.0
    for r in st.done:
        g = st.gen[r.rid]
        flops, act = ctx.cfg_mod.planner_layers(m, g["prompt_len"])
        t_dev, t_edge = pm.layer_times_ref(st.ens, pcfg, flops, act)
        env = (np.asarray([g["link_bw"]]), np.asarray([0.005]),
               np.asarray([4.0 * g["prompt_len"]]))
        cost = pm.split_costs_ref(t_dev, t_edge, act, *env)[0]
        split, total = r.offload.split, r.offload.total_time_s
        if low is not None:
            c = np.asarray(pm.split_costs_ref(
                *(xp.asarray(a, low) for a in (t_dev, t_edge, act, *env)),
                xp=xp, dtype=low)[0]).astype(np.float64)
            split = int(np.argmin(c))
            total = c[split]
        best = cost.min()
        gap = max(gap, rel_gap(cost[split], best))
        err = max(err, rel_gap(abs(total - best) + best, best))
    return gap, err


def readings(ctx, st) -> dict:
    st.sample = check_sample(st, st.r_keep, ctx.traffic["check_requests"])
    mean, widest = token_gaps(ctx, st, st.sample)
    gap, err = plan_gaps(ctx, st)
    return {"token_gap_mean": mean, "token_gap_widest": widest,
            "plan_gap_rel": gap, "plan_err_rel": err}


def control(ctx, st) -> dict:
    """The reference in the program's place at the step below the
    configuration's precision: for the model, its weights in float8
    (e4m3, a scale per output column) choose the tokens at the same
    positions of the same sampled requests; for the planner, its costs
    in bfloat16 choose each admission's split."""
    import jax.numpy as jnp
    mean, widest = token_gaps(ctx, st, st.sample, ctx.cfg_mod.fp8_weights)
    gap, err = plan_gaps(ctx, st, jnp.bfloat16, jnp)
    return {"token_gap_mean": mean, "token_gap_widest": widest,
            "plan_gap_rel": gap, "plan_err_rel": err}
