"""DeepSeek-V2-Lite through the continuous-batching engine, against the
plain float32 reference the benchmark compares it with
(``bench/configs/deepseek-v2-lite.py``), at a small size on the CPU: the
published block (MLA with YaRN rope, a leading dense layer, 64 routed
experts top-6 unnormalised, 2 shared) with 8 of the 64 experts held.
Also pins that minitron-4b is served and priced as before."""
from __future__ import annotations

import contextlib
import importlib.util
import json
import math
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced_config
from repro.configs.base import ModelConfig
from repro.core.offload import transformer_layer_costs
from repro.models import build_model, moe
from repro.models.layers import (apply_rope, gated_mlp, init_tree, rope_for,
                                 yarn_freqs, yarn_mscale)
from repro.models.mla import softmax_scale
from repro.obs import MetricsRegistry, Tracer
from repro.obs.analyze import idle
from repro.serve.continuous import ContinuousBatchEngine
from repro.serve.engine import Request

BENCH = Path(__file__).resolve().parents[1] / "bench"


def _load(name: str):
    spec = importlib.util.spec_from_file_location(
        f"ref_{name.replace('-', '_')}", BENCH / "configs" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _load("deepseek-v2-lite")
PUBLISHED = json.loads((BENCH / "configs" / "deepseek-v2-lite.json")
                       .read_text())["model"]
SMALL = {**PUBLISHED, **REF.small(PUBLISHED), "dtype": "float32"}


def small_cfg(**kw) -> ModelConfig:
    return ModelConfig(**{**SMALL, **kw})


@pytest.fixture(scope="module")
def params():
    return REF.make_params(SMALL, 5)


def ref_logits(params, tokens, rows):
    """The reference's full-vocabulary logits at ``rows`` of ``tokens``."""
    h, _, _ = REF.forward(params, SMALL, tokens, rows)
    with jax.default_matmul_precision("highest"):
        return np.asarray(h @ params["lm_head"].astype(jnp.float32))


def serve_small(params, lens_new, slots=3, max_len=64, cfg=None, **kw):
    """Serve requests of ``(prompt length, answer)`` arriving a few steps
    apart, so slots sit at ragged positions; records every decode step's
    slot positions, requests and logits."""
    eng = ContinuousBatchEngine(cfg or small_cfg(), slots=slots,
                                max_len=max_len, seed=0, **kw)
    eng.params = params
    real, steps = eng._decode, []

    def spy(p, batch, cache):
        pos = np.array(cache["pos"])
        owners = [r.rid if r is not None else None for r in eng.slot_req]
        out = real(p, batch, cache)
        steps.append((pos, owners, np.asarray(out[0][:, -1])))
        return out

    eng._decode = spy
    rng = np.random.default_rng(1)
    reqs = [Request(rid=i, prompt=rng.integers(0, SMALL["vocab_size"], n,
                                               dtype=np.int32),
                    max_new_tokens=k, arrived_at=0.004 * i)
            for i, (n, k) in enumerate(lens_new)]
    done = eng.serve(reqs)
    return eng, sorted(done, key=lambda r: r.rid), steps


def test_engine_prefill_and_ragged_decode_match_the_reference(params):
    eng, done, steps = serve_small(params, [(5, 9), (17, 4), (9, 12),
                                            (30, 7), (12, 10)])
    assert len(done) == 5
    assert any(len({int(p) for p, o in zip(pos, owners) if o is not None})
               > 1 for pos, owners, _ in steps), "no ragged step"
    for r in done:
        seq = np.concatenate([r.prompt, r.output[:-1]])[None]
        p, n = len(r.prompt), len(r.output)
        want = ref_logits(params, seq, np.arange(p - 1, p - 1 + n)[None])[0]
        # the prefill's argmax chose the first token
        assert int(np.argmax(want[0])) == r.output[0]
        got = {int(pos[s]): lg[s] for pos, owners, lg in steps
               for s in range(eng.slots) if owners[s] == r.rid}
        assert sorted(got) == list(range(p, p + n))
        for j in range(1, n):       # decoded at position p + j - 1
            np.testing.assert_allclose(got[p + j - 1], want[j], rtol=2e-4,
                                       atol=2e-4)
        np.testing.assert_array_equal(np.argmax(want, -1), r.output)


def _share(full: dict, j: int, held: int) -> dict:
    """Share ``j`` of an uncut layer: its experts first in the router."""
    lo = j * held
    w = {k: full[k][lo:lo + held] for k in ("w_gate", "w_up", "w_down")}
    return {**w, "router": jnp.roll(full["router"], -lo, axis=1),
            "shared": full["shared"]}


def test_expert_shares_sum_to_the_uncut_reference_layer():
    cfg = small_cfg(experts_held=8)
    uncut = small_cfg(experts_held=0)
    full = init_tree(jax.random.key(3), moe.moe_param_shapes(uncut),
                     jnp.float32)
    full["router"] = full["router"] * 8.0
    x = jax.random.normal(jax.random.key(4), (40, cfg.d_model))
    shared = gated_mlp(x, full["shared"], cfg.mlp_act)
    total = shared
    for j in range(cfg.num_experts // 8):
        y, _ = moe.moe_mlp(_share(full, j, 8), x, cfg, dropless=True)
        total = total + (y - shared)
    with jax.default_matmul_precision("highest"):
        want = REF.moe_ref({**SMALL, "experts_held": 0}, full, x)
        whole, _ = moe.moe_mlp(full, x, uncut, dropless=True)
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(whole), np.asarray(want),
                               rtol=1e-4, atol=1e-5)
    # one share alone is the reference with only its experts held
    with jax.default_matmul_precision("highest"):
        part = REF.moe_ref(SMALL, _share(full, 0, 8), x)
    y0, _ = moe.moe_mlp(_share(full, 0, 8), x, cfg, dropless=True)
    np.testing.assert_allclose(np.asarray(y0), np.asarray(part), rtol=1e-4,
                               atol=1e-5)


def test_yarn_frequencies_and_scale_match_the_hand_table():
    cfg = get_config("deepseek-v2-lite-16b")
    # correction range at 64 rope dims, base 10^4, 4096 positions:
    # 64·ln(4096/(32·2π))/(2 ln 10^4) = 10.47 -> 10; beta 1: 22.5 -> 23
    base = [10000.0 ** (-2 * i / 64) for i in range(32)]
    table = []
    for i, f in enumerate(base):
        ramp = min(max((i - 10) / 13, 0.0), 1.0)
        table.append(f / 40 * ramp + f * (1 - ramp))
    got = np.asarray(yarn_freqs(64, cfg), np.float64)
    np.testing.assert_allclose(got, table, rtol=2e-6)
    assert got[9] == pytest.approx(base[9], rel=2e-6)
    assert got[24] == pytest.approx(base[24] / 40, rel=2e-6)
    mscale = 0.1 * 0.707 * math.log(40) + 1
    assert mscale ** 2 == pytest.approx(1.5896, abs=1e-4)
    assert softmax_scale(cfg) == pytest.approx(192 ** -0.5 * mscale ** 2,
                                               rel=1e-12)
    np.testing.assert_allclose(got, REF.yarn_inv_freq(SMALL | {
        "qk_rope_dim": 64}), rtol=2e-6)
    # cos and sin keep their scale: mscale(f, 0.707) / mscale(f, 0.707)
    x = jax.random.normal(jax.random.key(0), (1, 5, 2, 64))
    pos = jnp.arange(5)[None]
    np.testing.assert_array_equal(
        np.asarray(rope_for(x, pos, cfg)),
        np.asarray(apply_rope(x, pos, 1e4, inv_freq=yarn_freqs(64, cfg))))


def test_yarn_settings_are_the_published_rope_scaling():
    rs = json.loads((BENCH / "configs" / "deepseek-v2-lite.json")
                    .read_text())["rope_scaling"]
    cfg = get_config("deepseek-v2-lite-16b")
    mine = (cfg.yarn_factor, cfg.yarn_original_max_pos, cfg.yarn_beta_fast,
            cfg.yarn_beta_slow, cfg.yarn_mscale, cfg.yarn_mscale_all_dim)
    assert mine == (rs["factor"], rs["original_max_position_embeddings"],
                    rs["beta_fast"], rs["beta_slow"], rs["mscale"],
                    rs["mscale_all_dim"])
    assert mine == (PUBLISHED["yarn_factor"],
                    PUBLISHED["yarn_original_max_pos"],
                    PUBLISHED["yarn_beta_fast"], PUBLISHED["yarn_beta_slow"],
                    PUBLISHED["yarn_mscale"], PUBLISHED["yarn_mscale_all_dim"])


def test_yarn_settings_other_than_deepseeks_move_the_program_and_reference():
    # a model whose mscale differs from mscale_all_dim scales cos and sin
    # by their ratio, and a narrower beta range moves the ramp
    cfg = get_config("deepseek-v2-lite-16b").replace(
        yarn_mscale=1.0, yarn_mscale_all_dim=0.5, yarn_beta_fast=16.0)
    x = jax.random.normal(jax.random.key(2), (1, 5, 2, 64))
    pos = jnp.arange(5)[None]
    ratio = yarn_mscale(40.0, 1.0) / yarn_mscale(40.0, 0.5)
    np.testing.assert_allclose(
        np.asarray(rope_for(x, pos, cfg)),
        ratio * np.asarray(apply_rope(x, pos, 1e4,
                                      inv_freq=yarn_freqs(64, cfg))),
        rtol=1e-5, atol=1e-6)
    m = SMALL | {"qk_rope_dim": 64, "yarn_mscale": 1.0,
                 "yarn_mscale_all_dim": 0.5, "yarn_beta_fast": 16.0}
    np.testing.assert_allclose(np.asarray(yarn_freqs(64, cfg), np.float64),
                               REF.yarn_inv_freq(m), rtol=2e-6)
    assert not np.allclose(
        REF.yarn_inv_freq(m), REF.yarn_inv_freq(SMALL | {"qk_rope_dim": 64}))
    assert softmax_scale(cfg) == pytest.approx(
        REF.softmax_scale(m | {"qk_nope_dim": 128}), rel=1e-12)


def test_rope_without_scaling_is_unchanged():
    cfg = get_config("minitron-4b")
    x = jax.random.normal(jax.random.key(1), (2, 7, 3, 128))
    pos = jnp.arange(7)[None]
    np.testing.assert_array_equal(np.asarray(rope_for(x, pos, cfg)),
                                  np.asarray(apply_rope(x, pos, 1e4)))
    assert softmax_scale(reduced_config("deepseek-v2-lite-16b").replace(
        yarn_factor=0.0)) == (32 + 16) ** -0.5


def test_routing_is_unnormalised():
    cfg = small_cfg()
    params = {"router": 0.02 * jax.random.normal(jax.random.key(2),
                                                 (cfg.d_model, 64))}
    x = jax.random.normal(jax.random.key(3), (20, cfg.d_model))
    w, idx, _ = moe.route(params, x, cfg)
    probs = jax.nn.softmax(x @ params["router"], axis=-1)
    np.testing.assert_allclose(
        np.asarray(w), np.asarray(jnp.take_along_axis(probs, idx, -1)),
        rtol=1e-6)
    assert float(w.sum(-1).max()) < 0.999
    wn, _, _ = moe.route(params, x, cfg.replace(norm_topk_prob=True))
    np.testing.assert_allclose(np.asarray(wn.sum(-1)), 1.0, rtol=1e-5)


def test_a_router_skewed_to_one_held_expert_drops_nothing():
    cfg = small_cfg(experts_held=8)
    params = init_tree(jax.random.key(6), moe.moe_param_shapes(cfg),
                       jnp.float32)
    # every token's first choice is expert 3, the rest ties among 0-5
    params["router"] = jnp.zeros((cfg.d_model, 64)).at[:, 3].set(1.0)
    x = jnp.abs(jax.random.normal(jax.random.key(7), (48, cfg.d_model)))
    _, idx, _ = moe.route(params, x, cfg)
    assert bool((idx[:, 0] == 3).all())
    y, _ = moe.moe_mlp(params, x, cfg, dropless=True)
    with jax.default_matmul_precision("highest"):
        want = REF.moe_ref(SMALL, params, x)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), rtol=1e-4,
                               atol=1e-5)
    # the training path's capacity drops what this router piles on one
    # expert, so the comparison above would see a drop
    dropped, _ = moe.moe_mlp(params, x, cfg)
    assert not np.allclose(np.asarray(dropped), np.asarray(want), atol=1e-3)


def test_moe_mlp_counts_the_pairs_on_held_experts():
    cfg = small_cfg(experts_held=8)
    params = init_tree(jax.random.key(8), moe.moe_param_shapes(cfg),
                       jnp.float32)
    x = jax.random.normal(jax.random.key(9), (40, cfg.d_model))
    y, aux = moe.moe_mlp(params, x, cfg, dropless=True)
    y2, aux2, pairs = moe.moe_mlp(params, x, cfg, dropless=True,
                                  count_held=True)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(y2))
    assert float(aux) == float(aux2)
    _, idx, _ = moe.route(params, x, cfg)
    assert int(pairs) == int((np.asarray(idx) < 8).sum())
    assert 0 < int(pairs) < 40 * cfg.top_k


# the parent's numbers: transformer_layer_costs(minitron-4b, seq, batch)
# as float.hex of (flops, act_bytes) of every layer, and the tokens the
# engine served for the reduced minitron-4b (bf16, seed 3)
MINITRON_COSTS = {(1, 1): ("0x1.3806000000000p+27", "0x1.8000000000000p+12"),
                  (128, 1): ("0x1.3b00000000000p+34", "0x1.8000000000000p+19"),
                  (1024, 4): ("0x1.5000000000000p+39",
                              "0x1.8000000000000p+24"),
                  (1288, 1): ("0x1.ae68600000000p+37",
                              "0x1.e300000000000p+22")}
MINITRON_TOKENS = {0: [310, 409, 420, 268, 310, 54, 405, 77],
                   1: [322, 87, 14, 498, 507, 242, 409, 235],
                   2: [151, 6, 226, 239, 56, 120, 56, 237],
                   3: [350, 127, 286, 55, 88, 5, 213, 392]}


@pytest.mark.parametrize("seq,batch", sorted(MINITRON_COSTS))
def test_minitron_costs_are_the_parents(seq, batch):
    layers = transformer_layer_costs(get_config("minitron-4b"), seq, batch)
    flops, act = MINITRON_COSTS[seq, batch]
    assert len(layers) == 32
    assert all(l.flops.hex() == flops and l.act_bytes.hex() == act
               and l.param_bytes == 0.0 for l in layers)


def test_minitron_served_tokens_are_the_parents():
    cfg = reduced_config("minitron-4b")
    eng = ContinuousBatchEngine(cfg, slots=2, max_len=48, seed=3)
    rng = np.random.default_rng(11)
    reqs = [Request(rid=i, prompt=rng.integers(0, cfg.vocab_size, size=n,
                                               dtype=np.int32),
                    max_new_tokens=8, arrived_at=0.0)
            for i, n in enumerate((5, 9, 7, 12))]
    done = eng.serve(reqs)
    assert {r.rid: r.output.tolist() for r in done} == MINITRON_TOKENS
    assert set(eng.cache) == {"layers", "pos"}


def test_planner_prices_the_published_model(params):
    cfg = get_config("deepseek-v2-lite-16b")
    layers = transformer_layer_costs(cfg, 512, 1)
    f, a = REF.planner_layers(PUBLISHED, 512)
    assert [l.flops for l in layers] == f.tolist()
    assert [l.act_bytes for l in layers] == a.tolist()
    # the dense layer apart from the MoE layers, MoE at top-6 + 2 shared
    # of the whole model whatever share is held
    assert layers[0].flops != layers[1].flops == layers[26].flops
    held = transformer_layer_costs(cfg.replace(experts_held=8), 512, 1)
    assert [l.flops for l in held] == [l.flops for l in layers]


@contextlib.contextmanager
def profiled(trace_dir):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def inside(inner, outer) -> bool:
    return (inner[3] == outer[3] and outer[1] <= inner[1]
            and inner[2] <= outer[2])


def test_serve_regions_cover_the_model(params, tmp_path):
    from repro.core.costs import AnalyticCost
    tracer = Tracer()
    with profiled(tmp_path):
        eng, done, steps = serve_small(params, [(5, 3), (9, 4), (7, 3)],
                                       cost=AnalyticCost(), obs=tracer)
    spans = tracer.all_spans()
    admits = [s for s in spans if (s.track, s.name) == ("serve", "admit")]
    assert sorted(s.tid for s in admits) == [0, 1, 2]
    decodes = [s for s in spans if (s.track, s.name) == ("serve", "decode")]
    assert len(decodes) == eng.steps == len(steps)
    prof = idle.load(str(tmp_path))
    names = [s[0] for s in prof.spans]
    assert names.count("serve/admit") == 3
    assert names.count("serve/decode") == len(decodes)
    for phase in ("plan", "prefill", "splice"):
        mine = [s for s in prof.spans if s[0] == f"serve/{phase}"]
        assert len(mine) == 3
        assert all(any(inside(s, a) for a in prof.spans
                       if a[0] == "serve/admit") for s in mine)


@pytest.mark.parametrize("held", [8, 0])
def test_counters_of_held_pairs_and_cache_rows(params, held):
    metrics = MetricsRegistry()
    lens = [(5, 6), (11, 3), (8, 5)]
    if held:
        eng, done, steps = serve_small(params, lens, metrics=metrics)
    else:
        # every expert held: the uncut layer's weights, all pairs land
        uncut = REF.make_params({**SMALL, "experts_held": 0}, 5)
        eng, done, steps = serve_small(uncut, lens, metrics=metrics,
                                       cfg=small_cfg(experts_held=0))
    pairs = metrics.counter("serve_expert_pairs_held_total").value
    rows = metrics.counter("serve_cache_rows_total").value
    # the count is a return of the step, not a leaf of the cache
    assert set(eng.cache) == {"dense_layers", "layers", "pos"}
    assert rows == sum(n + j + 1 for n, k in lens for j in range(k))
    n_moe = SMALL["num_layers"] - SMALL["first_dense_layers"]
    every = eng.steps * eng.slots * SMALL["top_k"] * n_moe
    if held:
        assert 0 < pairs < every
    else:
        assert pairs == every
