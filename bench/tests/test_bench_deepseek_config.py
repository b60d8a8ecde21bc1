"""deepseek-v2-lite's configuration module: its parameter tree is the
program's, its planner layers are the program's pricing of the whole
published model, and what ``serve.mfu`` and ``serve.weight_read_share``
count is pinned as numbers at the published widths."""
from __future__ import annotations

import math

import jax
import pytest

import small_cells
from common import load_json, load_module

MOD = load_module(small_cells.BENCH / "configs" / "deepseek-v2-lite.py")
CFG = load_json(small_cells.BENCH / "configs" / "deepseek-v2-lite.json")
PUBLISHED = CFG["model"]
READER = load_module(small_cells.BENCH / "metrics"
                     / "serve.weight_read_share.py")


def _leaves(tree):
    return jax.tree_util.tree_leaves(
        tree, is_leaf=lambda s: isinstance(s, tuple))


def test_the_file_holds_the_published_numbers_and_the_share():
    m = PUBLISHED
    assert (m["num_layers"], m["d_model"], m["num_heads"], m["d_ff"],
            m["moe_d_ff"], m["kv_lora_rank"], m["qk_nope_dim"],
            m["qk_rope_dim"], m["v_head_dim"], m["vocab_size"]) == (
        CFG["num_hidden_layers"], CFG["hidden_size"],
        CFG["num_attention_heads"], CFG["intermediate_size"],
        CFG["moe_intermediate_size"], CFG["kv_lora_rank"],
        CFG["qk_nope_head_dim"], CFG["qk_rope_head_dim"], CFG["v_head_dim"],
        CFG["vocab_size"])
    assert (m["num_experts"], m["top_k"], m["num_shared_experts"],
            m["first_dense_layers"], m["norm_topk_prob"]) == (
        CFG["n_routed_experts"], CFG["num_experts_per_tok"],
        CFG["n_shared_experts"], CFG["first_k_dense_replace"],
        CFG["norm_topk_prob"])
    rs = CFG["rope_scaling"]
    assert rs["type"] == "yarn"
    assert (m["yarn_factor"], m["yarn_original_max_pos"], m["yarn_beta_fast"],
            m["yarn_beta_slow"], m["yarn_mscale"],
            m["yarn_mscale_all_dim"]) == (
        rs["factor"], rs["original_max_position_embeddings"],
        rs["beta_fast"], rs["beta_slow"], rs["mscale"], rs["mscale_all_dim"])
    assert m["experts_held"] == CFG["experts_held"] == 8
    assert CFG["reduced"] == ["experts_held"]


def test_the_parameter_tree_is_the_programs():
    from repro.configs.base import ModelConfig
    from repro.models import transformer
    mine = MOD.param_shapes(PUBLISHED)
    program = transformer.param_shapes(ModelConfig(**PUBLISHED))
    assert jax.tree_util.tree_structure(
        mine, is_leaf=lambda s: isinstance(s, tuple)) == \
        jax.tree_util.tree_structure(
            program, is_leaf=lambda s: isinstance(s, tuple))
    assert _leaves(mine) == _leaves(program)
    # 3.11 B parameters this chip holds, 6.22 GB in bf16
    assert sum(math.prod(s) for s in _leaves(mine)) == 3_110_989_312


@pytest.mark.parametrize("seq", [1, 512, 1024, 2048])
def test_planner_layers_are_the_programs_pricing(seq):
    from repro.configs.base import ModelConfig
    from repro.core.offload import transformer_layer_costs
    f, a = MOD.planner_layers(PUBLISHED, seq)
    layers = transformer_layer_costs(ModelConfig(**PUBLISHED), seq, 1)
    assert f.tolist() == [lc.flops for lc in layers]
    assert a.tolist() == [lc.act_bytes for lc in layers]


def test_counted_work_at_the_published_widths():
    m = PUBLISHED
    # every weight but the routed experts and the embedding, bf16
    assert MOD.step_weight_bytes(m) == 2_204_088_320.0
    assert MOD.prefill_flops(m, 2048) == 4_925_419_421_696.0
    assert MOD.token_flops(m, 1024, True) == 3_503_554_560.0
    # the routed share: top-6 x 8/64 of the experts a token would see
    routed = 26 * 2 * 3 * 2048 * 1408 * 6
    everything = dict(m, experts_held=64)
    assert MOD.token_flops(everything, 1024, True) \
        - MOD.token_flops(m, 1024, True) == pytest.approx(
            routed * (1 - 8 / 64), rel=1e-12)


def _record(cfg, steps, seconds=5.0):
    return {"cfg": cfg, "counters": {"traced_steps": steps},
            "traced_s": seconds, "peak": small_cells.PEAK}


def test_weight_read_share_reads_only_where_the_module_states_bytes():
    got = READER.read(_record(CFG, 100))
    assert got == pytest.approx(
        100.0 * 100 * 2_204_088_320.0 / 819e9 / 5.0, rel=1e-12)
    assert READER.read(_record(CFG, 0)) is None
    minitron = load_json(small_cells.BENCH / "configs" / "minitron-4b.json")
    assert READER.read(_record(minitron, 100)) is None
