"""minitron-4b's configuration module states the model for the serve
driver exactly as the driver's own dense formulas did before they moved
there: the planner's layers, the model FLOPs of a prefill and of a
decoded token, and the CPU cut, pinned as numbers."""
from __future__ import annotations

import pytest

import small_cells
from common import load_json, load_module

MOD = load_module(small_cells.BENCH / "configs" / "minitron-4b.py")
TINY = {"d_model": 4, "head_dim": 2, "num_heads": 2, "num_kv_heads": 1,
        "d_ff": 8, "vocab_size": 10, "num_layers": 3, "mlp_act": "relu2"}
PUBLISHED = load_json(small_cells.BENCH / "configs"
                      / "minitron-4b.json")["model"]

# (keys, seq): (planner FLOPs a layer, activation bytes a layer, layers,
# prefill FLOPs, token FLOPs with head, token FLOPs without)
PINNED = {
    ("tiny", 1): (240.0, 8.0, 3, 800.0, 800.0, 720.0),
    ("tiny", 3): (816.0, 24.0, 3, 2384.0, 896.0, 816.0),
    ("tiny", 7): (2352.0, 56.0, 3, 6128.0, 1088.0, 1008.0),
    ("published", 128): (21139292160.0, 786432.0, 32, 674834153472.0,
                         6857687040.0, 5284823040.0),
    ("published", 1024): (180388626432.0, 6291456.0, 32, 5568051806208.0,
                          7210008576.0, 5637144576.0),
    ("published", 1288): (231073382400.0, 7913472.0, 32, 7070012669952.0,
                          7313817600.0, 5740953600.0),
}


@pytest.mark.parametrize("keys,seq", sorted(PINNED))
def test_minitron_module_matches_the_dense_formulas(keys, seq):
    m = TINY if keys == "tiny" else PUBLISHED
    flops, act, n, prefill, token, token_no_head = PINNED[keys, seq]
    f, a = MOD.planner_layers(m, seq)
    assert f.tolist() == [flops] * n and a.tolist() == [act] * n
    assert MOD.prefill_flops(m, seq) == prefill
    assert MOD.token_flops(m, seq, True) == token
    assert MOD.token_flops(m, seq, False) == token_no_head


def test_minitron_small_is_the_cut_the_tests_used():
    assert MOD.small(PUBLISHED) == {
        "num_layers": 2, "d_model": 256, "num_heads": 4, "num_kv_heads": 2,
        "head_dim": 64, "d_ff": 512, "vocab_size": 512}


def test_every_served_configuration_module_gives_what_the_driver_calls():
    spec = load_json(small_cells.ROOT / "BENCHMARK.json")
    serve = load_module(small_cells.BENCH / "drivers" / "serve.py")
    served = [c for c in (small_cells.run.Cell(spec, w["name"])
                          for w in spec["workloads"])
              if c.traffic["driver"] == "serve"]
    assert served
    for cell in served:
        mod = cell.config_module()
        assert all(callable(getattr(mod, f, None))
                   for f in serve.MODULE_FUNCTIONS), cell.name
