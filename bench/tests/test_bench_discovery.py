"""A cell, a configuration, a traffic mix and a per-layer metric are
added by adding files (and entries in BENCHMARK.json) alone: the harness
finds them by name, and no file it already had changes.  So is a served
model: its configuration module states its layers to the serve
driver."""
from __future__ import annotations

import hashlib
import json
import shutil

import pytest

import small_cells
from common import load_module

TOY_DRIVER = '''
import time
import numpy as np


class State:
    pass


def setup(ctx):
    import jax.numpy as jnp
    st = State()
    st.x = jnp.full((ctx.traffic["n"],), float(ctx.cfg["scale"]))
    (st.x * 2).block_until_ready()
    return st


def window(ctx, st, seconds):
    calls, t0 = 0, time.perf_counter()
    while time.perf_counter() - t0 < seconds:
        ctx.trace.poll(time.perf_counter() - t0)
        with ctx.spans("toy"):
            st.y = (st.x * 2).block_until_ready()
        calls += 1
    return {"e2e": {"toy_per_s": calls / seconds},
            "counters": {"calls": calls}, "attempted": calls, "failed": 0}


def release(st):
    pass


def readings(ctx, st):
    return {"toy_err": float(np.max(np.abs(np.asarray(st.y)
                                           - 2 * ctx.cfg["scale"])))}
'''

TOY_METRIC = '''
def read(record):
    return record["counters"]["calls"]
'''


def tree_digest(root):
    return {str(p.relative_to(root)): hashlib.sha256(p.read_bytes()
                                                     ).hexdigest()
            for p in sorted(root.rglob("*")) if p.is_file()
            and "__pycache__" not in p.parts}


def test_new_cell_config_traffic_and_metric_are_found(tmp_path):
    shutil.copytree(small_cells.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = tree_digest(tmp_path / "bench")
    b = tmp_path / "bench"
    (b / "configs" / "toy.json").write_text(json.dumps({"scale": 3.0}))
    (b / "configs" / "toy.py").write_text('"""toy: nothing to build."""\n')
    (b / "traffic" / "toyload.json").write_text(
        json.dumps({"driver": "toy", "n": 1024}))
    (b / "drivers" / "toy.py").write_text(TOY_DRIVER)
    (b / "metrics" / "toy.calls.py").write_text(TOY_METRIC)
    (b / "limits" / "toy.cell.json").write_text(json.dumps({"toy_err": 0}))
    spec = json.loads((small_cells.ROOT / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "toy", "source": "none",
                            "file": "bench/configs/toy.json", "reduced": [],
                            "why": "test"})
    spec["workloads"].append({"name": "toy.cell", "config": "toy",
                              "traffic": "toyload", "chips": 1,
                              "why": "test"})
    spec["end_to_end"].append({"name": "toy_per_s", "unit": "calls/s",
                               "better": "higher", "bound": 0.1,
                               "source": "host_clock",
                               "workloads": ["toy.cell"]})
    spec["per_layer"].append({"name": "toy.calls", "unit": "calls",
                              "better": "higher", "source": "host_clock",
                              "layer": "toy", "moves": "toy_per_s",
                              "workloads": ["toy.cell"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))

    copy = load_module(b / "run.py", "bench_copy_run")
    cell = copy.Cell(spec, "toy.cell", root=tmp_path)
    assert cell.traffic["driver"] == "toy"
    assert cell.cfg == {"scale": 3.0}
    assert sorted(m["name"] for m in cell.end_to_end) == ["setup_s",
                                                         "toy_per_s"]
    assert [m["name"] for m in cell.per_layer] == ["toy.calls"]

    import jax
    out = copy.run_cell(cell, 5, 0.2, False, jax.devices(),
                        small_cells.PEAK, "")
    assert out["correct"] and out["checks"]["toy_err"]["value"] == 0.0
    assert set(out["metrics"]) == {"toy_per_s", "setup_s"}
    traced = copy.run_cell(cell, 6, 0.2, True, jax.devices(),
                           small_cells.PEAK, "")
    assert traced["metrics"]["toy.calls"]["value"] > 0
    assert "breakdown" in traced

    after = tree_digest(b)
    assert {k: v for k, v in after.items() if k in before} == before


# A served model whose layers the planner prices otherwise than the dense
# formula does: minitron-4b's block at a tiny size, whose module counts
# the MLP three times over.  Its weights and reference are minitron-4b's.
TOYSERVE_PLANNER = '''
CALLS = []


def planner_layers(m, seq):
    CALLS.append(seq)
    flops, act = base.planner_layers(m, seq)
    mlp = 2 * 2.0 * seq * m["d_model"] * m["d_ff"]      # relu2: 2 matrices
    return flops + 2 * mlp, act
'''

TOYSERVE_MODULE = '''
from pathlib import Path

from common import load_module

base = load_module(Path(__file__).with_name("minitron-4b.py"))
make_params, forward = base.make_params, base.forward
logits_at, fp8_weights = base.logits_at, base.fp8_weights
prefill_flops = base.prefill_flops

TOKENS = []


def token_flops(m, context, with_head):
    TOKENS.append(context)
    return base.token_flops(m, context, with_head)
'''


def toyserve_config() -> dict:
    """minitron-4b's configuration with its model at two layers of width
    64."""
    cfg = json.loads((small_cells.BENCH / "configs"
                      / "minitron-4b.json").read_text())
    cfg["model"].update(name="toyserve", num_layers=2, d_model=64,
                        num_heads=4, num_kv_heads=2, head_dim=16, d_ff=128,
                        vocab_size=256)
    cfg["serving"].update(slots=4, max_len=80)
    return cfg


def add_toyserve(tmp_path, name: str, module: str):
    """A copy of the benchmark with the served cell ``<name>.chat`` of
    configuration ``name`` (``toyserve_config()``, module ``module``)
    added by new files and entries; returns ``(bench dir, spec)``."""
    shutil.copytree(small_cells.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    b = tmp_path / "bench"
    (b / "configs" / f"{name}.json").write_text(
        json.dumps(toyserve_config()))
    (b / "configs" / f"{name}.py").write_text(module)
    chat = json.loads((b / "traffic" / "chat.json").read_text())
    chat.update(prompt_lens=[8, 16, 32, 64], answer_median=6, answer_min=2,
                answer_max=15, rate_per_s=4.0, check_requests=4,
                trace_start_s=0.0, trace_s=1.0)
    (b / "traffic" / "toychat.json").write_text(json.dumps(chat))
    (b / "limits" / f"{name}.chat.json").write_text(json.dumps(
        {"token_gap_mean": 5e-3, "plan_err_rel": 1e-5}))
    spec = json.loads((small_cells.ROOT / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": name, "source": "none",
                            "file": f"bench/configs/{name}.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": f"{name}.chat", "config": name,
                              "traffic": "toychat", "chips": 1,
                              "why": "test"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "minitron-4b.chat" in m.get("workloads", []):
            m["workloads"].append(f"{name}.chat")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    return b, spec


def test_new_served_configuration_is_found(tmp_path):
    b, spec = add_toyserve(tmp_path, "toyserve",
                           TOYSERVE_MODULE + TOYSERVE_PLANNER)
    before = tree_digest(small_cells.BENCH)
    copy = load_module(b / "run.py", "bench_copy_run_toyserve")
    cell = copy.Cell(spec, "toyserve.chat", root=tmp_path)
    mod = cell.config_module()
    dense = load_module(b / "configs" / "minitron-4b.py")
    m = cell.cfg["model"]
    assert mod.planner_layers(m, 16)[0][0] > dense.planner_layers(m, 16)[0][0]
    mod.CALLS.clear()

    import jax
    out = copy.run_cell(cell, 5, 2.0, False, jax.devices(),
                        small_cells.PEAK, "")
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert set(out["checks"]) == {"token_gap_mean", "plan_err_rel"}
    assert set(out["metrics"]) == {"serve_latency_p95_s", "setup_s"}
    # every admission's plan was checked against the module's layers
    assert len(mod.CALLS) == out["attempted"]
    traced = copy.run_cell(cell, 6, 2.0, True, jax.devices(),
                           small_cells.PEAK, "")
    assert traced["correct"], traced["checks"]
    assert traced["metrics"]["serve.mfu"]["value"] > 0
    assert mod.TOKENS
    after = tree_digest(b)
    assert {k: v for k, v in after.items() if k in before} == before


def test_served_module_without_planner_layers_fails_at_setup(tmp_path):
    b, spec = add_toyserve(tmp_path, "toybare", TOYSERVE_MODULE)
    copy = load_module(b / "run.py", "bench_copy_run_toybare")
    cell = copy.Cell(spec, "toybare.chat", root=tmp_path)
    import jax
    with pytest.raises(AttributeError, match="planner_layers"):
        copy.run_cell(cell, 5, 2.0, False, jax.devices(), small_cells.PEAK,
                      "")
