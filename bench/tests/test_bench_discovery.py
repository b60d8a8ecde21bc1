"""A cell, a configuration, a traffic mix and a per-layer metric are
added by adding files (and entries in BENCHMARK.json) alone: the harness
finds them by name, and no file it already had changes."""
from __future__ import annotations

import hashlib
import json
import shutil

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
