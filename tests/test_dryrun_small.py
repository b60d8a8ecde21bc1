"""Dry-run machinery on a tiny forced-host-device mesh (subprocess so the
512-device production flag never leaks into other tests)."""
import json
import subprocess
import sys

import pytest

# subprocess jax re-imports + 8-device mesh dry-runs: minutes on CPU —
# excluded from the fast lane, covered by the tier-1 job
pytestmark = pytest.mark.slow

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax
from repro.configs import reduced_config
from repro.configs.base import InputShape
from repro.launch.mesh import make_debug_mesh
from repro.launch.steps import assemble
from repro.roofline import analyze, model_flops_estimate

mesh = jax.make_mesh((4, 2), ("data", "model"))
out = {}
for arch, shape in [
    ("qwen3-1.7b", InputShape("t", 64, 8, "train")),
    ("deepseek-moe-16b", InputShape("p", 64, 8, "prefill")),
    ("zamba2-1.2b", InputShape("d", 64, 8, "decode")),
]:
    cfg = reduced_config(arch)
    step = assemble(cfg, shape, mesh, auto_knobs=False)
    with mesh:
        compiled = step.jitted.lower(*step.arg_specs).compile()
    cost = compiled.cost_analysis()
    roof = analyze(arch, cost, compiled.as_text(), chips=8,
                   model_flops=model_flops_estimate(cfg, shape))
    out[arch] = {"flops": roof.flops, "dominant": roof.dominant,
                 "mem": compiled.memory_analysis().temp_size_in_bytes}
print(json.dumps(out))
"""


def test_dryrun_pipeline_on_debug_mesh():
    res = subprocess.run([sys.executable, "-c", SCRIPT], capture_output=True,
                         text=True, timeout=540,
                         env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                              "JAX_PLATFORMS": "cpu"})
    assert res.returncode == 0, res.stderr[-2000:]
    out = json.loads(res.stdout.strip().splitlines()[-1])
    assert set(out) == {"qwen3-1.7b", "deepseek-moe-16b", "zamba2-1.2b"}
    for arch, rec in out.items():
        assert rec["flops"] > 0, arch
        assert rec["dominant"] in ("compute", "memory", "collective")
