"""chip_smoke.py off the chip: it refuses to report without a TPU, and
each of its phases passes its own checks at a small size on the CPU
(Pallas in interpret mode), so a chip call is spent only on what a CPU
cannot show.  Also the device-kind table the profiler stamps records
with, and where the entry points keep the compile cache."""
import importlib.util
import os
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from repro.hw import DEVICE_KINDS, TPU_V5E, get_device, spec_for_kind

ROOT = Path(__file__).resolve().parent.parent
SCRIPT = ROOT / "chip_smoke.py"


def _load_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def smoke():
    return _load_smoke()


@pytest.fixture(scope="module")
def gbt(smoke):
    return smoke.fit_predictor(60, n_trees=20, max_depth=5)


def _run(args, cwd, extra_env=None):
    env = {k: v for k, v in os.environ.items()
           if k not in ("PYTHONPATH", "XLA_FLAGS")}
    env.update({"JAX_PLATFORMS": "cpu", **(extra_env or {})})
    return subprocess.run([sys.executable, *args], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


# --------------------------------------------------------------------------
# no chip, no result
# --------------------------------------------------------------------------
@pytest.mark.parametrize("flags", [[], ["--four-chips"]])
def test_smoke_refuses_cpu(flags):
    r = _run([str(SCRIPT), *flags], ROOT)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "needs a TPU" in r.stderr


def test_smoke_refuses_without_repository(tmp_path):
    shutil.copy(SCRIPT, tmp_path / "chip_smoke.py")
    r = _run(["chip_smoke.py"], tmp_path)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout


def test_smoke_import_graph_leaves_xla_flags_alone():
    """Nothing the smoke imports assigns XLA_FLAGS (the dry-run and the
    perf experiments do, and must stay off its import graph)."""
    r = _run(["-c", "import os, sys, chip_smoke; "
              "assert 'XLA_FLAGS' not in os.environ; "
              "assert 'repro.launch.dryrun' not in sys.modules; "
              "print('CLEAN')"], ROOT)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "CLEAN" in r.stdout


# --------------------------------------------------------------------------
# every phase at a small size on the CPU
# --------------------------------------------------------------------------
def test_phase_profile(smoke):
    out = smoke.phase_profile(smoke.PROFILE_WORKLOADS[:1], 2)
    assert out["device"] == "xps15-i5"       # the CPU's stand-in
    assert len(out["records"]) == 1


def test_phase_predict(smoke, gbt):
    out = smoke.phase_predict(gbt, 700, on_chip=False)
    assert out["rows"] == 700 and out["jax_f64_bitwise"]


@pytest.mark.parametrize("layer_counts", [(8,), (40,)])
def test_phase_decide(smoke, gbt, layer_counts):
    out = smoke.phase_decide(gbt, 300, layer_counts, on_chip=False)
    for n in layer_counts:
        assert out[f"L{n}"]["jax"]["bitwise"]  # f64 is native on the CPU


def test_phase_fleet(smoke):
    out = smoke.phase_fleet(8, 6000.0)
    assert out["bitwise"] and out["tasks"] >= 512


def test_phase_serve(smoke, gbt):
    from repro.configs import reduced_config
    out = smoke.phase_serve(reduced_config("qwen3-1.7b"), gbt, slots=2,
                            prompt_len=12, max_new=(4, 6, 3),
                            on_chip=False)
    assert out["requests"] == 3 and out["tokens_out"] == 13


def test_phase_sharded_four_cpu_devices():
    """The --four-chips path on four virtual CPU devices: pad and trim,
    the env axis split over all four, agreement with one device."""
    r = _run(["-c", "import chip_smoke as s, json; "
              "print(json.dumps(s.phase_sharded(130, 8, 4)))"], ROOT,
             {"XLA_FLAGS": "--xla_force_host_platform_device_count=4"})
    assert r.returncode == 0, r.stderr[-2000:]
    assert '"padded": 132' in r.stdout


def test_near_tie_tolerance(smoke):
    """Eight bf16 ulps of the top logit: 2**-7 relative per ulp."""
    assert smoke.near_tie(8 * 2.0 ** -6, 3.0)      # ulp(3.0) = 2**-6
    assert not smoke.near_tie(9 * 2.0 ** -6, 3.0)
    assert smoke.near_tie(0.0, -40.0)


# --------------------------------------------------------------------------
# device kinds and the compile cache
# --------------------------------------------------------------------------
def test_device_kind_table():
    import jax
    assert spec_for_kind("TPU v5 lite") is TPU_V5E
    assert spec_for_kind("cpu") is get_device("xps15-i5")
    assert jax.devices()[0].device_kind in DEVICE_KINDS


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no DeviceSpec"):
        spec_for_kind("TPU v7 imaginary")


def test_profile_record_names_its_device():
    from repro.core.dataset import project_hardware
    from repro.core.profiler import profile_workload
    from repro.core.workloads import WorkloadConfig
    rec = profile_workload(WorkloadConfig("mlp", 0, epochs=5,
                                          optimiser="sgd", lr=1e-3,
                                          batch_size=32, dataset_size=64),
                           measure=False)
    assert rec.device == "xps15-i5"
    projected = project_hardware([rec], get_device(rec.device))
    assert {p.device for p in projected} \
        == {"gtx-1650", "pi5-arm", "jetson-orin-nano", "edge-server-a100"}
    assert np.all([p.total_time_s > 0 for p in projected])


def test_compile_cache_placement(monkeypatch):
    import jax

    from repro.launch import cache
    was = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        assert cache.use_compile_cache() == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == str(ROOT
                                                           / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", was)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        assert cache.use_compile_cache() == "/elsewhere"
        assert jax.config.jax_compilation_cache_dir == was  # left to JAX
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
