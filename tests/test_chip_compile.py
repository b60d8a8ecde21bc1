"""The main path's Pallas kernels and its f64 decide compile for a TPU v5e.

Nothing runs: each program is compiled for a described (not attached)
v5e chip at the sizes ``chip_smoke.py`` drives, which is what catches a
block shape the TPU lowering refuses or a kernel over its VMEM budget
before any chip time is spent.  The topology is described inside a
fixture, so only the worker that runs this file loads the TPU compiler;
where it cannot be described the tests skip.
"""
import functools
import os

import pytest

jax = pytest.importorskip("jax")
jnp = jax.numpy

from repro.kernels.decide_split import ops as decide_ops  # noqa: E402
from repro.kernels.decide_split.kernel import (SPEC_LEN,  # noqa: E402
                                               decide_split_kernel)
from repro.kernels.tree_predict.kernel import tree_predict_kernel  # noqa: E402
from repro.x64 import x64  # noqa: E402


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                        # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    """A compile for a described chip can be written to the persistent
    cache but never read back here; keep these out of it."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _hlo(fn, *shapes) -> str:
    return jax.jit(fn).lower(*shapes).compile().as_text()


def test_decide_split_kernel_compiles(one_chip, no_compile_cache):
    """16384 envs × L+1 = 1025 splits at the ops defaults."""
    f32 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.float32,
                            sharding=one_chip)
    fn = functools.partial(decide_split_kernel, block_e=256, block_s=128,
                           interpret=False)
    text = _hlo(fn, *[f32((1025,))] * 3, *[f32((16384,))] * 7,
                f32((SPEC_LEN,)))
    assert "tpu_custom_call" in text


def test_tree_predict_kernel_compiles(one_chip, no_compile_cache):
    """65536 rows, 100 trees of up to 256 nodes, depth 7."""
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    fn = functools.partial(tree_predict_kernel, max_depth=7,
                           interpret=False)
    text = _hlo(fn, sds((65536, 16), jnp.int32),
                *[sds((100, 256), jnp.int32)] * 4,
                sds((100, 256), jnp.float32))
    assert "tpu_custom_call" in text


def test_decide_latency_f64_compiles(one_chip, no_compile_cache):
    """The jax backend's f64 decide, which the chip emulates."""
    with x64():
        f64 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.float64,
                                sharding=one_chip)
        text = _hlo(functools.partial(decide_ops._decide_latency, eff=0.4),
                    *[f64((64,))] * 2, *[f64((16384,))] * 5)
    assert "f64" in text
