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
import re

import pytest

jax = pytest.importorskip("jax")
jnp = jax.numpy

from repro.kernels.decide_split.kernel import (SPEC_LEN,  # noqa: E402
                                               decide_split_kernel)
from repro.kernels.decide_split.ops import BLOCK_E, BLOCK_S  # noqa: E402
from repro.kernels.tree_predict.kernel import tree_predict_kernel  # noqa: E402
from repro.sim import fleet  # noqa: E402
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


def _traced_kernels(text: str) -> list[str]:
    """The compiled HLO's Pallas kernels under the name a device trace
    gives them, and by which its readers find them: instructions named
    ``%tpu_custom_call``."""
    lines = (ln.strip().removeprefix("ROOT ") for ln in text.splitlines())
    return [ln for ln in lines
            if ln.startswith("%tpu_custom_call") and "custom-call(" in ln]


#: (envs, splits) of the decide kernel: the smoke's sweep, the
#: ``edge-metro.sweep`` cell's users at its fewest and most splits, and an
#: admission
DECIDE_SHAPES = {"smoke": (16384, 1025), "sweep3": (1 << 20, 3),
                 "sweep34": (1 << 20, 34), "admit": (1, 34)}
#: an env column relaid for the kernel, one env a row: ``f32[E,1]``
ENV_COLUMN_COPY = re.compile(r"= f32\[\d+,1\]\S* copy\(")


@pytest.mark.parametrize("shape", sorted(DECIDE_SHAPES))
def test_decide_split_kernel_compiles(one_chip, no_compile_cache, shape):
    """The decide kernel at ``DECIDE_SHAPES[shape]`` and the ops block
    sizes: the env columns enter as they are, with no relayout copy and
    no temp beyond a few tiles."""
    n_envs, n_splits = DECIDE_SHAPES[shape]
    f32 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.float32,
                            sharding=one_chip)
    fn = functools.partial(decide_split_kernel, block_e=BLOCK_E,
                           block_s=BLOCK_S, interpret=False)
    compiled = jax.jit(fn).lower(*[f32((n_splits,))] * 3,
                                 *[f32((n_envs,))] * 7,
                                 f32((SPEC_LEN,))).compile()
    text = compiled.as_text()
    kernels = _traced_kernels(text)
    assert len(kernels) == 1 and " = (s32[" in kernels[0]
    assert not ENV_COLUMN_COPY.search(text)
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20


#: per-level node counts of the compiled layouts: a full depth-7 tree
#: (255 of 256 nodes), and the catalog cell's ensemble (100 trees of up to
#: 1,427 nodes, depth 12: the widest level d over its trees)
TREE_SHAPES = {
    "depth7": (65536, 16, 100, (1, 2, 4, 8, 16, 32, 64, 128)),
    "catalog": (262144, 7, 100, (1, 2, 4, 8, 16, 32, 62, 104, 154, 206,
                                 258, 332, 386)),
}


@pytest.mark.parametrize("shape", sorted(TREE_SHAPES))
def test_tree_predict_kernel_compiles(one_chip, no_compile_cache, shape):
    """The level-layout tree kernel at ``TREE_SHAPES[shape]``: rows,
    features, trees, level widths."""
    from repro.kernels.tree_predict.kernel import LANES, level_slots
    n_rows, n_feat, n_trees, counts = TREE_SHAPES[shape]
    slots = level_slots(counts)
    levels = tuple((lo, w, d < len(counts) - 1, d > 0)
                   for d, (lo, w) in enumerate(slots))
    n_slots = slots[-1][0] + slots[-1][1]

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    fn = functools.partial(tree_predict_kernel, levels=levels,
                           n_trees=n_trees, thr_bits=6,
                           feat_bits=(n_feat - 1).bit_length(),
                           interpret=False)
    chunks = -(-n_trees // LANES)
    text = _hlo(fn, sds((n_rows, n_feat), jnp.int32),
                sds((chunks, n_slots, LANES), jnp.int32),
                sds((chunks, n_slots, LANES), jnp.float32))
    assert "tpu_custom_call" in text
    kernels = _traced_kernels(text)
    assert len(kernels) == 1 and " = f32[" in kernels[0]


def test_decide_latency_f64_compiles(one_chip, no_compile_cache):
    """The sharded decide's f64 body, which the chip emulates."""
    with x64():
        f64 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.float64,
                                sharding=one_chip)
        text = _hlo(functools.partial(fleet._decide_latency, eff=0.4),
                    *[f64((64,))] * 2, *[f64((16384,))] * 5)
    assert "f64" in text
