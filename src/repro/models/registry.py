"""Uniform model API over all architecture families.

``build_model(cfg)`` returns a :class:`ModelAPI` whose five callables are
pure functions of (params, batch[, cache]) — directly jit/pjit-able by the
launchers, the profiler, and the tests.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax

from repro.models import encdec, hybrid, transformer, xlstm_model

PyTree = Any


@dataclasses.dataclass(frozen=True)
class ModelAPI:
    cfg: Any
    init_params: Callable[[jax.Array], PyTree]
    train_loss: Callable[[PyTree, dict], tuple[jax.Array, dict]]
    prefill: Callable[[PyTree, dict, int], tuple[jax.Array, PyTree]]
    decode_step: Callable[[PyTree, dict, PyTree], tuple[jax.Array, PyTree]]
    init_cache: Callable[[int, int], PyTree]
    cache_shapes: Callable[[int, int], PyTree]
    param_shapes: Callable[[], PyTree]


def build_model(cfg, *, impl: str = "chunked") -> ModelAPI:
    fam = cfg.family
    if fam in ("dense", "moe", "vlm"):
        mod = transformer
        return ModelAPI(
            cfg=cfg,
            init_params=lambda key: mod.init_params(cfg, key),
            train_loss=lambda p, b: mod.train_loss(p, b, cfg, impl=impl),
            prefill=lambda p, b, m: mod.prefill(p, b, cfg, m, impl=impl),
            decode_step=lambda p, b, c, **kw: mod.decode_step(p, b, c, cfg,
                                                              **kw),
            init_cache=lambda bs, m: mod.init_cache(cfg, bs, m),
            cache_shapes=lambda bs, m: mod.cache_shapes(cfg, bs, m),
            param_shapes=lambda: mod.param_shapes(cfg),
        )
    if fam == "ssm":
        mod = xlstm_model
    elif fam == "hybrid":
        mod = hybrid
    elif fam == "audio":
        mod = encdec
    else:
        raise ValueError(f"unknown family {fam!r}")
    kwargs = {} if fam == "ssm" else {"impl": impl}
    return ModelAPI(
        cfg=cfg,
        init_params=lambda key: mod.init_params(cfg, key),
        train_loss=lambda p, b: mod.train_loss(p, b, cfg, **kwargs),
        prefill=lambda p, b, m: mod.prefill(p, b, cfg, m, **kwargs),
        decode_step=lambda p, b, c: mod.decode_step(p, b, c, cfg),
        init_cache=lambda bs, m: mod.init_cache(cfg, bs, m),
        cache_shapes=lambda bs, m: mod.cache_shapes(cfg, bs, m),
        param_shapes=lambda: mod.param_shapes(cfg),
    )


def param_count(shapes: PyTree) -> int:
    import numpy as np
    leaves = jax.tree_util.tree_leaves(
        shapes, is_leaf=lambda x: isinstance(x, tuple))
    return int(sum(int(np.prod(s)) for s in leaves if isinstance(s, tuple)))
