"""Accelerated batched tree inference over :class:`TreeArrays`.

``backend="jax"`` runs the level-synchronous descent as jitted XLA:
binning is the same f32 edge-comparison count as the host, the per-tree
descent is gather-driven, and trees accumulate through a *sequential*
``lax.scan`` in training order — additions of identical f64 values in
the identical order, so the result is bit-for-bit equal to
``GBTRegressor.predict`` / :func:`repro.kernels.tree_predict.ref.
predict_ref` (leaf values are pre-scaled by ``learning_rate`` on the
host, leaving the scan multiply-free — nothing for XLA to contract).

``backend="pallas"`` calls the fused TPU kernel
(:mod:`repro.kernels.tree_predict.kernel`): f32, within tolerance, node
arrays resident in VMEM (interpret mode off-TPU).
"""
# repro: module-tags=fma-sensitive
# (DET001: the scan must stay multiply-free/add-only — a dot/matmul
#  would reintroduce FMA contraction and break the f64 bitwise pin)
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from repro.kernels.tree_predict.ref import TreeArrays
from repro.obs.trace import region
from repro.x64 import x64


def _bin_codes(x, edges):
    """``code[n, f] = #{edges[f] < x[n, f]}`` — exact ``searchsorted``
    (side='left') semantics on f32, as comparison counts."""
    return jnp.sum(edges[None, :, :] < x[:, :, None], axis=-1,
                   dtype=jnp.int32)


def _descend(codes, feat, thr, left, right, max_depth: int):
    """``[N]`` leaf index per row for one tree (arrays are that tree's
    ``[M]`` rows)."""
    n = codes.shape[0]
    rows = jnp.arange(n)

    def level(_, node):
        f = feat[node]
        split = f >= 0
        goes_left = jnp.where(split, codes[rows, jnp.maximum(f, 0)]
                              <= thr[node], False)
        nxt = jnp.where(goes_left, left[node], right[node])
        return jnp.where(split, nxt, node)

    node0 = jnp.zeros(n, jnp.int32)
    if max_depth == 0:
        return node0
    return jax.lax.fori_loop(0, max_depth, level, node0)


def _predict_jax(x, edges, feat, thr, left, right, scaled_value, base,
                 max_depth: int):
    codes = _bin_codes(x, edges)

    def one_tree(carry, tree):
        tf, tt, tl, tr, tv = tree
        leaf = _descend(codes, tf, tt, tl, tr, max_depth)
        return carry + tv[leaf], None

    init = jnp.full((x.shape[0],), base, scaled_value.dtype)
    pred, _ = jax.lax.scan(one_tree, init,
                           (feat, thr, left, right, scaled_value))
    return pred


def predict_trees(x: np.ndarray, arrays: TreeArrays, *,
                  backend: str = "jax", blk: int = 512,
                  interpret: bool | None = None) -> np.ndarray:
    """``[N]`` f64 predictions for ``x [N, F]`` — the accelerated twin of
    ``GBTRegressor.predict`` (bit-for-bit on ``backend="jax"``, within
    f32 tolerance on ``backend="pallas"``)."""
    if backend == "pallas":
        from repro.kernels.tree_predict.kernel import tree_predict_kernel
        with region("predict", "bin"):
            codes = _bin_codes(jnp.asarray(np.asarray(x, np.float32)),
                               jnp.asarray(arrays.edges))
        with region("predict", "kernel"):
            out = tree_predict_kernel(
                jnp.asarray(codes, jnp.int32),
                jnp.asarray(arrays.feature),
                jnp.asarray(arrays.threshold_bin),
                jnp.asarray(arrays.left), jnp.asarray(arrays.right),
                jnp.asarray(arrays.learning_rate * arrays.value,
                            jnp.float32),
                max_depth=arrays.max_depth, blk=blk, interpret=interpret)
        with region("predict", "sync"):
            return np.asarray(out, np.float64) + arrays.base
    if backend != "jax":
        raise ValueError(f"unknown tree-predict backend {backend!r}; "
                         "expected 'jax' or 'pallas'")
    x32 = np.asarray(x, np.float32)
    with region("predict", "jax"), x64():
        fn = getattr(arrays, "_jitted", None)
        if fn is None:
            # learning_rate folded into the leaf values host-side, in
            # f64 — the exact per-leaf products the host accumulation
            # produces (the scan is multiply-free)
            scaled = arrays.learning_rate * arrays.value
            consts = tuple(jnp.asarray(a) for a in
                           (arrays.edges, arrays.feature,
                            arrays.threshold_bin, arrays.left,
                            arrays.right, scaled))
            depth = arrays.max_depth

            def fn(xv):
                return _predict_jax(xv, *consts, arrays.base, depth)

            fn = jax.jit(fn)
            # memoised on the (frozen) arrays instance: one compile per
            # fitted model, dropped with it
            object.__setattr__(arrays, "_jitted", fn)
        return np.asarray(fn(jnp.asarray(x32)), np.float64)
