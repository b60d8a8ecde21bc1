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
(:mod:`repro.kernels.tree_predict.kernel`): f32, within tolerance, over
the ensemble's level layout (:func:`level_layout`, built once per
ensemble), interpret mode off-TPU.  The kernel compiles once per static
signature (row count, level layout, bit widths, block size), so repeated
calls at one shape dispatch the compiled program.
"""
# repro: module-tags=fma-sensitive
# (DET001: the scan must stay multiply-free/add-only — a dot/matmul
#  would reintroduce FMA contraction and break the f64 bitwise pin)
from __future__ import annotations

import dataclasses

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


@dataclasses.dataclass(frozen=True)
class LevelLayout:
    """The kernel's node slots: level ``d`` of every tree in slots ``[lo_d,
    lo_d + w_d)``, breadth-first, a split's children adjacent.

    ``node`` and ``value`` are ``[C, S, 128]``: tree ``t`` in lane ``t %
    128`` of chunk ``t // 128``.  A split's ``node`` packs ``left <<
    (thr_bits + feat_bits) | feature << thr_bits | threshold_bin``, with
    ``left`` its left child's index within level ``d + 1`` (right = left
    + 1); a leaf's is -1 and its ``value`` the f32 ``learning_rate *
    value`` (0 for a split).  Slots no node fills read as leaves of value
    0 and are never reached.
    """
    node: np.ndarray                  # [C, S, 128] int32
    value: np.ndarray                 # [C, S, 128] f32
    levels: tuple                     # ((lo, width, has_split, has_leaf),)
    n_trees: int
    thr_bits: int
    feat_bits: int

    @property
    def lanes_per_tree(self) -> int:
        """Slots a row's descent scans per tree: the sum of the padded
        level widths (the one-hot's extent over all levels)."""
        return sum(width for _, width, _, _ in self.levels)


def _tree_levels(feature, left, right, max_depth: int) -> list:
    """Node ids of one tree, level by level, breadth-first (children of a
    split in (left, right) order)."""
    levels = [np.zeros(1, np.int64)]
    for _ in range(max_depth):
        nodes = levels[-1]
        split = nodes[feature[nodes] >= 0]
        levels.append(np.stack([left[split], right[split]], 1).ravel())
    return levels


def level_layout(arrays: TreeArrays) -> LevelLayout:
    """Build the kernel's :class:`LevelLayout` from ``arrays`` (numpy,
    host side)."""
    from repro.kernels.tree_predict.kernel import LANES, level_slots
    n_trees, depth = arrays.n_trees, arrays.max_depth
    per_tree = [_tree_levels(arrays.feature[t], arrays.left[t],
                             arrays.right[t], depth)
                for t in range(n_trees)]
    counts = np.array([[len(lv) for lv in tl] for tl in per_tree],
                      np.int64).reshape(n_trees, depth + 1)
    lo, widths = np.array(level_slots(counts.max(axis=0, initial=1))).T
    thr_bits = max(1, int(arrays.threshold_bin.max(initial=0)).bit_length())
    feat_bits = max(1, int(arrays.feature.max(initial=0)).bit_length())
    if (thr_bits + feat_bits + int(widths.max()).bit_length() > 31
            or arrays.threshold_bin.min(initial=0) < 0):
        raise ValueError("child, feature and threshold bin do not pack "
                         "into one int32")
    n_chunks = max(1, -(-n_trees // LANES))
    shape = (n_chunks * LANES, int(lo[-1] + widths[-1]))
    node = np.full(shape, -1, np.int32)
    value = np.zeros(shape, np.float32)
    has_split = np.zeros(depth + 1, bool)
    has_leaf = np.zeros(depth + 1, bool)
    scaled = arrays.learning_rate * arrays.value
    for t, tree_levels in enumerate(per_tree):
        feat, thr = arrays.feature[t], arrays.threshold_bin[t]
        for d, nodes in enumerate(tree_levels):
            slots = lo[d] + np.arange(len(nodes))
            inner = feat[nodes] >= 0
            has_split[d] |= inner.any()
            has_leaf[d] |= not inner.all()
            split = nodes[inner]
            left = 2 * np.arange(len(split))
            node[t, slots[inner]] = ((left << (thr_bits + feat_bits))
                                     | (feat[split] << thr_bits)
                                     | thr[split])
            value[t, slots[~inner]] = scaled[t, nodes[~inner]]

    def chunked(a):
        return np.ascontiguousarray(
            a.reshape(n_chunks, LANES, -1).transpose(0, 2, 1))

    levels = tuple((int(lo[d]), int(widths[d]), bool(has_split[d]),
                    bool(has_leaf[d])) for d in range(depth + 1))
    return LevelLayout(chunked(node), chunked(value), levels, n_trees,
                       thr_bits, feat_bits)


def _device_layout(arrays: TreeArrays):
    """``(level layout, its slot arrays on the device)``, memoised on the
    (frozen) arrays instance: built once per fitted model."""
    cached = getattr(arrays, "_levels", None)
    if cached is None:
        layout = level_layout(arrays)
        cached = (layout, (jnp.asarray(layout.node),
                           jnp.asarray(layout.value)))
        object.__setattr__(arrays, "_levels", cached)
    return cached


def predict_trees(x: np.ndarray, arrays: TreeArrays, *,
                  backend: str = "jax", blk: int = 8192,
                  interpret: bool | None = None) -> np.ndarray:
    """``[N]`` f64 predictions for ``x [N, F]`` — the accelerated twin of
    ``GBTRegressor.predict`` (bit-for-bit on ``backend="jax"``, within
    f32 tolerance on ``backend="pallas"``)."""
    if backend == "pallas":
        from repro.kernels.tree_predict.kernel import tree_predict_kernel
        layout, slots = _device_layout(arrays)
        with region("predict", "bin"):
            codes = _bin_codes(jnp.asarray(np.asarray(x, np.float32)),
                               jnp.asarray(arrays.edges))
        with region("predict", "kernel"):
            out = tree_predict_kernel(
                jnp.asarray(codes, jnp.int32), *slots,
                levels=layout.levels, n_trees=layout.n_trees,
                thr_bits=layout.thr_bits, feat_bits=layout.feat_bits,
                blk=blk, interpret=interpret)
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
