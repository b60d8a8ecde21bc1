"""Flattened tree-ensemble form + the host reference for batched inference.

The GBT training code (:mod:`repro.core.predictors.gbt`) keeps each tree
as a Python list of ``_Node`` objects — fine for growing, hostile to
accelerators.  :func:`flatten_gbt` compiles a *fitted* ensemble into five
padded ``[n_trees, max_nodes]`` arrays — ``(feature, threshold_bin,
left, right, value)`` — plus the quantile bin edges, which is the form
every inference backend consumes:

  * :func:`predict_ref` (here)   — vectorised numpy level-synchronous
    descent, bit-for-bit with ``GBTRegressor.predict`` (the pin the
    accelerated paths are tested against);
  * ``ops.predict_trees``        — the same descent as jitted XLA
    (sequential tree accumulation, so f64 results stay bit-for-bit);
  * ``kernel.tree_predict_kernel`` — the fused Pallas TPU kernel (the
    arrays re-laid level by level by ``ops.level_layout``, one-hot
    gathers within the current level on the VPU).

The same arrays are what predictor persistence
(:mod:`repro.core.predictors.persist`) writes to ``.npz``, so a saved
model *is* its lowered form.
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass(frozen=True)
class TreeArrays:
    """One fitted GBT ensemble as padded node arrays (struct-of-arrays).

    Node 0 is each tree's root.  ``feature < 0`` marks a leaf; padding
    slots beyond a tree's ``n_nodes`` are leaves with value 0, so a
    descent that never reaches them stays well-defined.  ``value`` holds
    *raw* leaf values — scale by ``learning_rate`` (already folded into
    f64 by the lowering) to accumulate predictions.
    """
    feature: np.ndarray          # [T, M] int32, -1 == leaf
    threshold_bin: np.ndarray    # [T, M] int32 (bin code, go left if <=)
    left: np.ndarray             # [T, M] int32
    right: np.ndarray            # [T, M] int32
    value: np.ndarray            # [T, M] f64 raw leaf values
    n_nodes: np.ndarray          # [T] int32 real node count per tree
    edges: np.ndarray            # [F, n_bins-1] f32 quantile bin edges
    base: float                  # ensemble intercept (mean target)
    learning_rate: float
    max_depth: int               # deepest split depth over all trees

    @property
    def n_trees(self) -> int:
        return self.feature.shape[0]

    @property
    def max_nodes(self) -> int:
        return self.feature.shape[1]


def _tree_depth(feature: np.ndarray, left: np.ndarray, right: np.ndarray
                ) -> int:
    """Deepest split chain of one flattened tree (0 for a stump leaf)."""
    depth = 0
    stack = [(0, 0)]
    while stack:
        node, d = stack.pop()
        if feature[node] < 0:
            depth = max(depth, d)
        else:
            stack.append((int(left[node]), d + 1))
            stack.append((int(right[node]), d + 1))
    return depth


def flatten_gbt(model) -> TreeArrays:
    """Compile a *fitted* :class:`repro.core.predictors.gbt.GBTRegressor`
    into :class:`TreeArrays` (raises ``AttributeError`` if unfitted)."""
    trees = model.trees_
    n_trees = len(trees)
    max_nodes = max((len(t) for t in trees), default=1)
    feat = np.full((n_trees, max_nodes), -1, np.int32)
    thr = np.zeros((n_trees, max_nodes), np.int32)
    left = np.zeros((n_trees, max_nodes), np.int32)
    right = np.zeros((n_trees, max_nodes), np.int32)
    value = np.zeros((n_trees, max_nodes), np.float64)
    n_nodes = np.zeros(n_trees, np.int32)
    depth = 0
    for t, tree in enumerate(trees):
        n_nodes[t] = len(tree)
        for i, node in enumerate(tree):
            feat[t, i] = node.feature
            thr[t, i] = node.threshold_bin
            left[t, i] = node.left
            right[t, i] = node.right
            value[t, i] = node.value
        depth = max(depth, _tree_depth(feat[t], left[t], right[t]))
    return TreeArrays(feat, thr, left, right, value, n_nodes,
                      np.asarray(model.edges_, np.float32),
                      float(model.base_), float(model.learning_rate),
                      depth)


def unflatten_gbt(arrays: TreeArrays) -> list:
    """Rebuild the ``list[list[_Node]]`` tree representation — the
    persistence load path (round-trips :func:`flatten_gbt` exactly)."""
    from repro.core.predictors.gbt import _Node
    trees = []
    for t in range(arrays.n_trees):
        trees.append([
            _Node(feature=int(arrays.feature[t, i]),
                  threshold_bin=int(arrays.threshold_bin[t, i]),
                  left=int(arrays.left[t, i]),
                  right=int(arrays.right[t, i]),
                  value=float(arrays.value[t, i]))
            for i in range(int(arrays.n_nodes[t]))])
    return trees


def bin_codes_ref(x: np.ndarray, edges: np.ndarray) -> np.ndarray:
    """``[N, F]`` int32 bin codes — ``searchsorted`` semantics expressed
    as comparison counts (``code = #{edges < x}``), the exact form the
    accelerated paths replay."""
    x = np.asarray(x, np.float32)
    return np.sum(edges[None, :, :] < x[:, :, None], axis=-1,
                  dtype=np.int32)


def predict_ref(x: np.ndarray, arrays: TreeArrays) -> np.ndarray:
    """Host reference: ``[N]`` predictions via level-synchronous descent.

    Bit-for-bit with ``GBTRegressor.predict``: codes from the same f32
    edge comparisons, per-tree leaf values scaled by ``learning_rate``
    as one elementwise f64 multiply, trees accumulated sequentially in
    training order onto the ``base`` intercept.
    """
    codes = bin_codes_ref(x, arrays.edges)
    n = len(codes)
    pred = np.full(n, arrays.base, np.float64)
    rows = np.arange(n)
    for t in range(arrays.n_trees):
        node = np.zeros(n, np.int32)
        for _ in range(arrays.max_depth):
            feat = arrays.feature[t, node]
            split = feat >= 0
            thr = arrays.threshold_bin[t, node]
            goes_left = np.where(split,
                                 codes[rows, np.maximum(feat, 0)] <= thr,
                                 False)
            nxt = np.where(goes_left, arrays.left[t, node],
                           arrays.right[t, node])
            node = np.where(split, nxt, node).astype(np.int32)
        pred += arrays.learning_rate * arrays.value[t, node]
    return pred
