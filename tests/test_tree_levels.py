"""The tree kernel's level layout against the host reference's walk.

For every ensemble shape the kernel serves — a stump, depth 1, a
depth-12 chain, random depth-12 trees of up to 1,427 nodes, a fitted
``GBTRegressor``, a row count that is not a multiple of the row block,
and more trees than one 128-lane chunk holds — the layout must send
every row to the leaf ``predict_ref``'s walk reaches, the interpret-mode
kernel must return the f32 tree-order sum of those leaves' values bit
for bit, and ``lanes_per_tree`` must be the sum of the padded level
widths.
"""
import dataclasses

import numpy as np
import pytest

from repro.core.predictors import GBTRegressor
from repro.kernels.tree_predict import ops, ref
from repro.kernels.tree_predict.kernel import (LANES, SUBLANES,
                                               tree_predict_kernel)

N_FEAT, N_BINS = 7, 64


def _arrays(trees, rng, lr=0.1) -> ref.TreeArrays:
    """Pad ``[(feature, threshold, left, right)]`` trees into
    :class:`TreeArrays` with random leaf values and unit-spaced edges
    (so a row's bin codes are its feature values)."""
    n_trees, m = len(trees), max(len(t[0]) for t in trees)
    feat = np.full((n_trees, m), -1, np.int32)
    thr, left, right = (np.zeros((n_trees, m), np.int32) for _ in range(3))
    for i, (f, th, le, ri) in enumerate(trees):
        n = len(f)
        feat[i, :n], thr[i, :n], left[i, :n], right[i, :n] = f, th, le, ri
    value = np.where(feat < 0, rng.normal(size=feat.shape), 0.0)
    edges = np.tile(np.arange(N_BINS - 1, dtype=np.float32) + 0.5,
                    (N_FEAT, 1))
    depth = max(ref._tree_depth(feat[i], left[i], right[i])
                for i in range(n_trees))
    return ref.TreeArrays(feat, thr, left, right, value,
                          np.array([len(t[0]) for t in trees], np.int32),
                          edges, 0.25, lr, depth)


def _grow(rng, n_split: int, max_depth: int, chain: bool):
    """A random tree: splits drawn among the open nodes above
    ``max_depth`` (``chain``: first one path down to ``max_depth``)."""
    feat, thr, left, right, depth = [-1], [0], [0], [0], [0]
    open_ = [0]

    def split(i):
        feat[i] = int(rng.integers(N_FEAT))
        thr[i] = int(rng.integers(0, N_BINS - 1))
        for side in (left, right):
            side[i] = len(feat)
            feat.append(-1)
            thr.append(0)
            left.append(0)
            right.append(0)
            depth.append(depth[i] + 1)
            if depth[-1] < max_depth:
                open_.append(len(feat) - 1)

    done = 0
    if chain:
        node = 0
        for _ in range(max_depth):
            open_.remove(node)
            split(node)
            node = left[node] if rng.random() < 0.5 else right[node]
            done += 1
    while done < n_split and open_:
        i = open_.pop(int(rng.integers(len(open_))))
        split(i)
        done += 1
    return feat, thr, left, right


def _stumps(rng):
    return _arrays([([-1], [0], [0], [0])] * 3, rng)


def _depth1(rng):
    return _arrays([_grow(rng, 1, 1, False) for _ in range(4)], rng)


def _chain12(rng):
    return _arrays([_grow(rng, 12, 12, True) for _ in range(3)], rng)


def _random12(rng):
    return _arrays([_grow(rng, 713, 12, True)]
                   + [_grow(rng, int(rng.integers(356, 714)), 12, False)
                      for _ in range(4)], rng)


def _fitted(rng):
    x = rng.normal(size=(400, N_FEAT))
    y = x[:, 0] * 2 + np.sin(3 * x[:, 1]) + 0.1 * rng.normal(size=400)
    gbt = GBTRegressor(n_trees=12, max_depth=5, subsample=0.9, seed=3)
    return ref.flatten_gbt(gbt.fit(x, y))


def _ragged(rng):
    return _arrays([_grow(rng, 40, 6, True) for _ in range(3)], rng)


def _two_chunks(rng):
    """More trees than one 128-lane chunk holds."""
    return _arrays([_grow(rng, 5, 3, False) for _ in range(LANES + 3)], rng)


#: name -> (ensemble builder, rows, row block)
CASES = {
    "stump": (_stumps, 300, 2048),
    "depth1": (_depth1, 300, 2048),
    "chain12": (_chain12, 300, 2048),
    "random12": (_random12, 300, 2048),
    "fitted_gbt": (_fitted, 300, 2048),
    "ragged_n": (_ragged, 2 * 256 + 45, 256),
    "two_chunks": (_two_chunks, 300, 2048),
}


def _case(name):
    build, n_rows, blk = CASES[name]
    rng = np.random.default_rng(sorted(CASES).index(name))
    arrays = build(rng)
    if name == "fitted_gbt":
        x = rng.normal(size=(n_rows, N_FEAT))
    else:
        x = rng.integers(0, N_BINS, (n_rows, N_FEAT)).astype(np.float32)
    return arrays, x, blk


def _ref_leaves(arrays, x) -> np.ndarray:
    """``[T, N]`` leaf node of every row in every tree, from
    ``predict_ref``'s own walk (one tree at a time, its node ids as leaf
    values, so the prediction *is* the leaf reached)."""
    ids = np.arange(arrays.max_nodes, dtype=np.float64)
    out = []
    for t in range(arrays.n_trees):
        one = dataclasses.replace(
            arrays, **{k: getattr(arrays, k)[t:t + 1] for k in (
                "feature", "threshold_bin", "left", "right", "n_nodes")},
            value=ids[None, :], base=0.0, learning_rate=1.0)
        out.append(ref.predict_ref(x, one).astype(np.int64))
    return np.stack(out)


def _layout_walk(layout, codes) -> np.ndarray:
    """``[T, N]`` leaf value each row collects per tree, walking the
    layout's slots level by level (the kernel's descent in numpy)."""
    n = codes.shape[0]
    rows = np.arange(n)
    tb, fb = layout.thr_bits, layout.feat_bits
    out = []
    for t in range(layout.n_trees):
        c, lane = divmod(t, LANES)
        node, value = (a[c, :, lane] for a in (layout.node, layout.value))
        pos = np.zeros(n, np.int64)
        acc = np.zeros(n, np.float32)
        for lo, width, _, _ in layout.levels:
            here = (pos >= 0) & (pos < width)
            slot = lo + np.where(here, pos, 0)
            acc += np.where(here, value[slot], 0.0).astype(np.float32)
            word = np.where(here, node[slot], -1)
            feat = (word >> tb) & ((1 << fb) - 1)
            right = codes[rows, np.minimum(feat, codes.shape[1] - 1)] > (
                word & ((1 << tb) - 1))
            pos = np.where(word >= 0, (word >> (tb + fb)) + right, -1)
        out.append(acc)
    return np.stack(out)


@pytest.mark.parametrize("name", sorted(CASES))
def test_layout_reaches_reference_leaf(name):
    arrays, x, _ = _case(name)
    leaves = _ref_leaves(arrays, x)
    ids = np.broadcast_to(np.arange(arrays.max_nodes, dtype=np.float64),
                          arrays.value.shape)
    by_id = ops.level_layout(dataclasses.replace(
        arrays, value=np.where(arrays.feature < 0, ids, 0.0),
        learning_rate=1.0))
    codes = ref.bin_codes_ref(x, arrays.edges)
    np.testing.assert_array_equal(_layout_walk(by_id, codes), leaves)
    assert np.all(arrays.feature[np.arange(arrays.n_trees)[:, None],
                                 leaves] < 0)


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_bitwise_tree_order_sum(name):
    arrays, x, blk = _case(name)
    leaves = _ref_leaves(arrays, x)
    scaled = (arrays.learning_rate * arrays.value).astype(np.float32)
    want = np.zeros(x.shape[0], np.float32)
    for t in range(arrays.n_trees):
        want = want + scaled[t, leaves[t]]
    layout = ops.level_layout(arrays)
    got = tree_predict_kernel(
        ref.bin_codes_ref(x, arrays.edges), layout.node, layout.value,
        levels=layout.levels, n_trees=layout.n_trees,
        thr_bits=layout.thr_bits, feat_bits=layout.feat_bits, blk=blk,
        interpret=True)
    got = np.asarray(got)
    assert got.dtype == np.float32 and got.shape == want.shape
    np.testing.assert_array_equal(got.view(np.uint32), want.view(np.uint32))


@pytest.mark.parametrize("name", sorted(CASES))
def test_lanes_per_tree_sums_padded_level_widths(name):
    arrays, _, _ = _case(name)
    widest = np.zeros(arrays.max_depth + 1, np.int64)
    for t in range(arrays.n_trees):
        level, d = [0], 0
        while level:
            widest[d] = max(widest[d], len(level))
            level = [c for i in level if arrays.feature[t, i] >= 0
                     for c in (arrays.left[t, i], arrays.right[t, i])]
            d += 1
    layout = ops.level_layout(arrays)
    padded = -(-widest // SUBLANES) * SUBLANES
    assert layout.lanes_per_tree == int(padded.sum())
    assert [w for _, w, _, _ in layout.levels] == list(padded)
