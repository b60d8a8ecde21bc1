"""Fused batched tree-inference Pallas TPU kernel — the serving twin of
the ``gbt_hist`` *training* kernel.

Per grid step one ``[blk]`` row block descends one tree.  Per-row
pointer chasing has no TPU analogue (the VPU has no per-lane gather from
VMEM), so — exactly like the one-hot histogram trick in
:mod:`repro.kernels.gbt_hist` — every gather becomes a dense masked
reduction: a ``[blk, max_nodes]`` one-hot of the current node index
against a ``broadcasted_iota`` selects that node's ``(feature,
threshold, left, right)`` row-wise, and a second ``[blk, F]`` one-hot
selects each row's split-feature bin code.  The five node arrays of the
active tree live in VMEM for the whole descent (they are ``[1,
max_nodes]`` rows — a depth-6 ensemble is a few KB), predictions
accumulate in the output block across the sequential tree axis of the
grid, and the ``[N, n_trees]`` per-tree prediction matrix is never
materialised.

The node arrays go to the kernel as ``[T, 1, M]`` with the tree axis
squeezed out of the block: each block is then one whole ``[1, M]`` row,
which the TPU lowering accepts for any ``M`` (a ``(1, M)`` block over a
``[T, M]`` array is refused unless ``T == 1``).

VMEM per step: codes block (blk × F int32) + 5 node rows + the
``[blk, max_nodes]`` one-hot transient + out (blk × 1) ≈ 1–2 MB at
blk=512, F ≤ 32, max_nodes ≤ 256.

Leaf values arrive pre-scaled by ``learning_rate``; the ``base``
intercept is added by the caller (f64, host side).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _select(onehot, row):
    """Row-wise one-hot gather: ``[blk, M] bool, [1, M] int -> [blk]``."""
    return jnp.sum(jnp.where(onehot, row, 0), axis=1, dtype=jnp.int32)


def _kernel(codes_ref, feat_ref, thr_ref, left_ref, right_ref, value_ref,
            out_ref, *, max_depth: int, max_nodes: int, n_feat: int):
    it = pl.program_id(1)

    @pl.when(it == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    codes = codes_ref[...]                       # [blk, F] int32
    blk = codes.shape[0]
    node_iota = jax.lax.broadcasted_iota(jnp.int32, (blk, max_nodes), 1)
    feat_iota = jax.lax.broadcasted_iota(jnp.int32, (blk, n_feat), 1)
    feat_row = feat_ref[...]                     # [1, M] int32
    thr_row = thr_ref[...]
    left_row = left_ref[...]
    right_row = right_ref[...]

    def level(_, node):
        onehot = node[:, None] == node_iota      # [blk, M]
        f = _select(onehot, feat_row)
        split = f >= 0
        thr = _select(onehot, thr_row)
        code = jnp.sum(jnp.where(feat_iota == jnp.maximum(f, 0)[:, None],
                                 codes, 0), axis=1, dtype=jnp.int32)
        goes_left = split & (code <= thr)
        nxt = jnp.where(goes_left, _select(onehot, left_row),
                        _select(onehot, right_row))
        return jnp.where(split, nxt, node)

    node = jnp.zeros((blk,), jnp.int32)
    if max_depth > 0:
        node = jax.lax.fori_loop(0, max_depth, level, node)
    leaf_hot = node[:, None] == node_iota
    val = jnp.sum(jnp.where(leaf_hot, value_ref[...], 0.0), axis=1)
    out_ref[...] += val[:, None]


def tree_predict_kernel(codes, feature, threshold_bin, left, right,
                        scaled_value, *, max_depth: int, blk: int = 512,
                        interpret: bool | None = None):
    """``codes [N, F]`` int32 bin codes; node arrays ``[T, M]`` (value
    f32, pre-scaled by the learning rate).  Returns ``[N]`` f32 summed
    tree outputs (add the ensemble ``base`` on the host)."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    n, f = codes.shape
    n_trees, max_nodes = feature.shape
    if n == 0:                           # nothing to grid over
        return jnp.zeros((0,), jnp.float32)
    blk = min(blk, max(n, 1))
    pad = (-n) % blk
    if pad:
        codes = jnp.pad(codes, ((0, pad), (0, 0)))
    nb = (n + pad) // blk
    kernel = functools.partial(_kernel, max_depth=max_depth,
                               max_nodes=max_nodes, n_feat=f)
    tree_spec = pl.BlockSpec((pl.squeezed, 1, max_nodes),
                             lambda ir, it: (it, 0, 0))
    feature, threshold_bin, left, right, scaled_value = (
        x[:, None, :] for x in (feature, threshold_bin, left, right,
                                scaled_value))
    out = pl.pallas_call(
        kernel,
        grid=(nb, n_trees),
        in_specs=[
            pl.BlockSpec((blk, f), lambda ir, it: (ir, 0)),    # codes
            tree_spec, tree_spec, tree_spec, tree_spec,        # f, t, l, r
            tree_spec,                                         # values
        ],
        out_specs=pl.BlockSpec((blk, 1), lambda ir, it: (ir, 0)),
        out_shape=jax.ShapeDtypeStruct((n + pad, 1), jnp.float32),
        interpret=interpret,
    )(codes, feature, threshold_bin, left, right, scaled_value)
    return out[:n, 0]
