"""Fused batched tree-inference Pallas TPU kernel — the serving twin of
the ``gbt_hist`` *training* kernel.

Per-row pointer chasing has no TPU analogue (the VPU has no per-lane
gather from VMEM), so — like the one-hot histogram trick in
:mod:`repro.kernels.gbt_hist` — every gather is a dense masked
reduction.  What keeps that cheap is the *level layout*
(:func:`repro.kernels.tree_predict.ops.level_layout`): a tree's nodes
are stored breadth-first, level ``d`` in a slot range ``[lo_d, lo_d +
w_d)`` that is the same for every tree (``w_d`` the widest level ``d``
of the ensemble, padded to 8 sublanes), and a split's two children are
adjacent (right = left + 1).  A row at level ``d`` can only be at one of
that level's slots, so the one-hot it selects with is ``[w_d, rows]``,
not ``[max_nodes, rows]``.

Rows lie on lanes and slots on sublanes.  A tile of ``L`` rows descends
with ``pos [1, L]``, its index within the current level (-1 once it has
left the tree), and each level makes two masked sublane reductions: the
leaf value (0 for a split), and the node word — ``left << (thr_bits +
feat_bits) | feature << thr_bits | threshold`` for a split, -1 for a
leaf — plus one over the ``[F, L]`` bin codes for the split feature.  A
row that meets a leaf adds its value and carries -1, which matches no
slot, so its later levels add exact zeros: each tree adds exactly its
leaf value, trees in training order, and the f32 sums are the same bits
as any descent that adds one leaf value per tree in that order.  Levels
with no split skip the node select, levels with no leaf the value select.

The slots arrive as ``[C, S, 128]`` arrays, tree ``t`` in lane ``t %
128`` of chunk ``t // 128``; the grid is ``(row blocks, C)``, and within
a step a loop over the chunk's trees rolls the tree's lane to lane 0 and
broadcasts it across a ``[S, 128]`` VMEM scratch once, which every row
tile of the block then reads (repeated across its ``L`` lanes).

VMEM at the catalog shape (100 trees of depth 12, ``S`` = 1,608 slots,
7 features, ``blk`` = 8192, ``L`` = 512): the slot chunk 2 × S × 128 × 4
B = 1.6 MB, double-buffered 3.3 MB, the broadcast scratch 1.6 MB, codes
and output blocks 2 × 256 KB each; the compiler reserves 5.0 MB.

Leaf values arrive pre-scaled by ``learning_rate``; the ``base``
intercept is added by the caller (f64, host side).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
SUBLANES = 8
#: rows a descent carries at once (four lane tiles of independent work)
ROW_TILE = 512


def level_slots(counts) -> list[tuple[int, int]]:
    """``[(lo_d, w_d)]`` for the widest node count of each level: widths
    padded to whole sublane tiles, levels laid end to end."""
    slots, lo = [], 0
    for count in counts:
        width = -(-max(int(count), 1) // SUBLANES) * SUBLANES
        slots.append((lo, width))
        lo += width
    return slots


# The descent is written in lax, not jnp, with each constant array made
# once, which keeps the kernel's trace short: each jnp wrapper is a jitted
# function that traces again.  The kernel is traced once per static
# signature (see tree_predict_kernel), when a shape is first used.

def _descend(codes, node_s, value_s, *, levels, thr_bits: int,
             feat_bits: int):
    """``[1, L]`` f32 sum of one tree's leaf value for a ``[F, L]`` code
    tile (``L`` a multiple of the scratch's 128 lanes)."""
    n_feat, lanes = codes.shape
    full = functools.cache(lambda rows, fill, dtype: lax.full(
        (rows, lanes), fill, dtype))

    def spread(x, rows: int):
        """``[1, L] -> [rows, L]`` along sublanes."""
        return lax.broadcast_in_dim(x, (rows, lanes), (0, 1))

    def slots(ref, lo: int, width: int):
        """The level's ``[width, 128]`` scratch rows, repeated to ``L``
        lanes."""
        part = ref[lo:lo + width, :]
        return lax.concatenate([part] * (lanes // LANES), 1)

    def select(hot, picked, fill, reducer, identity):
        """One-hot gather along sublanes: ``[w, L] -> [1, L]``; ``fill``
        where no slot is hot."""
        picked = lax.select(hot, picked,
                            full(picked.shape[0], fill, picked.dtype))
        total = lax.reduce(picked, np.array(identity, picked.dtype),
                           reducer, (0,))
        return lax.broadcast_in_dim(total, (1, lanes), (1,))

    int_min = np.iinfo(np.int32).min
    feat_iota = lax.broadcasted_iota(jnp.int32, (n_feat, lanes), 0)
    pos = full(1, 0, jnp.int32)
    acc = full(1, 0, jnp.float32)
    for lo, width, has_split, has_leaf in levels:
        hot = lax.eq(spread(pos, width),
                     lax.broadcasted_iota(jnp.int32, (width, lanes), 0))
        if has_leaf:
            acc = lax.add(acc, select(hot, slots(value_s, lo, width), 0,
                                      lax.add, 0))
        if has_split:
            node = select(hot, slots(node_s, lo, width), -1, lax.max,
                          int_min)
            thr = lax.bitwise_and(node, (1 << thr_bits) - 1)
            feat = lax.bitwise_and(lax.shift_right_arithmetic(
                node, thr_bits), (1 << feat_bits) - 1)
            child = lax.shift_right_arithmetic(node, thr_bits + feat_bits)
            code = select(lax.eq(feat_iota, spread(feat, n_feat)), codes,
                          0, lax.add, 0)
            right = lax.convert_element_type(lax.gt(code, thr), jnp.int32)
            pos = lax.select(lax.ge(node, 0), lax.add(child, right),
                             full(1, -1, jnp.int32))
    return acc


def _kernel(codes_ref, node_ref, value_ref, out_ref, node_s, value_s, *,
            levels, thr_bits: int, feat_bits: int, n_trees: int,
            n_chunks: int, tile: int):
    chunk = pl.program_id(1)

    @pl.when(chunk == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    n_tiles = out_ref.shape[1] // tile
    if n_chunks == 1:
        in_chunk = n_trees
    else:
        in_chunk = jnp.minimum(LANES, n_trees - chunk * LANES)

    def tree(j, carry):
        # tree j's lane to lane 0, then across the scratch's lanes
        shift = lax.rem(lax.sub(LANES, j), LANES)
        for src, dst in ((node_ref, node_s), (value_ref, value_s)):
            col = lax.slice(pltpu.roll(src[...], shift, 1), (0, 0),
                            (dst.shape[0], 1))
            dst[...] = lax.broadcast_in_dim(col, dst.shape, (0, 1))

        def rows(i, c):
            at = pl.ds(pl.multiple_of(i * tile, tile), tile)
            out_ref[:, at] += _descend(codes_ref[:, at], node_s, value_s,
                                       levels=levels, thr_bits=thr_bits,
                                       feat_bits=feat_bits)
            return c

        return lax.fori_loop(0, n_tiles, rows, carry)

    lax.fori_loop(0, in_chunk, tree, 0)


def tree_predict_kernel(codes, node, value, *, levels, n_trees: int,
                        thr_bits: int, feat_bits: int, blk: int = 8192,
                        interpret: bool | None = None):
    """``codes [N, F]`` int32 bin codes; level-layout slots ``[C, S,
    128]`` (``node`` int32 packed splits, ``value`` f32 leaf values
    pre-scaled by the learning rate) with their static ``levels`` ``((lo,
    width, has_split, has_leaf), ...)``.  Returns ``[N]`` f32 summed tree
    outputs (add the ensemble ``base`` on the host).

    The kernel is built once per static signature: the operands' shapes
    and dtypes with ``levels``, ``n_trees``, ``thr_bits``, ``feat_bits``,
    ``blk`` and ``interpret``.  Later calls with the same signature
    dispatch the compiled program."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if codes.shape[0] == 0:              # nothing to grid over
        return jnp.zeros((0,), jnp.float32)
    return _tree_predict(codes, node, value, levels=tuple(levels),
                         n_trees=n_trees, thr_bits=thr_bits,
                         feat_bits=feat_bits, blk=blk,
                         interpret=interpret)


def _tree_predict(codes, node, value, *, levels, n_trees: int,
                  thr_bits: int, feat_bits: int, blk: int, interpret: bool):
    """:func:`tree_predict_kernel`'s pad, transpose, ``pallas_call`` and
    slice, as one jitted program."""
    n, f = codes.shape
    n_chunks, n_slots, _ = node.shape
    tile = min(ROW_TILE, -(-min(blk, n) // LANES) * LANES)
    blk = -(-min(blk, n) // tile) * tile
    pad = (-n) % blk
    codes_t = jnp.pad(codes, ((0, pad), (0, 0))).T           # [F, N + pad]
    nb = (n + pad) // blk
    kernel = functools.partial(_kernel, levels=levels,
                               thr_bits=thr_bits, feat_bits=feat_bits,
                               n_trees=n_trees, n_chunks=n_chunks, tile=tile)
    slot_spec = pl.BlockSpec((pl.squeezed, n_slots, LANES),
                             lambda ir, ic: (ic, 0, 0))
    out = pl.pallas_call(
        kernel,
        grid=(nb, n_chunks),
        in_specs=[
            pl.BlockSpec((f, blk), lambda ir, ic: (0, ir)),    # codes
            slot_spec, slot_spec,                              # node, value
        ],
        out_specs=pl.BlockSpec((1, blk), lambda ir, ic: (0, ir)),
        out_shape=jax.ShapeDtypeStruct((1, n + pad), jnp.float32),
        scratch_shapes=[pltpu.VMEM((n_slots, LANES), jnp.int32),
                        pltpu.VMEM((n_slots, LANES), jnp.float32)],
        interpret=interpret,
    )(codes_t, node, value)
    return out[0, :n]


# named as the decide kernel's entry is, for the same reason: the device
# trace shows the compiled kernel as ``%tpu_custom_call``
_tree_predict.__name__ = "tpu_custom_call"
_tree_predict = jax.jit(_tree_predict,
                        static_argnames=("levels", "n_trees", "thr_bits",
                                         "feat_bits", "blk", "interpret"))
