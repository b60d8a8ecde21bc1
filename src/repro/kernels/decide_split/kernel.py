"""Fused offloading-decision Pallas TPU kernel: cost + row-argmin.

The ``[n_envs, L+1]`` decision sweep on lane-dense env tiles: each
``[E]`` env column enters as ``[E/128, 128]`` (a bitcast of the array, one
env a lane), and a grid step takes ``block_e`` envs and ``block_s``
splits.  The step walks its envs ``SWEEP`` rows of 128 at a time and,
for each pass, loops over its splits: the cost of every (environment, split)
pair is computed on the fly from the factored form and immediately
reduced into a running per-environment ``(min, argmin)``, carried across
split blocks in VMEM scratch, so the full cost tensor is never
materialised in HBM (the numpy path builds all ``[E, L+1]`` matrices; at
fleet scale that is the HBM bottleneck).

The factorisation that makes the fusion cheap: the forward/backward
prefix sums over per-layer times are environment-invariant up to one
divide.  The caller ships two ``[L+1]`` prefix rows and two ``[E]``
divisor columns and the kernel reconstructs both cumulative sums per
tile::

    dev_cum[e, s]  = dcum[s] / dev_div[e]
    edge_cum[e, s] = (etot − ecum[s]) / edge_div[e]
    xfer[e, s]     = 0 at s == L, else lat[e] + ship[e, s] / max(bw[e], 1)
    ship[e, s]     = input_bytes[e] at s == 0, else act_bytes[s − 1]

For the analytic roofline model ``dcum = ecum = F`` (the FLOPs prefix)
and ``dev_div[e] = dev_flops[e] · eff``; for a lowered profiling
predictor (``repro.oracle.lowered``) ``dcum``/``ecum`` are the prefix
sums of the *predicted* per-layer times and the divisors are 1 — one
kernel serves both families.

On top of latency the tile evaluates the full CompositeCost objective
stack (energy from TDP, price, deadline slack) and the weighted
scalarisation — latency-only decisions are the ``weights = (1, 0, 0, 0)``
special case, so one kernel serves every cost model that lowers.

Memory per step: the three split rows' ``[block_s]`` blocks in SMEM; in
VMEM, seven env and two output ``[block_e/128, 128]`` blocks, each
double-buffered, and two more as scratch: 80 B an env, 2.6 MB at
``block_e`` 32768.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# spec vector layout (SMEM): scalar parameters of the lowered cost model
SPEC_RADIO, SPEC_PPS, SPEC_PPG, SPEC_DEADLINE = range(4)
SPEC_W0, SPEC_W1, SPEC_W2, SPEC_W3, SPEC_ETOT = range(4, 9)
# queue-aware extension: predicted edge-pool wait added to every
# offloading split's latency, and the tail_latency_s objective
# (latency + tail-RTT excess on offloading splits) weighted by W4.
# All three default to 0.0, which reproduces the 9-slot kernel math.
SPEC_WAIT, SPEC_TEXC, SPEC_W4 = range(9, 12)
SPEC_LEN = 12


#: a [SUBLANES, LANES] f32 tile, the unit of the env layout: one env a lane
SUBLANES, LANES = 8, 128
TILE_E = SUBLANES * LANES
#: env rows one pass of the (fully unrolled) split loop carries
SWEEP = 16


def _kernel(spec_ref, dcum_ref, ecum_ref, bvec_ref, dev_div_ref,
            edge_div_ref, bw_ref, lat_ref, inp_ref, dev_w_ref, edge_w_ref,
            split_ref, cost_ref, best_scr, idx_scr,
            *, block_s: int, n_split_blocks: int, n_splits: int):
    ib = pl.program_id(1)

    @pl.when(ib == 0)
    def _init():
        best_scr[...] = jnp.full_like(best_scr, jnp.inf)
        idx_scr[...] = jnp.zeros_like(idx_scr)

    etot = spec_ref[SPEC_ETOT]
    radio, pps, ppg = (spec_ref[SPEC_RADIO], spec_ref[SPEC_PPS],
                       spec_ref[SPEC_PPG])
    deadline, wait, texc = (spec_ref[SPEC_DEADLINE], spec_ref[SPEC_WAIT],
                            spec_ref[SPEC_TEXC])
    w0, w1, w2, w3, w4 = (spec_ref[SPEC_W0], spec_ref[SPEC_W1],
                          spec_ref[SPEC_W2], spec_ref[SPEC_W3],
                          spec_ref[SPEC_W4])
    padded = n_split_blocks * block_s != n_splits

    def tile(t, _):
        rows = pl.ds(pl.multiple_of(t * sweep, sweep), sweep)
        dev_div, edge_div = dev_div_ref[rows, :], edge_div_ref[rows, :]
        bw1 = jnp.maximum(bw_ref[rows, :], 1.0)
        lat, inp = lat_ref[rows, :], inp_ref[rows, :]
        dev_w, edge_w = dev_w_ref[rows, :], edge_w_ref[rows, :]

        def split(j, carry):
            best, idx = carry
            s = ib * block_s + j
            dev_t = dcum_ref[0, j] / dev_div
            edge_t = (etot - ecum_ref[0, j]) / edge_div
            is_last = s == n_splits - 1                  # split == L
            ship = jnp.where(is_last, 0.0,
                             jnp.where(s == 0, inp, bvec_ref[0, j]))
            xfer = jnp.where(is_last, 0.0, lat + ship / bw1)
            total = dev_t + xfer + edge_t
            energy = dev_t * dev_w + xfer * radio + edge_t * edge_w
            price = edge_t * pps + ship / 1e9 * ppg
            slack = jnp.maximum(total - deadline, 0.0)
            # queue-aware latency: offloading splits pay the edge-pool
            # wait in the latency objective only (energy/price/slack come
            # from the base model, exactly as QueueAwareCost bumps column
            # 0 on the host); SPEC_WAIT == 0.0 adds literal zero
            lat_col = total + jnp.where(is_last, 0.0, wait)
            scal = w0 * lat_col + w1 * energy + w2 * price + w3 * slack
            # tail_latency_s objective: total + tail-RTT excess where
            # offloading
            scal = scal + w4 * (total + jnp.where(is_last, 0.0, texc))
            # strict < keeps the earlier split on ties: np.argmin's rule
            better = scal < best
            if padded:                                   # split padding
                better = better & (s < n_splits)
            return jnp.where(better, scal, best), jnp.where(better, s, idx)

        best, idx = jax.lax.fori_loop(
            0, block_s, split, (best_scr[rows, :], idx_scr[rows, :]),
            unroll=True)
        best_scr[rows, :] = best
        idx_scr[rows, :] = idx
        return 0

    sweep = math.gcd(SWEEP, best_scr.shape[0])          # rows a pass
    jax.lax.fori_loop(0, best_scr.shape[0] // sweep, tile, 0)

    @pl.when(ib == n_split_blocks - 1)
    def _emit():
        split_ref[...] = idx_scr[...]
        cost_ref[...] = best_scr[...]


def decide_split_kernel(dcum, ecum, bvec, dev_div, edge_div, bw, lat, inp,
                        dev_w, edge_w, spec, *, block_e: int = TILE_E,
                        block_s: int = 128,
                        interpret: bool | None = None):
    """``dcum``/``ecum``/``bvec`` [L+1] f32 split rows; env arrays [E]
    f32; ``spec`` [SPEC_LEN] f32.  Returns ``(split [E] int32, scalar
    cost [E] f32)``.

    ``block_e`` envs (rounded up to whole ``[8, 128]`` tiles, and down to
    the envs there are) and ``block_s`` splits make one grid step.  The
    kernel is built once per static signature: the operands' shapes and
    dtypes with ``block_e``, ``block_s`` and ``interpret``.  Later calls
    with the same signature dispatch the compiled program.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return _decide_split(dcum, ecum, bvec, dev_div, edge_div, bw, lat, inp,
                         dev_w, edge_w, spec, block_e=block_e,
                         block_s=block_s, interpret=interpret)


def _decide_split(dcum, ecum, bvec, dev_div, edge_div, bw, lat, inp, dev_w,
                  edge_w, spec, *, block_e: int, block_s: int,
                  interpret: bool):
    """:func:`decide_split_kernel`'s pads, reshapes, ``pallas_call`` and
    slices, as one jitted program."""
    n_envs, n_splits = dev_div.shape[0], dcum.shape[0]
    block_e = min(-(-block_e // TILE_E), -(-max(n_envs, 1) // TILE_E)) \
        * TILE_E
    block_s = min(block_s, n_splits)
    pad_e = (-n_envs) % block_e
    pad_s = (-n_splits) % block_s
    ep, sp = n_envs + pad_e, n_splits + pad_s

    def lanes(x, value):
        # [E] -> [E_pad / 128, 128]: a bitcast where nothing is padded
        return jnp.pad(x, (0, pad_e), constant_values=value).reshape(
            ep // LANES, LANES)

    # padded envs divide by 1.0 and are sliced off below
    dev_div, edge_div, bw = (lanes(x, 1.0) for x in (dev_div, edge_div, bw))
    lat, inp, dev_w, edge_w = (lanes(x, 0.0)
                               for x in (lat, inp, dev_w, edge_w))
    n_split_blocks = sp // block_s
    dcum, ecum, bvec = (jnp.pad(x, (0, pad_s)).reshape(n_split_blocks, 1,
                                                        block_s)
                        for x in (dcum, ecum, bvec))
    rows = block_e // LANES

    env_spec = pl.BlockSpec((rows, LANES), lambda ie, ib: (ie, 0))
    row_spec = pl.BlockSpec((None, 1, block_s), lambda ie, ib: (ib, 0, 0),
                            memory_space=pltpu.SMEM)
    kernel = functools.partial(_kernel, block_s=block_s,
                               n_split_blocks=n_split_blocks,
                               n_splits=n_splits)
    split, cost = pl.pallas_call(
        kernel,
        grid=(ep // block_e, n_split_blocks),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),       # spec scalars
            row_spec, row_spec, row_spec,                # dcum, ecum, bvec
            env_spec, env_spec, env_spec, env_spec,      # divs, bw, lat
            env_spec, env_spec, env_spec,                # inp, dev_w, edge_w
        ],
        out_specs=[env_spec, env_spec],
        out_shape=[
            jax.ShapeDtypeStruct((ep // LANES, LANES), jnp.int32),
            jax.ShapeDtypeStruct((ep // LANES, LANES), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((rows, LANES), jnp.float32),
                        pltpu.VMEM((rows, LANES), jnp.int32)],
        interpret=interpret,
    )(spec, dcum, ecum, bvec, dev_div, edge_div, bw, lat, inp, dev_w,
      edge_w)
    return split.reshape(ep)[:n_envs], cost.reshape(ep)[:n_envs]


# XLA names a Pallas kernel's compiled instruction after the jitted
# function around it.  Under this name the device trace shows the kernel
# as it shows an eager pallas_call, ``%tpu_custom_call``, which is how
# trace readers find the Pallas kernels.
_decide_split.__name__ = "tpu_custom_call"
_decide_split = jax.jit(_decide_split,
                        static_argnames=("block_e", "block_s", "interpret"))


def pack_spec(weights, radio_watts=0.0, price_per_edge_s=0.0,
              price_per_gb=0.0, deadline_s=np.inf, edge_total=0.0,
              queue_wait_s=0.0, tail_excess_s=0.0, tail_weight=0.0):
    """Build the [SPEC_LEN] f32 scalar vector the kernel reads from SMEM
    (``edge_total`` is ``ecum[-1]``, the full edge-side prefix)."""
    out = np.zeros(SPEC_LEN, np.float32)
    out[SPEC_RADIO] = radio_watts
    out[SPEC_PPS] = price_per_edge_s
    out[SPEC_PPG] = price_per_gb
    out[SPEC_DEADLINE] = deadline_s
    out[SPEC_W0:SPEC_W0 + 4] = weights
    out[SPEC_ETOT] = edge_total
    out[SPEC_WAIT] = queue_wait_s
    out[SPEC_TEXC] = tail_excess_s
    out[SPEC_W4] = tail_weight
    return out
