"""Fused offloading-decision Pallas TPU kernel: cost + row-argmin.

One ``[block_e, block_s]`` tile of the ``[n_envs, L+1]`` decision sweep
per grid step — the cost of every (environment, split) pair is computed
on the fly from the factored form and immediately reduced into a running
per-environment ``(min, argmin)`` carried in VMEM scratch, so the full
cost tensor is never materialised in HBM (the numpy/jit paths build all
``[E, L+1]`` matrices; at fleet scale that is the HBM bottleneck).

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

VMEM per step: three [1, block_s] layer rows + nine [block_e, 1] env
columns + the [block_e, block_s] tile intermediates + [block_e, 1]
scratch ≈ 0.6 MB at (256, 128) f32.
"""
from __future__ import annotations

import functools

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
    be = best_scr.shape[0]
    cols = ib * block_s + jax.lax.broadcasted_iota(
        jnp.int32, (be, block_s), 1)                     # [BE, BS]

    dc = dcum_ref[...]                                   # [1, BS]
    ec = ecum_ref[...]
    b = bvec_ref[...]

    dev_t = dc / dev_div_ref[...]                        # [BE, BS]
    edge_t = (etot - ec) / edge_div_ref[...]
    is_last = cols == n_splits - 1                       # split == L
    ship = jnp.where(is_last, 0.0,
                     jnp.where(cols == 0, inp_ref[...], b))
    xfer = jnp.where(is_last, 0.0,
                     lat_ref[...] + ship / jnp.maximum(bw_ref[...], 1.0))

    total = dev_t + xfer + edge_t
    energy = dev_t * dev_w_ref[...] + xfer * spec_ref[SPEC_RADIO] \
        + edge_t * edge_w_ref[...]
    price = edge_t * spec_ref[SPEC_PPS] \
        + ship / 1e9 * spec_ref[SPEC_PPG]
    slack = jnp.maximum(total - spec_ref[SPEC_DEADLINE], 0.0)
    # queue-aware latency: offloading splits pay the edge-pool wait in
    # the latency objective only (energy/price/slack come from the base
    # model, exactly as QueueAwareCost bumps column 0 on the host);
    # SPEC_WAIT == 0.0 adds literal zero — bit-identical historical math
    lat_col = total + jnp.where(is_last, 0.0, spec_ref[SPEC_WAIT])
    scal = spec_ref[SPEC_W0] * lat_col + spec_ref[SPEC_W1] * energy \
        + spec_ref[SPEC_W2] * price + spec_ref[SPEC_W3] * slack
    # tail_latency_s objective: total + tail-RTT excess where offloading
    scal = scal + spec_ref[SPEC_W4] * (
        total + jnp.where(is_last, 0.0, spec_ref[SPEC_TEXC]))
    scal = jnp.where(cols < n_splits, scal, jnp.inf)     # mask split padding

    local_min = jnp.min(scal, axis=1)[:, None]           # [BE, 1]
    local_idx = jnp.argmin(scal, axis=1)[:, None].astype(jnp.int32)
    # strict < keeps the earlier block on ties — np.argmin semantics
    better = local_min < best_scr[...]
    best_scr[...] = jnp.where(better, local_min, best_scr[...])
    idx_scr[...] = jnp.where(better, ib * block_s + local_idx, idx_scr[...])

    @pl.when(ib == n_split_blocks - 1)
    def _emit():
        split_ref[...] = idx_scr[...]
        cost_ref[...] = best_scr[...]


def decide_split_kernel(dcum, ecum, bvec, dev_div, edge_div, bw, lat, inp,
                        dev_w, edge_w, spec, *, block_e: int = 8,
                        block_s: int = 128,
                        interpret: bool | None = None):
    """``dcum``/``ecum``/``bvec`` [L+1] f32 split rows; env arrays [E]
    f32; ``spec`` [SPEC_LEN] f32.  Returns ``(split [E] int32, scalar
    cost [E] f32)``.

    The kernel is built once per static signature: the operands' shapes
    and dtypes with ``block_e``, ``block_s`` and ``interpret``.  Later
    calls with the same signature dispatch the compiled program.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return _decide_split(dcum, ecum, bvec, dev_div, edge_div, bw, lat, inp,
                         dev_w, edge_w, spec, block_e=block_e,
                         block_s=block_s, interpret=interpret)


def _decide_split(dcum, ecum, bvec, dev_div, edge_div, bw, lat, inp, dev_w,
                  edge_w, spec, *, block_e: int, block_s: int,
                  interpret: bool):
    """:func:`decide_split_kernel`'s pads, ``pallas_call`` and slices, as
    one jitted program."""
    n_envs, n_splits = dev_div.shape[0], dcum.shape[0]
    block_e = min(block_e, max(n_envs, 1))
    block_s = min(block_s, n_splits)
    pad_e = (-n_envs) % block_e
    pad_s = (-n_splits) % block_s
    # padded env rows divide by 1.0 and are sliced off below
    dev_div, edge_div, bw = (jnp.pad(x, (0, pad_e),
                                     constant_values=1.0)[:, None]
                             for x in (dev_div, edge_div, bw))
    lat, inp, dev_w, edge_w = (jnp.pad(x, (0, pad_e))[:, None]
                               for x in (lat, inp, dev_w, edge_w))
    dcum, ecum, bvec = (jnp.pad(x, (0, pad_s))[None, :]
                        for x in (dcum, ecum, bvec))
    ep, sp = n_envs + pad_e, n_splits + pad_s
    n_split_blocks = sp // block_s

    env_spec = pl.BlockSpec((block_e, 1), lambda ie, ib: (ie, 0))
    row_spec = pl.BlockSpec((1, block_s), lambda ie, ib: (0, ib))
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
            jax.ShapeDtypeStruct((ep, 1), jnp.int32),
            jax.ShapeDtypeStruct((ep, 1), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((block_e, 1), jnp.float32),
                        pltpu.VMEM((block_e, 1), jnp.int32)],
        interpret=interpret,
    )(spec, dcum, ecum, bvec, dev_div, edge_div, bw, lat, inp, dev_w,
      edge_w)
    return split[:n_envs, 0], cost[:n_envs, 0]


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
