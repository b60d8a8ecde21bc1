"""Shared low-level layers: norms, rotary embeddings, gated MLPs, initialisers.

Numerics policy (TPU-native):
  * weights & activations in ``cfg.dtype`` (bf16 for production, f32 for tests)
  * all reductions (norm statistics, softmax, logsumexp) in f32
  * matmuls accumulate in f32 via ``preferred_element_type``
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def matmul(x: jax.Array, w: jax.Array) -> jax.Array:
    """Native-dtype dot.

    No forced f32 output: the TPU MXU accumulates bf16 dots in f32
    internally, and requesting preferred_element_type=f32 makes XLA:CPU
    materialise (and hoist out of layer scans) full f32 copies of the
    weights — polluting the dry-run memory analysis with copies that would
    not exist on the TPU target.
    """
    return jnp.matmul(x, w.astype(x.dtype))


def einsum(spec: str, *args: jax.Array) -> jax.Array:
    dt = args[0].dtype
    return jnp.einsum(spec, *(a.astype(dt) for a in args))


# --------------------------------------------------------------------------
# Norms
# --------------------------------------------------------------------------
def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    normed = x32 * jax.lax.rsqrt(var + eps)
    return (normed * (1.0 + scale.astype(jnp.float32))).astype(x.dtype)


def layer_norm(x: jax.Array, scale: jax.Array, bias: jax.Array,
               eps: float = 1e-5) -> jax.Array:
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    normed = (x32 - mu) * jax.lax.rsqrt(var + eps)
    # (1 + scale) convention: zero-init == identity, matching rms_norm
    return (normed * (1.0 + scale.astype(jnp.float32))
            + bias.astype(jnp.float32)).astype(x.dtype)


# --------------------------------------------------------------------------
# Rotary position embedding
# --------------------------------------------------------------------------
def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    """Inverse frequencies for the even half of the head dimension."""
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                            / head_dim))


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's magnitude factor ``0.1 · mscale · ln(factor) + 1``."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_freqs(head_dim: int, cfg) -> jax.Array:
    """YaRN inverse frequencies (DeepSeek-V2): below the correction range
    (where a dim turns ``yarn_beta_fast`` to ``yarn_beta_slow`` times over
    ``yarn_original_max_pos`` positions) the plain frequency, above it the
    frequency over ``yarn_factor``, a linear ramp between."""
    theta = cfg.rope_theta

    def dim_at(rotations):
        return (head_dim * math.log(cfg.yarn_original_max_pos
                                    / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(dim_at(cfg.yarn_beta_fast)), 0)
    high = min(math.ceil(dim_at(cfg.yarn_beta_slow)), head_dim - 1)
    if low == high:
        high += 0.001
    expo = jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
    extra = 1.0 / theta ** expo
    inter = 1.0 / (cfg.yarn_factor * theta ** expo)
    ramp = jnp.clip((jnp.arange(head_dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    return inter * ramp + extra * (1.0 - ramp)


def yarn_softmax_scale(cfg) -> float:
    """The factor YaRN puts on the attention softmax scale,
    ``yarn_mscale(factor, mscale_all_dim)²``; 1 without YaRN."""
    if cfg.yarn_factor <= 1:
        return 1.0
    return yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale_all_dim) ** 2


def apply_rope(x: jax.Array, positions: jax.Array, theta: float, *,
               inv_freq: jax.Array | None = None,
               scale: float = 1.0) -> jax.Array:
    """Rotate ``x`` ([..., S, H, D]) by ``positions`` ([..., S]).

    Uses the split-halves convention (first half paired with second half),
    matching the LLaMA/Gemma family.  ``inv_freq`` replaces the plain
    frequencies and ``scale`` multiplies cos and sin (YaRN).
    """
    head_dim = x.shape[-1]
    inv = rope_freqs(head_dim, theta) if inv_freq is None else inv_freq
    ang = positions[..., None].astype(jnp.float32) * inv    # [..., S, D/2]
    cos = jnp.cos(ang)[..., None, :]                        # [..., S, 1, D/2]
    sin = jnp.sin(ang)[..., None, :]
    if scale != 1.0:
        cos, sin = cos * scale, sin * scale
    x32 = x.astype(jnp.float32)
    x1, x2 = jnp.split(x32, 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def rope_for(x: jax.Array, positions: jax.Array, cfg) -> jax.Array:
    """``apply_rope`` with the configuration's scaling (YaRN where
    ``yarn_factor`` > 1, else plain)."""
    if cfg.yarn_factor <= 1:
        return apply_rope(x, positions, cfg.rope_theta)
    f = cfg.yarn_factor
    return apply_rope(x, positions, cfg.rope_theta,
                      inv_freq=yarn_freqs(x.shape[-1], cfg),
                      scale=yarn_mscale(f, cfg.yarn_mscale)
                      / yarn_mscale(f, cfg.yarn_mscale_all_dim))


def sinusoidal_positions(seq: int, d_model: int) -> jax.Array:
    """Classic sin/cos table (Whisper encoder)."""
    pos = jnp.arange(seq, dtype=jnp.float32)[:, None]
    dim = jnp.arange(d_model // 2, dtype=jnp.float32)[None, :]
    ang = pos / (10000.0 ** (2 * dim / d_model))
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


# --------------------------------------------------------------------------
# Gated MLPs
# --------------------------------------------------------------------------
_ACTS = {
    "silu": jax.nn.silu,
    "gelu": lambda x: jax.nn.gelu(x, approximate=True),
    "gelu_plain": lambda x: jax.nn.gelu(x, approximate=True),
    "relu": jax.nn.relu,
    "relu2": lambda x: jnp.square(jax.nn.relu(x)),   # Nemotron squared-ReLU
}


_NON_GATED = ("gelu_plain", "relu2")


def gated_mlp(x: jax.Array, params: dict, act: str) -> jax.Array:
    """SwiGLU / GeGLU: act(x W_gate) * (x W_up) W_down.

    ``gelu_plain`` (Whisper/StarCoder2) and ``relu2`` (Nemotron) use the
    classic non-gated 2-matrix MLP.
    """
    fn = _ACTS[act]
    if act in _NON_GATED:
        h = fn(matmul(x, params["w_up"]))
        return matmul(h, params["w_down"])
    g = fn(matmul(x, params["w_gate"]))
    u = matmul(x, params["w_up"])
    return matmul(g * u, params["w_down"])


def mlp_param_shapes(d_model: int, d_ff: int, act: str) -> dict:
    if act in _NON_GATED:
        return {"w_up": (d_model, d_ff), "w_down": (d_ff, d_model)}
    return {
        "w_gate": (d_model, d_ff),
        "w_up": (d_model, d_ff),
        "w_down": (d_ff, d_model),
    }


# --------------------------------------------------------------------------
# Initialisation
# --------------------------------------------------------------------------
def init_dense(key: jax.Array, shape: tuple[int, ...], dtype,
               scale: float | None = None) -> jax.Array:
    """Truncated-normal fan-in init."""
    fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
    std = scale if scale is not None else fan_in ** -0.5
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)
            * std).astype(dtype)


def init_tree(key: jax.Array, shapes: dict, dtype) -> dict:
    """Init a (nested) dict of shape-tuples into arrays.

    Name-based rules cover the special leaves of the SSM/xLSTM families
    (decay logs, dt biases, gate biases) so freshly-initialised models are
    NaN-free out of the box.
    """
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda x: isinstance(x, tuple))
    keys = jax.random.split(key, len(flat))
    leaves = []
    for (path, shape), k in zip(flat, keys):
        name = str(path[-1])
        if "a_log" in name:          # Mamba2: A ∈ [1, 16]
            leaves.append(jax.random.uniform(
                k, shape, jnp.float32, jnp.log(1.0), jnp.log(16.0)))
        elif "dt_bias" in name:      # softplus⁻¹(~0.02)
            leaves.append(jnp.full(shape, -4.0, jnp.float32))
        elif "d_skip" in name:
            leaves.append(jnp.ones(shape, jnp.float32))
        elif name == "b_fg":         # mLSTM forget-gate bias: start open
            leaves.append(jnp.linspace(3.0, 6.0, int(jnp.prod(
                jnp.array(shape)))).reshape(shape).astype(jnp.float32))
        elif name == "b_ig":         # mLSTM input-gate bias: start small
            leaves.append(jnp.full(shape, -5.0, jnp.float32))
        elif "scale" in name or "norm" in name:
            leaves.append(jnp.zeros(shape, dtype=jnp.float32))
        elif "bias" in name or name.startswith("b_"):
            leaves.append(jnp.zeros(shape, dtype=dtype))
        else:
            leaves.append(init_dense(k, shape, dtype))
    return jax.tree_util.tree_unflatten(treedef, leaves)
