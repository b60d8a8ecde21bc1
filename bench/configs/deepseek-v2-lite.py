"""deepseek-v2-lite: its weights from the seed, and the plain float32
reference forward pass that the served tokens are compared with.

The reference imports nothing of the program.  It follows the published
architecture (arXiv:2405.04434; the model's config.json): an unscaled
token embedding; pre-norm RMSNorm with the ``(1 + scale)`` convention;
multi-head latent attention without q-LoRA (a query of nope 128 + rope
64 a head, a latent of 512 normed and up-projected to each head's key
and value, one rope key of 64 shared by the heads), YaRN rope (factor
40 over 4,096 positions, beta 32/1, mscale and mscale_all_dim 0.707:
cos and sin unscaled, the softmax scale ``192^-0.5 · mscale²``),
split-half rotation; layer 0 a SwiGLU MLP of 10,944, layers 1-26 a
softmax router over 64 experts, greedy top-6 weights left unnormalised,
2 shared experts; a final RMSNorm and an untied head.  It reconstructs
each head's keys and values from the latent (the program decodes in
latent space), attends causally over the whole sequence, and runs in
float32 at ``highest`` matmul precision, one layer at a time.

The chip holds experts 0-7 of the 64 in each MoE layer (one chip of
8-way expert parallelism); the router keeps its 64 outputs and top-6,
and the reference, like the program, adds only the pairs routed to a
held expert.

The weights are laid out as the program's parameter tree (its
checkpoint layout: the leading dense layer in ``dense_layers``, the MoE
layers in ``layers``) and made here, in one jitted call, in the dtype
they are served in.

It also gives the serve driver what depends on the model's shape:
``planner_layers`` (the whole published model as the admission planner
prices it), ``prefill_flops`` and ``token_flops`` (the model FLOPs of
this chip's share that ``serve.mfu`` counts), ``step_weight_bytes``
(what ``serve.weight_read_share`` counts) and ``small`` (its cut for the
CPU tests).
"""
from __future__ import annotations

import functools
import math

import numpy as np

#: head logits are taken over vocabulary chunks of this many rows
VOCAB_CHUNK = 25600


def _dims(m: dict):
    return (m["d_model"], m["num_heads"], m["qk_nope_dim"],
            m["qk_rope_dim"], m["v_head_dim"], m["kv_lora_rank"])


def _held(m: dict) -> int:
    return m.get("experts_held") or m["num_experts"]


def param_shapes(m: dict) -> dict:
    """The parameter tree's shapes: a stack of leading dense layers and a
    stack of MoE layers holding ``experts_held`` experts, untied head."""
    d, h, dn, dr, dv, r = _dims(m)
    v, ff = m["vocab_size"], m["moe_d_ff"]
    n_dense = m["first_dense_layers"]

    def attn(n):
        return {"wq": (n, d, h * (dn + dr)), "w_dkv": (n, d, r),
                "w_kr": (n, d, dr), "kv_norm_scale": (n, r),
                "w_uk": (n, r, h * dn), "w_uv": (n, r, h * dv),
                "wo": (n, h * dv, d)}

    def mlp(n, f):
        return {"w_gate": (n, d, f), "w_up": (n, d, f), "w_down": (n, f, d)}

    n_moe, e = m["num_layers"] - n_dense, _held(m)
    return {"embed": (v, d), "final_norm_scale": (d,), "lm_head": (d, v),
            "dense_layers": {"ln1_scale": (n_dense, d),
                             "ln2_scale": (n_dense, d), "attn": attn(n_dense),
                             "mlp": mlp(n_dense, m["d_ff"])},
            "layers": {"ln1_scale": (n_moe, d), "ln2_scale": (n_moe, d),
                       "attn": attn(n_moe),
                       "moe": {"router": (n_moe, d, m["num_experts"]),
                               "w_gate": (n_moe, e, d, ff),
                               "w_up": (n_moe, e, d, ff),
                               "w_down": (n_moe, e, ff, d),
                               "shared": mlp(
                                   n_moe, ff * m["num_shared_experts"])}}}


def make_params(m: dict, seed: int):
    """Random weights on the device in one jitted call: matrices normal
    with std ``fan_in ** -0.5`` in the model dtype (the embedding's std is
    ``d_model ** -0.5``), norm scales f32 normal with std 0.1."""
    import jax
    import jax.numpy as jnp
    shapes = param_shapes(m)
    flat, tree = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda s: isinstance(s, tuple))
    dtype = jnp.dtype(m["dtype"])

    def make(key):
        keys = jax.random.split(key, len(flat))
        out = []
        for (path, shape), k in zip(flat, keys):
            name = str(getattr(path[-1], "key", path[-1]))
            if "scale" in name:
                out.append(0.1 * jax.random.normal(k, shape, jnp.float32))
                continue
            fan_in = m["d_model"] if name == "embed" else shape[-2]
            out.append((jax.random.normal(k, shape, jnp.float32)
                        * fan_in ** -0.5).astype(dtype))
        return jax.tree_util.tree_unflatten(tree, out)

    return jax.jit(make)(jax.random.key(seed))


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(m: dict) -> np.ndarray:
    """DeepSeek-V2's YaRN inverse frequencies over the rope dims, in
    float64: the plain frequency below the correction range, the
    frequency over the factor above it, a linear ramp between."""
    dim, base, f = m["qk_rope_dim"], m["rope_theta"], m["yarn_factor"]

    def corr_dim(rot):
        return (dim * math.log(m["yarn_original_max_pos"]
                               / (rot * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(corr_dim(m["yarn_beta_fast"])), 0)
    high = min(math.ceil(corr_dim(m["yarn_beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    expo = np.arange(0, dim, 2, dtype=np.float64) / dim
    extra, inter = 1.0 / base ** expo, 1.0 / (f * base ** expo)
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    return inter * ramp + extra * (1.0 - ramp)


def softmax_scale(m: dict) -> float:
    return ((m["qk_nope_dim"] + m["qk_rope_dim"]) ** -0.5
            * yarn_mscale(m["yarn_factor"], m["yarn_mscale_all_dim"]) ** 2)


def _rms(x, scale, eps):
    import jax.numpy as jnp
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x / jnp.sqrt(var + eps) * (1.0 + scale.astype(jnp.float32))


def _rope(x, pos, inv, cos_scale):
    """Split-half rotary embedding of ``x [B, S, H, D]``."""
    import jax.numpy as jnp
    d = x.shape[-1]
    ang = pos[:, :, None].astype(jnp.float32) * inv          # [B, S, D/2]
    cos = (jnp.cos(ang) * cos_scale)[:, :, None]
    sin = (jnp.sin(ang) * cos_scale)[:, :, None]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _weights(w, quant):
    import jax.numpy as jnp
    w = w.astype(jnp.float32)
    return w if quant is None else quant(w)


def _swiglu(x, w, quant):
    import jax
    g = x @ _weights(w["w_gate"], quant)
    u = x @ _weights(w["w_up"], quant)
    return (jax.nn.silu(g) * u) @ _weights(w["w_down"], quant)


def _attention(m, a, h, quant, head_group=4):
    """Multi-head latent attention over the whole sequence, keys and
    values reconstructed from the latent, causal."""
    import jax
    import jax.numpy as jnp
    d, nh, dn, dr, dv, r = _dims(m)
    b, s, _ = h.shape
    pos = jnp.broadcast_to(jnp.arange(s), (b, s))
    inv = jnp.asarray(yarn_inv_freq(m), jnp.float32)
    f = m["yarn_factor"]
    cos_scale = (yarn_mscale(f, m["yarn_mscale"])
                 / yarn_mscale(f, m["yarn_mscale_all_dim"]))
    q = (h @ _weights(a["wq"], quant)).reshape(b, s, nh, dn + dr)
    q_nope, q_rope = q[..., :dn], _rope(q[..., dn:], pos, inv, cos_scale)
    c = _rms(h @ _weights(a["w_dkv"], quant), a["kv_norm_scale"],
             m["norm_eps"])
    kr = _rope((h @ _weights(a["w_kr"], quant))[:, :, None], pos, inv,
               cos_scale)[:, :, 0]
    k_nope = (c @ _weights(a["w_uk"], quant)).reshape(b, s, nh, dn)
    v = (c @ _weights(a["w_uv"], quant)).reshape(b, s, nh, dv)
    causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    outs = []
    for lo in range(0, nh, head_group):       # a few heads' scores at once
        hs = slice(lo, lo + head_group)
        sc = (jnp.einsum("bqhd,bshd->bhqs", q_nope[:, :, hs], k_nope[:, :, hs])
              + jnp.einsum("bqhd,bsd->bhqs", q_rope[:, :, hs], kr)) \
            * softmax_scale(m)
        p = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
        outs.append(jnp.einsum("bhqs,bshd->bqhd", p, v[:, :, hs]))
    o = jnp.concatenate(outs, axis=2).reshape(b, s, nh * dv)
    return o @ _weights(a["wo"], quant)


def moe_ref(m: dict, w: dict, x, quant=None, first: int = 0):
    """The MoE layer at ``x [B, S, d]``: the shared experts, plus each held
    expert ``first + e`` (weights ``w[..][e]``) on the tokens whose top-k
    choices hold it, weighted by its unnormalised router probability."""
    import jax
    import jax.numpy as jnp
    probs = jax.nn.softmax(x @ _weights(w["router"], quant), axis=-1)
    top, idx = jax.lax.top_k(probs, m["top_k"])
    y = _swiglu(x, w["shared"], quant)
    for e in range(w["w_gate"].shape[0]):
        gate = jnp.sum(jnp.where(idx == first + e, top, 0.0), axis=-1)
        expert = {k: w[k][e] for k in ("w_gate", "w_up", "w_down")}
        y = y + gate[..., None] * _swiglu(x, expert, quant)
    return y


@functools.lru_cache(maxsize=None)
def _layer_fn(m_items: tuple, quant, kind: str):
    import jax
    m = dict(m_items)

    def layer(layers, i, x):
        lp = jax.tree_util.tree_map(lambda a: a[i], layers)
        with jax.default_matmul_precision("highest"):
            h = _rms(x, lp["ln1_scale"], m["norm_eps"])
            x = x + _attention(m, lp["attn"], h, quant)
            h = _rms(x, lp["ln2_scale"], m["norm_eps"])
            if kind == "dense":
                return x + _swiglu(h, lp["mlp"], quant)
            return x + moe_ref(m, lp["moe"], h, quant)

    return jax.jit(layer)


@functools.lru_cache(maxsize=None)
def _head_fns(m_items: tuple, quant, chunk: int):
    """Jitted ``(best, final)``: the best logit and its id over one
    vocabulary chunk, and the final norm at the compared rows."""
    import jax
    import jax.numpy as jnp
    m = dict(m_items)

    def best(params, x, lo):
        w = jax.lax.dynamic_slice_in_dim(params["lm_head"], lo, chunk, 1)
        with jax.default_matmul_precision("highest"):
            logits = x @ _weights(w, quant)                  # [B, P, chunk]
        return logits.max(-1), logits.argmax(-1) + lo

    def final(params, x, rows):
        x = jnp.take_along_axis(x, rows[:, :, None], axis=1)
        return _rms(x, params["final_norm_scale"], m["norm_eps"])

    return jax.jit(best), jax.jit(final)


@functools.lru_cache(maxsize=None)
def _logit_at():
    """Jitted head logit of given token ids at given hidden states."""
    import jax
    import jax.numpy as jnp

    def at(params, x, tok):
        w = jnp.take(params["lm_head"], tok.reshape(-1), axis=1)
        w = w.T.reshape(*tok.shape, -1).astype(jnp.float32)  # [B, P, d]
        return jnp.sum(x * w, axis=-1)

    return jax.jit(at)


def forward(params, m: dict, tokens: np.ndarray, rows: np.ndarray,
            quant=None):
    """Final hidden states ``[B, P, d]`` f32 at ``rows [B, P]`` of the
    token sequences ``tokens [B, S]`` (causal: padding after a row does
    not reach it), and the head's ``(best value, best id)`` there.
    ``quant`` maps every f32 weight matrix to its stand-in (the
    control); ``None`` is the reference."""
    import jax.numpy as jnp
    items = tuple(sorted(m.items()))
    x = params["embed"][jnp.asarray(tokens)].astype(jnp.float32)
    for stack, kind in (("dense_layers", "dense"), ("layers", "moe")):
        layer = _layer_fn(items, quant, kind)
        for i in range(params[stack]["ln1_scale"].shape[0]):
            x = layer(params[stack], i, x)
    chunk = min(VOCAB_CHUNK, m["vocab_size"])
    assert m["vocab_size"] % chunk == 0, "whole vocabulary chunks"
    best_fn, final_fn = _head_fns(items, quant, chunk)
    h = final_fn(params, x, jnp.asarray(rows))
    bv = bi = None
    for lo in range(0, m["vocab_size"], chunk):
        v, i = best_fn(params, h, lo)
        if bv is None:
            bv, bi = v, i
        else:
            take = v > bv
            bv, bi = jnp.where(take, v, bv), jnp.where(take, i, bi)
    return h, np.asarray(bv), np.asarray(bi)


def logits_at(params, h, tok: np.ndarray) -> np.ndarray:
    """Reference logits of ``tok [B, P]`` from hidden states ``h``."""
    import jax.numpy as jnp
    return np.asarray(_logit_at()(params, h, jnp.asarray(tok)))


def fp8_weights(w):
    """The control's weights: float8 e4m3 with one scale per output
    column (the step below the configuration's bfloat16)."""
    import jax.numpy as jnp
    s = jnp.max(jnp.abs(w), axis=-2, keepdims=True) / 448.0
    s = jnp.where(s > 0, s, 1.0)
    return (w / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def planner_layers(m: dict, seq: int):
    """Per-layer FLOPs and activation bytes of one sequence through the
    whole published model, as the admission planner prices it: MLA's
    query, latent down-projection and rope key, the latent's
    up-projections to keys and values, the output projection, scores at
    nope + rope and values at ``v_head_dim``; the leading dense layer's
    SwiGLU; each MoE layer's router and the experts a token activates
    (top-k routed of all of them, plus the shared); activations are the
    f16 hidden state."""
    d, h, dn, dr, dv, r = _dims(m)
    attn = (2.0 * seq * d * (h * (dn + dr) + r + dr)
            + 2.0 * seq * r * h * (dn + dv) + 2.0 * seq * h * dv * d
            + 2.0 * h * seq * seq * (dn + dr + dv))
    dense = 3 * 2.0 * seq * d * m["d_ff"]
    moe = (3 * 2.0 * seq * d * m["moe_d_ff"]
           * (m["top_k"] + m["num_shared_experts"])
           + 2.0 * seq * d * m["num_experts"])
    n_dense = m["first_dense_layers"]
    flops = np.asarray([attn + (dense if i < n_dense else moe)
                        for i in range(m["num_layers"])])
    return flops, np.full(m["num_layers"], 2.0 * seq * d)


def _token_params(m: dict) -> tuple[float, float]:
    """Matmul parameters one token passes through outside attention's
    context terms, all layers: ``(every token, absorbed decode only)``.
    The MoE layers count the shared experts, the router and the routed
    experts a token has on this chip: ``top_k · experts_held /
    num_experts`` of them."""
    d, h, dn, dr, dv, r = _dims(m)
    n_dense = m["first_dense_layers"]
    n_moe = m["num_layers"] - n_dense
    ff = m["moe_d_ff"]
    proj = d * h * (dn + dr) + d * r + d * dr + h * dv * d
    held = m["top_k"] * _held(m) / m["num_experts"]
    moe = (3 * d * ff * (m["num_shared_experts"] + held)
           + d * m["num_experts"])
    layers = (m["num_layers"] * proj + n_dense * 3 * d * m["d_ff"]
              + n_moe * moe)
    return layers, m["num_layers"] * h * r * (dn + dv)


def prefill_flops(m: dict, prompt_len: int) -> float:
    """Model FLOPs of this chip's share of a prompt's prefill: every
    token through the layers (keys and values reconstructed from the
    latent), causal attention at nope + rope and v, logits for the last
    position only."""
    d, h, dn, dr, dv, r = _dims(m)
    layers, _ = _token_params(m)
    up = m["num_layers"] * r * h * (dn + dv)
    attn = (2 * h * (dn + dr + dv) * m["num_layers"]
            * prompt_len * (prompt_len + 1) / 2)
    return (2.0 * (layers + up) * prompt_len + attn
            + 2.0 * d * m["vocab_size"])


def token_flops(m: dict, context: int, with_head: bool) -> float:
    """Model FLOPs of this chip's share of one token decoded in latent
    space over ``context`` positions: the projections, the query folded
    into the latent and the output out of it, scores over the latent and
    the rope key and values over the latent, the head where logits are
    needed."""
    d, h, dn, dr, dv, r = _dims(m)
    layers, absorbed = _token_params(m)
    attn = 2 * h * (r + dr + r) * context * m["num_layers"]
    return (2.0 * (layers + absorbed) + attn
            + (2.0 * d * m["vocab_size"] if with_head else 0.0))


def step_weight_bytes(m: dict) -> float:
    """Bytes every decode step must read: every weight but the routed
    experts (which a step reads only where a pair lands) and the
    embedding table (of which it reads its tokens' rows)."""
    import jax
    import jax.numpy as jnp
    itemsize = jnp.dtype(m["dtype"]).itemsize
    flat, _ = jax.tree_util.tree_flatten_with_path(
        param_shapes(m), is_leaf=lambda s: isinstance(s, tuple))
    total = 0.0
    for path, shape in flat:
        keys = [str(getattr(p, "key", p)) for p in path]
        if keys[-1] == "embed" or ("moe" in keys and "shared" not in keys
                                   and keys[-1] != "router"):
            continue
        total += float(np.prod(shape)) * (4 if "scale" in keys[-1]
                                          else itemsize)
    return total


def small(m: dict) -> dict:
    """The model keys cut for the CPU tests: three layers (the dense one
    and two MoE) of width 128, the router's 64 outputs, top-6 and 8
    experts held as published."""
    return {"num_layers": 3, "d_model": 128, "num_heads": 4,
            "num_kv_heads": 4, "head_dim": 32, "kv_lora_rank": 64,
            "qk_nope_dim": 32, "qk_rope_dim": 16, "v_head_dim": 32,
            "d_ff": 256, "moe_d_ff": 64, "vocab_size": 512}
