"""minitron-4b: its weights from the seed, and the plain float32 reference
forward pass that the served tokens are compared with.

The reference imports nothing of the program.  It follows the published
architecture (arXiv:2407.14679; Nemotron-4 block): token embedding
scaled by sqrt(d_model), pre-norm RMSNorm with the ``(1 + scale)``
convention, grouped-query attention with split-half rotary embedding,
a non-gated squared-ReLU MLP, a final RMSNorm and an untied head.  It
runs in float32 at ``highest`` matmul precision, one layer at a time.

The weights are laid out as the program's parameter tree (its
checkpoint layout) and made here, in one jitted call, in the dtype they
are served in.

It also gives the serve driver what depends on the model's shape:
``planner_layers`` (the model's layers as the admission planner prices
them), ``prefill_flops`` and ``token_flops`` (the model FLOPs
``serve.mfu`` counts) and ``small`` (its cut for the CPU tests).
"""
from __future__ import annotations

import functools

import numpy as np

import workcount


def param_shapes(m: dict) -> dict:
    """The parameter tree's shapes: stacked layers, untied head."""
    d, v, n = m["d_model"], m["vocab_size"], m["num_layers"]
    hq, hkv, hd, f = (m["num_heads"], m["num_kv_heads"], m["head_dim"],
                      m["d_ff"])
    return {"embed": (v, d), "final_norm_scale": (d,), "lm_head": (d, v),
            "layers": {"ln1_scale": (n, d), "ln2_scale": (n, d),
                       "attn": {"wq": (n, d, hq * hd), "wk": (n, d, hkv * hd),
                                "wv": (n, d, hkv * hd),
                                "wo": (n, hq * hd, d)},
                       "mlp": {"w_up": (n, d, f), "w_down": (n, f, d)}}}


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


def _rms(x, scale, eps):
    import jax.numpy as jnp
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x / jnp.sqrt(var + eps) * (1.0 + scale.astype(jnp.float32))


def _rope(x, pos, theta):
    """Split-half rotary embedding of ``x [B, S, H, D]``."""
    import jax.numpy as jnp
    d = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos[:, :, None].astype(jnp.float32) * inv          # [B, S, D/2]
    cos, sin = jnp.cos(ang)[:, :, None], jnp.sin(ang)[:, :, None]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _weights(w, quant):
    import jax.numpy as jnp
    w = w.astype(jnp.float32)
    return w if quant is None else quant(w)


@functools.lru_cache(maxsize=None)
def _layer_fn(m_items: tuple, quant):
    import jax
    import jax.numpy as jnp
    m = dict(m_items)
    hq, hkv, hd = m["num_heads"], m["num_kv_heads"], m["head_dim"]

    def layer(layers, i, x):
        lp = jax.tree_util.tree_map(lambda a: a[i], layers)
        b, s, _ = x.shape
        pos = jnp.broadcast_to(jnp.arange(s), (b, s))
        with jax.default_matmul_precision("highest"):
            h = _rms(x, lp["ln1_scale"], m["norm_eps"])
            a = lp["attn"]
            q = (h @ _weights(a["wq"], quant)).reshape(b, s, hq, hd)
            k = (h @ _weights(a["wk"], quant)).reshape(b, s, hkv, hd)
            v = (h @ _weights(a["wv"], quant)).reshape(b, s, hkv, hd)
            q = _rope(q, pos, m["rope_theta"])
            k = _rope(k, pos, m["rope_theta"])
            q = q.reshape(b, s, hkv, hq // hkv, hd)
            sc = jnp.einsum("bqkgd,bskd->bkgqs", q, k) * hd ** -0.5
            causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
            sc = jnp.where(causal, sc, -jnp.inf)
            p = jax.nn.softmax(sc, axis=-1)
            o = jnp.einsum("bkgqs,bskd->bqkgd", p, v).reshape(b, s, hq * hd)
            x = x + o @ _weights(a["wo"], quant)
            h = _rms(x, lp["ln2_scale"], m["norm_eps"])
            u = jnp.square(jnp.maximum(
                h @ _weights(lp["mlp"]["w_up"], quant), 0.0))
            return x + u @ _weights(lp["mlp"]["w_down"], quant)

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
            quant=None, vocab_chunk: int = 32000):
    """Final hidden states ``[B, P, d]`` f32 at ``rows [B, P]`` of the
    token sequences ``tokens [B, S]`` (causal: padding after a row does
    not reach it), and the head's ``(best value, best id)`` there.
    ``quant`` maps every f32 weight matrix to its stand-in (the
    control); ``None`` is the reference."""
    import jax.numpy as jnp
    items = tuple(sorted(m.items()))
    layer = _layer_fn(items, quant)
    x = params["embed"][jnp.asarray(tokens)].astype(jnp.float32) \
        * np.float32(np.sqrt(m["d_model"]))
    for i in range(m["num_layers"]):
        x = layer(params["layers"], i, x)
    vocab_chunk = min(vocab_chunk, m["vocab_size"])
    best_fn, final_fn = _head_fns(items, quant, vocab_chunk)
    h = final_fn(params, x, jnp.asarray(rows))
    bv = bi = None
    for lo in range(0, m["vocab_size"], vocab_chunk):
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
    decoder, as the admission planner states a model's layers: QKV and
    output projections, attention scores, the MLP; activations are the
    f16 hidden state."""
    d, n = m["d_model"], m["num_layers"]
    proj = 2.0 * seq * d * m["num_heads"] * m["head_dim"] * 2
    kv = 2.0 * seq * d * m["num_kv_heads"] * m["head_dim"] * 2
    scores = 2.0 * m["num_heads"] * seq * seq * m["head_dim"] * 2
    n_mat = 2 if m["mlp_act"] in ("gelu_plain", "relu2") else 3
    ff = n_mat * 2.0 * seq * d * m["d_ff"]
    return (np.full(n, proj + kv + scores + ff), np.full(n, 2.0 * seq * d))


def prefill_flops(m: dict, prompt_len: int) -> float:
    """Model FLOPs of a prompt's prefill (dense GQA decoder)."""
    return workcount.decoder_prefill_flops(m, prompt_len)


def token_flops(m: dict, context: int, with_head: bool) -> float:
    """Model FLOPs of one decoded token over ``context`` positions."""
    return workcount.decoder_token_flops(m, context, with_head)


def small(m: dict) -> dict:
    """The model keys cut for the CPU tests: two layers of width 256, the
    same family."""
    return {"num_layers": 2, "d_model": 256, "num_heads": 4,
            "num_kv_heads": 2, "head_dim": 64, "d_ff": 512,
            "vocab_size": 512}
