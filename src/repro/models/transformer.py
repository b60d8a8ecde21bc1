"""Generic decoder-only transformer: dense / MoE / MLA / VLM families.

Layers are *stacked* (leading L axis on every layer leaf) and executed with
``jax.lax.scan`` so the HLO contains one layer body regardless of depth —
essential for tractable multi-pod compile times.  Training wraps the layer
body in ``jax.checkpoint`` (remat).  An MoE model's leading dense layers
(``cfg.first_dense_layers``, a ``d_ff`` MLP in place of the experts) are a
stack of their own, ``dense_layers`` in the parameters and the cache, run
before ``layers``.

Uniform model API (shared by all families via ``repro.models.registry``):

    init_params(key)                        -> params
    train_loss(params, batch)               -> (loss, metrics)
    forward(params, batch)                  -> logits          (full segment)
    prefill(params, batch, max_len)         -> (logits, cache)
    decode_step(params, batch, cache)       -> (logits, cache)
    init_cache(batch_size, max_len)         -> cache (zeros)
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.distributed.context import constrain
from repro.models import attention as attn_mod
from repro.models import mla as mla_mod
from repro.models import moe as moe_mod
from repro.models.layers import (gated_mlp, init_tree, matmul,
                                 mlp_param_shapes, rms_norm)

PyTree = Any


# --------------------------------------------------------------------------
# Parameter shapes
# --------------------------------------------------------------------------
def layer_shapes(cfg, dense: bool = False) -> dict:
    """One layer's shapes; ``dense`` gives a leading dense layer's."""
    d = cfg.d_model
    shapes = {"ln1_scale": (d,), "ln2_scale": (d,)}
    if cfg.attn_kind == "mla":
        shapes["attn"] = mla_mod.mla_param_shapes(cfg)
    else:
        shapes["attn"] = attn_mod.attn_param_shapes(cfg)
    if cfg.num_experts and not dense:
        shapes["moe"] = moe_mod.moe_param_shapes(cfg)
    else:
        shapes["mlp"] = mlp_param_shapes(d, cfg.d_ff, cfg.mlp_act)
    return shapes


def stacks(cfg) -> tuple[tuple[str, int], ...]:
    """The layer stacks in order: (name, layer count)."""
    n_dense = cfg.first_dense_layers
    out = (("layers", cfg.num_layers - n_dense),)
    return (("dense_layers", n_dense),) + out if n_dense else out


def param_shapes(cfg) -> dict:
    d, v = cfg.d_model, cfg.vocab_size
    shapes = {
        "embed": (v, d),
        "final_norm_scale": (d,),
    }
    for name, n in stacks(cfg):
        shapes[name] = jax.tree_util.tree_map(
            lambda s: (n, *s), layer_shapes(cfg, name == "dense_layers"),
            is_leaf=lambda s: isinstance(s, tuple))
    if not cfg.tie_embeddings:
        shapes["lm_head"] = (d, v)
    return shapes


def init_params(cfg, key) -> PyTree:
    dtype = jnp.dtype(cfg.dtype)
    return init_tree(key, param_shapes(cfg), dtype)


# --------------------------------------------------------------------------
# Blocks
# --------------------------------------------------------------------------
def _attn_full(lp, x, cfg, positions, impl):
    if cfg.attn_kind == "mla":
        out, kv = mla_mod.mla_self_attention(lp["attn"], x, cfg,
                                             positions=positions, impl=impl)
    else:
        out, kv = attn_mod.gqa_self_attention(lp["attn"], x, cfg,
                                              positions=positions, impl=impl)
    return out, kv


def _ffn(lp, x, cfg, dropless=False, count_held=False):
    """(out, aux, the (token, choice) pairs routed to held experts: 0
    unless ``count_held`` and the layer has experts)."""
    if "moe" in lp:
        y = constrain(x, "activation")
        if count_held:
            return moe_mod.moe_mlp(lp["moe"], y, cfg, dropless=dropless,
                                   count_held=True)
        out, aux = moe_mod.moe_mlp(lp["moe"], y, cfg, dropless=dropless)
        return out, aux, jnp.int32(0)
    return gated_mlp(x, lp["mlp"], cfg.mlp_act), 0.0, jnp.int32(0)


def block_full(lp, x, cfg, positions, impl, dropless=False):
    """One pre-norm layer over a full segment. Returns (x, aux, (k, v))."""
    h, kv = _attn_full(lp, rms_norm(x, lp["ln1_scale"], cfg.norm_eps), cfg,
                       positions, impl)
    x = x + h
    f, aux, _ = _ffn(lp, rms_norm(x, lp["ln2_scale"], cfg.norm_eps), cfg,
                     dropless)
    return x + f, aux, kv


def block_decode(lp, x, cfg, cache_l, pos, count_held=False):
    """One layer, one token. cache_l: per-layer cache dict.  Returns (x,
    cache, the (token, choice) pairs routed to held experts, 0 unless
    ``count_held``)."""
    xn = rms_norm(x, lp["ln1_scale"], cfg.norm_eps)
    if cfg.attn_kind == "mla":
        h, (ckv, kr) = mla_mod.mla_decode_attention(
            lp["attn"], xn, cfg, ckv_cache=cache_l["ckv"],
            kr_cache=cache_l["kr"], pos=pos, absorbed=cfg.mla_absorbed)
        new_cache = {"ckv": ckv, "kr": kr}
    else:
        h, (k, v) = attn_mod.gqa_decode_attention(
            lp["attn"], xn, cfg, k_cache=cache_l["k"], v_cache=cache_l["v"],
            pos=pos)
        new_cache = {"k": k, "v": v}
    x = x + h
    f, _, held = _ffn(lp, rms_norm(x, lp["ln2_scale"], cfg.norm_eps), cfg,
                      dropless=True, count_held=count_held)
    return x + f, new_cache, held


# --------------------------------------------------------------------------
# Full-model passes
# --------------------------------------------------------------------------
def _embed_in(params, batch, cfg):
    if cfg.takes_embeddings and "embeds" in batch:
        x = batch["embeds"].astype(jnp.dtype(cfg.dtype))
    else:
        tokens = batch["tokens"]
        x = params["embed"][tokens].astype(jnp.dtype(cfg.dtype))
        if cfg.embed_scale:
            x = x * jnp.sqrt(float(cfg.d_model)).astype(x.dtype)
    return constrain(x, "activation")


def _lm_head(params, x, cfg):
    if cfg.tie_embeddings and "lm_head" not in params:
        logits = matmul(x, params["embed"].T)
    else:
        logits = matmul(x, params["lm_head"])
    return constrain(logits, "logits")


def backbone(params, batch, cfg, *, impl="chunked", remat=False):
    """All layers + final norm; returns (hidden [B,S,d], aux_loss)."""
    x = _embed_in(params, batch, cfg)
    positions = jnp.arange(x.shape[1])[None, :]
    act_name = "activation_sp" if cfg.seq_parallel else "activation"
    x = constrain(x, act_name)

    def body(carry, lp):
        h, aux = carry
        h2, aux2, _ = block_full(lp, h, cfg, positions, impl)
        return (constrain(h2, act_name), aux + aux2), None

    if remat:
        body = jax.checkpoint(body)
    aux = 0.0
    for name, _ in stacks(cfg):
        (x, aux), _ = jax.lax.scan(body, (x, aux), params[name])
    return rms_norm(x, params["final_norm_scale"], cfg.norm_eps), aux


def forward(params, batch, cfg, *, impl="chunked", remat=False):
    """Full-segment forward. Returns (logits [B,S,V], aux_loss)."""
    x, aux = backbone(params, batch, cfg, impl=impl, remat=remat)
    return _lm_head(params, x, cfg), aux


def lm_loss(logits, labels, mask=None):
    """Mean token cross-entropy. logits [B,S,V]; labels [B,S] int32."""
    logits32 = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(logits32, axis=-1)
    ll = jnp.take_along_axis(logits32, labels[..., None], axis=-1)[..., 0]
    nll = lse - ll
    if mask is None:
        return nll.mean()
    m = mask.astype(jnp.float32)
    return (nll * m).sum() / jnp.maximum(m.sum(), 1.0)


def chunked_lm_loss(x, head_w, labels, cfg):
    """Sequence-chunked vocab-parallel cross-entropy.

    Scans over sequence chunks so the f32 [B,S,V] logits never materialise —
    each chunk's logits are rematerialised in the backward pass.  Essential
    for 256k-vocab models at 1M-token global batches (DESIGN.md §5).
    """
    b, s, d = x.shape
    chunk = min(cfg.loss_chunk, s)
    pad = (-s) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
        labels = jnp.pad(labels, ((0, 0), (0, pad)), constant_values=-1)
    nc = (s + pad) // chunk
    xc = x.reshape(b, nc, chunk, d).swapaxes(0, 1)
    lc = labels.reshape(b, nc, chunk).swapaxes(0, 1)

    def body(acc, inp):
        xi, li = inp
        logits = constrain(matmul(xi, head_w), "logits")
        logits32 = logits.astype(jnp.float32)
        lse = jax.scipy.special.logsumexp(logits32, axis=-1)
        ll = jnp.take_along_axis(
            logits32, jnp.maximum(li, 0)[..., None], axis=-1)[..., 0]
        valid = (li >= 0).astype(jnp.float32)
        return acc + ((lse - ll) * valid).sum(), None

    total, _ = jax.lax.scan(jax.checkpoint(body), jnp.zeros((), jnp.float32),
                            (xc, lc))
    return total / (b * s)


def train_loss(params, batch, cfg, *, impl="chunked"):
    """batch: tokens [B,S+1] (or embeds [B,S,d] + labels [B,S])."""
    if cfg.takes_embeddings and "embeds" in batch:
        inputs = {"embeds": batch["embeds"]}
        labels = batch["labels"]
    else:
        tokens = batch["tokens"]
        inputs = {"tokens": tokens[:, :-1]}
        labels = tokens[:, 1:]
    if cfg.loss_chunk:
        x, aux = backbone(params, inputs, cfg, impl=impl, remat=True)
        head_w = (params["embed"].T if cfg.tie_embeddings
                  and "lm_head" not in params else params["lm_head"])
        loss = chunked_lm_loss(x, head_w, labels, cfg)
    else:
        logits, aux = forward(params, inputs, cfg, impl=impl, remat=True)
        loss = lm_loss(logits, labels, batch.get("mask"))
    return loss + aux, {"xent": loss, "aux": aux}


# --------------------------------------------------------------------------
# KV-cache: prefill & decode
# --------------------------------------------------------------------------
def cache_shapes(cfg, batch_size: int, max_len: int) -> dict:
    """Shape/dtype tree of the decode cache (stacked over layers, one
    subtree per stack)."""
    dtype = jnp.dtype(cfg.dtype)
    s = min(max_len, cfg.window) if cfg.window else max_len

    def stack(l):
        if cfg.attn_kind == "mla":
            return {
                "ckv": ((l, batch_size, s, cfg.kv_lora_rank), dtype),
                "kr": ((l, batch_size, s, cfg.qk_rope_dim), dtype),
            }
        kv = (l, batch_size, s, cfg.num_kv_heads, cfg.head_dim)
        return {"k": (kv, dtype), "v": (kv, dtype)}

    out = {name: stack(n) for name, n in stacks(cfg)}
    out["pos"] = ((), jnp.int32)
    return out


def init_cache(cfg, batch_size: int, max_len: int) -> dict:
    return jax.tree_util.tree_map(
        lambda sd: jnp.zeros(sd[0], sd[1]), cache_shapes(cfg, batch_size,
                                                         max_len),
        is_leaf=lambda x: isinstance(x, tuple) and isinstance(x[0], tuple))


def prefill(params, batch, cfg, max_len: int, *, impl="chunked"):
    """Run the prompt; build the cache. Returns (last-token logits, cache)."""
    x = _embed_in(params, batch, cfg)
    b, s = x.shape[0], x.shape[1]
    positions = jnp.arange(s)[None, :]

    def body(carry, lp):
        h, aux = carry
        h2, aux2, kv = block_full(lp, h, cfg, positions, impl, dropless=True)
        return (h2, aux + aux2), kv

    cache = init_cache(cfg, b, max_len)
    cache["pos"] = jnp.asarray(s, jnp.int32)
    carry = (x, 0.0)
    for stack, _ in stacks(cfg):
        carry, kvs = jax.lax.scan(body, carry, params[stack])
        names = ("ckv", "kr") if cfg.attn_kind == "mla" else ("k", "v")
        layer_cache = cache[stack]
        for name, val in zip(names, kvs):
            cache_len = layer_cache[name].shape[2]
            if cfg.window and s >= cache_len:
                # ring-buffer invariant: token p lives at slot p % window
                seg = val[:, :, -cache_len:]
                seg = jnp.roll(seg, shift=(s - cache_len) % cache_len,
                               axis=2)
            else:
                seg = val
            layer_cache[name] = jax.lax.dynamic_update_slice_in_dim(
                layer_cache[name], seg.astype(layer_cache[name].dtype),
                0, axis=2)
    x = rms_norm(carry[0], params["final_norm_scale"], cfg.norm_eps)
    logits = _lm_head(params, x[:, -1:], cfg)
    return logits, cache


def decode_step(params, batch, cache, cfg, *, count_held=False):
    """One decode step. batch: {"token": [B,1]}. Returns (logits, cache);
    ``count_held`` appends the (token, choice) pairs the step routed to
    held experts, summed over the MoE layers."""
    x = _embed_in(params, {"tokens": batch["token"]}, cfg)
    pos = cache["pos"]

    def body(carry, lp_cache):
        h, held = carry
        lp, cache_l = lp_cache
        h2, new_cache, held_l = block_decode(lp, h, cfg, cache_l, pos,
                                             count_held)
        return (h2, held + held_l), new_cache

    new = {"pos": pos + 1}
    carry = (x, jnp.int32(0))
    for stack, _ in stacks(cfg):
        carry, new[stack] = jax.lax.scan(body, carry,
                                         (params[stack], cache[stack]))
    x = rms_norm(carry[0], params["final_norm_scale"], cfg.norm_eps)
    logits = _lm_head(params, x, cfg)
    return (logits, new, carry[1]) if count_held else (logits, new)
