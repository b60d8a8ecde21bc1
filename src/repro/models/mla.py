"""Multi-head Latent Attention (DeepSeek-V2, arXiv:2405.04434).

KV state is compressed to a per-token latent ``c_kv`` of size
``kv_lora_rank`` plus a shared rope key of size ``qk_rope_dim``; per-head
keys/values are reconstructed with up-projections.  The decode cache stores
only (c_kv, k_rope) — 576 B/token/layer at the assigned dims — which is what
makes 512k-token decode contexts feasible (DESIGN.md §4).

Two decode paths:
  * ``absorbed=False`` (baseline): reconstruct full K/V each step — faithful
    to the naive formulation, heavy on HBM traffic.
  * ``absorbed=True`` (optimised): fold W_uk into the query and W_uv past the
    attention, so scores are taken directly against the latent cache.
    This is the §Perf hillclimb lever for decode shapes.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.layers import matmul, rms_norm, rope_for, yarn_softmax_scale

NEG_INF = -1e30


def mla_param_shapes(cfg) -> dict:
    d, h = cfg.d_model, cfg.num_heads
    r, dn, dr, dv = cfg.kv_lora_rank, cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    return {
        "wq": (d, h * (dn + dr)),          # queries (nope + rope parts)
        "w_dkv": (d, r),                   # KV down-projection (latent)
        "w_kr": (d, dr),                   # shared rope key
        "kv_norm_scale": (r,),
        "w_uk": (r, h * dn),               # latent -> per-head key (nope)
        "w_uv": (r, h * dv),               # latent -> per-head value
        "wo": (h * dv, d),
    }


def _queries(params, x, cfg, positions):
    b, s, _ = x.shape
    h, dn, dr = cfg.num_heads, cfg.qk_nope_dim, cfg.qk_rope_dim
    q = matmul(x, params["wq"]).reshape(b, s, h, dn + dr)
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    q_rope = rope_for(q_rope, positions, cfg)
    return q_nope, q_rope


def _latent(params, x, cfg, positions):
    c_kv = rms_norm(matmul(x, params["w_dkv"]), params["kv_norm_scale"],
                    cfg.norm_eps)                       # [B,S,r]
    k_rope = matmul(x, params["w_kr"])[:, :, None, :]   # [B,S,1,dr]
    k_rope = rope_for(k_rope, positions, cfg)[:, :, 0]
    return c_kv, k_rope


def softmax_scale(cfg) -> float:
    """``(dn + dr) ** -0.5``, times YaRN's ``mscale²`` where it scales."""
    return (cfg.qk_nope_dim + cfg.qk_rope_dim) ** -0.5 \
        * yarn_softmax_scale(cfg)


def mla_self_attention(params, x, cfg, *, positions, impl="chunked"):
    """Full-segment MLA (train / prefill). Returns (out, (c_kv, k_rope))."""
    from repro.models.attention import chunked_attention, naive_attention
    b, s, _ = x.shape
    h, dn, dv = cfg.num_heads, cfg.qk_nope_dim, cfg.v_head_dim
    dr = cfg.qk_rope_dim
    q_nope, q_rope = _queries(params, x, cfg, positions)
    c_kv, k_rope = _latent(params, x, cfg, positions)
    k_nope = matmul(c_kv, params["w_uk"]).reshape(b, s, h, dn)
    v = matmul(c_kv, params["w_uv"]).reshape(b, s, h, dv)
    # pack rope part into the head dim so one attention call handles both
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope[:, :, None, :], (b, s, h, dr))],
        axis=-1)
    scale = softmax_scale(cfg)
    fn = naive_attention if impl == "naive" else chunked_attention
    out = fn(q, k, v, causal=True, scale=scale)
    out = matmul(out.reshape(b, s, h * dv), params["wo"])
    return out, (c_kv, k_rope)


def mla_decode_attention(params, x, cfg, *, ckv_cache, kr_cache, pos,
                         absorbed=True):
    """One-token MLA against the latent cache.

    ckv_cache [B,Smax,r]; kr_cache [B,Smax,dr]; ``pos`` is the incoming
    token's position, scalar or [B] for ragged continuous-batching slots;
    returns (out, new caches).
    """
    b, _, _ = x.shape
    h, dn, dv, r = (cfg.num_heads, cfg.qk_nope_dim, cfg.v_head_dim,
                    cfg.kv_lora_rank)
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))
    positions = pos[:, None]
    q_nope, q_rope = _queries(params, x, cfg, positions)    # [B,1,h,dn/dr]
    c_new, kr_new = _latent(params, x, cfg, positions)
    # per-row write positions
    upd = jax.vmap(lambda c, new, p: jax.lax.dynamic_update_slice_in_dim(
        c, new, p, axis=0))
    ckv_cache = upd(ckv_cache, c_new, pos)
    kr_cache = upd(kr_cache, kr_new, pos)
    smax = ckv_cache.shape[1]
    valid = jnp.arange(smax)[None, :] <= pos[:, None]        # [B,Smax]
    scale = softmax_scale(cfg)

    dt = x.dtype
    if absorbed:
        # fold W_uk into q: per-head latent-space query [B,h,r]
        w_uk = params["w_uk"].reshape(r, h, dn)
        q_lat = jnp.einsum("bohd,rhd->bhr", q_nope, w_uk.astype(dt))
        s_nope = jnp.einsum("bhr,bsr->bhs", q_lat, ckv_cache.astype(dt))
        s_rope = jnp.einsum("bohd,bsd->bhs", q_rope, kr_cache.astype(dt))
        s = (s_nope + s_rope).astype(jnp.float32) * scale
        s = s + jnp.where(valid, 0.0, NEG_INF)[:, None, :]
        p = jax.nn.softmax(s, axis=-1).astype(dt)
        o_lat = jnp.einsum("bhs,bsr->bhr", p, ckv_cache.astype(dt))
        w_uv = params["w_uv"].reshape(r, h, dv)
        out = jnp.einsum("bhr,rhd->bhd", o_lat, w_uv.astype(dt))
        out = out.reshape(b, 1, h * dv).astype(x.dtype)
    else:
        # naive: reconstruct K/V for the whole context every step
        k_nope = matmul(ckv_cache, params["w_uk"]).reshape(b, smax, h, dn)
        v = matmul(ckv_cache, params["w_uv"]).reshape(b, smax, h, dv)
        s_nope = jnp.einsum("bohd,bshd->bhs", q_nope, k_nope.astype(dt))
        s_rope = jnp.einsum("bohd,bsd->bhs", q_rope, kr_cache.astype(dt))
        sc = (s_nope + s_rope).astype(jnp.float32) * scale
        sc = sc + jnp.where(valid, 0.0, NEG_INF)[:, None, :]
        p = jax.nn.softmax(sc, axis=-1).astype(dt)
        out = jnp.einsum("bhs,bshd->bhd", p, v)
        out = out.reshape(b, 1, h * dv).astype(x.dtype)

    return matmul(out, params["wo"]), (ckv_cache, kr_cache)
