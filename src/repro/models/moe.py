"""Mixture-of-Experts layer (DeepSeekMoE-style: shared + fine-grained routed).

Dispatch is *sort-based with static capacity* and built entirely from
gathers + batched matmuls (no large scatters), which keeps GSPMD lowering
clean when the expert buffer is sharded over the ``model`` mesh axis
(expert parallelism) while tokens are sharded over ``data``:

  1. route: softmax(router) → top-k experts/weights per token
  2. argsort token-choices by expert id → contiguous per-expert runs
  3. expert buffer [E, C, d] gathered from the sorted tokens (overflow beyond
     capacity C is dropped, matching Switch/GShard semantics; serving passes
     ``dropless=True``, C = all tokens, so nothing is dropped)
  4. batched expert matmuls [E,C,d]×[E,d,ff]
  5. inverse-permutation gather back to [T, k, d] → weighted combine

The auxiliary load-balance loss (DeepSeek eq. 12-style) is returned for the
training objective.

A layer may hold a share of the experts (``cfg.experts_held``, experts
``0 .. held-1``, as one chip of an expert-parallel deployment holds them):
the router keeps all ``num_experts`` outputs and its top-k, and only the
(token, choice) pairs routed to a held expert contribute.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.layers import _ACTS, gated_mlp, matmul, mlp_param_shapes


def moe_param_shapes(cfg) -> dict:
    d, e, ff = cfg.d_model, cfg.n_experts_held, cfg.moe_d_ff
    shapes = {
        "router": (d, cfg.num_experts),
        "w_gate": (e, d, ff),
        "w_up": (e, d, ff),
        "w_down": (e, ff, d),
    }
    if cfg.num_shared_experts:
        shapes["shared"] = mlp_param_shapes(
            d, ff * cfg.num_shared_experts, cfg.mlp_act)
    return shapes


def capacity(num_tokens: int, cfg) -> int:
    """Static per-expert capacity."""
    c = int(num_tokens * cfg.top_k / cfg.num_experts * cfg.capacity_factor)
    return max(8, -(-c // 8) * 8)  # round up to a multiple of 8


def route(params, x_flat: jax.Array, cfg):
    """Router: returns (weights [T,k], expert_idx [T,k], aux_loss scalar)."""
    logits = jnp.matmul(x_flat.astype(jnp.float32),
                        params["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)                  # [T,E]
    weights, idx = jax.lax.top_k(probs, cfg.top_k)           # [T,k]
    if cfg.norm_topk_prob:
        weights = weights / jnp.maximum(
            weights.sum(axis=-1, keepdims=True), 1e-9)
    # load-balance auxiliary loss: E * sum_e f_e * P_e
    e = cfg.num_experts
    f = jnp.zeros((e,), jnp.float32).at[idx.reshape(-1)].add(
        1.0 / (idx.size))                                    # dispatch frac
    p = probs.mean(axis=0)
    aux = e * jnp.sum(f * p) * cfg.router_aux_coef
    return weights, idx, aux


def _expert_compute(x_flat, idx, weights, w_gate, w_up, w_down, cfg,
                    e_base, n_local, cap):
    """Sort-based dispatch → batched matmuls → combine, over the ``n_local``
    experts starting at ``e_base``.  Pure local computation (per shard).

    §Perf C4: with ``cfg.moe_bf16_combine`` the [T, k, d] weighted combine
    stays in compute dtype (k ≤ 6 accumulands — bounded error) instead of
    materialising an f32 copy."""
    t, d = x_flat.shape
    k = cfg.top_k
    tk = t * k
    e_flat = idx.reshape(tk)
    order = jnp.argsort(e_flat)                              # [Tk]
    e_sorted = e_flat[order]
    tok_sorted = order // k                                  # source token
    # counts over the local expert range only
    e_local = e_sorted - e_base
    in_range = (e_local >= 0) & (e_local < n_local)
    e_clip = jnp.clip(e_local, 0, n_local - 1)
    counts = jnp.zeros((n_local,), jnp.int32).at[e_clip].add(
        in_range.astype(jnp.int32))
    first = jnp.argmax(in_range)                             # first local row
    starts = first + jnp.cumsum(counts) - counts             # exclusive
    pos_in_e = jnp.arange(tk) - starts[e_clip]               # rank in expert

    # ---- gather into the expert buffer [E_l, C, d] ---------------------
    buf_src = starts[:, None] + jnp.arange(cap)[None, :]     # [E_l,C]
    buf_valid = jnp.arange(cap)[None, :] < counts[:, None]
    buf_tok = jnp.where(buf_valid, tok_sorted[jnp.clip(buf_src, 0, tk - 1)],
                        0)
    buf = x_flat[buf_tok] * buf_valid[..., None].astype(x_flat.dtype)

    # ---- expert computation --------------------------------------------
    act = _ACTS[cfg.mlp_act]
    dt = x_flat.dtype
    g = jnp.einsum("ecd,edf->ecf", buf, w_gate.astype(dt))
    u = jnp.einsum("ecd,edf->ecf", buf, w_up.astype(dt))
    h = (act(g.astype(jnp.float32)) * u.astype(jnp.float32)).astype(dt)
    y_buf = jnp.einsum("ecf,efd->ecd", h, w_down.astype(dt))

    # ---- combine back (all gathers, no scatter) -------------------------
    inv_order = jnp.argsort(order)                           # rank of (t,k)
    kept = in_range & (pos_in_e < cap)
    dest = e_clip * cap + jnp.clip(pos_in_e, 0, cap - 1)
    y_flat = y_buf.reshape(n_local * cap, d)
    y_tk = (y_flat[dest[inv_order]]
            * kept[inv_order][:, None].astype(x_flat.dtype))
    if cfg.moe_bf16_combine:
        y = (y_tk.reshape(t, k, d)
             * weights[..., None].astype(x_flat.dtype)).sum(axis=1)
    else:
        y = (y_tk.reshape(t, k, d).astype(jnp.float32)
             * weights[..., None]).sum(axis=1)
    return y.astype(x_flat.dtype)


def moe_mlp(params, x: jax.Array, cfg, *, dropless: bool = False,
            count_held: bool = False):
    """x [B,S,d] (or [T,d]) → (y same shape, aux_loss).

    ``dropless`` sizes each expert's buffer for every local token, so no
    routed pair is dropped (serving); otherwise ``capacity``.
    ``count_held`` appends the number of (token, choice) pairs routed to
    a held expert: (y, aux_loss, pairs).

    On the production mesh this runs under ``shard_map``: tokens stay in
    their data shard, each "model" shard computes only its own experts over
    the (model-replicated) local tokens, and partial outputs combine with a
    single psum — Megatron-row-parallel-style expert parallelism with no
    all-to-all and no global sort (DESIGN.md §5).
    """
    from repro.distributed.context import current_mesh, dp_axes, tp_axes
    orig_shape = x.shape
    d = orig_shape[-1]
    x_flat = x.reshape(-1, d)
    t = x_flat.shape[0]
    e = cfg.n_experts_held

    weights, idx, aux = route(params, x_flat, cfg)

    mesh = current_mesh()
    tp = tp_axes()
    dp = dp_axes()
    axis = dict(mesh.shape) if mesh else {}
    tp_size = axis.get("model", 1) if tp else 1
    dp_size = 1
    for a in dp:
        dp_size *= axis.get(a, 1)

    if (mesh is not None and tp_size > 1 and e % tp_size == 0):
        from jax.sharding import PartitionSpec as P
        tok_dp = dp if (t % max(dp_size, 1) == 0 and dp_size > 1) else ()
        t_local = t // dp_size if tok_dp else t
        n_local = e // tp_size
        cap = t_local if dropless else capacity(t_local, cfg)
        tok_spec = P(tok_dp if tok_dp else None)
        w_spec = P("model", None, None)

        def local_fn(xl, il, wl, wg, wu, wd):
            e_base = jax.lax.axis_index("model") * n_local
            y = _expert_compute(xl, il, wl, wg, wu, wd, cfg, e_base,
                                n_local, cap)
            # psum runs in compute dtype (bf16) — _expert_compute already
            # returns x.dtype
            return jax.lax.psum(y, "model")

        y = jax.shard_map(
            local_fn, mesh=mesh,
            in_specs=(tok_spec, tok_spec, tok_spec, w_spec, w_spec, w_spec),
            out_specs=tok_spec,
            check_vma=False,
        )(x_flat, idx, weights, params["w_gate"], params["w_up"],
          params["w_down"])
    else:
        cap = t if dropless else capacity(t, cfg)
        y = _expert_compute(x_flat, idx, weights, params["w_gate"],
                            params["w_up"], params["w_down"], cfg, 0, e, cap)

    out = y.astype(x.dtype)
    if cfg.num_shared_experts:
        out = out + gated_mlp(x_flat, params["shared"], cfg.mlp_act)
    if count_held:
        return (out.reshape(orig_shape), aux,
                jnp.sum(idx < e, dtype=jnp.int32))
    return out.reshape(orig_shape), aux
