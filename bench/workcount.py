"""The work each timed call has to do, counted from the problem's shapes
and the configuration's dtypes, whatever implements it.

Each function returns ``(flops, bytes)``.  A roofline share is the least
time those take at the chip's peaks over the measured time.
"""
from __future__ import annotations

F32 = I32 = 4

#: per (user, split) cost evaluation of the latency objective:
#: dcum/dev_div, etot-ecum, /edge_div, ship/max(bw,1), lat+, two adds,
#: and one compare of the running argmin
DECIDE_FLOPS_PER_EVAL = 9
#: the kernel's env columns: dev_div, edge_div, bw, lat, inp, dev_w, edge_w
DECIDE_ENV_COLUMNS = 7


def decide_split(n_users: int, n_splits: int) -> tuple[float, float]:
    """One decide sweep: ``E * (L+1)`` cost evaluations; bytes are the
    env columns in (f32), split (int32) and cost (f32) out, and the three
    ``[L+1]`` split rows (f32)."""
    flops = DECIDE_FLOPS_PER_EVAL * n_users * n_splits
    nbytes = (n_users * (DECIDE_ENV_COLUMNS * F32 + I32 + F32)
              + 3 * n_splits * F32)
    return float(flops), float(nbytes)


def tree_predict(n_rows: int, n_features: int, n_trees: int,
                 max_nodes: int, max_depth: int) -> tuple[float, float]:
    """Tree-ensemble inference: ``N * T * (D+1)`` node visits, one
    operation each (a compare, or the leaf's add); bytes are the feature
    rows in (f32), the five node arrays (4 bytes a node) and the
    predictions out (f32)."""
    flops = n_rows * n_trees * (max_depth + 1)
    nbytes = (n_rows * n_features * F32 + 5 * n_trees * max_nodes * 4
              + n_rows * F32)
    return float(flops), float(nbytes)


def decoder_params(m: dict) -> dict:
    """Matmul parameters of a dense GQA decoder with a plain or gated MLP
    (``m`` holds the configuration's model keys)."""
    d, hd = m["d_model"], m["head_dim"]
    attn = d * hd * (2 * m["num_heads"] + 2 * m["num_kv_heads"])
    n_mlp = 2 if m["mlp_act"] in ("relu2", "gelu_plain") else 3
    mlp = n_mlp * d * m["d_ff"]
    return {"layer": attn + mlp, "layers": m["num_layers"] * (attn + mlp),
            "head": d * m["vocab_size"]}


def decoder_token_flops(m: dict, context: int, with_head: bool) -> float:
    """FLOPs of one token through the decoder: 2 per matmul parameter,
    plus causal attention over ``context`` positions (QK and PV), plus
    the head where the token's logits are needed."""
    p = decoder_params(m)
    attn = 2 * 2 * m["num_heads"] * m["head_dim"] * context * m["num_layers"]
    return 2.0 * p["layers"] + attn + (2.0 * p["head"] if with_head else 0)


def decoder_prefill_flops(m: dict, prompt_len: int) -> float:
    """A prompt of ``prompt_len`` tokens: every token through the layers
    with causal attention, logits for the last position only."""
    p = decoder_params(m)
    attn = (2 * 2 * m["num_heads"] * m["head_dim"] * m["num_layers"]
            * prompt_len * (prompt_len + 1) / 2)
    return 2.0 * p["layers"] * prompt_len + attn + 2.0 * p["head"]


def placement(n_tasks: int, n_nodes: int) -> tuple[float, float]:
    """Min-min placement of singleton arrivals: per (task, node) the
    finish time ``max(avail, arrival) + exec + transfer`` (4 operations)
    and one argmin compare; bytes are each task's inputs and its record
    out (f64: flops, input bytes, arrival; node, start, finish) and one
    read and write of the node state (f64 avail) per task."""
    flops = 5 * n_tasks * n_nodes
    nbytes = n_tasks * (6 * 8 + 2 * n_nodes * 8)
    return float(flops), float(nbytes)
