"""Parameter partitioning rules.

Name-and-divisibility-driven PartitionSpec assignment (DESIGN.md §5):

  * tensor parallelism ("model" axis): head-aligned projection dims, FFN
    hidden dims, expert axis, vocab — sharded iff the semantic unit count
    (heads / experts / vocab / d_ff) divides the axis size;
  * FSDP ("data"(+"pod") axes, training only): the largest not-yet-sharded
    dim of every ≥2D weight, iff divisible;
  * everything that fails divisibility falls back to replication and is
    recorded in the returned ``report`` (these show up in EXPERIMENTS.md —
    e.g. gemma's 8 q-heads on a 16-wide model axis).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

PyTree = Any

# weights whose LAST dim is a tp-shardable "output feature" dim
_COL_PARALLEL = ("wq", "wk", "wv", "w_q", "w_k", "w_v", "w_gate", "w_up",
                 "w_uk", "w_uv", "w_z", "w_x", "conv_x_w", "w_ig", "w_fg")
# weights whose FIRST dim is the matching "input feature" dim (row-parallel)
_ROW_PARALLEL = ("wo", "w_down", "w_out")
_REPLICATE = ("router", "w_dkv", "w_kr", "w_b", "w_c", "w_dt", "conv_b_w",
              "conv_c_w")


@dataclasses.dataclass
class ShardingReport:
    """What got sharded how — and what fell back to replication."""
    tp_sharded: list[str] = dataclasses.field(default_factory=list)
    fsdp_sharded: list[str] = dataclasses.field(default_factory=list)
    replicated: list[str] = dataclasses.field(default_factory=list)

    def summary(self) -> str:
        return (f"tp={len(self.tp_sharded)} fsdp={len(self.fsdp_sharded)} "
                f"replicated={len(self.replicated)}")


def _path_str(path) -> str:
    return "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                    for p in path)


def _leaf_name(path) -> str:
    return str(getattr(path[-1], "key", path[-1]))


def _num_stack_dims(path) -> int:
    """Layer-stacked leaves live under 'layers'/'dense_layers'/'mamba'/
    'mlstm'/'slstm'/'enc_layers'/'dec_layers'; their leading dims are stack
    axes."""
    parts = [str(getattr(p, "key", "")) for p in path]
    if "mamba" in parts or "mlstm" in parts:
        return 2                      # [G, K, ...]
    if any(s in parts for s in ("layers", "dense_layers", "enc_layers",
                                "dec_layers", "slstm")):
        return 1
    return 0


def partition_spec_for(path, shape: tuple[int, ...], cfg, *,
                       tp: int, fsdp: int, mode: str,
                       report: Optional[ShardingReport] = None):  # noqa: D401
    """PartitionSpec for one param leaf. mode: 'train' | 'serve'."""
    name = _leaf_name(path)
    nstack = _num_stack_dims(path)
    body = list(shape[nstack:])       # dims after layer-stack axes
    spec: list = [None] * len(shape)
    pstr = _path_str(path)

    def try_tp(dim_idx: int, unit: int) -> bool:
        """Shard body dim `dim_idx` on 'model' iff `unit` divides tp."""
        if tp > 1 and unit % tp == 0 and body[dim_idx] % tp == 0:
            spec[nstack + dim_idx] = "model"
            if report:
                report.tp_sharded.append(pstr)
            return True
        return False

    tp_ok = False
    if name == "embed":
        tp_ok = try_tp(0, shape[-2])                 # vocab rows
    elif name == "lm_head":
        tp_ok = try_tp(1, body[1])                   # vocab cols
    elif name in ("w_gate", "w_up", "w_down") and len(body) == 3:
        # MoE expert weights [E, d, ff]: expert parallelism
        if tp > 1 and body[0] % tp == 0:
            spec[nstack] = "model"
            tp_ok = True
            if report:
                report.tp_sharded.append(pstr)
    elif name in ("wq", "w_q"):
        tp_ok = try_tp(1, cfg.num_heads)
    elif name in ("wk", "wv", "w_k", "w_v"):
        tp_ok = try_tp(1, cfg.num_kv_heads)
    elif name in ("w_uk", "w_uv"):                   # MLA up-proj [r, H*dn]
        tp_ok = try_tp(1, cfg.num_heads)
    elif name in ("w_z", "w_x", "conv_x_w", "w_ig", "w_fg"):
        # mamba/xlstm inner width: head-aligned
        unit = cfg.n_ssm_heads if cfg.family in ("hybrid",) else cfg.num_heads
        tp_ok = try_tp(len(body) - 1, unit)
    elif name in ("w_gate", "w_up"):                 # dense FFN [d, ff]
        tp_ok = try_tp(1, body[1])
    elif name in _ROW_PARALLEL and len(body) >= 2:
        if name == "w_down" and len(body) == 2:
            tp_ok = try_tp(0, body[0])
        elif name == "wo":
            tp_ok = try_tp(0, cfg.num_heads)
        elif name == "w_out":
            unit = cfg.n_ssm_heads if cfg.family in ("hybrid",) \
                else cfg.num_heads
            tp_ok = try_tp(0, unit)

    # §Perf D: row-parallel fallback for attention projections whose head
    # count does not divide the model axis (e.g. gemma's 8 q-heads on 16):
    # shard the CONTRACTION dim instead (partial sums -> psum), trading a
    # per-layer all-reduce for 16x less replicated matmul compute.
    import os
    if (os.environ.get("REPRO_ROWPAR_ATTN") and not tp_ok and tp > 1
            and name in ("wq", "wk", "wv", "wo") and len(body) == 2
            and body[0] % tp == 0):
        spec[nstack] = "model"
        tp_ok = True
        if report:
            report.tp_sharded.append(pstr + "(rowpar)")

    # FSDP (training only): largest remaining body dim of ≥2D weights
    if os.environ.get("REPRO_NO_FSDP"):
        fsdp = 1                      # §Perf experiment lever
    if mode == "train" and fsdp > 1 and len(body) >= 2:
        order = sorted(range(len(body)), key=lambda i: -body[i])
        for i in order:
            if spec[nstack + i] is None and body[i] % fsdp == 0:
                spec[nstack + i] = ("pod", "data") if fsdp > 16 else "data"
                if report:
                    report.fsdp_sharded.append(pstr)
                break

    if report and not tp_ok and all(s is None for s in spec):
        report.replicated.append(pstr)
    return P(*spec)


def param_specs(cfg, shapes: PyTree, mesh: Mesh, mode: str = "train",
                no_fsdp: bool = False):
    """PartitionSpec pytree for a param-shapes tree. Returns (specs, report)."""
    axis = dict(mesh.shape)
    tp = axis.get("model", 1)
    fsdp = 1 if no_fsdp else axis.get("data", 1) * axis.get("pod", 1)
    report = ShardingReport()
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda x: isinstance(x, tuple))
    specs = [partition_spec_for(path, shape, cfg, tp=tp, fsdp=fsdp,
                                mode=mode, report=report)
             for path, shape in flat]
    return jax.tree_util.tree_unflatten(treedef, specs), report


def batch_specs(cfg, batch_shapes: PyTree, mesh: Mesh):
    """Shard the batch dim over ('pod','data') where divisible."""
    axis = dict(mesh.shape)
    dp = axis.get("data", 1) * axis.get("pod", 1)
    dp_axes = tuple(a for a in ("pod", "data") if a in axis and axis[a] > 1)

    def spec(sd):
        shape = sd if isinstance(sd, tuple) else sd.shape
        if shape and shape[0] % dp == 0 and dp > 1:
            return P(dp_axes, *([None] * (len(shape) - 1)))
        return P(*([None] * len(shape)))

    return jax.tree_util.tree_map(
        spec, batch_shapes, is_leaf=lambda x: isinstance(x, tuple))


_KV_LEAVES = ("k", "v", "self_k", "self_v", "cross_k", "cross_v",
              "attn_k", "attn_v")
_LATENT_LEAVES = ("ckv", "kr")


def cache_specs(cfg, cache_shapes: PyTree, mesh: Mesh, *,
                seq_shard: bool = False):
    """Decode-cache sharding.

    Baseline policy (DESIGN.md §5):
      * batch dim over dp where divisible;
      * KV heads over 'model' where divisible, else the *sequence* dim over
        'model' (distributed-softmax decode; GSPMD inserts the lse
        reductions);
      * MLA latent caches shard sequence over 'model' (no head dim);
      * when the batch cannot shard (long_500k B=1), the sequence
        additionally shards over 'data' — flash-decode style.
    ``seq_shard=True`` forces sequence-over-'data' even when the batch is
    shardable (a §Perf experiment lever).
    """
    axis = dict(mesh.shape)
    dp = axis.get("data", 1) * axis.get("pod", 1)
    tp = axis.get("model", 1)
    data = axis.get("data", 1)
    dp_axes = tuple(a for a in ("pod", "data") if a in axis and axis[a] > 1)

    def spec(path, sd):
        shape, _ = sd if isinstance(sd, tuple) else (sd.shape, None)
        name = _leaf_name(path)
        if name == "pos" or not shape:
            return P()
        s: list = [None] * len(shape)
        # leading layer-stack dims before the batch dim
        if name in _KV_LEAVES or name in _LATENT_LEAVES:
            nstack = 1
        elif name in ("ssm", "conv") or name.startswith("m_"):
            nstack = 2
        elif name.startswith("s_"):
            nstack = 1
        else:
            nstack = 0
        batch_ok = (nstack < len(shape) and dp > 1
                    and shape[nstack] % dp == 0)
        if batch_ok:
            s[nstack] = dp_axes
        if name in _KV_LEAVES and len(shape) >= 4:
            seq_dim, head_dim_idx = nstack + 1, len(shape) - 2
            if tp > 1 and shape[head_dim_idx] % tp == 0:
                s[head_dim_idx] = "model"
            elif tp > 1 and shape[seq_dim] % tp == 0:
                s[seq_dim] = "model"
            if (seq_shard or not batch_ok) and data > 1 \
                    and shape[seq_dim] % (data * tp) == 0:
                s[seq_dim] = (("model", "data") if s[seq_dim] == "model"
                              else "data" if s[seq_dim] is None
                              else s[seq_dim])
        elif name in _LATENT_LEAVES and len(shape) >= 3:
            seq_dim = nstack + 1
            if tp > 1 and shape[seq_dim] % tp == 0:
                s[seq_dim] = "model"
            if (seq_shard or not batch_ok) and data > 1 \
                    and shape[seq_dim] % (tp * data) == 0:
                # split the sequence over model×data jointly
                s[seq_dim] = ("model", "data") if tp > 1 else "data"
        elif name == "ssm" and tp > 1 and len(shape) > 3 \
                and shape[3] % tp == 0:
            s[3] = "model"
        return P(*s)

    flat, treedef = jax.tree_util.tree_flatten_with_path(
        cache_shapes, is_leaf=lambda x: isinstance(x, tuple))
    return jax.tree_util.tree_unflatten(
        treedef, [spec(p, sd) for p, sd in flat])


def to_named(specs: PyTree, mesh: Mesh) -> PyTree:
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), specs,
        is_leaf=lambda x: isinstance(x, P))
