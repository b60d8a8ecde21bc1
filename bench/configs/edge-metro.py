"""edge-metro: the builder of its predictor from the seed, and the plain
reference of the three answers its cells compare (layer-time
predictions, split decisions and fleet placements).

The reference imports nothing of the program.  The ensemble is made
here, from the seed, at the shape of the paper's fitted model (the
configuration's ``predictor`` sizes): random split features and
thresholds, leaf values boosted on roofline layer times of seeded
layers over every device of the configuration.  It reaches the program
through the program's own predictor file format (``.npz`` arrays and a
``.json`` header), as a fitted model would.
"""
from __future__ import annotations

import json
import os

import numpy as np

N_FEATURES = 7


def layer_features(flops, act_bytes, dev: dict) -> np.ndarray:
    """``[L, 7]`` f32 feature rows of layers on one device: log-scaled
    layer size and the device's hardware features (the profiling
    predictor's inputs, paper Sec. II-A)."""
    flops = np.asarray(flops, np.float64)
    act = np.asarray(act_bytes, np.float64)
    n = flops.shape[0]
    cols = [np.log10(np.maximum(flops, 1.0)),
            np.log10(np.maximum(act, 1.0)),
            np.full(n, np.log10(max(dev["hw_peak_flops"], 1.0))),
            np.full(n, np.log10(max(dev["hw_hbm_bw"], 1.0))),
            np.full(n, dev["hw_clock_ghz"]),
            np.full(n, dev["hw_is_accelerated"]),
            np.full(n, dev["hw_tdp_watts"])]
    return np.stack(cols, axis=1).astype(np.float32)


def random_layers(rng, n: int, flops_range, act_range):
    """``n`` layers with uniform FLOPs and activation bytes."""
    return rng.uniform(*flops_range, n), rng.uniform(*act_range, n)


def catalog_rows(cfg: dict, rng, n_rows: int) -> np.ndarray:
    """``[n_rows, 7]`` feature rows: seeded layers on every device."""
    p = cfg["predictor"]
    devs = list(cfg["devices"].values())
    flops, act = random_layers(rng, -(-n_rows // len(devs)),
                               p["train_flops"], p["train_act_bytes"])
    x = np.concatenate([layer_features(flops, act, d) for d in devs])
    return x[:n_rows]


def training_rows(cfg: dict, rng):
    """Profiling rows: seeded layers on every device, with the roofline
    layer time ``flops / (peak_f32 * efficiency)`` as the target."""
    p = cfg["predictor"]
    flops, act = random_layers(rng, p["train_layers"], p["train_flops"],
                               p["train_act_bytes"])
    x, y = [], []
    for d in cfg["devices"].values():
        x.append(layer_features(flops, act, d))
        y.append(flops / (d["peak_flops_f32"] * cfg["efficiency"]))
    return np.concatenate(x), np.concatenate(y)


def bin_codes(x, edges) -> np.ndarray:
    """``code[n, f] = #{edges[f] < x[n, f]}`` in f32."""
    x = np.asarray(x, np.float32)
    return np.sum(edges[None, :, :] < x[:, :, None], axis=-1,
                  dtype=np.int32)


def _grow(rng, n_split: int, max_depth: int, n_bins: int, chain: bool):
    feat, thr, left, right, depth = [-1], [0], [0], [0], [0]
    open_ = [0]

    def split(i):
        feat[i] = int(rng.integers(N_FEATURES))
        thr[i] = int(rng.integers(0, n_bins - 1))
        for side in (left, right):
            side[i] = len(feat)
            feat.append(-1)
            thr.append(0)
            left.append(0)
            right.append(0)
            depth.append(depth[i] + 1)
            if depth[-1] < max_depth:
                open_.append(len(feat) - 1)

    done = 0
    if chain:                       # one path reaches max_depth
        node = 0
        for _ in range(max_depth):
            open_.remove(node)
            split(node)
            node = left[node]
            done += 1
    while done < n_split:
        k = int(rng.integers(len(open_)))
        i = open_[k]
        open_[k] = open_[-1]
        open_.pop()
        split(i)
        done += 1
    return (np.asarray(feat, np.int32), np.asarray(thr, np.int32),
            np.asarray(left, np.int32), np.asarray(right, np.int32))


def leaves(codes, feat, thr, left, right, max_depth: int) -> np.ndarray:
    """Leaf index of every row in one tree, by walking it level by level
    (padding slots are leaves)."""
    rows = np.arange(codes.shape[0])
    node = np.zeros(codes.shape[0], np.int64)
    for _ in range(max_depth):
        f = feat[node]
        inner = f >= 0
        go_left = codes[rows, np.maximum(f, 0)] <= thr[node]
        node = np.where(inner, np.where(go_left, left[node], right[node]),
                        node)
    return node


def make_ensemble(cfg: dict, rng) -> dict:
    """The configuration's tree ensemble, from ``rng``: ``n_trees`` trees
    of at most ``max_nodes`` nodes and depth ``max_depth`` (the first
    tree reaches both, so every seed gives the same padded shapes)."""
    p = cfg["predictor"]
    x, y = training_rows(cfg, rng)
    qs = np.linspace(0, 1, p["n_bins"] + 1)[1:-1]
    edges = np.quantile(x, qs, axis=0).T.astype(np.float32)
    codes = bin_codes(x, edges)
    t, m, depth = p["n_trees"], p["max_nodes"], p["max_depth"]
    out = {k: np.zeros((t, m), np.int32)
           for k in ("feature", "threshold_bin", "left", "right")}
    out["feature"][:] = -1
    out["value"] = np.zeros((t, m), np.float64)
    out["n_nodes"] = np.zeros(t, np.int32)
    base = float(y.mean())
    pred = np.full(y.shape, base)
    max_split = (m - 1) // 2
    for i in range(t):
        n_split = max_split if i == 0 else int(
            rng.integers(max_split // 2, max_split + 1))
        f, th, le, ri = _grow(rng, n_split, depth, p["n_bins"], i == 0)
        n = f.shape[0]
        leaf = leaves(codes, f, th, le, ri, depth)
        rows = rng.random(y.shape[0]) < p["subsample"]
        g = np.bincount(leaf[rows], weights=(y - pred)[rows], minlength=n)
        c = np.bincount(leaf[rows], minlength=n)
        value = np.where(f < 0, g / (c + p["lambda_reg"]), 0.0)
        pred = pred + p["learning_rate"] * value[leaf]
        for k, a in (("feature", f), ("threshold_bin", th), ("left", le),
                     ("right", ri)):
            out[k][i, :n] = a
        out["value"][i, :n] = value
        out["n_nodes"][i] = n
    out.update(edges=edges, base=base, learning_rate=p["learning_rate"],
               max_depth=depth)
    return out


def save_bundle(ens: dict, cfg: dict, path: str) -> str:
    """Write the ensemble in the program's predictor file format
    (``<path>.npz`` + ``<path>.json``); returns ``path``."""
    p = cfg["predictor"]
    np.savez(f"{path}.npz", **{k: ens[k] for k in (
        "feature", "threshold_bin", "left", "right", "value", "n_nodes",
        "edges")})
    meta = {"format": 1, "type": p["family"], "base": ens["base"],
            "params": {"n_trees": p["n_trees"], "max_depth": p["max_depth"],
                       "learning_rate": p["learning_rate"],
                       "subsample": p["subsample"], "n_bins": p["n_bins"],
                       "min_samples_leaf": 2,
                       "lambda_reg": p["lambda_reg"], "seed": 0,
                       "use_kernel": False}}
    with open(f"{path}.json", "w") as f:
        json.dump(meta, f)
    return path


def load_program_predictor(ens: dict, cfg: dict, tmpdir: str):
    """The program's fitted-model object for the ensemble, read back
    through the program's own loader."""
    from repro.core.predictors.persist import load_predictor
    return load_predictor(save_bundle(ens, cfg, os.path.join(tmpdir, "gbt")))


def predict_ref(ens: dict, x, dtype=np.float64) -> np.ndarray:
    """Plain tree walk: base plus each tree's scaled leaf value, summed
    tree by tree in ``dtype`` (f64 for the reference; a lower precision
    for the control)."""
    codes = bin_codes(x, ens["edges"])
    lr = ens["learning_rate"]
    pred = np.full(codes.shape[0], ens["base"], dtype)
    for i in range(ens["feature"].shape[0]):
        leaf = leaves(codes, ens["feature"][i], ens["threshold_bin"][i],
                      ens["left"][i], ens["right"][i], ens["max_depth"])
        pred = (pred + (lr * ens["value"][i, leaf]).astype(dtype)).astype(
            dtype)
    return pred.astype(np.float64)


def layer_times_ref(ens: dict, cfg: dict, flops, act, dtype=np.float64):
    """Predicted per-layer times on the user device and the edge,
    clamped at 0: ``(t_dev [L], t_edge [L])``."""
    d, e = cfg["devices"][cfg["device"]], cfg["devices"][cfg["edge"]]
    x = np.concatenate([layer_features(flops, act, d),
                        layer_features(flops, act, e)])
    t = np.maximum(predict_ref(ens, x, dtype), 0.0)
    n = len(flops)
    return t[:n], t[n:]


def split_costs_ref(t_dev, t_edge, act, bw, lat, inp, xp=np,
                    dtype=np.float64):
    """``[E, L+1]`` latency of every (user, split): layers ``[0, s)`` on
    the device, the shipped bytes over the link, layers ``[s, L)`` on
    the edge.  Split 0 ships the input, split ``L`` ships nothing."""
    n = t_dev.shape[0]
    dev = xp.concatenate([xp.zeros(1), xp.cumsum(t_dev)]).astype(dtype)
    edge = xp.concatenate([xp.cumsum(t_edge[::-1])[::-1],
                           xp.zeros(1)]).astype(dtype)
    ship = xp.concatenate([inp[:, None].astype(dtype),
                           xp.broadcast_to(xp.asarray(act, dtype)[None, :],
                                           (bw.shape[0], n))], axis=1)
    xfer = lat[:, None].astype(dtype) + ship / xp.maximum(
        bw, 1.0)[:, None].astype(dtype)
    xfer = xp.concatenate([xfer[:, :n], xp.zeros((bw.shape[0], 1), dtype)],
                          axis=1)
    return dev[None, :] + xfer + edge[None, :]


def placements_ref(peak_eff, spec_bw, v0, table, dt, arrivals, flops,
                   input_bytes, dtype=np.float64):
    """Min-min placement of tasks that arrive one at a time (distinct,
    sorted instants) on nodes of effective speed ``peak_eff``: each task
    goes to the node where it would finish first, ``max(free, arrival) +
    flops / speed + input_bytes / bandwidth`` (first node on ties), and
    that node is busy until then.  Bandwidths change at the ticks ``dt,
    2 dt, ...``: an arrival at a tick's instant still sees the old ones,
    and a node keeps its spec bandwidth ``spec_bw`` until its link's
    value (``v0``, then row ``k - 1`` of ``table`` after tick ``k``)
    first changes.  Returns ``(node, start, finish)`` per task, computed
    in ``dtype``."""
    prev = np.vstack([v0[None, :], table[:-1]])
    ever = np.logical_or.accumulate(table != prev, axis=0)
    rows = np.maximum(np.vstack([spec_bw[None, :],
                                 np.where(ever, table, spec_bw[None, :])]),
                      1.0).astype(dtype)
    seg = np.searchsorted(np.cumsum(np.full(table.shape[0], dt)), arrivals,
                          side="left")
    speed = np.asarray(peak_eff, dtype)
    arr, fl, ib = (np.asarray(a, dtype) for a in (arrivals, flops,
                                                  input_bytes))
    free = np.zeros(speed.shape[0], dtype)
    n = arr.shape[0]
    node = np.empty(n, np.int64)
    start, finish = np.empty(n, dtype), np.empty(n, dtype)
    for i in range(n):
        etc = fl[i] / speed + ib[i] / rows[seg[i]]
        fin = np.maximum(free, arr[i]) + etc
        j = int(np.argmin(fin))
        node[i], start[i], finish[i] = j, max(free[j], arr[i]), fin[j]
        free[j] = fin[j]
    return node, start, finish
