#!/usr/bin/env python3
"""Faults planted under a cell's timed path, by name.

    python bench/faults.py --workload edge-metro.sweep --seeds 3 --seconds 4

Each fault wraps the one function of the program where an answer is
produced (a kernel, the fleet scan, the model's decode step) and breaks
what it returns.  Faults are kept by driver, so every cell of a driver
gets its faults; a cell's driver is found through ``BENCHMARK.json`` and
its traffic file.  The tests under ``tests/`` plant them on the CPU at a
cut size; this script plants each in turn on the chip at the cell's own
size and prints, for every seed and fault, whether the run read
``correct`` and the numbers it compared.  ``none`` is the sound run beside them.
"""
from __future__ import annotations

import argparse
import importlib
import json
import sys

from common import BENCH, ROOT, load_json

DECIDE = ("repro.kernels.decide_split.kernel", "decide_split_kernel")
TREE = ("repro.kernels.tree_predict.kernel", "tree_predict_kernel")
DECODE = ("repro.models.transformer", "decode_step")
SCAN = ("repro.sim.fleet", "_place_singleton_run")


def _splits(fault):
    def wrap(real):
        def broken(dcum, *args, **kw):
            split, cost = real(dcum, *args, **kw)
            return fault(split, cost, dcum.shape[0])
        return broken
    return wrap


def _rows(fault):
    def wrap(real):
        return lambda *args, **kw: fault(real(*args, **kw))
    return wrap


def _step(fault):
    def wrap(real):
        def broken(params, batch, cache, cfg):
            logits, new = real(params, batch, cache, cfg)
            return fault(logits, new, cache)
        return broken
    return wrap


def _placed(fault):
    def wrap(real):
        def broken(avail, *args):
            j, start, fin, etc = real(avail, *args)
            return fault(j, start, fin, etc, avail.shape[0])
        return broken
    return wrap


def _half(x, value):
    return x.at[x.shape[0] // 2:].set(value)


def _np_half(x, value):
    x = x.copy()
    x[x.shape[0] // 2:] = value
    return x


# an answer altered where it is produced: every split moved on by one
SPLIT_ALTERED = _splits(lambda s, c, n: ((s + 1) % n, c))
# half of the batch left out: the upper half's answers never made, their
# slots left as allocated (zeros)
SPLIT_HALF = _splits(lambda s, c, n: (_half(s, 0), _half(c, 0.0)))
# a constant answer: split 0 (offload everything) for every user
SPLIT_CONSTANT = _splits(lambda s, c, n: (s * 0, c * 0.0))

FAULTS = {
    "sweep": {
        "altered": (DECIDE, SPLIT_ALTERED),
        "half": (DECIDE, SPLIT_HALF),
        "constant": (DECIDE, SPLIT_CONSTANT),
    },
    "catalog": {
        # every 7th row's sum moved on
        "altered": (TREE, _rows(lambda out: out.at[::7].add(0.05))),
        "half": (TREE, _rows(lambda out: _half(out, 0.0))),
    },
    "day": {
        # every task's node moved on by one
        "altered": (SCAN, _placed(lambda j, s, f, e, n: ((j + 1) % n, s, f,
                                                          e))),
        # the second half of each run's placements never made
        "half": (SCAN, _placed(lambda j, s, f, e, n: (
            _np_half(j, 0), _np_half(s, 0.0), _np_half(f, 0.0),
            _np_half(e, 0.0)))),
    },
    "serve": {
        # one token id always wins
        "token": (DECODE, _step(lambda lg, new, old: (lg.at[..., 7].add(1e3),
                                                      new))),
        "half": (DECODE, _step(lambda lg, new, old: (_half(lg, 0.0), new))),
        # a step that returns its state unchanged: the cache never advances
        "stale": (DECODE, _step(lambda lg, new, old: (lg, old))),
        "planner": (DECIDE, SPLIT_ALTERED),
    },
}


def faults_of(cell: str, spec: dict | None = None) -> dict:
    """The faults of ``cell``'s driver (the ``driver`` of the traffic
    file its entry in ``spec``, by default ``BENCHMARK.json``, names)."""
    spec = spec or load_json(ROOT / "BENCHMARK.json")
    traffic = {w["name"]: w["traffic"] for w in spec["workloads"]}[cell]
    return FAULTS[load_json(BENCH / "traffic" / f"{traffic}.json")["driver"]]


def plant(cell: str, name: str, setattr_=setattr,
          spec: dict | None = None) -> None:
    """Put fault ``name`` of ``cell`` in place, through ``setattr_`` (a
    test's ``monkeypatch.setattr`` undoes it at the test's end)."""
    (mod_name, attr), wrap = faults_of(cell, spec)[name]
    mod = importlib.import_module(mod_name)
    setattr_(mod, attr, wrap(getattr(mod, attr)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=3)
    ap.add_argument("--base", type=int, default=8_000_000_000)
    ap.add_argument("--seconds", type=float, default=4.0)
    args = ap.parse_args(argv)
    import run
    sys.path.insert(0, str(run.ROOT / "src"))
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print("faults: needs a TPU", file=sys.stderr)
        return 3
    from peaks import peaks
    cache = run.use_compile_cache()
    spec = load_json(run.ROOT / "BENCHMARK.json")
    names = sorted(faults_of(args.workload, spec))
    for k in range(args.seeds):
        seed = args.base + 1000 * k
        for name in ["none", *names]:
            cell = run.Cell(spec, args.workload)
            undo = []
            if name != "none":
                plant(args.workload, name,
                      lambda m, a, v: (undo.append((m, a, getattr(m, a))),
                                       setattr(m, a, v)), spec)
            try:
                out = run.run_cell(cell, seed, args.seconds, False,
                                   devices[:cell.entry["chips"]],
                                   peaks(devices[0].device_kind), cache)
            finally:
                for m, a, v in undo:
                    setattr(m, a, v)
            print(json.dumps({"seed": seed, "fault": name,
                              "correct": out["correct"],
                              "attempted": out["attempted"],
                              "checks": out["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
