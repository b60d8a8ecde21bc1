"""Determinism rules: bit-for-bit and virtual-clock contracts.

DET001 — the numpy ≡ jax f64 bit-for-bit guarantees (the jitted tree
predict, the sharded decide) exist only because their scalarisation /
cumulative-sum paths accumulate term by term; a ``@`` / ``dot`` /
``matmul`` lets BLAS or XLA fuse multiply-adds (FMA contraction) and
the two sides round differently.  Modules that carry this guarantee declare it with a
``# repro: module-tags=fma-sensitive`` directive and this rule keeps
them honest.

DET002 — ``repro.sim`` is virtual-clock-only (event time comes from the
``Clock`` / slab timeline, never the host), and ``repro.serve``'s
admission control runs on the same virtual clock.  A stray
``time.time()`` makes seeded runs diverge across hosts.  Genuine
wall-time *measurement* of real model execution (ServeEngine stats)
carries an explicit per-line suppression instead.
"""
from __future__ import annotations

import ast
from typing import Iterator

from repro.analysis.core import (FileContext, Finding, Rule, Severity,
                                 dotted, register)

FMA_TAG = "fma-sensitive"

#: dense-contraction callables whose FMA fusion breaks bitwise equality
_MATMUL_CALLS = frozenset({
    f"{mod}.{fn}"
    for mod in ("np", "numpy", "jnp", "jax.numpy")
    for fn in ("dot", "matmul", "vdot", "inner", "tensordot", "einsum")
})

#: wall-clock reads (virtual-clock modules must never call these)
_WALL_CLOCK_CALLS = frozenset({
    "time.time", "time.time_ns", "time.monotonic", "time.monotonic_ns",
    "time.perf_counter", "time.perf_counter_ns", "time.process_time",
    "time.process_time_ns",
})
_WALL_CLOCK_SUFFIXES = ("datetime.now", "datetime.utcnow",
                        "datetime.today", "date.today")


@register
class MatmulInFmaSensitive(Rule):
    """DET001: no matmul-family ops in fma-sensitive modules."""

    id = "DET001"
    severity = Severity.ERROR
    title = ("no @ / dot / matmul / einsum in modules tagged "
             "fma-sensitive (FMA contraction breaks numpy ≡ jax "
             "bit-for-bit); accumulate sequentially")

    def check(self, ctx: FileContext) -> Iterator[Finding]:
        if FMA_TAG not in ctx.tags:
            return
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.BinOp) and isinstance(
                    node.op, ast.MatMult):
                yield self.finding(
                    ctx, node,
                    "`@` matmul in an fma-sensitive module: BLAS/XLA "
                    "FMA contraction rounds differently per backend — "
                    "accumulate term-by-term instead")
            elif isinstance(node, ast.Call):
                name = dotted(node.func)
                if name in _MATMUL_CALLS:
                    yield self.finding(
                        ctx, node,
                        f"`{name}` in an fma-sensitive module: dense "
                        f"contraction is FMA-fusible and backend-"
                        f"dependent — accumulate term-by-term instead")


@register
class WallClockInVirtualTime(Rule):
    """DET002: no wall-clock reads in virtual-clock modules."""

    id = "DET002"
    severity = Severity.ERROR
    title = ("no wall-clock (time.time / perf_counter / datetime.now) "
             "in repro.sim / repro.serve — event time is virtual")

    SCOPES = ("repro.sim", "repro.serve")

    def check(self, ctx: FileContext) -> Iterator[Finding]:
        if not ctx.in_module(*self.SCOPES):
            return
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            name = dotted(node.func)
            if name is None:
                continue
            if name in _WALL_CLOCK_CALLS or any(
                    name == suf or name.endswith("." + suf)
                    for suf in _WALL_CLOCK_SUFFIXES):
                yield self.finding(
                    ctx, node,
                    f"wall-clock `{name}()` inside {ctx.module}: this "
                    f"module runs on the virtual clock — seeded runs "
                    f"must not observe host time (suppress explicitly "
                    f"if measuring real execution)")
