"""Continuous batching engine (slot-based, vLLM-style scheduling discipline).

Unlike :class:`repro.serve.engine.ServeEngine` (static batches), slots are
freed the moment a sequence finishes and refilled from the broker queue —
the decode step always runs at full batch width.  Prefill for an incoming
request runs as its own (batch=1) call and its KV rows are spliced into the
shared cache; per-slot position masking handles ragged sequence states.

Works with every cache family exposing per-slot batch rows (GQA k/v, MLA
latents, SSM/xLSTM states): splicing is a pure tree_map over the batch dim.

When constructed with a cost model, the engine also closes the paper's
offloading loop per admitted request: at admission it observes the current
link bandwidth and re-plans the device/edge split for that request through
:func:`repro.core.decisions.decide_all` (mirroring
``ServeEngine.offload_plan``, but continuous — every admission re-plans
against fresh link state instead of one plan per static batch).
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import build_model
from repro.obs.trace import NULL_TRACER
from repro.serve.engine import Request


def _batch_dim_index(path_leafname: str) -> Optional[int]:
    """Index of the batch dim per cache leaf (after layer-stack dims)."""
    name = path_leafname
    if name in ("k", "v", "ckv", "kr", "self_k", "self_v", "cross_k",
                "cross_v", "attn_k", "attn_v", "s_c", "s_n", "s_h", "s_m",
                "s_conv"):
        return 1
    if name in ("ssm", "conv") or name.startswith("m_"):
        return 2
    return None                      # pos etc.


def _splice_rows(cache, cache1, slot):
    """``cache`` with each per-slot leaf's row ``slot`` replaced by the
    batch-1 ``cache1``'s; other leaves (``pos``, counts) kept."""
    def put(path, big, small):
        bdim = _batch_dim_index(str(getattr(path[-1], "key", path[-1])))
        if bdim is None:
            return big
        return jax.lax.dynamic_update_slice_in_dim(
            big, small.astype(big.dtype), slot, axis=bdim)

    return jax.tree_util.tree_map_with_path(put, cache, cache1)


class ContinuousBatchEngine:
    """Slot-based continuous batching for one model.

    ``cost`` is an optional :class:`repro.core.costs.CostModel`; when set,
    every admitted request gets an offload split re-planned against the
    current ``link_bw`` observation (a float, or a zero-arg callable
    returning the observed bytes/s) and recorded on ``request.offload``.
    ``decision_backend`` selects where those re-planning sweeps run
    (``"numpy"`` host default, ``"pallas"`` the fused kernel next to the
    model) — see :func:`repro.core.decisions.decide_all`.

    Admission is clocked: the engine keeps a virtual
    :class:`repro.sim.events.Clock` that advances ``step_latency_s``
    per decode step (and jumps forward over idle gaps), and a request is
    only admitted once ``request.arrived_at`` has passed — never the
    moment a slot happens to be free.  Inject ``clock=`` to share one
    virtual time axis with a :mod:`repro.sim` run; each admitted request
    records its admission instant on ``request.admitted_at``.

    Its phases are :meth:`repro.obs.Tracer.region` spans on track
    ``serve``: ``admit`` (holding ``plan``, ``prefill`` and ``splice``)
    per admission and ``decode`` per step — profiler annotations always,
    and spans on the engine clock when ``obs`` is a live tracer.
    """

    def __init__(self, cfg, *, slots: int = 4, max_len: int = 256,
                 seed: int = 0, cost=None, link_bw=1.25e9,
                 offload_device=None, offload_edge=None,
                 decision_backend: str = "numpy",
                 clock=None, step_latency_s: float = 5e-3, obs=None,
                 metrics=None):
        assert cfg.family in ("dense", "moe", "vlm") \
            and cfg.attn_kind in ("gqa", "mla"), \
            "continuous batching requires a vector-position decode path " \
            "(GQA or MLA)"
        self.cfg = cfg
        self.api = build_model(cfg, impl="naive")
        self.slots = slots
        self.max_len = max_len
        self.cost = cost
        self.decision_backend = decision_backend
        self.link_bw = link_bw           # float or () -> float observation
        self.offload_device = offload_device
        self.offload_edge = offload_edge
        if clock is None:
            # deferred: the serving layer must not pull in the whole
            # simulator at import time — any object with .now/.advance/
            # .advance_to (e.g. an injected sim Clock) works
            from repro.sim.events import Clock
            clock = Clock()
        self.clock = clock
        self.step_latency_s = float(step_latency_s)
        self.obs = obs if obs is not None else NULL_TRACER
        # live rolling quantiles: pass a repro.obs.MetricsRegistry and
        # the engine streams per-request sojourn / queue-wait into
        # mergeable sketches (summary kind) a /metrics scrape reads as
        # p50/p90/p99 without stored samples, and counts each decode
        # step's cache rows and (for MoE) its pairs on held experts
        self.metrics = metrics
        if metrics is not None:
            self._q_sojourn = metrics.quantile(
                "serve_sojourn_seconds",
                help="request sojourn (arrival to completion)")
            self._q_wait = metrics.quantile(
                "serve_queue_wait_seconds",
                help="request queue wait (arrival to admission)")
            self._c_rows = metrics.counter(
                "serve_cache_rows_total",
                help="cache rows the decode steps attend for live slots")
            if cfg.num_experts:
                self._c_pairs = metrics.counter(
                    "serve_expert_pairs_held_total",
                    help="(token, choice) pairs the decode steps route "
                         "to held experts")
        self.replans = 0
        self.params = self.api.init_params(jax.random.key(seed))
        self.cache = self.api.init_cache(slots, max_len)
        # per-slot state (host side)
        self.slot_pos = np.zeros(slots, np.int32)        # tokens consumed
        self.slot_req: list[Optional[Request]] = [None] * slots
        self.slot_remaining = np.zeros(slots, np.int32)
        self.slot_last_tok = np.zeros(slots, np.int32)
        self._prefill1 = jax.jit(lambda p, b: self.api.prefill(p, b, max_len))
        # with metrics, an MoE step also returns its pairs on held experts
        self._count_held = metrics is not None and bool(cfg.num_experts)
        decode = (functools.partial(self.api.decode_step, count_held=True)
                  if self._count_held else self.api.decode_step)
        self._decode = jax.jit(decode, donate_argnums=(2,))
        self._splice_rows = jax.jit(_splice_rows, donate_argnums=(0,))
        self.steps = 0
        self.tokens_out = 0

    # -- cache splicing -----------------------------------------------------
    def _splice(self, slot: int, cache1):
        """Copy request-cache (batch=1) rows into ``slot`` of the shared
        cache, in place (the shared cache is donated)."""
        self.cache = self._splice_rows(self.cache, cache1,
                                       jnp.asarray(slot, jnp.int32))

    # -- offload re-planning --------------------------------------------------
    def observe_link_bw(self) -> float:
        """Current link-bandwidth observation (bytes/s)."""
        bw = self.link_bw() if callable(self.link_bw) else self.link_bw
        return float(bw)

    def _plan_offload(self, req: Request) -> None:
        """Re-plan the device/edge split for one admitted request against
        the engine's cost model and the fresh link observation."""
        from repro.core.decisions import decide_all, make_envs
        from repro.core.offload import transformer_layer_costs
        from repro.hw import get_device
        device = self.offload_device or get_device("jetson-orin-nano")
        edge = self.offload_edge or get_device("edge-server-a100")
        seq = max(len(req.prompt), 1)
        layers = transformer_layer_costs(self.cfg, seq, 1)
        envs = make_envs(device, edge,
                         link_bw=np.asarray([self.observe_link_bw()]),
                         input_bytes=4.0 * seq)
        req.offload = decide_all(layers, envs, cost=self.cost,
                                 backend=self.decision_backend)[0]
        self.replans += 1

    # -- admission ------------------------------------------------------------
    def _now(self) -> float:
        return self.clock.now

    def _region(self, name: str, tid: int = 0, args=None):
        """The engine's phase ``name`` on the profiler's clock, and on the
        tracer's (the engine clock) when tracing."""
        return self.obs.region("serve", name, tid=tid, args=args,
                               now=self._now)

    def _admit(self, req: Request, slot: int):
        req.admitted_at = self.clock.now
        with self._region("admit", req.rid, {"slot": slot}):
            if self.cost is not None:
                plan_args: dict = {}
                with self._region("plan", req.rid, plan_args):
                    self._plan_offload(req)
                    plan_args["split"] = int(req.offload.split)
            with self._region("prefill", req.rid):
                batch = {"tokens": jnp.asarray(req.prompt[None], jnp.int32)}
                logits, cache1 = self._prefill1(self.params, batch)
                self.slot_last_tok[slot] = int(jnp.argmax(logits[0, -1]))
            with self._region("splice", req.rid):
                self._splice(slot, cache1)
        self.slot_pos[slot] = len(req.prompt)
        self.slot_req[slot] = req
        self.slot_remaining[slot] = req.max_new_tokens
        req.output = np.zeros(req.max_new_tokens, np.int32)
        req._written = 0              # type: ignore[attr-defined]

    # -- main loop ------------------------------------------------------------
    def serve(self, requests: list[Request]) -> list[Request]:
        queue = sorted(requests, key=lambda r: r.arrived_at)
        done: list[Request] = []
        while queue or any(r is not None for r in self.slot_req):
            # idle engine + future arrivals only: jump the virtual clock
            # to the next arrival instead of spinning empty decode steps
            if queue and not any(r is not None for r in self.slot_req) \
                    and queue[0].arrived_at > self.clock.now:
                self.clock.advance_to(queue[0].arrived_at)
            # fill free slots — only with requests that have arrived
            for s in range(self.slots):
                if self.slot_req[s] is None and queue \
                        and queue[0].arrived_at <= self.clock.now:
                    self._admit(queue.pop(0), s)
            # one decode step for all active slots, ragged per-slot positions
            with self._region("decode"):
                toks = jnp.asarray(self.slot_last_tok[:, None], jnp.int32)
                self.cache["pos"] = jnp.asarray(self.slot_pos, jnp.int32)
                out = self._decode(self.params, {"token": toks}, self.cache)
                logits, self.cache = out[:2]
                top = jnp.argmax(logits[:, -1], axis=-1)
                if self.metrics is None:
                    nxt = np.asarray(top, np.int32)
                else:
                    nxt = self._count_step(top, out[2:])
            self.steps += 1
            self.clock.advance(self.step_latency_s)
            for s in range(self.slots):
                req = self.slot_req[s]
                if req is None:
                    continue
                w = req._written        # type: ignore[attr-defined]
                req.output[w] = self.slot_last_tok[s]
                req._written = w + 1    # type: ignore[attr-defined]
                self.tokens_out += 1
                self.slot_last_tok[s] = nxt[s]
                self.slot_pos[s] += 1
                self.slot_remaining[s] -= 1
                if self.slot_remaining[s] <= 0 \
                        or self.slot_pos[s] >= self.max_len - 1:
                    done.append(req)
                    self.slot_req[s] = None
                    if self.metrics is not None:
                        self._q_sojourn.observe(
                            self.clock.now - req.arrived_at)
                        self._q_wait.observe(
                            req.admitted_at - req.arrived_at)
                        self.metrics.counter(
                            "serve_requests_completed").inc()
                    if self.obs.enabled:
                        # virtual-clock lifecycle on the shared time axis:
                        # sojourn [arrived, now] ⊃ queue_wait [arrived,
                        # admitted] · service [admitted, now]
                        self.obs.task_spans(
                            "continuous_engine", req.rid,
                            f"req{req.rid}", req.arrived_at,
                            req.admitted_at, self.clock.now)
        return done

    def _count_step(self, top, held) -> np.ndarray:
        """The step's argmax, fetched in one transfer with its held-pair
        count (``held``: that count, or empty for a dense model); counts
        that and the step's cache rows (position + 1 of each live slot)."""
        nxt, held = jax.device_get((top, held))
        live = np.asarray([r is not None for r in self.slot_req])
        self._c_rows.inc(float((self.slot_pos[live] + 1).sum()))
        if held:
            self._c_pairs.inc(float(held[0]))
        return np.asarray(nxt, np.int32)

    @property
    def occupancy(self) -> float:
        """Mean generated tokens per decode step (≤ slots)."""
        return self.tokens_out / max(self.steps, 1)
