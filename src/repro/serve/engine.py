"""Batched serving engine: prefill + decode with a static-batch scheduler.

A deliberately complete (if compact) serving path: requests queue in a
broker, get batched to the engine's batch size, prefill builds the KV
cache, greedy/temperature decode runs step-by-step, finished sequences
free their slots.  The *offloading* decision — serve locally vs ship to an
edge node — is delegated to ``repro.core.offload`` policies fed by the
profiling predictor, closing the paper's loop.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import build_model
from repro.obs.trace import NULL_TRACER


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray               # [S] int32
    max_new_tokens: int = 16
    temperature: float = 0.0
    arrived_at: float = 0.0
    # virtual time the request was admitted to a slot.  Filled only by
    # ContinuousBatchEngine (which guarantees admitted_at >= arrived_at);
    # stays 0.0 under ServeEngine's static batching
    admitted_at: float = 0.0
    # filled on completion
    output: Optional[np.ndarray] = None
    first_token_s: float = 0.0
    total_s: float = 0.0
    # repro.core.offload.SplitDecision, filled at admission by
    # ContinuousBatchEngine when it carries a cost model (ServeEngine
    # plans per batch via offload_plan instead of per request)
    offload: Optional[object] = None


@dataclasses.dataclass
class EngineStats:
    served: int = 0
    tokens_out: int = 0
    prefill_s: float = 0.0
    decode_s: float = 0.0

    @property
    def tokens_per_s(self) -> float:
        return self.tokens_out / max(self.decode_s, 1e-9)


class ServeEngine:
    """Static-batch serving for one model.

    ``cost`` is an optional :class:`repro.core.costs.CostModel`; when set
    it becomes the default cost model for :meth:`offload_plan`, so one
    engine can plan against analytic, predictor-driven, or multi-objective
    costs without per-call plumbing.  ``decision_backend`` picks where
    re-planning sweeps run (``"numpy"`` host default, ``"pallas"`` the
    fused kernel next to the model) — see
    :func:`repro.core.decisions.decide_all`.
    """

    def __init__(self, cfg, *, batch_size: int = 4, max_len: int = 256,
                 seed: int = 0, cost=None, decision_backend: str = "numpy",
                 obs=None, metrics=None):
        self.cfg = cfg
        self.api = build_model(cfg, impl="naive")
        self.batch_size = batch_size
        self.max_len = max_len
        self.cost = cost
        self.decision_backend = decision_backend
        self.obs = obs if obs is not None else NULL_TRACER
        # live rolling quantiles: pass a repro.obs.MetricsRegistry and
        # the engine streams per-batch first-token latency and
        # per-request total latency into mergeable sketches (summary
        # kind) — the scrape-time p50/p99 view, reusing the wall
        # readings the stats block already measured
        self.metrics = metrics
        if metrics is not None:
            self._q_first = metrics.quantile(
                "serve_first_token_seconds",
                help="time to first token per batch")
            self._q_total = metrics.quantile(
                "serve_request_total_seconds",
                help="end-to-end request latency")
        self._batches = 0                # obs track row per batch
        self.params = self.api.init_params(jax.random.key(seed))
        self._prefill = jax.jit(
            lambda p, b: self.api.prefill(p, b, max_len))
        self._decode = jax.jit(self.api.decode_step, donate_argnums=(2,))
        self.stats = EngineStats()
        self.last_first_token_s = 0.0

    def load_params(self, params):
        self.params = params

    # -- core batched generation ------------------------------------------
    def generate_batch(self, prompts: np.ndarray, max_new: int,
                       temperature=0.0, seed: int = 0
                       ) -> np.ndarray:
        """prompts [B, S] → generated tokens [B, max_new].

        ``temperature`` may be a scalar (whole batch) or a ``[B]`` vector
        (per-row sampling temperature; ≤ 0 means greedy for that row).
        """
        b, s = prompts.shape
        assert b == self.batch_size, (b, self.batch_size)
        t0 = time.perf_counter()  # repro: disable=DET002 (real prefill wall time)
        batch = {"tokens": jnp.asarray(prompts, jnp.int32)}
        if self.cfg.family == "audio":
            rng = np.random.default_rng(seed)
            frames = rng.normal(size=(b, self.cfg.enc_seq,
                                      self.cfg.d_model)).astype(np.float32)
            batch["frames"] = jnp.asarray(frames)
        logits, cache = self._prefill(self.params, batch)
        jax.block_until_ready(logits)
        t_pf = time.perf_counter()  # repro: disable=DET002 (measurement)
        self.stats.prefill_s += t_pf - t0

        key = jax.random.key(seed)
        out = np.zeros((b, max_new), np.int32)
        tok = self._sample(logits[:, -1], temperature, key)
        jax.block_until_ready(tok)
        t_ft = time.perf_counter()  # repro: disable=DET002 (measurement)
        self.last_first_token_s = t_ft - t0
        t1 = time.perf_counter()  # repro: disable=DET002 (real decode wall time)
        for i in range(max_new):
            out[:, i] = np.asarray(tok[:, 0])
            logits, cache = self._decode(self.params, {"token": tok}, cache)
            key, sub = jax.random.split(key)
            tok = self._sample(logits[:, -1], temperature, sub)
        jax.block_until_ready(logits)
        t_end = time.perf_counter()  # repro: disable=DET002 (measurement)
        self.stats.decode_s += t_end - t1
        self.stats.tokens_out += b * max_new
        if self.metrics is not None:
            self._q_first.observe(self.last_first_token_s)
        if self.obs.enabled:
            # the spans reuse the already-measured wall readings above —
            # tracing adds no perf_counter calls to the serving path
            bid = self._batches
            self._batches += 1
            self.obs.span("serve_engine", "prefill", t0, t_pf, tid=bid,
                          args={"batch": b})
            self.obs.instant("serve_engine", "first_token", t_ft, tid=bid)
            self.obs.span("serve_engine", "decode", t1, t_end, tid=bid,
                          args={"tokens": b * max_new})
        return out

    @staticmethod
    def _sample(logits, temperature, key):
        temp = jnp.asarray(temperature, jnp.float32)
        if temp.ndim == 0:
            if float(temp) <= 0:
                return jnp.argmax(logits, axis=-1)[:, None] \
                    .astype(jnp.int32)
            temp = jnp.full(logits.shape[:1], temp)
        greedy = jnp.argmax(logits, axis=-1)
        sampled = jax.random.categorical(
            key, logits / jnp.maximum(temp, 1e-6)[:, None])
        return jnp.where(temp > 0, sampled, greedy)[:, None] \
            .astype(jnp.int32)

    # -- broker loop --------------------------------------------------------
    def serve(self, requests: list[Request]) -> list[Request]:
        """Process a queue of requests in arrival order, batched."""
        queue = sorted(requests, key=lambda r: r.arrived_at)
        done = []
        while queue:
            chunk = queue[:self.batch_size]
            queue = queue[self.batch_size:]
            # pad the batch to engine size with dummy repeats
            while len(chunk) < self.batch_size:
                chunk.append(dataclasses.replace(chunk[-1], rid=-1))
            s = max(len(r.prompt) for r in chunk)
            prompts = np.stack([
                np.pad(r.prompt, (s - len(r.prompt), 0)) for r in chunk])
            max_new = max(r.max_new_tokens for r in chunk)
            temps = np.asarray([r.temperature for r in chunk], np.float32)
            t0 = time.perf_counter()  # repro: disable=DET002 (measurement)
            outs = self.generate_batch(prompts, max_new, temps)
            dt = time.perf_counter() - t0  # repro: disable=DET002 (measurement)
            for r, o in zip(chunk, outs):
                if r.rid < 0:
                    continue
                r.output = o[:r.max_new_tokens]
                r.first_token_s = self.last_first_token_s
                r.total_s = dt
                done.append(r)
                self.stats.served += 1
                if self.metrics is not None:
                    self._q_total.observe(dt)
                    self.metrics.counter(
                        "serve_requests_completed").inc()
        return done

    # -- offload delegation -------------------------------------------------
    def offload_plan(self, link_bws, *, device=None, edge=None,
                     seq_len: int = 0, link_latency_s: float = 0.005,
                     cost=None, backend=None):
        """Split-computing plan for this model across candidate link states.

        Delegates to the vectorized decision core: one ``[n_links, L+1]``
        cost matrix and one argmin per link, so the broker can re-plan
        every batch without measurable overhead.  ``cost`` overrides the
        engine's construction-time cost model (``None`` falls back to it,
        then to the analytic latency model); ``backend`` likewise
        overrides the engine's ``decision_backend``.  Returns a
        :class:`repro.core.decisions.DecisionPlan`; index it to get the
        ``SplitDecision`` for one link state.
        """
        from repro.core.decisions import decide_all, make_envs
        from repro.core.offload import transformer_layer_costs
        from repro.hw import get_device
        device = device or get_device("jetson-orin-nano")
        edge = edge or get_device("edge-server-a100")
        seq_len = seq_len or self.max_len
        layers = transformer_layer_costs(self.cfg, seq_len, self.batch_size)
        envs = make_envs(device, edge,
                         link_bw=np.atleast_1d(link_bws).astype(np.float64),
                         link_latency_s=link_latency_s,
                         input_bytes=4.0 * self.batch_size * seq_len)
        return decide_all(layers, envs,
                          cost=cost if cost is not None else self.cost,
                          backend=backend or self.decision_backend)
