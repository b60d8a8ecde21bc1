"""Compile fitted profiling regressors to pure array form.

``PredictorCost`` evaluates its regressor through ``model.predict`` —
arbitrary host Python as far as the decision kernels are concerned.
This module closes that gap: :func:`lower_predictor` maps each fitted
regressor family onto an equivalent array program —

  * :class:`~repro.core.predictors.linear.RidgeRegressor` → one f64
    standardise + dot;
  * :class:`~repro.core.predictors.mlp.MLPRegressor` → the jitted f32
    matmul chain (the exact forward the host ``predict`` runs eagerly);
  * :class:`~repro.core.predictors.gbt.GBTRegressor` /
    ``MultiTargetGBT`` → flattened ``(feature, threshold_bin, left,
    right, value)`` node arrays walked by the vectorised
    level-synchronous descent in :mod:`repro.kernels.tree_predict`
    (jitted XLA, *bit-for-bit* with the host ensemble in f64, or the
    fused Pallas batched tree-inference kernel within f32 tolerance) —

and :class:`LoweredLayerTimes` packages the lowered model together with
a ``PredictorCost``'s feature function so the accelerator decide path
(:mod:`repro.kernels.decide_split.ops`) can reconstruct the
per-layer device/edge time vectors on its own, which is what lets
``decide_all(cost=PredictorCost(...), backend="pallas")`` run
predictor-driven sweeps without ever calling back into host Python.

Models outside these families still raise ``TypeError`` from
:func:`lower_predictor` — their ``predict`` evaluates host-side and
cannot lower; use ``backend="numpy"``.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional, Sequence

import numpy as np

from repro.core.predictors.gbt import GBTRegressor, MultiTargetGBT
from repro.core.predictors.linear import RidgeRegressor
from repro.core.predictors.mlp import MLPRegressor
from repro.kernels.tree_predict.ops import predict_trees
from repro.kernels.tree_predict.ref import TreeArrays, flatten_gbt
from repro.obs.trace import region


class LoweredPredictor:
    """A fitted regressor compiled to array form.  ``predict`` mirrors
    the host model's ``predict`` surface (``[N, F] -> [N]`` or
    ``[N, T]``) but evaluates as jitted XLA (``backend="jax"``) or the
    fused Pallas tree kernel (``backend="pallas"``, trees only)."""

    #: backends this lowered form supports
    backends: tuple[str, ...] = ("jax",)

    def predict(self, x: np.ndarray, *, backend: str = "jax") -> np.ndarray:
        raise NotImplementedError


@dataclasses.dataclass
class LoweredLinear(LoweredPredictor):
    """Ridge: standardise + augmented dot, all f64 (matches the host
    ``xs @ w_`` up to BLAS-vs-XLA accumulation order — last-ulp)."""
    x_mu: np.ndarray
    x_sd: np.ndarray
    w: np.ndarray                       # [F+1, T]

    @classmethod
    def lower(cls, model: RidgeRegressor) -> "LoweredLinear":
        return cls(model.x_mu_, model.x_sd_, model.w_)

    def predict(self, x: np.ndarray, *, backend: str = "jax") -> np.ndarray:
        _require_jax_backend(self, backend)
        import jax.numpy as jnp
        from repro.x64 import x64
        with x64():
            xs = (jnp.asarray(np.asarray(x, np.float64))
                  - jnp.asarray(self.x_mu)) / jnp.asarray(self.x_sd)
            xs = jnp.concatenate(
                [xs, jnp.ones((xs.shape[0], 1), xs.dtype)], axis=1)
            out = np.asarray(xs @ jnp.asarray(self.w), np.float64)
        return out


@dataclasses.dataclass
class LoweredMLP(LoweredPredictor):
    """MLP: the jitted twin of the host forward (f32 matmul chain, f32
    destandardisation — the host path's exact dtypes)."""
    params: dict
    n_layers: int
    x_mu: Optional[np.ndarray]
    x_sd: Optional[np.ndarray]
    y_mu: Optional[np.ndarray]
    y_sd: Optional[np.ndarray]

    @classmethod
    def lower(cls, model: MLPRegressor) -> "LoweredMLP":
        std = model.standardize
        return cls(dict(model.params_), model.n_layers_,
                   model.x_mu_ if std else None,
                   model.x_sd_ if std else None,
                   model.y_mu_ if std else None,
                   model.y_sd_ if std else None)

    def _jitted(self):
        fn = getattr(self, "_fwd", None)
        if fn is None:
            import jax
            import jax.numpy as jnp
            params = {k: jnp.asarray(v, jnp.float32)
                      for k, v in self.params.items()}
            n_layers = self.n_layers

            def fwd(x):
                return MLPRegressor._forward(params, x, n_layers)

            fn = jax.jit(fwd)
            self._fwd = fn
        return fn

    def predict(self, x: np.ndarray, *, backend: str = "jax") -> np.ndarray:
        _require_jax_backend(self, backend)
        import jax.numpy as jnp
        x = np.asarray(x, np.float32)
        if self.x_mu is not None:
            x = (x - self.x_mu) / self.x_sd
        pred = np.asarray(self._jitted()(jnp.asarray(x)))
        if self.y_mu is not None:
            pred = pred * self.y_sd + self.y_mu
        return pred


@dataclasses.dataclass
class LoweredTrees(LoweredPredictor):
    """GBT ensemble over flattened node arrays — one :class:`TreeArrays`
    per target, dispatched through :mod:`repro.kernels.tree_predict`."""
    arrays: tuple[TreeArrays, ...]
    multi_target: bool

    backends = ("jax", "pallas")

    @classmethod
    def lower(cls, model) -> "LoweredTrees":
        if isinstance(model, MultiTargetGBT):
            return cls(tuple(flatten_gbt(m) for m in model.models_), True)
        return cls((flatten_gbt(model),), False)

    def predict(self, x: np.ndarray, *, backend: str = "jax") -> np.ndarray:
        with region("predict", "call"):
            cols = [predict_trees(x, a, backend=backend)
                    for a in self.arrays]
        if not self.multi_target:
            return cols[0]
        return np.stack(cols, axis=1)


def _require_jax_backend(lowered, backend: str) -> None:
    if backend not in lowered.backends:
        raise ValueError(
            f"{type(lowered).__name__} supports backends "
            f"{lowered.backends}, got {backend!r} (only tree ensembles "
            "have a fused Pallas inference kernel; dense models already "
            "run as one jitted XLA op)")


_LOWERINGS: list[tuple[type, Callable]] = [
    (RidgeRegressor, LoweredLinear.lower),
    (MLPRegressor, LoweredMLP.lower),
    (GBTRegressor, LoweredTrees.lower),
    (MultiTargetGBT, LoweredTrees.lower),
]


def lower_predictor(model) -> LoweredPredictor:
    """Fitted regressor → :class:`LoweredPredictor`, or ``TypeError``
    if the model is not one of the lowerable families (its ``predict``
    is arbitrary host-side Python — use ``backend='numpy'``).

    Memoised on the model instance (flattening a tree ensemble and
    compiling its descent is the expensive part): treat fitted models
    as immutable, and build a fresh model per refit — the convention
    every identity-keyed memo in this codebase already relies on.
    """
    cached = getattr(model, "_lowered_", None)
    if cached is not None:
        return cached
    for klass, lowering in _LOWERINGS:
        if type(model) is klass:
            lowered = lowering(model)
            try:
                model._lowered_ = lowered
            except (AttributeError, TypeError):
                pass                     # slotted/frozen model: no memo
            return lowered
    raise TypeError(
        f"{type(model).__name__} does not lower to array form: its "
        "predict evaluates host-side, so predictor-driven decisions "
        "must use backend='numpy' (lowerable: RidgeRegressor, "
        "MLPRegressor, GBTRegressor, MultiTargetGBT)")


# --------------------------------------------------------------------------
# The layer-times seam the accelerator decision backends consume
# --------------------------------------------------------------------------
@dataclasses.dataclass
class LoweredLayerTimes:
    """Per-layer device/edge execution times from a lowered predictor.

    The accelerator twin of ``PredictorCost.layer_times``: features are
    built host-side by the same ``feature_fn`` (cheap, O(L)), inference
    runs through the lowered model, and the result replays the host
    pipeline op-for-op — multi-target column select, clamp to ≥ 0, and
    the oracle's affine residual correction ``t*gain + bias`` (identity
    short-circuited, re-clamped otherwise) — so the jax decide backend
    stays bit-for-bit with the host for tree models.  Memoised on the
    layers object identity, mirroring the host memo: one predict per
    decision sweep.
    """
    predictor: LoweredPredictor
    feature_fn: Callable
    device: object                      # DeviceSpec
    edge: object
    target_index: int = 0
    correction: tuple[float, float] = (1.0, 0.0)

    def __post_init__(self):
        self._cache: tuple = (None, None, None)

    def times(self, layers: Sequence, *, backend: str = "jax"
              ) -> tuple[np.ndarray, np.ndarray]:
        """``(t_dev [L], t_edge [L])`` f64 — the lowered twin of the
        host ``PredictorCost.layer_times`` + correction."""
        cached = self._cache
        if cached[0] is layers and cached[1] == backend:
            return cached[2]
        feats = np.concatenate([self.feature_fn(layers, self.device),
                                self.feature_fn(layers, self.edge)], axis=0)
        pred = np.asarray(self.predictor.predict(feats, backend=backend),
                          np.float64)
        if pred.ndim == 2:
            pred = pred[:, self.target_index]
        pred = np.maximum(pred, 0.0)
        gain, bias = self.correction
        if gain != 1.0 or bias != 0.0:
            pred = np.maximum(pred * gain + bias, 0.0)
        out = (pred[:len(layers)], pred[len(layers):])
        self._cache = (layers, backend, out)
        return out


def lower_layer_times(cost, correction: tuple[float, float] = (1.0, 0.0)
                      ) -> LoweredLayerTimes:
    """Lower a ``PredictorCost``-shaped cost model's layer-time pipeline
    (raises ``TypeError`` through :func:`lower_predictor` when the
    wrapped regressor has no array form)."""
    return LoweredLayerTimes(lower_predictor(cost.model), cost.feature_fn,
                             cost.device, cost.edge,
                             target_index=cost.target_index,
                             correction=correction)
