"""repro.oracle — accelerator-lowered predictor serving with online
profiling-in-the-loop.

Closes the paper's loop end to end: fitted profiling regressors are
*compiled to pure array form* (``lowered``) so predictor-driven
offloading sweeps run on ``backend="pallas"`` next to the model; live
``(features, realised time)`` observations from the streaming simulator
feed an ``OnlineOracle`` (``online``) that applies an always-on cheap
residual correction, detects drift with a Page–Hinkley test on
normalised residuals, and refits on trigger; and a versioned
``PredictorRegistry`` (``registry``) snapshots every published model
with an atomic current-pointer swap so serving never observes a
half-written predictor.

Seams (pinned by ``tests/test_oracle.py``):

  * lowered  — ``lower_predictor`` / ``LoweredLayerTimes``: ridge → dot,
               MLP → jitted matmul chain, GBT → flattened node arrays
               through :mod:`repro.kernels.tree_predict`
  * online   — ``OnlineOracle`` + ``OracleCost`` (the CostModel the
               streaming scheduler plugs in), ``PageHinkley``
  * registry — ``PredictorRegistry`` versioned snapshots, optional
               on-disk persistence via ``repro.core.predictors.persist``
"""
from repro.oracle.lowered import (LoweredLayerTimes, lower_layer_times,
                                  lower_predictor)
from repro.oracle.online import OnlineOracle, OracleCost, PageHinkley
from repro.oracle.registry import PredictorRegistry, Snapshot

__all__ = [
    "LoweredLayerTimes",
    "lower_layer_times",
    "lower_predictor",
    "OnlineOracle",
    "OracleCost",
    "PageHinkley",
    "PredictorRegistry",
    "Snapshot",
]
